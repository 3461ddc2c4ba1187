"""Serving driver: Amber-sparse prefill, dense decode.

One-shot batch mode (the whole-batch reference engine):

    PYTHONPATH=src python -m repro.launch.serve \
        --arch qwen2_7b --smoke --sparsity 8:16 --batch 4 --new-tokens 32

Continuous-batching trace mode — Poisson arrivals through the scheduler,
reporting throughput, per-request latency, and retrace counts:

    PYTHONPATH=src python -m repro.launch.serve --smoke --trace \
        --num-requests 16 --rate 0.5 --len-range 8:48 --slots 4 --chunk 16
"""
from __future__ import annotations

import argparse


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--sparsity", default="8:16")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--pallas-kernels", action="store_true",
                    help="route sparse projections through the fused Pallas "
                         "kernels (Mosaic on a TPU, interpreted elsewhere)")
    ap.add_argument("--trace", action="store_true",
                    help="continuous-batching driver: Poisson request "
                         "arrivals, mixed prompt lengths, per-request "
                         "latency + throughput + retrace report")
    ap.add_argument("--num-requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="mean arrivals per scheduler iteration (Poisson)")
    ap.add_argument("--len-range", default="8:48",
                    help="uniform prompt-length range lo:hi for --trace")
    ap.add_argument("--slots", type=int, default=4,
                    help="KV-cache slots (decode batch bucket)")
    ap.add_argument("--chunk", type=int, default=16,
                    help="prefill chunk size (prefill shape bucket)")
    ap.add_argument("--no-paged", action="store_true",
                    help="dense per-slot KV slab instead of the paged "
                         "block pool")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV rows per block (paged allocator)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="global block-pool size; default covers "
                         "slots*max_seq (no memory pressure) — size it "
                         "lower to exercise admission gating + preemption")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable block-level prefix caching across "
                         "requests (refcounted content-addressed pool; "
                         "on by default under --trace paged serving)")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="prepend the same N-token system prompt to every "
                         "--trace request (exercises the prefix cache)")
    ap.add_argument("--ttl", type=int, default=None, metavar="ITERS",
                    help="per-request deadline in scheduler iterations "
                         "(--trace): requests exceeding it end TIMED_OUT")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel engine replicas for --trace "
                         "(host-level: independent schedulers + block "
                         "pools behind the admission router)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shards per replica for --trace; "
                         "dp*tp > 1 needs that many devices (fake them "
                         "with XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import time

    import jax
    import jax.numpy as jnp

    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()

    from repro.configs.base import get_config, get_smoke_config
    from repro.core.policy import DENSE, paper_policy
    from repro.core.pruner import precompute_scales
    from repro.models import build_model
    from repro.serve.engine import ServeConfig, ServingEngine

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    n, m = (int(x) for x in args.sparsity.split(":"))
    policy = paper_policy(n, m, cfg.qgate_skip_layers,
                          use_pallas_kernels=args.pallas_kernels)
    params = precompute_scales(params, policy)  # offline Robust-Norm scales

    if args.trace:
        return _trace_mode(args, cfg, model, params, policy)

    scfg = ServeConfig(max_seq=args.prompt_len + args.new_tokens + 8,
                       temperature=args.temperature)
    # the one-shot batch path runs the monolithic-prefill reference engine
    engine = ServingEngine(model, policy, scfg)
    dense_engine = ServingEngine(model, DENSE, scfg)

    batch = {
        "tokens": jax.random.randint(
            jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
            cfg.vocab_size),
    }
    if cfg.is_encdec:
        batch["frame_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, cfg.encoder_seq, cfg.d_model)).astype(jnp.bfloat16)
    if cfg.vision_stub:
        batch["pixel_embeds"] = jax.random.normal(
            jax.random.PRNGKey(3),
            (args.batch, cfg.n_patches, cfg.d_model)).astype(jnp.bfloat16)

    for name, eng in [("dense", dense_engine), (f"amber {n}:{m}", engine)]:
        t0 = time.perf_counter()
        out = eng.generate(params, batch, max_new_tokens=args.new_tokens)
        out["tokens"].block_until_ready()
        dt = time.perf_counter() - t0
        print(f"[{name:>10s}] generated {out['tokens'].shape} in {dt:.2f}s; "
              f"first row: {out['tokens'][0, :12].tolist()}")

    agree = (dense_engine.generate(params, batch, max_new_tokens=args.new_tokens)
             ["tokens"] == engine.generate(params, batch,
                                           max_new_tokens=args.new_tokens)
             ["tokens"]).mean()
    print(f"greedy-decode agreement dense vs sparse-prefill: {float(agree):.3f}")
    return 0


def _trace_mode(args, cfg, model, params, policy):
    """Poisson-arrival request stream through the serving facade."""
    import jax
    import numpy as np

    from repro.serve.api import Engine, EngineConfig
    from repro.serve.continuous import ContinuousConfig

    rng = np.random.default_rng(args.seed)
    lo, hi = (int(x) for x in args.len_range.split(":"))
    gaps = rng.exponential(1.0 / max(args.rate, 1e-9), args.num_requests)
    arrivals = np.floor(np.cumsum(gaps)).astype(int)
    lens = rng.integers(lo, hi + 1, args.num_requests)
    max_seq = hi + args.new_tokens + 8

    max_seq += args.shared_prefix
    eng = Engine.from_config(model, EngineConfig(
        dp=args.dp, tp=args.tp,
        serving=ContinuousConfig(
            max_seq=max_seq, num_slots=args.slots, chunk_size=args.chunk,
            temperature=args.temperature, seed=args.seed,
            paged=not args.no_paged, block_size=args.block_size,
            num_blocks=args.num_blocks,
            prefix_cache=not args.no_prefix_cache,
            ttl_default=args.ttl)),
        policy=policy)
    sysp = np.asarray(jax.random.randint(
        jax.random.PRNGKey(99), (args.shared_prefix,), 0, cfg.vocab_size))
    extras = {}
    for i in range(args.num_requests):
        toks = np.asarray(jax.random.randint(
            jax.random.PRNGKey(100 + i), (int(lens[i]),), 0, cfg.vocab_size))
        if args.shared_prefix:
            toks = np.concatenate([sysp, toks])
        rid = eng.submit(toks, max_new_tokens=args.new_tokens,
                         arrival=int(arrivals[i]))
        ex = {}
        if cfg.is_encdec:
            ex["frame_embeds"] = np.asarray(jax.random.normal(
                jax.random.PRNGKey(200 + i),
                (1, cfg.encoder_seq, cfg.d_model)), np.float32)
        if cfg.vision_stub:
            ex["pixel_embeds"] = np.asarray(jax.random.normal(
                jax.random.PRNGKey(300 + i),
                (1, cfg.n_patches, cfg.d_model)), np.float32)
        if ex:
            extras[rid] = ex

    eng.run(params, extras=extras)
    m = eng.metrics          # typed MetricsSnapshot (router-merged when dp>1)
    print(f"# {args.num_requests} requests, λ={args.rate}/iter, "
          f"lens {lo}..{hi}, slots={args.slots}, chunk={args.chunk}, "
          f"dp={args.dp}, tp={args.tp} "
          f"(metrics schema v{m.schema_version})")
    print("rid,prompt_len,arrival,state,first_token_iter,done_iter,"
          "latency_iters,latency_s,n_out,preemptions,retries")
    for r in sorted(m.requests, key=lambda r: r.rid):
        print(f"{r.rid},{r.prompt_len},{r.arrival},{r.state},"
              f"{r.first_token_iter},{r.done_iter},"
              f"{r.latency_iters},{r.latency_s:.3f},{r.n_out},"
              f"{r.preemptions},{r.retries}")
    lat = [r.latency_iters for r in m.requests]
    print(f"# throughput: {m.generated_tokens} tokens in "
          f"{m.wall_s:.2f}s = {m.tokens_per_s:.1f} tok/s "
          f"over {m.iterations} iterations")
    print(f"# latency iters p50/p95: {int(np.percentile(lat, 50))}/"
          f"{int(np.percentile(lat, 95))}")
    lc = m.lifecycle
    ts = lc.terminal_states
    print(f"# terminal states: done={ts.get('done', 0)} "
          f"rejected={ts.get('rejected', 0)} "
          f"timed_out={ts.get('timed_out', 0)} "
          f"cancelled={ts.get('cancelled', 0)}")
    print(f"# lifecycle: degraded_iterations={m.degraded_iterations} "
          f"admission_retries={lc.admission_retries} "
          f"watchdog_trips={lc.watchdog_trips} "
          f"restores={lc.restores} faults_fired={lc.faults_fired}")
    terminal = ("done", "rejected", "timed_out", "cancelled")
    leaked = [r.rid for r in m.requests if r.state not in terminal]
    if leaked:
        print(f"# ERROR: {len(leaked)} request(s) leaked in a non-terminal "
              f"state at drain: rids {leaked}")
        return 1
    tc = ", ".join(f"{k}={v}" for k, v in sorted(m.trace_counts.items()))
    print(f"# traces: {tc} (shape buckets: chunk={args.chunk}, "
          f"decode batch={args.slots})")
    print(f"# dispatches: {m.dispatches} programs / "
          f"{m.iterations} iterations = "
          f"{m.dispatches_per_iteration:.2f} per work iteration")
    if m.replicas is not None:
        per = ", ".join(
            f"r{i}: {p.generated_tokens} tok / {p.iterations} iters / "
            f"dpi {p.dispatches_per_iteration:.2f}"
            for i, p in enumerate(m.replicas))
        print(f"# replicas ({len(m.replicas)}): {per}")
    pg = m.paged
    if pg.enabled:
        print(f"# paged KV: block_size={pg.block_size} "
              f"pool={pg.num_blocks} blocks "
              f"({pg.num_blocks * pg.block_size} rows vs "
              f"{args.slots * max_seq * args.dp} dense-slab rows); "
              f"peak_in_use={pg.peak_blocks_in_use} "
              f"preemptions={pg.preemptions} "
              f"rejections={pg.rejections}; "
              f"attention={'pallas block-walk kernel' if pg.attention_kernel else 'jnp gather oracle'} "
              f"(toggle: --pallas-kernels)")
        if pg.prefix_cache:
            pct = (100.0 * pg.tokens_skipped / max(pg.prefill_tokens, 1))
            print(f"# prefix cache: hits={pg.prefix_hits} requests, "
                  f"blocks_reused={pg.blocks_reused}, "
                  f"tokens_skipped={pg.tokens_skipped}/"
                  f"{pg.prefill_tokens} ({pct:.0f}% of prefill rows), "
                  f"cached_blocks={pg.cached_blocks}, "
                  f"evictions={pg.evictions} "
                  f"(--shared-prefix N to exercise; --no-prefix-cache "
                  f"to disable)")
        else:
            print("# prefix cache: disabled")
    else:
        print("# paged KV: disabled (dense per-slot slab)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
