"""Chip smoke test: serve Qwen2-7B widths on a TPU through ``repro.serve.api``
with the Amber kernels compiled by Mosaic.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the multi-chip paths only (dp=4, tp=4)

One chip: an 8-layer cut of ``qwen2_7b`` at every published width (seeded
random weights, offline Amber scales) serves 8 requests of 1024-4096 prompt
tokens through ``Engine.from_config`` with paged KV, prefix caching and the
fused kernels on.  It checks that every request finishes, that no iteration
fell back to the jnp oracle, that each step bucket compiled once, and that the
compiled prefill step holds one projection kernel per pruned projection; then
it compares chunked prefill logits with kernels on and off, on the chip (see
``LOGIT_TOLERANCE`` for which comparison is held to a bound, and why).

``--chips 4`` runs only what needs four chips: dp=4 against dp=1 and tp=4
against tp=1 on the same config.  dp must give identical greedy tokens with
each replica's params and cache on its own device; tp's tokens are shown,
and its prefill logits on a float32 cut are held to ``LOGIT_TOLERANCE``.

The last line of standard output is one JSON object naming the device; any
failure raises, so the exit code is non-zero and that line is not printed.
Every phase below is a function of a config, so the tests run them on the
CPU at smoke size; only :func:`main` insists on a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

PROJECTIONS = ("q_proj", "k_proj", "v_proj", "o_proj",
               "gate_proj", "up_proj", "down_proj")
# jit names of the projection kernels: each names its custom call in HLO
PROJECTION_KERNELS = ("nm_prune_matmul_pallas", "nm_spmm_pallas",
                      "osparse_matmul_pallas", "w8a8_matmul_pallas")
PREFILL_BUCKET = (False, True, False)      # executor.STEP_BUCKETS key

# Kernels on vs off compute the same Amber model, but not in the same
# rounding order, and N:M selection is discontinuous: a one-ulp difference
# flips a near-tied selection, the flip moves the next layer's inputs by far
# more than an ulp, and the next layer flips more.  In bfloat16 at full width
# that cascade reaches the size of the pruning gap within two layers on a
# TPU v5e (XLA may keep the jnp path's activations in float32 where the
# kernel reads them rounded to bfloat16), so the served bfloat16 model's
# on/off gap is printed as information only.  The bound is held on a
# float32 cut of COMPARE_LAYERS layers of the same widths, where the two
# paths differ by ~1e-7 relative (a HIGHEST-precision Mosaic dot on a v5e)
# and flip only the rare near-tied selections: 0.0148 of the pruning gap
# measured on a v5e, with every greedy token equal.  A wrong mask, block,
# scale or KV row moves the logits by about the pruning gap itself (ratio
# near 1), so the bound sits between the two.  The same bound holds tp=4
# against tp=1 (6.6e-5 measured on four v5e chips).
LOGIT_TOLERANCE = 0.1      # max |got - want|  <=  0.1 * max |amber - dense|
COMPARE_LAYERS = 2         # layer 0 skips q/gate (lax.cond), layer 1 prunes


class SmokeFailure(AssertionError):
    """A check of chip_smoke failed (raised even under ``python -O``)."""


def check(ok, detail) -> None:
    if not ok:
        raise SmokeFailure(detail)


def model_config(n_layers: int = 8):
    """``qwen2_7b`` at every published width, cut to ``n_layers`` deep
    (layers 0 and 6 keep their q/gate skips, the rest are pruned)."""
    from repro.configs.base import get_config
    return dataclasses.replace(get_config("qwen2_7b"), n_layers=n_layers)


def amber_policy(cfg, use_pallas_kernels: bool = True):
    from repro.core.policy import paper_policy
    return paper_policy(8, 16, cfg.qgate_skip_layers,
                        use_pallas_kernels=use_pallas_kernels)


def build(cfg, policy, seed: int = 0):
    """Seeded random weights plus Amber scales, made on the device in one
    program."""
    import jax

    from repro.core.pruner import precompute_scales
    from repro.models import build_model
    model = build_model(cfg)
    params = jax.jit(lambda key: precompute_scales(model.init(key), policy))(
        jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    return model, params


def make_prompts(cfg, lengths, shared: int = 0, seed: int = 0):
    """Seeded token prompts of the given lengths; the first ``shared``
    tokens are one common system prompt (exercises the prefix cache)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    sysp = rng.integers(0, cfg.vocab_size, shared, dtype=np.int32)
    return [np.concatenate([sysp, rng.integers(
        0, cfg.vocab_size, n - shared, dtype=np.int32)]) for n in lengths]


def serve(model, params, policy, prompts, *, new_tokens: int, chunk: int,
          max_seq: int, slots: int, block_size: int = 16, dp: int = 1,
          tp: int = 1, arrivals=None):
    """One request stream through the public serving facade."""
    from repro.serve.api import Engine, EngineConfig
    from repro.serve.continuous import ContinuousConfig
    eng = Engine.from_config(model, EngineConfig(
        dp=dp, tp=tp, serving=ContinuousConfig(
            max_seq=max_seq, num_slots=slots, chunk_size=chunk,
            block_size=block_size, paged=True, prefix_cache=True)),
        policy=policy)
    arrivals = arrivals or [0] * len(prompts)
    rids = [eng.submit(p, max_new_tokens=new_tokens, arrival=a)
            for p, a in zip(prompts, arrivals)]
    res = eng.run(params)
    return eng, [res["outputs"][r] for r in rids]


def check_served(eng, outputs, new_tokens: int) -> None:
    """Every request done with all its tokens, nothing re-run on the jnp
    oracle, every replica's step buckets traced exactly once each."""
    m = eng.metrics
    states = [r.state for r in m.requests]
    check(states == ["done"] * len(outputs), states)
    check(all(len(o) == new_tokens for o in outputs),
          [len(o) for o in outputs])
    check(m.degraded_iterations == 0, m.degraded_iterations)
    traces = [rep.trace_counts for rep in eng.replicas]
    check(all(t and set(t.values()) == {1} for t in traces), traces)


def pruned_projections(cfg, policy) -> int:
    """Projection sites of one layer-scan body that some layer prunes in
    prefill — each must lower to exactly one fused kernel."""
    return sum(any(policy.should_prune(mod, i) for i in range(cfg.n_layers))
               for mod in PROJECTIONS) if policy.active("prefill") else 0


def step_args(ex, params):
    """Operands of the prefill step bucket for an engine replica's
    executor (one full chunk into slot 0, no decode rows)."""
    import jax.numpy as jnp
    n, c = ex.cfg.num_slots, ex.cfg.chunk_size
    key = jnp.zeros((2,), jnp.uint32)
    return (ex.place(params), ex.cache, jnp.asarray(0, jnp.int32),
            jnp.zeros((1, c), jnp.int32), jnp.asarray(c, jnp.int32), {},
            jnp.zeros((n,), jnp.int32), jnp.zeros((n,), bool), key, key,
            jnp.float32(0.0))


def projection_kernel_count(hlo_text: str) -> int:
    """Mosaic custom calls of the projection kernels in compiled HLO."""
    return sum(1 for line in hlo_text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and any(k in line for k in PROJECTION_KERNELS))


def step_kernel_counts(eng, params):
    """(projection kernels, all kernels) in the first replica's prefill
    step program: Mosaic custom calls in its compiled HLO on a TPU; where
    the interpreter runs the kernels and leaves no custom call, the
    ``pallas_call``s in its jaxpr."""
    import jax

    from repro.analysis.jaxpr_utils import iter_eqns
    ex = eng.replicas[0].exec
    args = step_args(ex, params)
    with ex._tp_scope():
        if jax.default_backend() == "tpu":
            text = ex._step_jits[PREFILL_BUCKET].lower(
                *args).compile().as_text()
            return (projection_kernel_count(text),
                    text.count('custom_call_target="tpu_custom_call"'))
        eqns = list(iter_eqns(jax.make_jaxpr(
            ex.step_program(PREFILL_BUCKET))(*args)))
    return (sum(e.params.get("name") in PROJECTION_KERNELS for e in eqns),
            sum(e.primitive.name == "pallas_call" for e in eqns))


def prefill_logits(model, params, policy, tokens, *, chunk: int,
                   block_size: int = 16, mesh=None):
    """Last-position logits of each ``chunk``-token prefill chunk of
    ``tokens``, through the paged chunked path the step program runs.
    With a 1-axis ``("model",)`` ``mesh`` the params and cache are
    replicated over it and the kernels run tensor-parallel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed import tp as tp_mod
    from repro.serve import slots as slot_ops
    from repro.serve.paged import init_paged_cache
    spec = model.paged_kv_spec()
    t = len(tokens)
    mb = -(-t // block_size)
    cache = init_paged_cache(model, 1, mb * block_size, block_size, mb, spec)
    cache["block_table"] = jnp.arange(mb, dtype=jnp.int32)[None]
    sub = slot_ops.slice_slot(cache, 0, spec)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        params, sub = jax.device_put((params, sub),
                                     NamedSharding(mesh, PartitionSpec()))
    step = jax.jit(lambda p, c, x, n: model.prefill_chunk(
        p, {"tokens": x, "chunk_len": n}, c, policy=policy))
    out = []
    with tp_mod.scope(mesh):
        for lo in range(0, t, chunk):
            x = np.asarray(tokens[lo:lo + chunk], np.int32)
            n = len(x)
            x = np.pad(x, (0, chunk - n))[None]
            logits, sub = step(params, sub, jnp.asarray(x),
                               jnp.asarray(n, jnp.int32))
            out.append(np.asarray(logits[0].astype(jnp.float32)))
    return np.stack(out)


def logit_gap(got, want, dense) -> dict:
    """``got`` against ``want`` (two runs of the same Amber model), judged
    against the gap pruning opens between ``want`` and the dense model."""
    import numpy as np
    check(np.isfinite(got).all() and np.isfinite(want).all(),
          "non-finite prefill logits")
    gap = float(np.abs(got - want).max())
    scale = float(np.abs(want - dense).max())
    return {"gap": gap, "pruning_gap": scale,
            "ratio": gap / max(scale, 1e-30),
            "argmax_agree": float((got.argmax(-1) == want.argmax(-1)).mean()),
            "argmax_amber_dense": float(
                (want.argmax(-1) == dense.argmax(-1)).mean())}


def compare_prefill(model, params, cfg, tokens, *, chunk: int, mesh=None):
    """Amber prefill logits with the kernels on against ``mesh=None``:
    the kernels off on one device; a mesh: the kernels on one device,
    the result under test tensor-parallel over ``mesh``."""
    from repro.core.policy import DENSE
    policy = amber_policy(cfg, True)
    got = prefill_logits(model, params, policy, tokens, chunk=chunk,
                         mesh=mesh)
    if mesh is None:
        policy = amber_policy(cfg, False)
    want = prefill_logits(model, params, policy, tokens, chunk=chunk)
    dense = prefill_logits(model, params, DENSE, tokens, chunk=chunk)
    return logit_gap(got, want, dense)


def nm_prune_exact(seed: int = 0) -> bool:
    """The fused N:M prune kernel against the jnp oracle, bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.policy import SparsityPolicy
    from repro.core.pruner import prune_input
    from repro.kernels import ops
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (256, 1024), jnp.bfloat16)
    scale = jax.random.uniform(k2, (1024,), jnp.bfloat16, 0.5, 1.5)
    got = ops.nm_prune(x, scale, 8, 16)
    want = prune_input(x, scale, SparsityPolicy(n=8, m=16))
    return bool(np.array_equal(np.asarray(got), np.asarray(want)))


def replica_devices(eng, params):
    """Per replica: the devices holding its cache and its params."""
    import jax
    out = []
    for rep in eng.replicas:
        ex = rep.exec
        devs = lambda tree: set().union(*(
            a.devices() for a in jax.tree_util.tree_leaves(tree)))
        out.append((devs(ex.cache), devs(ex.place(params))))
    return out


# ---------------------------------------------------------------- drivers

@dataclasses.dataclass(frozen=True)
class Workload:
    """A request stream and the serving shape that takes it."""
    lengths: tuple            # prompt tokens per request
    new_tokens: int
    chunk: int                # prefill chunk (tokens)
    max_seq: int              # KV capacity per slot
    slots: int
    block_size: int = 16
    shared: int = 0           # common system-prompt tokens
    late: bool = False        # all but the first arrive one iteration later
    compare_len: int = 0      # prompt tokens in the on/off logit comparison

    def serve(self, model, params, policy, prompts, dp=1, tp=1):
        arrivals = [0] + [int(self.late)] * (len(prompts) - 1)
        return serve(model, params, policy, prompts,
                     new_tokens=self.new_tokens, chunk=self.chunk,
                     max_seq=self.max_seq, slots=self.slots,
                     block_size=self.block_size, dp=dp, tp=tp,
                     arrivals=arrivals)


# one chip: 8 slots x 8192 tokens of paged KV; the later requests share a
# 512-token system prompt the first one has cached by then
ONE_CHIP = Workload(lengths=(1024, 4096, 1536, 3072, 2048, 2560, 1280, 3584),
                    new_tokens=16, chunk=512, max_seq=8192, slots=8,
                    shared=512, late=True, compare_len=2048)
# four chips: compile-bound, so a short stream that still spans prefill,
# mixed prefill+decode and decode steps on every layout
FOUR_CHIPS = Workload(lengths=(1024, 768, 512, 896), new_tokens=4,
                      chunk=512, max_seq=1536, slots=4)


def one_chip(cfg, wl: Workload = ONE_CHIP, *, seed: int = 0) -> None:
    policy = amber_policy(cfg)
    t = time.perf_counter()
    model, params = build(cfg, policy, seed)
    print(f"weights: {cfg.name} x{cfg.n_layers} layers, "
          f"{sum(a.size for a in _leaves(params)) / 1e9:.3f} B params "
          f"in {time.perf_counter() - t:.1f}s")

    exact = nm_prune_exact(seed)
    print(f"nm_prune kernel bit-exact vs jnp oracle: {exact}")
    check(exact, "nm_prune kernel differs from the jnp oracle")

    prompts = make_prompts(cfg, wl.lengths, shared=wl.shared, seed=seed)
    t = time.perf_counter()
    eng, outs = wl.serve(model, params, policy, prompts)
    wall = time.perf_counter() - t
    check_served(eng, outs, wl.new_tokens)
    m = eng.metrics
    print(f"served {len(outs)} requests ({sum(wl.lengths)} prompt tokens, "
          f"{m.generated_tokens} generated) in {wall:.1f}s incl. compile "
          "(first chip run, not a benchmark)")
    print(f"states={[r.state for r in m.requests]} "
          f"degraded_iterations={m.degraded_iterations} "
          f"traces={m.trace_counts} prefix_hits={m.paged.prefix_hits} "
          f"tokens_skipped={m.paged.tokens_skipped}")

    t = time.perf_counter()
    got, kernels = step_kernel_counts(eng, params)
    want = pruned_projections(cfg, policy)
    print(f"prefill step program: {got} projection kernels for {want} "
          f"pruned projections, {kernels} kernels in all "
          f"({time.perf_counter() - t:.1f}s)")
    check(got == want, (got, want))

    tokens = prompts[1][:wl.compare_len]
    t = time.perf_counter()
    info = compare_prefill(model, params, cfg, tokens, chunk=wl.chunk)
    print(f"bfloat16 {cfg.n_layers}-layer prefill logits, kernels on vs off "
          f"(information, rounding cascades): {_gaps(info)} "
          f"({time.perf_counter() - t:.1f}s)")
    del eng, model, params
    gc.collect()
    float32_comparison(cfg, tokens, "kernels on vs off", chunk=wl.chunk,
                       seed=seed)


def float32_comparison(cfg, tokens, what: str, *, chunk: int, seed: int,
                       mesh=None, served=None) -> None:
    """:func:`compare_prefill` on a float32 cut of ``cfg`` to
    COMPARE_LAYERS layers, held to LOGIT_TOLERANCE.  ``served(model,
    params)``, when given, runs first on the same cut."""
    import jax
    fcfg = dataclasses.replace(cfg, dtype="float32",
                               n_layers=min(cfg.n_layers, COMPARE_LAYERS))
    t = time.perf_counter()
    model, params = build(fcfg, amber_policy(fcfg), seed)
    with jax.default_matmul_precision("highest"):
        if served is not None:
            served(model, params)
        cmp = compare_prefill(model, params, fcfg, tokens, chunk=chunk,
                              mesh=mesh)
    print(f"float32 {fcfg.n_layers}-layer prefill logits, {what}: "
          f"{_gaps(cmp)} (tolerance {LOGIT_TOLERANCE}) "
          f"({time.perf_counter() - t:.1f}s)")
    check(cmp["ratio"] <= LOGIT_TOLERANCE, cmp)


def _gaps(cmp) -> str:
    return (f"max|diff|={cmp['gap']:.6g}, pruning gap "
            f"max|amber-dense|={cmp['pruning_gap']:.6g}, "
            f"ratio={cmp['ratio']:.6g}; greedy agreement="
            f"{cmp['argmax_agree']:.3f}, amber/dense="
            f"{cmp['argmax_amber_dense']:.3f}")


def token_agreement(got, want) -> float:
    """Share of greedy tokens equal position by position."""
    pairs = [(a, b) for g, w in zip(got, want) for a, b in zip(g, w)]
    return sum(a == b for a, b in pairs) / max(len(pairs), 1)


def four_chips(cfg, wl: Workload = FOUR_CHIPS, *, seed: int = 0) -> None:
    """dp=4 vs dp=1: identical greedy tokens, each replica's params and
    cache on its own device.  tp=4 vs tp=1: served tokens compared as
    information (bfloat16 cascades, see LOGIT_TOLERANCE), prefill logits
    of a float32 cut held to LOGIT_TOLERANCE."""
    policy = amber_policy(cfg)
    model, params = build(cfg, policy, seed)
    prompts = make_prompts(cfg, wl.lengths, shared=wl.shared, seed=seed)
    runs = {}
    for dp, tp in ((1, 1), (4, 1), (1, 4)):
        t = time.perf_counter()
        eng, outs = wl.serve(model, params, policy, prompts, dp=dp, tp=tp)
        check_served(eng, outs, wl.new_tokens)
        runs[dp, tp] = outs
        print(f"dp={dp} tp={tp}: {len(outs)} requests done in "
              f"{time.perf_counter() - t:.1f}s incl. compile "
              "(first chip run, not a benchmark)")
        if dp > 1:
            placed = replica_devices(eng, params)
            for i, (cache_devs, param_devs) in enumerate(placed):
                print(f"  replica {i}: cache on {sorted(map(str, cache_devs))}"
                      f", params on {sorted(map(str, param_devs))}")
            check(all(len(c) == 1 and c == p for c, p in placed), placed)
            check(len(set().union(*(c for c, _ in placed))) == dp, placed)
        del eng               # its params copies and cache, before the next
        gc.collect()
    same = runs[4, 1] == runs[1, 1]
    print(f"dp=4 tp=1 tokens identical to dp=1 tp=1: {same}")
    check(same, (runs[4, 1], runs[1, 1]))
    print(f"dp=1 tp=4 greedy tokens equal to dp=1 tp=1 (information): "
          f"{token_agreement(runs[1, 4], runs[1, 1]):.3f} "
          f"{[a == b for a, b in zip(runs[1, 4], runs[1, 1])]}")
    del model, params
    gc.collect()

    def served(model, params):
        outs = {}
        for tp in (1, 4):
            eng, outs[tp] = wl.serve(model, params, policy, prompts, tp=tp)
            check_served(eng, outs[tp], wl.new_tokens)
            del eng
            gc.collect()
        print("float32 cut, tp=4 greedy tokens equal to tp=1 (information): "
              f"{token_agreement(outs[4], outs[1]):.3f} "
              f"{[a == b for a, b in zip(outs[4], outs[1])]}")

    import jax
    import numpy as np
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:4]), ("model",))
    float32_comparison(cfg, prompts[0], "tp=4 vs tp=1", chunk=wl.chunk,
                       seed=seed, mesh=mesh, served=served)


def _leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(tree)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())}", file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} x{len(jax.devices())}, jax "
          f"{jax.__version__}")
    cfg = model_config()
    (four_chips if args.chips == 4 else one_chip)(cfg, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
