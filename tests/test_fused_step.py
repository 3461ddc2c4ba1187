"""One-dispatch iterations (ISSUE 7): the fused hybrid step program.

Covers the tentpole contract and its satellites:

  * ``_dyadic_sizes`` properties — non-increasing powers of two ≤ cap that
    sum exactly to the requested length, and the empty ladder for a zero
    remainder (the infinite-loop / IndexError bugfix).
  * Token-identity: the one-dispatch engine matches the one-shot oracle
    across staggered bucket shapes, with ``dispatches_per_iteration == 1``
    on clean runs; with an Amber-sparse prefill the Pallas kernels match
    the kernels-off policy token for token.
  * Scheduling: the decode roster is frozen before dispatch, so a request
    samples its first token in the iteration of its last prefill chunk
    and decodes from the next one.
  * Compile discipline: exactly one step program per phase-presence
    bucket, and with kernels on the step program's jaxpr carries ZERO
    pool-shaped gathers or scatters outside a ``pallas_call`` (the KV
    scatter moved in-kernel; the jnp oracle keeps both, so the pin bites).
  * Chaos: seeds 0-2 stay green on the one step path.
  * Latency report (bugfix): ``visible_t`` is stamped unconditionally,
    so no terminal request — finished, cancelled, or timed out — reports
    the garbage ``-1.0`` default through the ``--trace`` latency report.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.configs.base import get_smoke_config
from repro.core.policy import DENSE, paper_policy
from repro.core.pruner import precompute_scales
from repro.models import build_model
from repro.serve import (ContinuousConfig, ContinuousServingEngine,
                         ServeConfig, ServingEngine)
from repro.serve.faults import FaultInjector, FaultSpec
from repro.serve.scheduler import TERMINAL, _dyadic_sizes

MAX_SEQ = 64


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(get_smoke_config("llama31_8b"),
                              dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, lens, seed0=700):
    return [np.asarray(jax.random.randint(jax.random.PRNGKey(seed0 + i),
                                          (l,), 0, cfg.vocab_size))
            for i, l in enumerate(lens)]


def _oracle(model, params, policy, prompt, max_new):
    eng = ServingEngine(model, policy, ServeConfig(max_seq=MAX_SEQ))
    out = eng.generate(params, {"tokens": jnp.asarray(prompt)[None, :]},
                       max_new_tokens=max_new)
    return np.asarray(out["tokens"])[0].tolist()


def _serve(model, policy, params, prompts, arrivals, max_new, **kw):
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("num_slots", 2)
    kw.setdefault("chunk_size", 8)
    kw.setdefault("block_size", 4)
    kw.setdefault("validate_pool", True)
    eng = ContinuousServingEngine(model, policy,
                                  ContinuousConfig(**kw))
    for p, a, mn in zip(prompts, arrivals, max_new):
        eng.submit(p, max_new_tokens=mn, arrival=a)
    return eng, eng.run(params)


# ------------------------------------------------ dyadic chunk ladder

def test_dyadic_zero_length_is_empty():
    """The bugfix: a zero/negative remainder terminates with an empty
    ladder instead of spinning the halving loop forever."""
    assert _dyadic_sizes(0, 16) == []
    assert _dyadic_sizes(-3, 16) == []
    assert _dyadic_sizes(0, 1) == []


def test_dyadic_known_ladders():
    assert _dyadic_sizes(13, 8) == [8, 4, 1]
    assert _dyadic_sizes(8, 8) == [8]
    assert _dyadic_sizes(1, 64) == [1]
    assert _dyadic_sizes(7, 2) == [2, 2, 2, 1]


@settings(max_examples=200, deadline=None)
@given(length=st.integers(min_value=0, max_value=4096),
       cap=st.integers(min_value=1, max_value=512))
def test_dyadic_properties(length, cap):
    """Every ladder: powers of two, ≤ cap, non-increasing, exact sum."""
    sizes = _dyadic_sizes(length, cap)
    assert sum(sizes) == max(length, 0)
    assert all(s & (s - 1) == 0 and 0 < s <= cap for s in sizes)
    assert all(a >= b for a, b in zip(sizes, sizes[1:]))
    assert (sizes == []) == (length <= 0)


# ------------------------------------------ step path vs one-shot oracle

def test_fused_token_identity_across_buckets(tiny):
    """Staggered mixed-length stream exercising every phase-presence
    bucket (prefill-only, hybrid, decode-only): the one-dispatch engine
    is token-identical to the per-request one-shot oracle, at exactly one
    dispatch per iteration."""
    cfg, model, params = tiny
    lens, arrivals = [9, 27, 14, 33, 21, 12], [0, 0, 2, 4, 5, 8]
    max_new = [12] * len(lens)
    prompts = _prompts(cfg, lens)
    eng, res = _serve(model, DENSE, params, prompts, arrivals, max_new)
    for i, p in enumerate(prompts):
        assert res["outputs"][i] == _oracle(model, params, DENSE, p,
                                            max_new[i]), f"request {i}"
    assert res["metrics"]["dispatches_per_iteration"] == 1.0
    # all three hybrid buckets actually ran, each compiled exactly once
    assert eng.trace_counts == {"step_prefill": 1, "step_decode": 1,
                                "step_prefill_decode": 1}, eng.trace_counts


@pytest.mark.parametrize("n,m", [(2, 4), (8, 16)])
def test_fused_token_identity_sparse_prefill_kernels(tiny, n, m):
    """Under an Amber-sparse prefill policy the Pallas dispatch ladder
    (in-kernel KV scatter, fused projections, paged attention) serves
    the same stream token for token as the kernels-off policy, at one
    dispatch per iteration."""
    cfg, model, params = tiny
    policy = paper_policy(n, m, cfg.qgate_skip_layers,
                          use_pallas_kernels=True)
    params = precompute_scales(params, policy)
    lens, arrivals, max_new = [7, 17, 12], [0, 0, 2], [6, 8, 6]
    prompts = _prompts(cfg, lens, seed0=720)
    ek, rk = _serve(model, policy, params, prompts, arrivals, max_new)
    eo, ro = _serve(model, policy.with_(use_pallas_kernels=False), params,
                    prompts, arrivals, max_new)
    assert ek.exec.paged_kernel and not eo.exec.paged_kernel
    assert rk["outputs"] == ro["outputs"]
    assert rk["metrics"]["dispatches_per_iteration"] == 1.0
    assert rk["metrics"]["degraded_iterations"] == 0


def test_final_chunk_decodes_next_iteration(tiny):
    """The decode roster is frozen before dispatch: a request whose last
    prefill chunk lands in iteration i samples its first token in i and
    decodes from i + 1, so with max_new_tokens = k and no other traffic
    it finishes in iteration i + k - 1."""
    cfg, model, params = tiny
    k = 5
    prompt = _prompts(cfg, [19], seed0=780)[0]   # three chunks of 8
    eng = ContinuousServingEngine(model, DENSE, ContinuousConfig(
        max_seq=MAX_SEQ, num_slots=2, chunk_size=8, block_size=4,
        validate_pool=True))
    eng.submit(prompt, max_new_tokens=k, arrival=0)
    filled = {}     # prefill progress seen at the top of each iteration
    eng.iteration_hook = lambda e, it: filled.setdefault(
        it, e.requests[0].filled)
    res = eng.run(params)
    # the chunk that completed the prompt ran one iteration before the
    # first iteration to see it whole
    i = min(it for it, f in filled.items() if f == len(prompt)) - 1
    assert filled[i] < len(prompt)
    (r,) = res["metrics"]["requests"]
    assert r["first_token_iter"] == i
    assert r["done_iter"] == i + k - 1
    assert res["outputs"][0] == _oracle(model, params, DENSE, prompt, k)


# --------------------------------------------------- jaxpr dispatch pins

from repro.analysis.jaxpr_utils import (  # noqa: E402
    pool_eqn_count as _pool_eqn_count)


def test_step_program_pool_ops_stay_in_kernel(tiny):
    """Acceptance pin: with kernels on, the fused hybrid step program
    (prefill chunk + batched decode in ONE jaxpr) contains zero gathers
    AND zero scatters on pool-shaped KV arrays — both the logical-view
    gather and the host-side flat-index KV scatter moved inside
    pallas_call.  With kernels off the oracle forms are still there, so
    the pin bites."""
    from repro.serve.paged import (device_pool_rows, init_paged_cache,
                                   max_blocks_per_slot)
    cfg, model, params = tiny
    slots, bs = 2, 8
    mb = max_blocks_per_slot(MAX_SEQ, bs)
    nb = slots * mb
    rows = device_pool_rows(nb)
    # the head-major pooled-KV leaves (+1 sentinel row)
    pool_shapes = {(rows, cfg.n_kv_heads, bs, cfg.head_dim)}

    def jaxpr_for(kernels):
        pol = DENSE.with_(use_pallas_kernels=kernels)
        eng = ContinuousServingEngine(model, pol, ContinuousConfig(
            max_seq=MAX_SEQ, num_slots=slots, chunk_size=8, block_size=bs))
        cache = init_paged_cache(model, slots, MAX_SEQ, bs, nb, eng._spec)
        tab = np.full((slots, mb), -1, np.int32)
        tab[0, :3], tab[1, :3] = [1, 2, 3], [4, 5, 6]
        cache["block_table"] = jnp.asarray(tab)
        cache["pos"] = jnp.asarray([10, 7], jnp.int32)
        step = eng._step_raw[(False, True, True)]   # the hybrid bucket
        args = (params, cache, jnp.asarray(0, jnp.int32),
                jnp.zeros((1, 8), jnp.int32), jnp.asarray(8, jnp.int32),
                {}, jnp.zeros((slots,), jnp.int32),
                jnp.asarray([False, True]), jnp.zeros((2,), jnp.uint32),
                jnp.zeros((2,), jnp.uint32), jnp.float32(0.0))
        return jax.make_jaxpr(step)(*args).jaxpr

    hot = jaxpr_for(True)
    assert _pool_eqn_count(hot, pool_shapes, "gather") == 0
    assert _pool_eqn_count(hot, pool_shapes, "scatter") == 0
    oracle = jaxpr_for(False)
    assert _pool_eqn_count(oracle, pool_shapes, "gather") > 0
    assert _pool_eqn_count(oracle, pool_shapes, "scatter") > 0


# ----------------------------------------------------- chaos, fused path

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chaos_seeds_green_fused(tiny, seed):
    """The CI chaos matrix contract: mixed fault schedule under the fused
    step, seeds 0-2 — surviving outputs match the undisturbed fused run,
    nothing leaks, every request ends terminal."""
    cfg, model, params = tiny
    lens, arrivals, max_new = [11, 18, 7, 13], [0, 1, 2, 4], [7] * 4
    prompts = _prompts(cfg, lens, seed0=740)

    def serve(faults):
        eng = ContinuousServingEngine(model, DENSE, ContinuousConfig(
            max_seq=MAX_SEQ, num_slots=2, chunk_size=8, block_size=4,
            validate_pool=True), faults=faults)
        for p, a, mn in zip(prompts, arrivals, max_new):
            eng.submit(p, max_new_tokens=mn, arrival=a)
        return eng, eng.run(params)

    _, base = serve(None)
    inj = FaultInjector(seed=seed, schedule=[
        FaultSpec("prefill", "nonfinite", p=0.2, limit=3),
        FaultSpec("decode", "nonfinite", p=0.2, limit=3),
        FaultSpec("pool.alloc", "exhausted", p=0.2, limit=3),
    ])
    eng, res = serve(inj)
    assert res["outputs"] == base["outputs"], \
        f"seed {seed}: faults changed tokens"
    assert all(r.state in TERMINAL for r in eng.requests)
    assert all(not r.blocks and r.slot == -1 for r in eng.requests)
    assert eng.pool.in_use == 0
    deg = res["metrics"]["degraded_iterations"]
    assert deg == sum(1 for f in inj.fired
                      if f["site"] in ("prefill", "decode"))


# --------------------------------------- latency-report bugfix (--trace)

def test_terminal_latency_never_default(tiny):
    """Every terminal request — done, timed out, cancelled — carries a
    real non-negative wall-clock latency_s, including requests admitted
    the same iteration they became visible (previously stamped only while
    still WAITING → the -1.0 default leaked into the report)."""
    cfg, model, params = tiny
    prompts = _prompts(cfg, [9, 14, 40], seed0=760)
    eng = ContinuousServingEngine(model, DENSE, ContinuousConfig(
        max_seq=MAX_SEQ, num_slots=2, chunk_size=8, block_size=4,
        validate_pool=True, ttl_default=None))
    eng.submit(prompts[0], max_new_tokens=6, arrival=0)
    eng.submit(prompts[1], max_new_tokens=6, arrival=1)
    eng.submit(prompts[2], max_new_tokens=6, arrival=2, ttl=3)  # times out
    rid_cancel = eng.submit(prompts[1], max_new_tokens=6, arrival=3)
    eng.iteration_hook = lambda e, it: (it == 4 and e.cancel(rid_cancel))
    res = eng.run(params)
    states = {r["rid"]: r for r in res["metrics"]["requests"]}
    assert states[2]["state"] == "timed_out"
    assert states[rid_cancel]["state"] == "cancelled"
    for r in res["metrics"]["requests"]:
        assert r["latency_s"] >= 0.0, \
            f"rid {r['rid']} ({r['state']}): garbage latency {r['latency_s']}"


def test_trace_mode_latency_report(capsys):
    """launch.serve --trace end-to-end: exits 0 and the CSV latency column
    contains no -1.0 defaults (the arrival-stamp regression)."""
    from repro.launch.serve import main
    rc = main(["--smoke", "--arch", "llama31_8b", "--trace",
               "--num-requests", "4", "--rate", "0.7", "--len-range",
               "6:20", "--slots", "2", "--chunk", "8", "--new-tokens", "5"])
    out = capsys.readouterr().out
    assert rc == 0
    rows = [l for l in out.splitlines()
            if l and l[0].isdigit()]
    assert rows, out
    for row in rows:
        lat = float(row.split(",")[7])
        assert lat >= 0.0, row
    assert "dispatches" in out and "1.00 per work iteration" in out
