"""Paged KV-cache allocation (ISSUE 3).

The contract: sizing the block pool well below ``num_slots * max_seq``
must change only WHEN requests run, never WHAT they emit — greedy outputs
stay token-identical to the one-shot engine through block-budget
admission, block-table scatter/gather, and pool-exhaustion preemption —
and paging must not add shape buckets (one compile per phase).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.core.policy import DENSE, paper_policy
from repro.core.pruner import precompute_scales
from repro.models import build_model
from repro.serve import (ContinuousConfig, ContinuousServingEngine,
                         ServeConfig, ServingEngine)
from repro.serve.paged import BlockPool

MAX_SEQ = 64


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(get_smoke_config("llama31_8b"),
                              dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, lens, seed0=10):
    return [np.asarray(jax.random.randint(jax.random.PRNGKey(seed0 + i),
                                          (l,), 0, cfg.vocab_size))
            for i, l in enumerate(lens)]


def _oracle(model, params, policy, prompt, max_new):
    eng = ServingEngine(model, policy, ServeConfig(max_seq=MAX_SEQ))
    out = eng.generate(params, {"tokens": jnp.asarray(prompt)[None, :]},
                       max_new_tokens=max_new)
    return np.asarray(out["tokens"])[0].tolist()


def _serve(model, params, policy, prompts, arrivals, max_new, **cfg_kw):
    eng = ContinuousServingEngine(model, policy, ContinuousConfig(
        max_seq=MAX_SEQ, **cfg_kw))
    for p, a, mn in zip(prompts, arrivals, max_new):
        eng.submit(p, max_new_tokens=mn, arrival=a)
    return eng, eng.run(params)


# ------------------------------------------------------------- BlockPool

def test_block_pool_never_double_allocates():
    pool = BlockPool(num_blocks=8, block_size=4)
    a = pool.alloc(5)
    b = pool.alloc(3)
    assert len(set(a + b)) == 8, "same block handed out twice"
    assert pool.available == 0
    with pytest.raises(RuntimeError):
        pool.alloc(1)
    pool.release(b)
    c = pool.alloc(2)
    assert not set(c) & set(a), "released-and-reissued id collided with live"
    with pytest.raises(AssertionError):
        pool.release(b[:1] + b[:1])        # double free
    assert pool.peak_in_use == 8


def test_block_pool_fragmentation_roundtrip():
    """Interleaved alloc/free (fragmenting pattern) round-trips: every id
    returns exactly once and the pool refills completely."""
    pool = BlockPool(num_blocks=16, block_size=2)
    held = {}
    rng = np.random.default_rng(0)
    for step in range(200):
        if held and (pool.available == 0 or rng.random() < 0.45):
            key = rng.choice(list(held))
            pool.release(held.pop(key))
        else:
            n = int(rng.integers(1, min(4, pool.available) + 1))
            held[step] = pool.alloc(n)
        live = [i for ids in held.values() for i in ids]
        assert len(live) == len(set(live)) == pool.in_use
    for ids in held.values():
        pool.release(ids)
    assert pool.available == 16
    assert sorted(pool.alloc(16)) == list(range(16))


def test_blocks_for():
    pool = BlockPool(num_blocks=4, block_size=8)
    assert pool.blocks_for(1) == 1
    assert pool.blocks_for(8) == 1
    assert pool.blocks_for(9) == 2


def test_block_pool_alloc_validates_before_mutating():
    """ISSUE-5 bugfix: the double-allocation check must fire BEFORE any
    block leaves the free list — the old implementation popped first and
    asserted after, so the failing path corrupted pool state.  Inject a
    duplicate id into the free list and check the failed alloc leaves the
    pool exactly as it found it."""
    pool = BlockPool(num_blocks=4, block_size=2)
    live = pool.alloc(2)
    pool._free.appendleft(live[0])          # simulated corruption
    free_before = list(pool._free)
    ref_before = dict(pool._ref)
    with pytest.raises(AssertionError, match="double allocation"):
        pool.alloc(2)
    assert list(pool._free) == free_before, "failed alloc mutated free list"
    assert dict(pool._ref) == ref_before, "failed alloc leaked references"
    # exhaustion is still validated first and still RuntimeError
    pool._free.popleft()                    # undo the corruption
    with pytest.raises(RuntimeError):
        pool.alloc(3)
    assert pool.available == 2 and pool.in_use == 2


# --------------------------------------------- engine under a 50% pool

@pytest.mark.parametrize("attn_kernel", [False, True],
                         ids=["gather-oracle", "pallas-kernel"])
def test_half_pool_token_identical_one_trace(tiny, attn_kernel):
    """Acceptance: pool at 50% of num_slots*max_seq, staggered greedy
    outputs token-identical to the one-shot engine, one compile per shape
    bucket — on the jnp gather oracle AND (ISSUE-4) on the Pallas
    block-table-walk kernel (interpreted on the CPU)."""
    cfg, model, params = tiny
    policy = DENSE.with_(use_pallas_kernels=True) if attn_kernel else DENSE
    slots, bs = 3, 8
    half_pool = (slots * MAX_SEQ) // (2 * bs)          # 50% of the slab
    lens, arrivals, max_new = [5, 21, 13, 30, 9], [0, 0, 2, 4, 7], \
        [8, 10, 6, 8, 12]
    prompts = _prompts(cfg, lens)
    eng, res = _serve(model, params, policy, prompts, arrivals, max_new,
                      num_slots=slots, chunk_size=16,
                      block_size=bs, num_blocks=half_pool)
    assert eng.paged and eng.pool.num_blocks == half_pool
    assert res["metrics"]["paged"]["attention_kernel"] is attn_kernel
    for i, p in enumerate(prompts):
        assert res["outputs"][i] == _oracle(model, params, DENSE, p,
                                            max_new[i]), f"request {i}"
    # fused one-dispatch default: one step program per phase-presence bucket
    assert all(v == 1 for v in eng.trace_counts.values()), eng.trace_counts
    assert set(eng.trace_counts) <= {"step_prefill", "step_decode",
                                     "step_prefill_decode"}, eng.trace_counts
    assert res["metrics"]["dispatches_per_iteration"] == 1.0
    pg = res["metrics"]["paged"]
    assert pg["enabled"] and pg["peak_blocks_in_use"] <= half_pool
    # the pool must have been genuinely shared/recycled, not just sliced
    assert eng.pool.total_allocs > half_pool
    assert eng.pool.in_use == 0                         # all released


def test_pool_exhaustion_preempts_and_preserves_tokens(tiny):
    """Two long-decoding requests over a pool that cannot hold both:
    the youngest is preempted (blocks released, requeued) and every
    output stream still matches the one-shot engine."""
    cfg, model, params = tiny
    bs = 4
    # each request peaks at ceil((10+24)/4) = 9 blocks; pool of 12 admits
    # both (3+3 at admission) but cannot carry both through decode
    lens, arrivals, max_new = [10, 10], [0, 0], [24, 24]
    prompts = _prompts(cfg, lens, seed0=40)
    eng, res = _serve(model, params, DENSE, prompts, arrivals, max_new,
                      num_slots=2, chunk_size=8, block_size=bs,
                      num_blocks=12)
    pg = res["metrics"]["paged"]
    assert pg["preemptions"] > 0, "scenario failed to exhaust the pool"
    for i, p in enumerate(prompts):
        assert res["outputs"][i] == _oracle(model, params, DENSE, p,
                                            max_new[i]), f"request {i}"
    reqs = {r["rid"]: r for r in res["metrics"]["requests"]}
    assert reqs[1]["preemptions"] > 0          # youngest was the victim
    assert reqs[0]["preemptions"] == 0         # oldest never requeued
    assert eng.pool.in_use == 0


def test_preemption_sparse_prefill_replays_dense(tiny):
    """Preemption under an Amber-sparse prefill policy: emitted tokens are
    replayed through the DENSE program (their KV was first written by the
    dense decode step), so outputs still match the one-shot engine."""
    cfg, model, params = tiny
    policy = paper_policy(2, 4, cfg.qgate_skip_layers)
    params = precompute_scales(params, policy)
    lens, arrivals, max_new = [10, 10], [0, 0], [24, 24]
    prompts = _prompts(cfg, lens, seed0=60)
    eng, res = _serve(model, params, policy, prompts, arrivals, max_new,
                      num_slots=2, chunk_size=8, block_size=4,
                      num_blocks=12)
    assert res["metrics"]["paged"]["preemptions"] > 0
    for i, p in enumerate(prompts):
        assert res["outputs"][i] == _oracle(model, params, policy, p,
                                            max_new[i]), f"request {i}"
    # replay is its own step bucket, compiled once per phase-presence combo
    assert any(k.startswith("step_replay") for k in eng.trace_counts), \
        eng.trace_counts
    assert all(v == 1 for v in eng.trace_counts.values()), eng.trace_counts


def test_admission_gated_by_block_budget(tiny):
    """A pool that fits one request at a time serializes admission instead
    of preempting: the second request waits for blocks, outputs and the
    free list stay intact."""
    cfg, model, params = tiny
    lens, arrivals, max_new = [16, 16], [0, 0], [8, 8]
    prompts = _prompts(cfg, lens, seed0=80)
    eng, res = _serve(model, params, DENSE, prompts, arrivals, max_new,
                      num_slots=2, chunk_size=8, block_size=8,
                      num_blocks=3)   # ceil(24/8)=3 → one request at a time
    for i, p in enumerate(prompts):
        assert res["outputs"][i] == _oracle(model, params, DENSE, p,
                                            max_new[i]), f"request {i}"
    reqs = {r["rid"]: r for r in res["metrics"]["requests"]}
    assert reqs[1]["admitted_iter"] >= reqs[0]["done_iter"]
    assert res["metrics"]["paged"]["preemptions"] == 0


def test_paged_auto_disabled_where_pointless():
    """Archs with no full-attention KV (pure recurrent) fall back to the
    dense slab automatically and still serve correctly."""
    cfg = dataclasses.replace(get_smoke_config("rwkv6_7b"), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ContinuousServingEngine(model, DENSE, ContinuousConfig(
        max_seq=MAX_SEQ, num_slots=2, chunk_size=8))
    assert not eng.paged and eng.pool is None
    eng.submit(_prompts(cfg, [9], seed0=90)[0], max_new_tokens=4)
    res = eng.run(params)
    assert res["metrics"]["paged"] == {"enabled": False}
    assert len(res["outputs"][0]) == 4


def test_unservable_request_rejected_not_livelocked(tiny):
    """ISSUE-5 bugfix (head-of-line livelock): a request whose replay
    sequence can never fit the pool must be REJECTED at admission, not
    waited on forever — strict FCFS would otherwise starve every request
    behind it.  ``submit`` guards the normal path, so craft the oversized
    request directly (as a preemption-grown replay would look)."""
    from repro.serve.scheduler import REJECTED, Request
    cfg, model, params = tiny
    eng = ContinuousServingEngine(model, DENSE, ContinuousConfig(
        max_seq=MAX_SEQ, num_slots=2, chunk_size=8, block_size=8,
        num_blocks=3))                      # pool holds 24 tokens
    big = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (30,), 0,
                                        cfg.vocab_size), np.int32)
    # blocks_for(30) = 4 > 3: unservable forever; enqueue behind nothing
    # and ahead (by rid order at equal arrival) of a healthy request
    eng.requests.append(Request(rid=0, tokens=big, max_new_tokens=4))
    ok = _prompts(cfg, [10], seed0=95)[0]
    eng.submit(ok, max_new_tokens=4, arrival=0)
    res = eng.run(params)
    reqs = {r["rid"]: r for r in res["metrics"]["requests"]}
    assert reqs[0]["state"] == REJECTED and reqs[0]["n_out"] == 0
    assert res["metrics"]["paged"]["rejections"] == 1
    # the queue behind the dead request made progress and fully completed
    assert reqs[1]["state"] == "done"
    assert res["outputs"][1] == _oracle(model, params, DENSE, ok, 4)
    assert eng.pool.in_use == 0


def test_preempt_prefill_victim_interleaving(tiny):
    """ISSUE-5 audit pin: ``_ensure_decode_blocks`` may preempt a victim
    that is still in PREFILL, in the same scheduler iteration in which the
    victim's chunk program already ran — its freed blocks can be handed to
    a decoding slot immediately.  The host-table write ordering (victim row
    -1'd and re-synced before the next device program) plus kv_len fencing
    must keep the interleaving invisible: outputs stay token-identical.
    Engineered deterministically: req0 decodes and crosses a block
    boundary exactly while req1 (40-token prompt, 5 chunks) is mid-prefill
    with the pool fully committed."""
    from repro.serve.scheduler import PREFILL
    cfg, model, params = tiny
    lens, arrivals, max_new = [8, 40], [0, 2], [24, 8]
    prompts = _prompts(cfg, lens, seed0=85)
    eng, res = _serve(model, params, DENSE, prompts, arrivals, max_new,
                      num_slots=2, chunk_size=8, block_size=4,
                      num_blocks=13, validate_pool=True)
    assert any(rid == 1 and st == PREFILL for rid, st in eng.preempt_log), \
        f"scenario drifted: preempt_log={eng.preempt_log}"
    for i, p in enumerate(prompts):
        assert res["outputs"][i] == _oracle(model, params, DENSE, p,
                                            max_new[i]), f"request {i}"
    assert eng.pool.in_use == 0


def test_submit_rejects_over_pool_capacity(tiny):
    cfg, model, params = tiny
    eng = ContinuousServingEngine(model, DENSE, ContinuousConfig(
        max_seq=MAX_SEQ, num_slots=2, chunk_size=8, block_size=8,
        num_blocks=2))                     # 16 tokens of pool capacity
    with pytest.raises(AssertionError):
        eng.submit(np.arange(10, dtype=np.int32), max_new_tokens=10)


# ----------------------------------------- paged-attention tile counters

@pytest.mark.parametrize("attn_kernel", [False, True],
                         ids=["gather-oracle", "pallas-kernel"])
def test_attention_tile_counters(tiny, attn_kernel):
    """``paged.attention_tiles_*`` replay the kernel's KV-tile walk per
    attention layer: at max_seq 64 and block 8 a row is one tile of 8
    pages, and the smoke model has 2 KV heads.  One 20-token request,
    chunk 16, 3 tokens out, 2 slots: two prefill dispatches (one visible
    tile each), then two decode dispatches walking both slots, of which
    only the live one is visible.  Zero with the gather oracle."""
    cfg, model, params = tiny
    policy = DENSE.with_(use_pallas_kernels=True) if attn_kernel else DENSE
    eng, res = _serve(model, params, policy, _prompts(cfg, [20]), [0], [3],
                      num_slots=2, chunk_size=16, block_size=8)
    pg = res["metrics"]["paged"]
    hkv, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    if attn_kernel:
        assert (pg["attention_tiles_walked"],
                pg["attention_tiles_visible"]) == (6 * hkv, 4 * hkv)
        assert tuple(pg["attention_tile"]) == (g * 16, 8)
    else:
        assert (pg["attention_tiles_walked"], pg["attention_tiles_visible"],
                tuple(pg["attention_tile"])) == (0, 0, (0, 0))


# ------------------------------------------- unallocated-block fencing

def test_unallocated_block_fence_survives_poison():
    """Regression for the ``-1`` → block-0 clip contract: unallocated table
    entries resolve to physical block 0 during the gather, so whatever
    block 0 holds must NEVER reach an output.  Poison it with NaN (the one
    value a 0-probability softmax fence cannot absorb, 0·NaN = NaN) and
    assert paged prefill- and decode-shaped attention outputs are
    bit-identical to the clean pool — on the jnp oracle and the kernel."""
    from repro.models.attention import paged_attention
    rng = np.random.default_rng(3)
    nb, bs, mb, B, Hq, Hkv, hd = 12, 8, 6, 3, 4, 2, 16
    kp = np.asarray(rng.normal(size=(nb, Hkv, bs, hd)), np.float32)
    vp = np.asarray(rng.normal(size=(nb, Hkv, bs, hd)), np.float32)
    # disjoint per-row prefixes over blocks 1..11; block 0 stays free
    tab = np.full((B, mb), -1, np.int32)
    tab[0, :3] = [5, 1, 8]
    tab[1, :5] = [3, 9, 2, 7, 4]
    tab[2, :2] = [6, 10]
    assert (tab != 0).all()
    poisoned_k = kp.copy()
    poisoned_v = vp.copy()
    poisoned_k[0] = np.nan
    poisoned_v[0] = np.nan

    q_pre = np.asarray(rng.normal(size=(B, 8, Hq, hd)), np.float32)
    q_dec = np.asarray(rng.normal(size=(B, 1, Hq, hd)), np.float32)
    posv = jnp.asarray([20, 37, 10], jnp.int32)
    calls = {
        "prefill": (q_pre, dict(causal=True,
                                q_offset=jnp.asarray(13, jnp.int32),
                                kv_len=jnp.asarray([21, 38, 15], jnp.int32),
                                chunk=16)),
        "decode": (q_dec, dict(causal=False, q_offset=posv,
                               kv_len=posv + 1, chunk=16)),
    }
    for name, (q, kw) in calls.items():
        for use_kernel in (False, True):
            clean = paged_attention(
                jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(tab), use_kernel=use_kernel, interpret=True,
                **kw)
            dirty = paged_attention(
                jnp.asarray(q), jnp.asarray(poisoned_k),
                jnp.asarray(poisoned_v), jnp.asarray(tab),
                use_kernel=use_kernel, interpret=True, **kw)
            assert np.isfinite(np.asarray(dirty)).all(), \
                f"{name} kernel={use_kernel}: NaN leaked through the fence"
            np.testing.assert_array_equal(
                np.asarray(clean), np.asarray(dirty),
                err_msg=f"{name} kernel={use_kernel}")


# -------------------------------------- no full-view gather on the hot path

def _pool_gather_count(jaxpr, pool_shape) -> int:
    from repro.analysis.jaxpr_utils import pool_eqn_count
    return pool_eqn_count(jaxpr, pool_shape, "gather")


def test_paged_hot_path_has_no_full_view_gather(tiny):
    """Acceptance: with the kernel enabled, the jitted paged prefill-chunk
    and decode programs contain NO gather that reads the pooled KV leaves
    (the O(max_blocks·block_size) logical-view materialization) — and with
    it disabled the oracle gather is still there (the check bites)."""
    from repro.serve import slots as slot_ops
    from repro.serve.paged import (device_pool_rows, init_paged_cache,
                                   max_blocks_per_slot)
    cfg, model, params = tiny
    slots, bs = 2, 8
    mb = max_blocks_per_slot(MAX_SEQ, bs)
    nb = slots * mb
    spec = model.paged_kv_spec()
    cache = init_paged_cache(model, slots, MAX_SEQ, bs, nb, spec)
    tab = np.full((slots, mb), -1, np.int32)
    tab[0, :3] = [1, 2, 3]
    tab[1, :3] = [4, 5, 6]
    cache["block_table"] = jnp.asarray(tab)
    cache["pos"] = jnp.asarray([10, 7], jnp.int32)
    pool_shape = (device_pool_rows(nb), cfg.n_kv_heads, bs, cfg.head_dim)
    kernel_pol = DENSE.with_(use_pallas_kernels=True)

    toks = jnp.zeros((slots, 1), jnp.int32)
    dec = lambda pol: jax.make_jaxpr(
        lambda t, c: model.decode_step(params, t, c, policy=pol))(toks, cache)
    assert _pool_gather_count(dec(kernel_pol).jaxpr, pool_shape) == 0
    assert _pool_gather_count(dec(DENSE).jaxpr, pool_shape) > 0

    sub = slot_ops.slice_slot(cache, jnp.asarray(0, jnp.int32), spec)
    batch = {"tokens": jnp.zeros((1, 16), jnp.int32),
             "chunk_len": jnp.asarray(16, jnp.int32)}
    pre = lambda pol: jax.make_jaxpr(
        lambda b, c: model.prefill_chunk(params, b, c, policy=pol))(batch,
                                                                    sub)
    assert _pool_gather_count(pre(kernel_pol).jaxpr, pool_shape) == 0
    assert _pool_gather_count(pre(DENSE).jaxpr, pool_shape) > 0
