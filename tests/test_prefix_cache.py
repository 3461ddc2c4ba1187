"""Block-level prefix caching across requests (ISSUE 5).

The contract: a refcounted, content-addressed block pool may only ever
change WHEN prefill compute happens, never WHAT any request emits —
greedy outputs stay token-identical to the one-shot engine (and to the
same engine with caching off) while shared-system-prompt traffic skips
the shared blocks' prefill entirely.  Pool invariants: no block is ever
simultaneously writable from two slots, and refcounts drain to a fully
reclaimable pool.
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
from repro.serve.paged import BlockPool, chain_block_hashes

MAX_SEQ = 64


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(get_smoke_config("llama31_8b"),
                              dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _rand_tokens(cfg, n, seed):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         cfg.vocab_size), np.int32)


def _oracle(model, params, policy, prompt, max_new):
    eng = ServingEngine(model, policy, ServeConfig(max_seq=MAX_SEQ))
    out = eng.generate(params, {"tokens": jnp.asarray(prompt)[None, :]},
                       max_new_tokens=max_new)
    return np.asarray(out["tokens"])[0].tolist()


# ----------------------------------------------------------- chain hashes

def test_chain_hashes_address_the_whole_prefix():
    toks = np.arange(40, dtype=np.int32)
    h = chain_block_hashes(toks, 8)
    assert len(h) == 5 and len(set(h)) == 5
    # same block content, different prefix → different hash
    other = toks.copy()
    other[0] += 1
    assert chain_block_hashes(other, 8)[3] != h[3]
    # identical prefix → identical chain, regardless of suffix
    assert chain_block_hashes(toks[:17], 8) == h[:2]


def test_chain_hashes_salt_dense_written_rows():
    """Under a sparse prefill policy, rows a request EMITTED were written
    by the dense program; a different request whose own prompt spans those
    rows would prefill them sparsely, so the per-block dense-row count
    must split the hash space.  Pure-prompt blocks stay shared."""
    toks = np.arange(32, dtype=np.int32)
    a = chain_block_hashes(toks, 8, dense_from=20)   # emitted from row 20
    b = chain_block_hashes(toks, 8, dense_from=None)  # all one path
    assert a[:2] == b[:2], "blocks before the boundary must still match"
    assert a[2] != b[2] and a[3] != b[3]
    # same boundary reproduces the chain (preemption replay re-match)
    assert chain_block_hashes(toks, 8, dense_from=20) == a


# ------------------------------------------------------ BlockPool lifecycle

def test_pool_refcount_and_lru_lifecycle():
    pool = BlockPool(num_blocks=6, block_size=4)
    toks = np.arange(8, dtype=np.int32)
    h = chain_block_hashes(toks, 4)
    a = pool.alloc(2)
    for bid, hh in zip(a, h):
        assert pool.register(bid, hh)
    # a second copy of the same content loses the index race
    dup = pool.alloc(1)
    assert not pool.register(dup[0], h[0])
    assert pool.match(h) == a
    # share with a live holder: refcount 2, still matched
    for bid in pool.match(h):
        pool.acquire_cached(bid)
    assert pool.refcount(a[0]) == 2
    pool.release(a)
    assert pool.refcount(a[0]) == 1 and pool.match(h) == a
    # last ref dropped → parked in the LRU, still matchable, not free
    pool.release(a[::-1])
    assert pool.in_use == 1                      # only dup remains live
    assert pool.cached_blocks == 2 and pool.match(h) == a
    # revive from the LRU
    pool.acquire_cached(a[0])
    assert pool.refcount(a[0]) == 1 and pool.cached_blocks == 1
    pool.release([a[0]])
    # unregistered release goes straight back to the free list
    pool.release(dup)
    assert pool.in_use == 0
    assert pool.available == 6 and pool.cached_blocks == 2
    pool.check_invariants()


def test_pool_evicts_lru_before_reporting_exhaustion():
    pool = BlockPool(num_blocks=4, block_size=2)
    toks = np.arange(8, dtype=np.int32)
    h = chain_block_hashes(toks, 2)
    a = pool.alloc(4)
    for bid, hh in zip(a, h):
        pool.register(bid, hh)
    pool.release(a[::-1])                       # chain head at MRU end
    assert pool.available == 4 and pool.cached_blocks == 4
    # demand 3 blocks: served by evicting the LRU end (deepest blocks),
    # dropping their index entries; the chain head survives and matches
    got = pool.alloc(3)
    assert set(got) == set(a[1:]), "eviction should consume the LRU end"
    assert pool.evictions == 3
    assert pool.match(h) == a[:1]
    assert not pool.is_registered(a[1])
    with pytest.raises(RuntimeError):           # 1 cached + 0 free < 2
        pool.alloc(2)
    pool.check_invariants()


# ----------------------------------------------- engine: shared prefixes

@pytest.mark.parametrize("attn_kernel", [False, True],
                         ids=["gather-oracle", "pallas-kernel"])
def test_shared_system_prompt_skips_prefill_token_identical(
        tiny, attn_kernel):
    """Acceptance: a shared-system-prompt stream reuses ≥ 1 block per
    following request and skips ≥ 50% of their prompt rows, while greedy
    outputs stay token-identical to BOTH the one-shot engine and the same
    engine with caching off — on the jnp gather oracle AND the Pallas
    block-walk kernel (interpreted on the CPU)."""
    cfg, model, params = tiny
    policy = DENSE.with_(use_pallas_kernels=True) if attn_kernel else DENSE
    sysp = _rand_tokens(cfg, 32, seed=70)
    prompts = [np.concatenate([sysp, _rand_tokens(cfg, 6 + i, seed=71 + i)])
               for i in range(4)]
    # staggered so request 0's prompt blocks are published before the rest
    # admit (registration happens as prefill chunks complete)
    arrivals, max_new = [0, 4, 6, 8], 8

    def serve(prefix_cache):
        eng = ContinuousServingEngine(model, policy, ContinuousConfig(
            max_seq=MAX_SEQ, num_slots=3, chunk_size=16, block_size=8,
            prefix_cache=prefix_cache, validate_pool=True))
        for p, a in zip(prompts, arrivals):
            eng.submit(p, max_new_tokens=max_new, arrival=a)
        return eng, eng.run(params)

    eng, res = serve(True)
    _, cold = serve(False)
    assert res["outputs"] == cold["outputs"], "caching changed outputs"
    for i, p in enumerate(prompts):
        assert res["outputs"][i] == _oracle(model, params, DENSE, p,
                                            max_new), f"request {i}"
    pg = res["metrics"]["paged"]
    assert pg["prefix_cache"] and pg["attention_kernel"] is attn_kernel
    assert cold["metrics"]["paged"]["prefix_hits"] == 0
    reqs = {r["rid"]: r for r in res["metrics"]["requests"]}
    for rid in (1, 2, 3):                        # every reusing request hit
        assert reqs[rid]["cached_tokens"] >= 32, reqs[rid]
    assert pg["prefix_hits"] == 3
    assert pg["tokens_skipped"] >= 3 * 32
    # ≥50% of the reusing requests' prompt rows came from the index
    reused_prompt_rows = sum(len(prompts[r]) for r in (1, 2, 3))
    assert pg["tokens_skipped"] / reused_prompt_rows >= 0.5
    assert eng.pool.in_use == 0
    # one step program per phase-presence bucket (fused default)
    assert all(v == 1 for v in eng.trace_counts.values()), eng.trace_counts


def test_preemption_replay_rematches_its_own_blocks(tiny):
    """Preemption-replay is nearly free when the released chain survives:
    the replayed prompt+emitted sequence re-acquires the blocks that were
    just parked in the LRU instead of recomputing them."""
    cfg, model, params = tiny
    # req0 (8-token prompt) decodes long; req1 (40-token prompt) is
    # preempted mid-prefill at full pool commitment (same deterministic
    # geometry as test_preempt_prefill_victim_interleaving); req0's growth
    # is then served from the free list, so req1's chain head survives
    # eviction and its re-admission matches its own blocks
    prompts = [_rand_tokens(cfg, 8, seed=85 + 10),
               _rand_tokens(cfg, 40, seed=85 + 11)]
    arrivals, max_new = [0, 2], [24, 8]
    eng = ContinuousServingEngine(model, DENSE, ContinuousConfig(
        max_seq=MAX_SEQ, num_slots=2, chunk_size=8, block_size=4,
        num_blocks=13, validate_pool=True))
    for p, a, mn in zip(prompts, arrivals, max_new):
        eng.submit(p, max_new_tokens=mn, arrival=a)
    res = eng.run(params)
    pg = res["metrics"]["paged"]
    assert pg["preemptions"] >= 1, "scenario drifted: no preemption"
    reqs = {r["rid"]: r for r in res["metrics"]["requests"]}
    assert reqs[1]["preemptions"] >= 1
    assert reqs[1]["cached_tokens"] > 0, "replay recomputed everything"
    assert pg["prefix_hits"] >= 1
    for i, p in enumerate(prompts):
        assert res["outputs"][i] == _oracle(model, params, DENSE, p,
                                            max_new[i]), f"request {i}"
    assert eng.pool.in_use == 0


def test_sparse_policy_does_not_share_across_the_emitted_boundary(tiny):
    """Under a sparse prefill policy a request whose prompt happens to
    reproduce another request's prompt+emitted tokens must NOT reuse the
    emitted-region blocks (their KV was dense-written); the salted chain
    hash splits them while pure-prompt blocks still share.  Outputs stay
    oracle-identical either way."""
    cfg, model, params = tiny
    policy = paper_policy(2, 4, cfg.qgate_skip_layers)
    sparams = precompute_scales(params, policy)
    p0 = _rand_tokens(cfg, 16, seed=120)
    eng = ContinuousServingEngine(model, policy, ContinuousConfig(
        max_seq=MAX_SEQ, num_slots=2, chunk_size=8, block_size=4,
        validate_pool=True))
    eng.submit(p0, max_new_tokens=8, arrival=0)
    res0 = eng.run(params=sparams)
    # second request's prompt = first's prompt ++ its emitted tokens
    p1 = np.concatenate([p0, np.asarray(res0["outputs"][0], np.int32)])
    eng.clear()                         # rids restart at 0 after clear()
    eng.submit(p1, max_new_tokens=6, arrival=0)
    res1 = eng.run(params=sparams)
    req = res1["metrics"]["requests"][0]
    # pure-prompt blocks (16 tokens = 4 blocks) shared; emitted-region
    # blocks correctly missed under the dense-row salt
    assert req["cached_tokens"] == 16, req
    assert res1["outputs"][0] == _oracle(model, sparams, policy, p1, 6)


def test_prefix_cache_auto_disabled_for_recurrent_archs():
    """Hybrid/recurrent archs carry scan state cached KV cannot restore —
    prefix caching must stay off even though their attention is paged."""
    cfg = dataclasses.replace(get_smoke_config("recurrentgemma_2b"),
                              dtype="float32")
    model = build_model(cfg)
    eng = ContinuousServingEngine(model, DENSE, ContinuousConfig(
        max_seq=MAX_SEQ, num_slots=2, chunk_size=8))
    if eng.paged:                       # hybrid: paged attn, no caching
        assert not eng.prefix_cache and not eng.pool.prefix_cache
    else:                               # pure recurrent: no paging at all
        assert eng.pool is None


# --------------------------------------------------- preemption storm

def test_preemption_storm_invariants_and_drain(tiny):
    """Satellite: a pool sized to force repeated preempt/replay cycles
    across ≥3 requests.  validate_pool audits refcount/ownership (incl.
    the no-block-writable-from-two-slots invariant) after EVERY scheduler
    iteration; outputs stay one-shot-identical and the pool drains with
    zero leaked blocks."""
    cfg, model, params = tiny
    lens, arrivals, max_new = [12, 12, 12], [0, 0, 0], [20, 20, 20]
    prompts = [_rand_tokens(cfg, l, seed=130 + i)
               for i, l in enumerate(lens)]
    # each request peaks at blocks_for(32) = 8; 11 blocks cannot carry
    # even two concurrently to completion, so the scheduler must thrash
    # preempt/replay (both younger requests cycle through WAITING)
    eng = ContinuousServingEngine(model, DENSE, ContinuousConfig(
        max_seq=MAX_SEQ, num_slots=3, chunk_size=8, block_size=4,
        num_blocks=11, validate_pool=True))
    for p, a, mn in zip(prompts, arrivals, max_new):
        eng.submit(p, max_new_tokens=mn, arrival=a)
    res = eng.run(params)
    pg = res["metrics"]["paged"]
    assert pg["preemptions"] >= 3, f"storm too mild: {pg['preemptions']}"
    assert sum(r["preemptions"] > 0
               for r in res["metrics"]["requests"]) >= 2
    for i, p in enumerate(prompts):
        assert res["outputs"][i] == _oracle(model, params, DENSE, p,
                                            max_new[i]), f"request {i}"
    # drained: every reference returned, cached + free cover the pool
    assert eng.pool.in_use == 0
    assert eng.pool.available == eng.pool.num_blocks
    eng.pool.check_invariants()
    # the pool can still hand out every block (nothing leaked/stuck)
    assert len(set(eng.pool.alloc(eng.pool.num_blocks))) == 11


def test_clear_drops_stale_extras_exclusions(tiny):
    """rids restart at 0 after clear(): a modality-extras exclusion from a
    previous stream must not leak onto an unrelated rid-colliding request
    and silently disable its caching."""
    cfg, model, params = tiny
    p = _rand_tokens(cfg, 20, seed=160)
    eng = ContinuousServingEngine(model, DENSE, ContinuousConfig(
        max_seq=MAX_SEQ, num_slots=2, chunk_size=8, block_size=4,
        validate_pool=True))
    eng.submit(p, max_new_tokens=4)
    res0 = eng.run(params, extras={0: {}})     # rid 0 marked extras-bearing
    assert res0["metrics"]["paged"]["prefix_hits"] == 0
    assert eng.pool.cached_blocks == 0         # excluded: nothing published
    eng.clear()
    eng.submit(p, max_new_tokens=4)            # rid 0 again, no extras now
    res1 = eng.run(params)
    eng.clear()
    eng.submit(p, max_new_tokens=4)
    res2 = eng.run(params)
    assert res2["metrics"]["paged"]["prefix_hits"] == 1, \
        "stale _extra_rids exclusion survived clear()"
    assert res2["outputs"][0] == res1["outputs"][0] == res0["outputs"][0]


def test_prefix_cache_off_matches_legacy_pool_semantics(tiny):
    """With prefix_cache=False released blocks go straight back to the
    free list: no index, no cached blocks, identical outputs."""
    cfg, model, params = tiny
    prompts = [_rand_tokens(cfg, 12, seed=150)]
    eng = ContinuousServingEngine(model, DENSE, ContinuousConfig(
        max_seq=MAX_SEQ, num_slots=2, chunk_size=8, block_size=4,
        prefix_cache=False, validate_pool=True))
    eng.submit(prompts[0], max_new_tokens=6)
    res = eng.run(params)
    assert not eng.prefix_cache
    assert eng.pool.cached_blocks == 0 and eng.pool.in_use == 0
    assert res["metrics"]["paged"]["prefix_hits"] == 0
    assert res["outputs"][0] == _oracle(model, params, DENSE, prompts[0], 6)


# ------------------------------------ hash-collision hardening (ISSUE 6)

def test_pool_detects_forced_hash_collision():
    """A chain-hash collision between DIFFERENT block contents must never
    share KV: match() verifies the stored (dense_rows, token_bytes) key
    and stops at the first mismatch, counting the collision."""
    from repro.serve.paged import chain_block_keys

    pool = BlockPool(num_blocks=4, block_size=4)
    toks_a = np.arange(8, dtype=np.int32)
    toks_b = np.arange(8, dtype=np.int32) + 100
    keys_a = chain_block_keys(toks_a, 4)
    keys_b = chain_block_keys(toks_b, 4)
    fake_chain = [12345, 67890]                # both contents hash here
    a = pool.alloc(2)
    for bid, h, k in zip(a, fake_chain, keys_a):
        assert pool.register(bid, h, key=k)
    # same content, verified keys → full match, no collision
    assert pool.match(fake_chain, keys=keys_a) == a
    assert pool.hash_collisions == 0
    # different content colliding on the hash → rejected, counted
    assert pool.match(fake_chain, keys=keys_b) == []
    assert pool.hash_collisions == 1
    # a sparse/dense row-split mismatch is content inequality too
    split = chain_block_keys(toks_a, 4, dense_from=2)
    assert pool.match(fake_chain, keys=split) == []
    assert pool.hash_collisions == 2
    # prefix verification is inductive: block 1 only reachable through a
    # verified block 0, so a tail collision truncates the match
    assert pool.match(fake_chain, keys=[keys_a[0], keys_b[1]]) == a[:1]
    pool.check_invariants()


def test_engine_survives_universal_hash_collisions(tiny, monkeypatch):
    """Regression: with chain_block_hashes forced to collide for EVERY
    sequence, the key check must refuse all false sharing — outputs stay
    oracle-identical and the collisions are metered."""
    cfg, model, params = tiny
    monkeypatch.setattr(
        "repro.serve.continuous.chain_block_hashes",
        lambda tokens, bs, n_blocks=None, dense_from=None, start=0, h0=None:
            list(range(start, n_blocks)))
    prompts = [_rand_tokens(cfg, 14, seed=160 + i) for i in range(3)]
    eng = ContinuousServingEngine(model, DENSE, ContinuousConfig(
        max_seq=MAX_SEQ, num_slots=3, chunk_size=8, block_size=4,
        validate_pool=True))
    for i, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=6, arrival=i)
    res = eng.run(params)
    assert eng.pool.hash_collisions >= 1, \
        "forced collisions never reached the key check"
    for i, p in enumerate(prompts):
        assert res["outputs"][i] == _oracle(model, params, DENSE, p, 6), \
            f"request {i} shared a colliding block"
    assert eng.pool.in_use == 0


def test_block_size_folded_into_chain_seed():
    """Identical tokens hashed at different block sizes must not collide
    structurally: the chain seed folds the block geometry."""
    toks = np.arange(32, dtype=np.int32)
    h4 = chain_block_hashes(toks, 4)
    h8 = chain_block_hashes(toks, 8)
    assert set(h4).isdisjoint(h8)
