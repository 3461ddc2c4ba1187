"""Fault-injection chaos harness for the continuous serving engine (ISSUE 6).

The contract: faults may change WHEN work happens — never WHAT surviving
requests emit.  Every scenario runs a seeded request stream under one
fault family (pool exhaustion, eviction storms, non-finite kernel output,
kernel compile failure, mid-iteration crash + restore, deadline/cancel
storms, admission livelock) and asserts

  * token-identity with the undisturbed run for every surviving request,
  * zero block leaks after drain (``pool.in_use == 0``, plus the
    per-iteration refcount/ownership audit — on for the whole suite via
    ``REPRO_VALIDATE_POOL=1`` in conftest.py),
  * terminal-state accounting (every request ends in exactly one of
    DONE/REJECTED/TIMED_OUT/CANCELLED).

Replay: injector seeds derive from ``REPRO_CHAOS_SEED`` (CI runs a small
seed matrix); on failure, ``chaos_guard`` dumps the injector's schedule +
fired log as JSON into ``REPRO_CHAOS_ARTIFACT_DIR`` so the exact scenario
replays locally with ``FaultInjector.from_json``.
"""
import contextlib
import dataclasses
import os

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
from repro.serve.continuous import (CANCELLED, DONE, REJECTED, TIMED_OUT,
                                    _TERMINAL)
from repro.serve.faults import EngineCrash, FaultInjector, FaultSpec

MAX_SEQ = 64
CHAOS_SEED = int(os.environ.get("REPRO_CHAOS_SEED", "0"))


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(get_smoke_config("llama31_8b"),
                              dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, lens, seed0=400):
    return [np.asarray(jax.random.randint(jax.random.PRNGKey(seed0 + i),
                                          (l,), 0, cfg.vocab_size))
            for i, l in enumerate(lens)]


def _oracle(model, params, policy, prompt, max_new):
    eng = ServingEngine(model, policy, ServeConfig(max_seq=MAX_SEQ))
    out = eng.generate(params, {"tokens": jnp.asarray(prompt)[None, :]},
                       max_new_tokens=max_new)
    return np.asarray(out["tokens"])[0].tolist()


def _engine(model, policy=DENSE, faults=None, **kw):
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("num_slots", 2)
    kw.setdefault("chunk_size", 8)
    kw.setdefault("block_size", 4)
    kw.setdefault("validate_pool", True)
    return ContinuousServingEngine(model, policy, ContinuousConfig(**kw),
                                   faults=faults)


def _drained(eng):
    """Post-drain leak check: every request terminal and holding nothing,
    every block back in the free list or parked zero-ref in the LRU."""
    assert all(r.state in _TERMINAL for r in eng.requests)
    assert all(not r.blocks and r.slot == -1 for r in eng.requests)
    if eng.paged:
        assert eng.pool.in_use == 0, "leaked live blocks after drain"
        eng.pool.check_invariants()


@contextlib.contextmanager
def chaos_guard(injector, name):
    """Dump the fault schedule + fired log on test failure so CI uploads
    it and the scenario replays locally (FaultInjector.from_json)."""
    try:
        yield
    except BaseException:
        art = os.environ.get("REPRO_CHAOS_ARTIFACT_DIR")
        if art and injector is not None:
            os.makedirs(art, exist_ok=True)
            with open(os.path.join(art, f"{name}.json"), "w") as f:
                f.write(injector.to_json())
        raise


# ------------------------------------------------------- injector mechanics

def test_injector_deterministic_replay():
    sched = [FaultSpec("pool.alloc", "exhausted", p=0.3),
             FaultSpec("decode", "nonfinite", calls=(2, 5), limit=1),
             FaultSpec("admit", "transient", iters=(1,))]

    def drive(inj):
        for it in range(4):
            inj.tick(it)
            for site in ("admit", "pool.alloc", "decode", "pool.alloc"):
                inj.fire(site)
        return inj.fired

    a = drive(FaultInjector(seed=7, schedule=sched))
    b = drive(FaultInjector(seed=7, schedule=sched))
    assert a == b and len(a) >= 2
    # round-trip through the CI artifact format reproduces the scenario
    c = drive(FaultInjector.from_json(
        FaultInjector(seed=7, schedule=sched).to_json()))
    assert c == a
    # a different seed perturbs only the probabilistic spec
    d = drive(FaultInjector(seed=8, schedule=sched))
    assert ([f for f in d if f["site"] != "pool.alloc"]
            == [f for f in a if f["site"] != "pool.alloc"])

    with pytest.raises(AssertionError):
        FaultSpec("no.such.site", "boom")


def test_clean_run_records_no_degradation(tiny):
    """Acceptance: zero degraded iterations, retries, or fault counters on
    an undisturbed run — the hardening is pay-per-fault."""
    cfg, model, params = tiny
    eng = _engine(model)
    for p, a in zip(_prompts(cfg, [9, 14]), [0, 1]):
        eng.submit(p, max_new_tokens=6, arrival=a)
    res = eng.run(params)
    m = res["metrics"]
    assert m["degraded_iterations"] == 0
    lc = m["lifecycle"]
    assert lc["admission_retries"] == lc["watchdog_trips"] == 0
    assert lc["timeouts"] == lc["cancellations"] == lc["faults_fired"] == 0
    assert lc["terminal_states"] == {DONE: 2, REJECTED: 0, TIMED_OUT: 0,
                                     CANCELLED: 0}
    assert not any(k.endswith("_oracle") for k in eng.trace_counts)
    _drained(eng)


# ------------------------------------- family 1: pool exhaustion + retries

def test_pool_exhaustion_retries_token_identical(tiny):
    """Injected allocation failures during admission are absorbed by
    bounded retry-with-backoff: every request still completes with the
    undisturbed outputs, and the rolled-back admissions leak nothing."""
    cfg, model, params = tiny
    lens, arrivals, max_new = [9, 17, 6, 12], [0, 0, 2, 3], 6
    prompts = _prompts(cfg, lens, seed0=410)

    def serve(faults):
        eng = _engine(model, faults=faults, num_slots=3)
        for p, a in zip(prompts, arrivals):
            eng.submit(p, max_new_tokens=max_new, arrival=a)
        return eng, eng.run(params)

    _, base = serve(None)
    inj = FaultInjector(seed=CHAOS_SEED, schedule=[
        # the first two admissions fail outright, then a random 30% of
        # later allocations (capped so the retry budget always wins)
        FaultSpec("pool.alloc", "exhausted", calls=(0, 1)),
        FaultSpec("pool.alloc", "exhausted", p=0.3, limit=4),
    ])
    with chaos_guard(inj, "pool_exhaustion"):
        eng, res = serve(inj)
        assert res["outputs"] == base["outputs"], \
            "injected exhaustion changed surviving outputs"
        lc = res["metrics"]["lifecycle"]
        assert lc["admission_retries"] >= 2
        assert lc["terminal_states"][DONE] == len(prompts)
        assert inj.total_fired >= 2
        _drained(eng)


def test_eviction_storm_token_identical(tiny):
    """Flushing the zero-ref prefix LRU at random allocations (cache-
    pressure storm) may cost recompute but never changes tokens."""
    cfg, model, params = tiny
    sysp = _prompts(cfg, [16], seed0=420)[0]
    prompts = [np.concatenate([sysp, p])
               for p in _prompts(cfg, [6, 9, 7], seed0=421)]
    arrivals, max_new = [0, 3, 5], 6

    def serve(faults):
        eng = _engine(model, faults=faults, num_slots=3)
        for p, a in zip(prompts, arrivals):
            eng.submit(p, max_new_tokens=max_new, arrival=a)
        return eng, eng.run(params)

    _, base = serve(None)
    inj = FaultInjector(seed=CHAOS_SEED, schedule=[
        FaultSpec("pool.alloc", "evict_storm", calls=(3,)),
        FaultSpec("pool.alloc", "evict_storm", p=0.25),
    ])
    with chaos_guard(inj, "evict_storm"):
        eng, res = serve(inj)
        assert res["outputs"] == base["outputs"]
        assert inj.total_fired >= 1
        assert res["metrics"]["lifecycle"]["terminal_states"][DONE] \
            == len(prompts)
        _drained(eng)


# --------------------------- family 2: non-finite logits → oracle re-run

@pytest.mark.parametrize("site,calls", [("prefill", (1, 3)),
                                        ("decode", (0, 4))])
def test_nonfinite_output_degrades_to_oracle(tiny, site, calls):
    """Acceptance: a NaN-producing iteration is detected host-side, the
    faulted outputs are discarded, and the same operands re-run on the
    jnp oracle program — tokens match the undisturbed run and the
    degradation is metered."""
    cfg, model, params = tiny
    lens, arrivals, max_new = [11, 18, 7], [0, 1, 2], 7
    prompts = _prompts(cfg, lens, seed0=430)

    def serve(faults):
        eng = _engine(model, faults=faults)
        for p, a in zip(prompts, arrivals):
            eng.submit(p, max_new_tokens=max_new, arrival=a)
        return eng, eng.run(params)

    _, base = serve(None)
    inj = FaultInjector(seed=CHAOS_SEED, schedule=[
        FaultSpec(site, "nonfinite", calls=calls)])
    with chaos_guard(inj, f"nonfinite_{site}"):
        eng, res = serve(inj)
        assert res["outputs"] == base["outputs"], \
            "degraded iterations changed tokens"
        assert res["metrics"]["degraded_iterations"] == len(calls)
        # the lazily-traced oracle twins compiled exactly once per step
        # bucket the faulted iterations landed in (fused default: the
        # whole hybrid step degrades, so the oracle key is the bucket's)
        oracle = {k: v for k, v in eng.trace_counts.items()
                  if k.endswith("_oracle")}
        assert oracle and all(v == 1 for v in oracle.values()), \
            eng.trace_counts
        assert len(oracle) <= len(calls)
        _drained(eng)


# ------------------------ family 3: kernel faults at the dispatch ladder

@pytest.mark.parametrize("site", ["kernel.projection",
                                  "kernel.paged_attention"])
def test_kernel_compile_failure_degrades_to_oracle(tiny, site):
    """A simulated Mosaic lowering failure aborts the trace; the engine
    re-runs the iteration on the kernels-off oracle jit and the request
    stream completes token-identically (kernel ≡ oracle math)."""
    cfg, model, params = tiny
    if site == "kernel.projection":
        policy = paper_policy(8, 16, cfg.qgate_skip_layers,
                              use_pallas_kernels=True)
        params = precompute_scales(params, policy)
    else:
        policy = DENSE.with_(use_pallas_kernels=True)
    lens, arrivals, max_new = [9, 13], [0, 1], 5
    prompts = _prompts(cfg, lens, seed0=440)

    def serve(faults):
        eng = _engine(model, policy, faults=faults)
        for p, a in zip(prompts, arrivals):
            eng.submit(p, max_new_tokens=max_new, arrival=a)
        return eng, eng.run(params)

    _, base = serve(None)
    # fire on the first dispatch consult: kernel dispatch runs at trace
    # time, so only the first call per shape bucket ever consults the site
    # (exactly like a real compile — it happens once)
    inj = FaultInjector(seed=CHAOS_SEED, schedule=[
        FaultSpec(site, "compile_error", calls=(0,), limit=1)])
    with chaos_guard(inj, f"compile_{site.split('.')[-1]}"):
        eng, res = serve(inj)
        assert res["outputs"] == base["outputs"]
        assert res["metrics"]["degraded_iterations"] == 1
        assert inj.fired_kinds(site) == ["compile_error"]
        # the aborted trace was not cached: the primary program re-traced
        # on the next call and served the rest of the run
        _drained(eng)


def test_kernel_fallback_is_silent(tiny):
    """The "fallback" kind routes a dispatch onto the jnp oracle branch
    WITHOUT an exception: same tokens, no degradation recorded (it is the
    ladder's ordinary uncovered-shape path, not a failure)."""
    cfg, model, params = tiny
    policy = DENSE.with_(use_pallas_kernels=True)
    prompts = _prompts(cfg, [10, 15], seed0=450)

    def serve(faults):
        eng = _engine(model, policy, faults=faults)
        for p, a in zip(prompts, [0, 1]):
            eng.submit(p, max_new_tokens=5, arrival=a)
        return eng, eng.run(params)

    _, base = serve(None)
    inj = FaultInjector(seed=CHAOS_SEED, schedule=[
        FaultSpec("kernel.paged_attention", "fallback", calls=(0,))])
    with chaos_guard(inj, "kernel_fallback"):
        eng, res = serve(inj)
        assert res["outputs"] == base["outputs"]
        assert res["metrics"]["degraded_iterations"] == 0
        assert inj.total_fired == 1
        _drained(eng)


# --------------------- family 4: mid-iteration crash + snapshot/restore

def test_crash_restore_resumes_token_identical(tiny):
    """Acceptance: EngineCrash mid-decode kills the engine; a NEW engine
    restored from the last auto-snapshot (request lifecycles, pool state,
    iteration clock, PRNG) finishes the stream with exactly the
    undisturbed outputs — in-flight requests replay through prefill, the
    same recompute path preemption uses."""
    cfg, model, params = tiny
    lens, arrivals, max_new = [9, 16, 12], [0, 1, 2], 8
    prompts = _prompts(cfg, lens, seed0=460)

    def submit_all(eng):
        for p, a in zip(prompts, arrivals):
            eng.submit(p, max_new_tokens=max_new, arrival=a)

    base_eng = _engine(model)
    submit_all(base_eng)
    base = base_eng.run(params)

    inj = FaultInjector(seed=CHAOS_SEED, schedule=[
        FaultSpec("decode", "crash", iters=tuple(range(4, 9)), limit=1),
        FaultSpec("prefill", "crash", iters=tuple(range(11, 15)), limit=1),
    ])
    with chaos_guard(inj, "crash_restore"):
        eng = _engine(model, faults=inj, snapshot_every=1)
        submit_all(eng)
        res, crashes = None, 0
        for _ in range(5):
            try:
                res = eng.run(params)
                break
            except EngineCrash:
                crashes += 1
                snap = eng.last_snapshot
                assert snap is not None
                # the crashed engine is dead: rebuild from scratch and
                # restore host state (device KV is lost by construction)
                eng = _engine(model, faults=inj, snapshot_every=1)
                eng.restore(snap)
        assert res is not None, "engine never finished after restores"
        assert crashes >= 1 and eng.restores == crashes
        assert res["outputs"] == base["outputs"], \
            "crash+restore changed tokens"
        lc = res["metrics"]["lifecycle"]
        assert lc["terminal_states"][DONE] == len(prompts)
        _drained(eng)


def test_snapshot_is_deep_and_reusable(tiny):
    """A snapshot is isolated from the live engine (deep-copied requests)
    and restoring the same snapshot twice yields the same completion."""
    cfg, model, params = tiny
    prompts = _prompts(cfg, [10, 14], seed0=470)
    inj = FaultInjector(seed=CHAOS_SEED, schedule=[
        FaultSpec("decode", "crash", iters=(5,), limit=1)])
    eng = _engine(model, faults=inj, snapshot_every=2)
    for p, a in zip(prompts, [0, 1]):
        eng.submit(p, max_new_tokens=6, arrival=a)
    with pytest.raises(EngineCrash):
        eng.run(params)
    snap = eng.last_snapshot
    outs = []
    for _ in range(2):
        e2 = _engine(model)
        e2.restore(snap)
        outs.append(e2.run(params)["outputs"])
        _drained(e2)
    assert outs[0] == outs[1]
    base = _engine(model)
    for p, a in zip(prompts, [0, 1]):
        base.submit(p, max_new_tokens=6, arrival=a)
    assert outs[0] == base.run(params)["outputs"]


# ----------------------------- family 5: deadline / cancellation storms

def test_deadline_and_cancel_storm(tiny):
    """TTL expiry and cancel() unwind requests from every lifecycle phase
    (waiting, mid-prefill, decoding) without touching the survivors'
    tokens or leaking a single block."""
    cfg, model, params = tiny
    lens = [9, 16, 20, 8, 11]
    arrivals = [0, 0, 1, 2, 3]
    max_new = 8
    prompts = _prompts(cfg, lens, seed0=480)
    eng = _engine(model, num_slots=2)
    for i, (p, a) in enumerate(zip(prompts, arrivals)):
        # rid 1 gets a deadline it cannot meet (prefill alone outlasts it)
        eng.submit(p, max_new_tokens=max_new, arrival=a,
                   ttl=3 if i == 1 else None)

    seen = {}

    def hook(engine, it):
        r2 = engine.requests[2]
        if r2.state == "prefill" and r2.filled > 0 and 2 not in seen:
            seen[2] = ("mid-prefill", it)       # cancel with a hot slot
            assert engine.cancel(2)
        r3 = engine.requests[3]
        if it == 1 and r3.state == "waiting":
            seen[3] = ("waiting", it)           # cancel before admission
            assert engine.cancel(3)

    eng.iteration_hook = hook
    res = eng.run(params)
    states = {r.rid: r.state for r in eng.requests}
    assert states[1] == TIMED_OUT
    assert states[2] == CANCELLED and seen[2][0] == "mid-prefill"
    assert states[3] == CANCELLED and seen[3][0] == "waiting"
    assert states[0] == states[4] == DONE
    for rid in (0, 4):
        assert res["outputs"][rid] == _oracle(model, params, DENSE,
                                              prompts[rid], max_new), \
            f"survivor {rid} drifted"
    lc = res["metrics"]["lifecycle"]
    assert lc["timeouts"] == 1 and lc["cancellations"] == 2
    assert sum(lc["terminal_states"].values()) == len(prompts)
    # double-cancel and cancelling a finished request are clean no-ops
    assert not eng.cancel(2) and not eng.cancel(0)
    _drained(eng)


# ------------------------------------ watchdog: livelock → forced reject

def test_watchdog_breaks_admission_livelock(tiny):
    """With a persistent allocation fault and an effectively unbounded
    retry budget, nothing can ever admit — the no-progress watchdog must
    force-reject the stuck requests instead of spinning to max_iters."""
    cfg, model, params = tiny
    inj = FaultInjector(seed=CHAOS_SEED, schedule=[
        FaultSpec("pool.alloc", "exhausted", p=1.0)])
    eng = _engine(model, faults=inj, admission_retries=10 ** 6,
                  watchdog_iters=8)
    for p, a in zip(_prompts(cfg, [9, 12], seed0=490), [0, 1]):
        eng.submit(p, max_new_tokens=4, arrival=a)
    with chaos_guard(inj, "watchdog_livelock"):
        res = eng.run(params)
        lc = res["metrics"]["lifecycle"]
        assert lc["watchdog_trips"] >= 1
        assert lc["terminal_states"][REJECTED] == 2
        assert res["metrics"]["iterations"] < 200, "livelock not bounded"
        assert all(not out for out in res["outputs"].values())
        _drained(eng)


# ---------------- satellite: preemption storm × cancellation × kernels

@pytest.mark.parametrize("attn_kernel", [False, True],
                         ids=["gather-oracle", "pallas-kernel"])
def test_preemption_storm_cancel_interleaving(tiny, attn_kernel):
    """Undersized pool → sustained preemption churn, plus a cancel landing
    mid-prefill: survivors stay token-identical on both the jnp gather
    oracle and the Pallas block-walk kernel, and the cancelled request's
    unwind never leaves a writable shared block (per-iteration audit +
    post-drain reclaim check)."""
    cfg, model, params = tiny
    policy = DENSE.with_(use_pallas_kernels=True) if attn_kernel else DENSE
    lens, arrivals, max_new = [16, 18, 14, 15], [0, 1, 2, 3], 8
    prompts = _prompts(cfg, lens, seed0=500)

    seen = {}

    def hook(engine, it):
        r1 = engine.requests[1]
        if r1.state == "prefill" and r1.filled > 0 and 1 not in seen:
            seen[1] = it
            engine.cancel(1)

    # 3 slots over a pool that cannot hold 3 fully-grown requests:
    # decode growth must preempt, and the cancel frees blocks mid-storm
    eng = _engine(model, policy, num_slots=3, num_blocks=14)
    eng.iteration_hook = hook
    for p, a in zip(prompts, arrivals):
        eng.submit(p, max_new_tokens=max_new, arrival=a)
    res = eng.run(params)
    assert 1 in seen, "cancel never landed mid-prefill"
    r1 = eng.requests[1]
    assert r1.state == CANCELLED and not r1.blocks and r1.slot == -1
    for rid in (0, 2, 3):
        assert res["outputs"][rid] == _oracle(model, params, DENSE,
                                              prompts[rid], max_new), \
            f"survivor {rid} drifted under preemption+cancel"
    assert res["metrics"]["paged"]["attention_kernel"] is attn_kernel
    assert res["metrics"]["paged"]["preemptions"] >= 1, \
        "pool was not actually under pressure"
    _drained(eng)
