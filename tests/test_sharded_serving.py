"""Sharded serving (ISSUE 8): the scheduler/executor API split, the
dp-replicated Router behind ``repro.serve.api``, and the tp-sharded
kernel path.

Contracts pinned here:

* the Scheduler layer is pure host code — importing it must not pull in
  jax (plans are numpy + ints, device arrays never cross the boundary);
* ``Executor.step_program(bucket)`` is a pure, effect-free function of
  ``(params, cache, plan operands)`` — the property that makes it
  ``shard_map``-able;
* dp routing never changes tokens: ``dp=2`` outputs are token-identical
  per request to a ``dp=1`` run, and prefix-affinity pins same-prefix
  requests to one replica;
* a replica crash drains to a survivor with outputs still identical
  (chaos seeds 0–2 against the Router);
* tp-sharded kernels are bit-exact vs the single-device oracle (own
  subprocess with 4 fake host devices), and the full dp=2/tp=2 engine is
  token-identical when the test process itself has ≥4 devices (the CI
  sharded job);
* ``make_mesh_auto`` fails up front, with the XLA_FLAGS fix in the
  message, when the mesh outgrows the backend.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.core.policy import DENSE
from repro.models import build_model
from repro.serve.api import Engine, EngineConfig
from repro.serve.continuous import ContinuousConfig, ContinuousServingEngine
from repro.serve.faults import FaultInjector, FaultSpec
from repro.serve.metrics import MetricsSnapshot
from repro.serve.router import Router

MAX_SEQ = 64
_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


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


def _serve_cfg(**kw):
    base = dict(max_seq=MAX_SEQ, num_slots=2, chunk_size=8)
    base.update(kw)
    return ContinuousConfig(**base)


def _run_dp(model, params, cfg, prompts, arrivals, *, dp, faults=None,
            max_new=6):
    eng = Engine.from_config(
        model, EngineConfig(dp=dp, serving=_serve_cfg()), policy=DENSE,
        faults=faults)
    rids = [eng.submit(p, max_new, arrival=a)
            for p, a in zip(prompts, arrivals)]
    res = eng.run(params)
    return eng, [res["outputs"][r] for r in rids]


# ------------------------------------------------------- layer separation

def test_scheduler_layer_is_pure_host():
    """The Scheduler half of the split must stay importable without jax:
    its plans are the host-side contract, and a jax import sneaking in
    would silently re-couple admission logic to device state.  Asserted
    through ``repro.analysis.purity`` (the AST import-graph pass the
    ``python -m repro.analysis`` CLI runs), which also covers the
    metrics module and paged.py's lazy-jax contract — and reports the
    offending import chain instead of a bare subprocess exit code."""
    from repro.analysis.purity import (check_jax_free, check_lazy_import,
                                       scan_tree)
    tree = scan_tree(_SRC)
    for mod in ("repro.serve.scheduler", "repro.serve.metrics",
                "repro.serve"):
        assert mod in tree, f"{mod} missing from the scanned tree"
        chain = check_jax_free(tree, mod)
        assert chain is None, \
            f"{mod} reaches jax at import time: {' -> '.join(chain)}"
    # paged.py may import jax ONLY inside init_paged_cache (device
    # arrays are built there and nowhere else)
    problems = check_lazy_import(tree["repro.serve.paged"], "jax",
                                 ("init_paged_cache",))
    assert not problems, problems


def test_analysis_purity_rule_matches_subprocess_truth():
    """Ground-truth the AST pass once against a real interpreter: the
    static claim "importing the scheduler never pulls in jax" must agree
    with what an actual import does."""
    code = ("import sys; import repro.serve.scheduler; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env={**os.environ, "PYTHONPATH": _SRC}, capture_output=True)
    assert proc.returncode == 0, \
        "importing repro.serve.scheduler pulled in jax"


def test_executor_step_program_is_pure(tiny):
    """``Executor.step_program(bucket)`` must trace as a pure, effect-free
    function of its operands — the property that lets the Router shard_map
    it.  An in-place cache mutation or host callback would surface as a
    jax effect on the jaxpr."""
    from repro.serve.paged import init_paged_cache, max_blocks_per_slot
    cfg, model, params = tiny
    slots, bs = 2, 8
    mb = max_blocks_per_slot(MAX_SEQ, bs)
    eng = ContinuousServingEngine(model, DENSE, _serve_cfg(block_size=bs),
                                  _via_api=True)
    cache = init_paged_cache(model, slots, MAX_SEQ, bs, slots * mb,
                             eng._spec)
    tab = np.full((slots, mb), -1, np.int32)
    tab[0, :2], tab[1, :2] = [1, 2], [3, 4]
    cache["block_table"] = jnp.asarray(tab)
    cache["pos"] = jnp.asarray([9, 5], jnp.int32)
    step = eng.exec.step_program((False, True, True))
    args = (params, cache, jnp.asarray(0, jnp.int32),
            jnp.zeros((1, 8), jnp.int32), jnp.asarray(8, jnp.int32),
            {}, jnp.zeros((slots,), jnp.int32),
            jnp.asarray([False, True]), jnp.zeros((2,), jnp.uint32),
            jnp.zeros((2,), jnp.uint32), jnp.float32(0.0))
    closed = jax.make_jaxpr(step)(*args)
    assert not closed.effects, \
        f"step program carries jax effects: {closed.effects}"
    # tracing twice from identical operands must give identical programs
    # (no trace-time dependence on mutable executor state)
    again = jax.make_jaxpr(step)(*args)
    assert str(closed) == str(again)


# ------------------------------------------------------------ dp identity

def test_dp2_token_identical_to_dp1(tiny):
    cfg, model, params = tiny
    prompts = _prompts(cfg, (9, 17, 12, 21, 11))
    arrivals = (0, 0, 2, 3, 5)
    e1, out1 = _run_dp(model, params, cfg, prompts, arrivals, dp=1)
    e2, out2 = _run_dp(model, params, cfg, prompts, arrivals, dp=2)
    assert out1 == out2
    # both replicas actually served traffic (the router load-balances)
    served = [len(r.requests) for r in e2.replicas]
    assert all(s > 0 for s in served), served
    m = e2.metrics
    assert m.replicas is not None and len(m.replicas) == 2
    assert m.generated_tokens == sum(len(o) for o in out2)
    # the fused one-dispatch property holds per replica, not amortized
    assert m.dispatches_per_iteration == max(
        p.dispatches_per_iteration for p in m.replicas) == 1.0


def test_prefix_affinity_routes_to_one_replica(tiny):
    cfg, model, params = tiny
    shared = np.asarray(jax.random.randint(
        jax.random.PRNGKey(7), (16,), 0, cfg.vocab_size))
    prompts = [np.concatenate([shared, p])
               for p in _prompts(cfg, (5, 6, 7, 8), seed0=30)]
    router = Router(model, DENSE, _serve_cfg(), dp=2)
    rids = [router.submit(p, 4) for p in prompts]
    reps = {router._rid_map[r][0] for r in rids}
    assert len(reps) == 1, \
        f"same-prefix requests split across replicas {reps}"
    # distinct leading blocks spread by load instead
    other = router.submit(_prompts(cfg, (20,), seed0=50)[0], 4)
    assert router._rid_map[other][0] not in reps


# --------------------------------------------------------- crash failover

@pytest.mark.parametrize("seed,site,it", [(0, "decode", 3),
                                          (1, "prefill", 1),
                                          (2, "decode", 5)])
def test_replica_crash_drains_to_survivor(tiny, seed, site, it):
    """Chaos seeds 0–2 vs the Router: a mid-run EngineCrash in one replica
    must drain it — terminal outputs kept, in-flight requests re-admitted
    to the survivor — with every output still token-identical to a clean
    dp=1 run and no request leaked non-terminal."""
    cfg, model, params = tiny
    prompts = _prompts(cfg, (9, 17, 12, 21, 11), seed0=60 + seed)
    arrivals = (0, 0, 2, 3, 5)
    _, clean = _run_dp(model, params, cfg, prompts, arrivals, dp=1)
    fi = FaultInjector(seed=seed, schedule=[
        FaultSpec(site, "crash", iters=(it,), limit=1)])
    eng, out = _run_dp(model, params, cfg, prompts, arrivals, dp=2,
                       faults=fi)
    router = eng._router
    assert router.crashes == 1
    assert router.transplants >= 1
    assert sum(router.alive) == 1
    assert out == clean
    terminal = ("done", "rejected", "timed_out", "cancelled")
    for g in range(len(prompts)):
        assert eng.request_state(g) in terminal
    # a degraded fleet refuses to snapshot (shape changed under it)
    with pytest.raises(AssertionError):
        eng.snapshot()


def test_dp1_crash_propagates(tiny):
    """With no survivor the crash must reach the caller — dp=1 keeps the
    single-engine snapshot/restore recovery contract."""
    from repro.serve.faults import EngineCrash
    cfg, model, params = tiny
    prompts = _prompts(cfg, (9, 17), seed0=80)
    fi = FaultInjector(seed=0, schedule=[
        FaultSpec("decode", "crash", iters=(2,), limit=1)])
    with pytest.raises(EngineCrash):
        _run_dp(model, params, cfg, prompts, (0, 0), dp=1, faults=fi)


# ----------------------------------------------------------- api adapters

def test_direct_engine_construction_warns(tiny):
    cfg, model, params = tiny
    with pytest.warns(DeprecationWarning, match="Engine.from_config"):
        ContinuousServingEngine(model, DENSE, _serve_cfg())
    from repro.serve.engine import ServeConfig, ServingEngine
    with pytest.warns(DeprecationWarning, match="Engine.from_config"):
        ServingEngine(model, DENSE, ServeConfig(max_seq=MAX_SEQ))
    import warnings as w
    with w.catch_warnings():
        w.simplefilter("error", DeprecationWarning)
        Engine.from_config(model, EngineConfig(serving=_serve_cfg()))


def test_engine_generate_oneshot_adapter(tiny):
    """``Engine.generate`` replaces ``ServingEngine.generate``: the whole
    batch submitted at arrival 0, admission closed, outputs in submission
    order — token-identical to the continuous run of the same requests."""
    cfg, model, params = tiny
    prompts = _prompts(cfg, (9, 14, 11), seed0=90)
    eng = Engine.from_config(model, EngineConfig(serving=_serve_cfg()),
                             policy=DENSE)
    outs = eng.generate(params, prompts, max_new_tokens=5)
    _, ref = _run_dp(model, params, cfg, prompts, (0, 0, 0), max_new=5,
                     dp=1)
    assert outs == ref


def test_router_snapshot_restore_roundtrip(tiny):
    cfg, model, params = tiny
    prompts = _prompts(cfg, (9, 17, 12), seed0=95)
    eng = Engine.from_config(model, EngineConfig(
        dp=2, serving=_serve_cfg()), policy=DENSE)
    rids = [eng.submit(p, 5) for p in prompts]
    res = eng.run(params)
    snap = eng.snapshot()
    eng2 = Engine.from_config(model, EngineConfig(
        dp=2, serving=_serve_cfg()), policy=DENSE)
    eng2.restore(snap)
    for r in rids:
        assert eng2.request_state(r) == eng.request_state(r)


# ---------------------------------------------------------------- metrics

def test_metrics_snapshot_roundtrip(tiny):
    cfg, model, params = tiny
    prompts = _prompts(cfg, (9, 17), seed0=97)
    eng, _ = _run_dp(model, params, cfg, prompts, (0, 1), dp=2)
    m = eng.metrics
    back = MetricsSnapshot.from_dict(m.to_dict())
    assert back.to_dict() == m.to_dict()
    d = m.to_dict()
    # legacy dict shape intact for existing consumers
    for key in ("iterations", "trace_counts", "lifecycle", "paged",
                "requests", "dispatches_per_iteration"):
        assert key in d
    assert d["schema_version"] == 1
    assert len(d["replicas"]) == 2
    # merged counters are the sum of the parts
    assert m.generated_tokens == sum(p.generated_tokens
                                     for p in m.replicas)
    rids = sorted(r.rid for r in m.requests)
    assert rids == list(range(len(prompts)))   # relabeled to global rids


# -------------------------------------------------------------- tp shards

def test_mesh_device_count_error():
    from repro.launch.mesh import make_serving_mesh
    with pytest.raises(ValueError,
                       match="xla_force_host_platform_device_count"):
        make_serving_mesh(64, 64)


_TP_PARITY = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.distributed import tp
from repro.kernels import ops
from repro.launch.mesh import make_serving_mesh
from repro.models import attention as attn

mesh = make_serving_mesh(1, 4)
sub = tp.replica_meshes(mesh)[0]
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((5, 32)), jnp.float32)
w = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
b = jnp.asarray(rng.standard_normal((64,)), jnp.float32)
scale = jnp.asarray(rng.random(32) + 0.5, jnp.float32)
wq = jnp.asarray(rng.integers(-127, 127, (32, 64)), jnp.int8)
smooth = jnp.asarray(rng.random(32) + 0.5, jnp.float32)
amber = jnp.asarray(rng.random(32) + 0.5, jnp.float32)
ws = jnp.asarray(rng.random(64) * 0.01 + 0.001, jnp.float32)
act = jnp.asarray([0.02], jnp.float32)

def check(name, fn, *args):
    ref = jax.jit(fn)(*args)
    with tp.scope(sub, "model"):
        got = jax.jit(fn)(*args)
    ok = jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), ref, got))
    assert ok, name
    print(name, "bitexact")

check("nm_prune_matmul",
      lambda x_, w_, b_: ops.nm_prune_matmul(x_, w_, scale, 2, 4, bias=b_),
      x, w, b)
check("nm_spmm", lambda x_, w_: ops.nm_spmm(x_, w_, scale, 2, 4), x, w)
check("osparse_matmul",
      lambda x_, wq_, ws_, b_: ops.osparse_matmul(
          x_, wq_, smooth, amber, ws_, 2, 4, act_scale=act, bias=b_),
      x, wq, ws, b)
check("w8a8_matmul",
      lambda xq_, wq_: ops.w8a8_matmul(xq_, wq_, act, ws),
      jnp.asarray(rng.integers(-127, 127, (5, 32)), jnp.int8), wq)

B, Hq, Hkv, D, bs, nb, T = 2, 4, 2, 8, 4, 16, 12
q = jnp.asarray(rng.standard_normal((B, T, Hq, D)), jnp.float32)
kp = jnp.asarray(rng.standard_normal((nb, Hkv, bs, D)), jnp.float32)
vp = jnp.asarray(rng.standard_normal((nb, Hkv, bs, D)), jnp.float32)
bt = jnp.asarray(np.arange(2 * 8).reshape(2, 8), jnp.int32)
qo = jnp.zeros((B,), jnp.int32)
kvl = jnp.full((B,), T, jnp.int32)
check("paged_attention",
      lambda q_, k_, v_: attn.paged_attention(
          q_, k_, v_, bt, q_offset=qo, kv_len=kvl, use_kernel=True),
      q, kp, vp)
kn = jnp.asarray(rng.standard_normal((B, 3, Hkv, D)), jnp.float32)
vn = jnp.asarray(rng.standard_normal((B, 3, Hkv, D)), jnp.float32)
check("paged_kv_update",
      lambda k_, v_, kn_, vn_: attn.paged_kv_update(
          k_, v_, kn_, vn_, bt, jnp.full((B,), T, jnp.int32),
          jnp.full((B,), 3, jnp.int32), use_kernel=True),
      kp, vp, kn, vn)
print("OK")
"""


def test_tp_kernel_parity_vs_single_device_oracle():
    """Every tp-sharded kernel entry point — the four column-parallel
    projections and the head-sharded paged attention/scatter — must be
    BIT-exact (jit-vs-jit) against the unsharded oracle.  The sweep runs
    in its own interpreter because faking host devices needs XLA_FLAGS
    set before the first jax call."""
    env = {**os.environ,
           "PYTHONPATH": _SRC,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, "-c", _TP_PARITY], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs 4 devices (CI sharded job sets "
                           "XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=4)")
def test_dp2_tp2_engine_token_identical(tiny):
    """The sized acceptance scenario: llama31_8b smoke on a (2, 2) mesh —
    two router replicas, each tp-sharding its kernels over 2 devices —
    token-identical to the plain single-device engine."""
    cfg, model, params = tiny
    prompts = _prompts(cfg, (9, 17, 12, 21), seed0=40)
    arrivals = (0, 0, 2, 3)
    _, ref = _run_dp(model, params, cfg, prompts, arrivals, dp=1)
    eng = Engine.from_config(model, EngineConfig(
        dp=2, tp=2, serving=_serve_cfg()), policy=DENSE)
    rids = [eng.submit(p, 6, arrival=a)
            for p, a in zip(prompts, arrivals)]
    res = eng.run(params)
    assert [res["outputs"][r] for r in rids] == ref
    assert all(p.dispatches_per_iteration == 1.0
               for p in eng.metrics.replicas)
