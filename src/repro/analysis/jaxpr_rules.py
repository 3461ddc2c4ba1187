"""jaxpr contract rules — dispatch pins checked over traced programs.

Every rule here is a statement about what a program *lowers to*, checked
abstractly with :func:`jax.make_jaxpr` (``jax.ShapeDtypeStruct`` operands
where possible — nothing runs, no TPU needed):

  * ``jaxpr.projection-dispatch``  each ``sparse_linear`` mode (per-token
    N:M, tile-consensus, Outstanding-sparse W8A8 prefill AND decode, and
    a ``layer_flag`` scan-stacked projection) is exactly ONE fused
    ``pallas_call`` with kernels on and zero with kernels off;
  * ``jaxpr.step-contracts``  for every fused step bucket of
    ``serve/executor.py`` (enumerated from ``STEP_BUCKETS``, never
    hand-listed): zero pool-shaped gathers/scatters outside kernels, no
    jax effects (the shard_map-ability pin), identical jaxpr on retrace,
    no f64 leakage — and the jnp oracle twins must still CONTAIN pool
    gathers/scatters, proving the kernels-on pins aren't vacuous;
  * ``jaxpr.tp-shards``  under a ≥2-device TP scope the column-parallel
    projection keeps one ``pallas_call``, gathers with ``all_gather``,
    and has NO ``psum`` (bit-identity forbids cross-device reductions);
    emits a ``skip`` finding on single-device hosts;
  * ``jaxpr.extra-entries``  fixture hook: trace ``JAXPR_ENTRIES`` from
    ``ctx.jaxpr_extra`` and apply the pool-containment pin, so the
    analyzer's own tests can seed a known-bad step.
"""
from __future__ import annotations

from typing import List

from repro.analysis import Context, Finding, rule
from repro.analysis.jaxpr_utils import (count_pallas_calls, eqn_dtypes,
                                        iter_eqns, pool_eqn_count)

__all__ = []


def _err(rule_name, obj, msg, **data):
    return Finding(rule=rule_name, severity="error", obj=obj, message=msg,
                   data=data)


def _ok(rule_name, obj, msg, **data):
    return Finding(rule=rule_name, severity="info", obj=obj, message=msg,
                   data=data)


def _policy(**kw):
    from repro.core.policy import SparsityPolicy
    base = dict(n=8, m=16, score_mode="naive", skip_modules=(),
                skip_layers={})
    base.update(kw)
    return SparsityPolicy(**base)


def _prim_count(jaxpr, name: str) -> int:
    return sum(1 for e in iter_eqns(jaxpr) if e.primitive.name == name)


# --------------------------------------------------- projection dispatch

def _projection_cases():
    """(case name, jaxpr thunk, expected pallas_call count) triples.

    Shapes are tiny but aligned (t=32, d=128, n_out=64) so every kernel
    dispatches without the padding fallback muddying the count.
    """
    import jax
    import jax.numpy as jnp

    from repro.layers.linear import sparse_linear

    x = jax.ShapeDtypeStruct((32, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 64), jnp.float32)
    wq = jax.ShapeDtypeStruct((128, 64), jnp.int8)
    w_scale = jax.ShapeDtypeStruct((64,), jnp.float32)
    smooth = jax.ShapeDtypeStruct((128,), jnp.float32)
    act_scale = jax.ShapeDtypeStruct((), jnp.float32)

    on = _policy(use_pallas_kernels=True)
    off = _policy()

    def trace(fn, *args):
        return lambda: jax.make_jaxpr(fn)(*args)

    def proj(pol, phase="prefill", **kw):
        return lambda x, w: sparse_linear(x, {"w": w}, "down_proj", pol,
                                          phase, **kw)

    def qproj(pol, phase="prefill"):
        return lambda x, wq, ws, sm, asc: sparse_linear(
            x, {"wq": wq, "w_scale": ws, "smooth": sm, "act_scale": asc},
            "q_proj", pol, phase)

    flag_on = _policy(use_pallas_kernels=True)

    return [
        ("per-token kernels-on", trace(proj(on), x, w), 1),
        ("per-token kernels-off", trace(proj(off), x, w), 0),
        ("tile-consensus kernels-on",
         trace(proj(_policy(use_pallas_kernels=True, tile_consensus=True,
                            tile_size=32)), x, w), 1),
        ("tile-consensus kernels-off",
         trace(proj(_policy(tile_consensus=True, tile_size=32)), x, w), 0),
        ("w8a8-prefill kernels-on", trace(qproj(on), x, wq, w_scale,
                                          smooth, act_scale), 1),
        ("w8a8-prefill kernels-off", trace(qproj(off), x, wq, w_scale,
                                           smooth, act_scale), 0),
        # decode: prune=False statically — still ONE fused W8A8 GEMM
        ("w8a8-decode kernels-on", trace(qproj(on, "decode"), x, wq,
                                         w_scale, smooth, act_scale), 1),
        # scan-stacked layer_flag models: the flag picks the kernel or
        # the dense projection with lax.cond — still ONE pallas_call
        ("layer-flag kernel",
         trace(lambda x, w, f: sparse_linear(
             x, {"w": w}, "down_proj", flag_on, "prefill",
             layer_flag=f), x, w, jax.ShapeDtypeStruct((), jnp.bool_)), 1),
        ("layer-flag kernels-off",
         trace(lambda x, w, f: sparse_linear(
             x, {"w": w}, "down_proj", off, "prefill",
             layer_flag=f), x, w, jax.ShapeDtypeStruct((), jnp.bool_)), 0),
    ]


@rule("jaxpr.projection-dispatch", family="jaxpr")
def rule_projection_dispatch(ctx: Context) -> List[Finding]:
    """One fused pallas_call per sparse projection (per-token,
    tile-consensus, W8A8 prefill/decode, layer_flag); zero on the jnp
    oracle."""
    findings: List[Finding] = []
    for name, thunk, want in _projection_cases():
        got = count_pallas_calls(thunk())
        if got != want:
            findings.append(_err(
                "jaxpr.projection-dispatch", name,
                f"{name}: expected {want} pallas_call(s), traced {got}",
                expected=want, got=got))
    if not findings:
        findings.append(_ok("jaxpr.projection-dispatch", "sparse_linear",
                            f"{len(_projection_cases())} dispatch pins hold"))
    return findings


# ------------------------------------------------------- step programs

def _step_fixture(ctx: Context):
    """(engine, pool_shapes, args) for tracing step buckets — one cache /
    operand set shared by every bucket (phase presence is static, unused
    operands are simply dead in the traced program)."""
    if "step_fixture" in ctx._cache:
        return ctx._cache["step_fixture"]
    import jax.numpy as jnp
    import numpy as np

    from repro.core.policy import DENSE
    from repro.serve.continuous import (ContinuousConfig,
                                        ContinuousServingEngine)
    from repro.serve.paged import (device_pool_rows, init_paged_cache,
                                   max_blocks_per_slot)

    cfg, model, params = ctx.smoke_model()
    slots, bs, max_seq = 2, 8, 64
    mb = max_blocks_per_slot(max_seq, bs)
    nb = slots * mb
    rows = device_pool_rows(nb)   # +1 sentinel row on device leaves
    pol = DENSE.with_(use_pallas_kernels=True)
    eng = ContinuousServingEngine(model, pol, ContinuousConfig(
        max_seq=max_seq, num_slots=slots, chunk_size=8, block_size=bs),
        _via_api=True)
    cache = init_paged_cache(model, slots, max_seq, bs, nb, eng._spec)
    tab = np.full((slots, mb), -1, np.int32)
    tab[0, :3], tab[1, :3] = [1, 2, 3], [4, 5, 6]
    cache["block_table"] = jnp.asarray(tab)
    cache["pos"] = jnp.asarray([10, 7], jnp.int32)
    pool_shapes = {(rows, cfg.n_kv_heads, bs, cfg.head_dim)}
    args = (params, cache, jnp.asarray(0, jnp.int32),
            jnp.zeros((1, 8), jnp.int32), jnp.asarray(8, jnp.int32),
            {}, jnp.zeros((slots,), jnp.int32),
            jnp.asarray([False, True]), jnp.zeros((2,), jnp.uint32),
            jnp.zeros((2,), jnp.uint32), jnp.float32(0.0))
    ctx._cache["step_fixture"] = (eng, pool_shapes, args)
    return ctx._cache["step_fixture"]


@rule("jaxpr.step-contracts", family="jaxpr")
def rule_step_contracts(ctx: Context) -> List[Finding]:
    """Every fused step bucket: pool ops stay in-kernel, no jax effects,
    stable retrace, no f64; oracle twins keep pool ops (vacuity check)."""
    import jax

    from repro.serve.executor import STEP_BUCKETS

    eng, pool_shapes, args = _step_fixture(ctx)
    findings: List[Finding] = []
    for bucket, name in STEP_BUCKETS.items():
        for oracle in (False, True):
            label = name + ("_oracle" if oracle else "")
            step = eng.exec.step_program(bucket, oracle=oracle)
            closed = jax.make_jaxpr(step)(*args)
            gathers = pool_eqn_count(closed, pool_shapes, "gather")
            scatters = pool_eqn_count(closed, pool_shapes, "scatter")
            if not oracle:
                for prim, n in (("gather", gathers), ("scatter", scatters)):
                    if n:
                        findings.append(_err(
                            "jaxpr.step-contracts", label,
                            f"{label}: {n} pool-shaped {prim}(s) escaped "
                            "the kernels", prim=prim, count=n))
            else:
                # the oracle must still do pool-shaped work, or the
                # kernels-on zero-counts above prove nothing
                if gathers == 0 and scatters == 0:
                    findings.append(_err(
                        "jaxpr.step-contracts", label,
                        f"{label}: oracle twin has NO pool-shaped ops — "
                        "the kernels-on containment pin is vacuous"))
            if closed.effects:
                findings.append(_err(
                    "jaxpr.step-contracts", label,
                    f"{label}: step program carries jax effects "
                    f"{closed.effects} (not shard_map-able)",
                    effects=str(closed.effects)))
            f64 = {d for d in eqn_dtypes(closed) if d == "float64"}
            if f64:
                findings.append(_err(
                    "jaxpr.step-contracts", label,
                    f"{label}: float64 leaked into the traced program"))
            again = jax.make_jaxpr(step)(*args)
            if str(closed) != str(again):
                findings.append(_err(
                    "jaxpr.step-contracts", label,
                    f"{label}: retracing from identical operands changed "
                    "the program (trace-time mutable-state dependence)"))
    if not findings:
        findings.append(_ok(
            "jaxpr.step-contracts", "executor",
            f"{len(STEP_BUCKETS)} buckets (+oracle twins) hold all pins"))
    return findings


# ------------------------------------------------------------- tp shards

@rule("jaxpr.tp-shards", family="jaxpr")
def rule_tp_shards(ctx: Context) -> List[Finding]:
    """Column-parallel projection under a 2-device TP scope: one
    pallas_call inside shard_map, gathered with all_gather, no psum."""
    import jax

    if jax.device_count() < 2:
        return [Finding(
            rule="jaxpr.tp-shards", severity="skip", obj="tp",
            message="needs >=2 devices (set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=2)")]
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.distributed import tp
    from repro.layers.linear import sparse_linear

    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    x = jax.ShapeDtypeStruct((32, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 64), jnp.float32)
    pol = _policy(use_pallas_kernels=True)
    fn = lambda x, w: sparse_linear(x, {"w": w}, "down_proj", pol,
                                    "prefill")
    with tp.scope(mesh, "model"):
        closed = jax.make_jaxpr(fn)(x, w)
    findings: List[Finding] = []
    checks = (("pallas_call", count_pallas_calls(closed), "== 1",
               lambda n: n == 1),
              ("shard_map", _prim_count(closed, "shard_map"), ">= 1",
               lambda n: n >= 1),
              ("all_gather", _prim_count(closed, "all_gather"), ">= 1",
               lambda n: n >= 1),
              ("psum", _prim_count(closed, "psum"), "== 0",
               lambda n: n == 0))
    for prim, n, want, pred in checks:
        if not pred(n):
            findings.append(_err(
                "jaxpr.tp-shards", prim,
                f"tp-sharded projection: {prim} count {n}, expected "
                f"{want}", count=n))
    if not findings:
        findings.append(_ok("jaxpr.tp-shards", "column_parallel",
                            "sharded projection pins hold (2 devices)"))
    return findings


# ------------------------------------------------------- fixture entries

@rule("jaxpr.extra-entries", family="jaxpr")
def rule_extra_entries(ctx: Context) -> List[Finding]:
    """Pool-containment pin over fixture ``JAXPR_ENTRIES``:
    ``(name, fn, args, pool_shapes)`` tuples traced and checked like the
    step buckets (analyzer-test hook)."""
    if not ctx.jaxpr_extra:
        return []
    import jax

    findings: List[Finding] = []
    mod = ctx.load_extra(ctx.jaxpr_extra)
    for name, fn, fargs, pool_shapes in mod.JAXPR_ENTRIES:
        closed = jax.make_jaxpr(fn)(*fargs)
        for prim in ("gather", "scatter"):
            n = pool_eqn_count(closed, pool_shapes, prim)
            if n:
                findings.append(_err(
                    "jaxpr.extra-entries", name,
                    f"{name}: {n} pool-shaped {prim}(s) outside "
                    "pallas_call", prim=prim, count=n))
    if not findings:
        findings.append(_ok("jaxpr.extra-entries", ctx.jaxpr_extra,
                            f"{len(mod.JAXPR_ENTRIES)} entries clean"))
    return findings
