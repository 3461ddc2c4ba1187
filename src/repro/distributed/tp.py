"""Tensor-parallel kernel sharding: a trace-time TP scope + shard_map
helpers.

The serving executor activates a :func:`scope` around every step-program
dispatch; inside it, the kernel wrappers in :mod:`repro.kernels.ops` and
the paged-attention dispatch in :mod:`repro.models.attention` consult
:func:`current` at **trace time** and, when the relevant axis divides,
wrap their Pallas call in a ``shard_map`` over the mesh's model axis:

* projection kernels (``nm_prune_matmul`` / ``nm_spmm`` /
  ``osparse_matmul`` / ``w8a8_matmul``) shard **N_out** — Megatron
  column-parallel: every device holds the full activations and a column
  slice of the weights, computes its output columns exactly as the
  single-device kernel would, and an ``all_gather(tiled=True)``
  concatenates them in axis order.  No cross-device reduction touches
  the accumulator, so the result is **bit-identical** to the unsharded
  kernel (the dp=2/tp=2 token-identity acceptance gate relies on this);
* ``paged_attention`` / ``paged_kv_scatter`` shard **KV heads**: heads
  are independent, the kernel's GQA index map (``h // g``) is preserved
  because Hq and Hkv divide by the same factor, and outputs gather (or
  stay head-sharded, for the pools) with no collectives inside the
  softmax.

Row-parallel layers (o_proj / down_proj contractions) are deliberately
NOT sharded: their ``psum`` would reorder float adds and break bit
identity.  Sharding them is the documented next step once the acceptance
gate moves from "token-identical" to "allclose" (serve/README.md).

The scope is read at trace time only — the lowered programs bake the
sharding in, exactly like the policy flags — so activating/deactivating
it never retraces an already-compiled bucket.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import List, Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["TPScope", "scope", "current", "degree", "column_parallel",
           "head_sharded_attention", "head_sharded_scatter",
           "replica_meshes"]


@dataclasses.dataclass(frozen=True)
class TPScope:
    mesh: Mesh
    axis: str = "model"

    @property
    def size(self) -> int:
        return self.mesh.shape[self.axis]


_CURRENT: Optional[TPScope] = None


def current() -> Optional[TPScope]:
    return _CURRENT


def degree() -> int:
    return _CURRENT.size if _CURRENT is not None else 1


@contextlib.contextmanager
def scope(mesh: Optional[Mesh], axis: str = "model"):
    """Activate a TP scope for the dynamic extent (trace-time dispatch
    decisions only).  ``mesh=None`` (or a 1-sized axis) is a no-op scope
    so callers can wrap unconditionally."""
    global _CURRENT
    if mesh is None or mesh.shape.get(axis, 1) <= 1:
        yield None
        return
    prev = _CURRENT
    _CURRENT = TPScope(mesh, axis)
    try:
        yield _CURRENT
    finally:
        _CURRENT = prev


@contextlib.contextmanager
def _suspended():
    """Clear the scope while tracing a shard_map body: the per-shard
    kernel call must not re-enter the column-parallel branch."""
    global _CURRENT
    prev, _CURRENT = _CURRENT, None
    try:
        yield
    finally:
        _CURRENT = prev


def _col_spec(a: jax.Array, axis_name: str) -> P:
    """Partition an array along its LAST axis."""
    return P(*([None] * (a.ndim - 1) + [axis_name]))


def column_parallel(fn, cols, out_axis: int = -1):
    """Run ``fn(*cols)`` column-parallel over the active TP scope.

    ``cols`` are the column-aligned operands (weights ``(K, N)``, biases /
    scales ``(N,)``) — each is sharded along its last axis; everything
    else (activations, K-aligned scales) must be closed over by ``fn``
    and is replicated.  The per-shard outputs are ``all_gather``ed
    (tiled) along ``out_axis``, so the caller sees the full array,
    bit-identical to the unsharded call.

    Returns None when no scope is active or any column axis does not
    divide — callers fall through to the unsharded path."""
    ctx = current()
    if ctx is None:
        return None
    tp = ctx.size
    real = [c for c in cols if c is not None]
    if not real or any(c.shape[-1] % tp for c in real):
        return None
    in_specs = tuple(P() if c is None else _col_spec(c, ctx.axis)
                     for c in cols)

    def body(*local):
        with _suspended():
            y = fn(*local)
        return jax.lax.all_gather(y, ctx.axis, axis=out_axis % y.ndim,
                                  tiled=True)

    return jax.shard_map(body, mesh=ctx.mesh, in_specs=in_specs,
                         out_specs=P(), check_vma=False)(*cols)


def head_sharded_attention(fn, q, k_pool, v_pool, rest):
    """Shard a paged-attention call over KV heads: ``q`` splits on its
    Hq axis (axis 2), the head-major pools on their Hkv axis (axis 1),
    the block table / offsets / lengths in ``rest`` replicate, and the
    per-shard outputs gather back along the head axis.  Per-head computation is exact, so
    the gathered result is bit-identical.  Returns None when no scope is
    active or the head counts do not divide."""
    ctx = current()
    if ctx is None:
        return None
    tp = ctx.size
    hq, hkv = q.shape[2], k_pool.shape[1]
    if hq % tp or hkv % tp or (hq // tp) % (hkv // tp):
        return None
    qs, ps = P(None, None, ctx.axis), P(None, ctx.axis)

    def body(q_, kp_, vp_, *rest_):
        with _suspended():
            y = fn(q_, kp_, vp_, *rest_)
        return jax.lax.all_gather(y, ctx.axis, axis=2, tiled=True)

    in_specs = (qs, ps, ps) + tuple(P() for _ in rest)
    return jax.shard_map(body, mesh=ctx.mesh, in_specs=in_specs,
                         out_specs=P(), check_vma=False)(q, k_pool, v_pool,
                                                         *rest)


def head_sharded_scatter(fn, k_new, v_new, k_pool, v_pool, rest):
    """Shard a paged KV scatter over KV heads: new rows split on their
    head axis (axis 2) and the head-major pools on theirs (axis 1),
    table/pos/len replicate, and the updated pools come back **gathered**
    (replicated) so the cache pytree stays a plain replicated array
    between steps.  Returns None
    when no scope is active or Hkv does not divide."""
    ctx = current()
    if ctx is None:
        return None
    tp = ctx.size
    if k_new.shape[2] % tp:
        return None
    ns, ps = P(None, None, ctx.axis), P(None, ctx.axis)

    def body(kn_, vn_, kp_, vp_, *rest_):
        with _suspended():
            k2, v2 = fn(kn_, vn_, kp_, vp_, *rest_)
        return (jax.lax.all_gather(k2, ctx.axis, axis=1, tiled=True),
                jax.lax.all_gather(v2, ctx.axis, axis=1, tiled=True))

    in_specs = (ns, ns, ps, ps) + tuple(P() for _ in rest)
    return jax.shard_map(body, mesh=ctx.mesh, in_specs=in_specs,
                         out_specs=(P(), P()), check_vma=False)(
                         k_new, v_new, k_pool, v_pool, *rest)


def replica_meshes(mesh: Mesh, dp_axis: str = "data",
                   tp_axis: str = "model") -> List[Mesh]:
    """Slice a ``(dp, tp)`` serving mesh into per-replica TP submeshes:
    replica *i* gets ``mesh.devices[i]`` as a 1-axis ``(tp,)`` mesh.
    The router runs one engine per submesh; dp replication itself is
    host-level (no collectives span the dp axis in serving)."""
    devs = mesh.devices
    assert mesh.axis_names == (dp_axis, tp_axis), \
        f"expected ({dp_axis!r}, {tp_axis!r}) mesh, got {mesh.axis_names}"
    return [Mesh(devs[i], (tp_axis,)) for i in range(devs.shape[0])]
