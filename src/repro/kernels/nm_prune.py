"""Pallas TPU kernel: fused Amber-Pruner scoring + N:M top-k + mask apply.

The paper's masking pass is bandwidth-bound: naive composition (score,
top_k, one-hot, where) makes 3-4 HBM round-trips over X.  This kernel does
ONE pass: X tiles stream HBM→VMEM, the per-group top-N selection runs on
registers/VMEM, and only the masked tile is written back.

Selection ranks every lane inside its group of M lanes (:func:`nm_rank`):
lane ``i`` counts the partners that beat it — a higher score, or an equal
score at a lower index — and is kept iff fewer than N do.  That is exactly
``lax.top_k``'s choice (lowest index wins ties), so the output is bit-equal
to the jnp oracle.  The partners are reached with lane rotations
(``pltpu.roll``) on the 2-D tile: ``lax.top_k``, ``cumsum`` and a
``(T, D/M, M)`` lane-splitting reshape do not lower in Mosaic.

Tiling: (block_t × block_d) VMEM tiles, block_d a multiple of M; the scale
vector rides along as a (1, block_d) tile.  dtype-preserving (bf16 in/out,
f32 scoring).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

__all__ = ["nm_prune_pallas", "nm_rank", "nm_kept_before"]


def _group_partners(v: jax.Array, m: int):
    """Yield ``(partner, wrapped)`` for d = 1..m-1: lane ``i`` at group
    position ``q`` sees the value at group position ``(q + d) % m`` of its
    own group; ``wrapped`` marks partners at a LOWER index than ``i``."""
    c = v.shape[-1]
    q = jax.lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 1) % m
    for d in range(1, m):
        wrapped = q + d >= m
        ahead = pltpu.roll(v, c - d, v.ndim - 1)       # lane i ← v[i + d]
        behind = pltpu.roll(v, m - d, v.ndim - 1)      # lane i ← v[i + d - m]
        yield jnp.where(wrapped, behind, ahead), wrapped


def nm_rank(s: jax.Array, m: int) -> jax.Array:
    """(R, C) f32 scores → int32 rank of each lane inside its aligned group
    of ``m`` lanes (C % m == 0): the number of group partners with a higher
    score, or an equal score at a lower index.  ``rank < n`` is the N:M
    keep-mask with ``lax.top_k`` tie-breaking."""
    rank = jnp.zeros(s.shape, jnp.int32)
    for other, wrapped in _group_partners(s, m):
        beats = (other > s) | ((other == s) & wrapped)
        rank = rank + beats.astype(jnp.int32)
    return rank


def nm_kept_before(keep: jax.Array, m: int) -> jax.Array:
    """(R, C) int32 0/1 keep-mask → number of kept lanes at a lower index in
    the same group of ``m`` lanes: a kept lane's slot in its group's
    ascending compacted channel list."""
    before = jnp.zeros(keep.shape, jnp.int32)
    for other, wrapped in _group_partners(keep, m):
        before = before + jnp.where(wrapped, other, 0)
    return before


def _kernel(x_ref, scale_ref, o_ref, *, n: int, m: int, has_scale: bool):
    x = x_ref[...]                                     # (bt, bd)
    s = jnp.abs(x.astype(jnp.float32))
    if has_scale:
        s = s * scale_ref[...].astype(jnp.float32)
    keep = nm_rank(s, m) < n
    o_ref[...] = jnp.where(keep, x, jnp.zeros((), x.dtype))


@functools.partial(jax.jit, static_argnames=("n", "m", "block_t", "block_d",
                                             "interpret"))
def nm_prune_pallas(
    x: jax.Array,                       # (T, D)
    scale: Optional[jax.Array],         # (D,) or None
    n: int,
    m: int,
    block_t: int = 256,
    block_d: int = 512,
    interpret: Optional[bool] = None,   # None → chosen by platform
) -> jax.Array:
    t, d = x.shape
    bt = min(block_t, t)
    bd = min(block_d, d)
    assert t % bt == 0 and d % bd == 0 and bd % m == 0, (t, d, bt, bd, m)
    grid = (t // bt, d // bd)
    has_scale = scale is not None
    if not has_scale:
        scale = jnp.ones((d,), jnp.float32)

    return pl.pallas_call(
        functools.partial(_kernel, n=n, m=m, has_scale=has_scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bd), lambda i, j: (i, j)),
            pl.BlockSpec((1, bd), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bt, bd), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x, scale.reshape(1, d))
