"""Pallas TPU kernel: tile-consensus N:M compacted matmul (the TPU SpMM).

This is the TPU-native analogue of the sparse-tensor-core SpMM the paper
targets.  Per token tile, one shared N:M channel pattern is chosen
(L2-pooled Amber scores), and the contraction runs over only the surviving
D·N/M channels — a real (M/N)× FLOP reduction in the main GEMM, unlike
per-token masking which the MXU cannot exploit.

In-kernel compaction uses **one-hot selection matmuls**: gathers with
traced indices don't vectorize on the TPU VPU, but matmuls run on the MXU.
The selection matrix ``St`` is ``(bk·n/m, bk)``: row ``g·n + r`` picks the
``r``-th kept channel (ascending, as ``nm.compact_columns`` orders them) of
group ``g``.  It is built on the 2-D tile from the in-group rank of the
pooled scores (``nm_prune.nm_rank``) — no lane-splitting reshape, which
Mosaic refuses.  Cost per (k, j) step:
  selection:  bt·bk·(bk·n/m) + (bk·n/m)·bk·bo
  main GEMM:  bt·(bk·n/m)·bo       (the (M/N)× win)
so the compaction costs more MXU work than it saves at these block sizes;
whether tile consensus can win on the chip is an open measurement.

Grid: (T/bt, N_out/bo, D/bk) with a float32 accumulator scratch.  The
consensus selection is *local to each group of M channels* (the tile-L2
pool is per-channel), so k-blocking the reduction depth is exact: each
k-step selects inside its own groups and accumulates its partial product.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.nm_prune import nm_kept_before, nm_rank
from repro.kernels.platform import contract_precision, resolve_interpret

__all__ = ["nm_spmm_pallas"]


def _selection(pooled: jax.Array, n: int, m: int) -> jax.Array:
    """(1, bk) pooled scores → (bk·n/m, bk) f32 one-hot selection ``St``."""
    bk = pooled.shape[-1]
    keep = (nm_rank(pooled, m) < n).astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    slot = (lane // m) * n + nm_kept_before(keep, m)
    row = jax.lax.broadcasted_iota(jnp.int32, (bk // m * n, bk), 0)
    return ((row == slot) & (keep > 0)).astype(jnp.float32)


def _kernel(x_ref, w_ref, scale_ref, o_ref, acc_ref, *, n: int, m: int,
            has_scale: bool, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)                  # (bt, bk)
    s = jnp.abs(x)
    if has_scale:
        s = s * scale_ref[...].astype(jnp.float32)
    pooled = jnp.sqrt((s * s).sum(axis=0, keepdims=True))  # (1, bk) tile-L2
    st = _selection(pooled, n, m)                       # (bk·n/m, bk)

    # compact activations and weights via one-hot selection matmuls
    prec = contract_precision(x_ref.dtype, w_ref.dtype)
    xc = jax.lax.dot_general(x, st, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32,
                             precision=prec)
    wc = jnp.dot(st, w_ref[...].astype(jnp.float32),
                 preferred_element_type=jnp.float32, precision=prec)
    acc_ref[...] += jnp.dot(xc, wc, preferred_element_type=jnp.float32,
                            precision=prec)

    @pl.when(k == k_steps - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "m", "block_t", "block_o",
                                             "block_k", "interpret"))
def nm_spmm_pallas(
    x: jax.Array,                       # (T, D)
    w: jax.Array,                       # (D, N_out)
    scale: Optional[jax.Array],         # (D,) or None
    n: int,
    m: int,
    block_t: int = 256,                 # = consensus tile size
    block_o: int = 256,
    block_k: int = 512,
    interpret: Optional[bool] = None,   # None → chosen by platform
) -> jax.Array:
    t, d = x.shape
    n_out = w.shape[-1]
    bt = min(block_t, t)
    bo = min(block_o, n_out)
    bk = min(block_k, d)
    assert t % bt == 0 and n_out % bo == 0 and d % bk == 0 and bk % m == 0, (
        t, d, n_out, bt, bo, bk, m)
    k_steps = d // bk
    has_scale = scale is not None
    if not has_scale:
        scale = jnp.ones((d,), jnp.float32)

    return pl.pallas_call(
        functools.partial(_kernel, n=n, m=m, has_scale=has_scale,
                          k_steps=k_steps),
        grid=(t // bt, n_out // bo, k_steps),
        in_specs=[
            pl.BlockSpec((bt, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bo), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bk), lambda i, j, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((bt, bo), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, n_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, bo), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, w, scale.reshape(1, d))
