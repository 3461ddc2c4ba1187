"""Jit'd public wrappers around the Pallas kernels.

``interpret=None`` (the default) lets the platform choose: Mosaic on a TPU
backend, the Pallas interpreter elsewhere (``kernels.platform``).

Wrappers handle leading-batch flattening and shape padding so callers can
use them as drop-in linear ops: when no well-sized block evenly divides an
axis, the axis is zero-padded up to the next block multiple (mirroring
``pruner.sparse_matmul``'s token padding) and the output is sliced back.
Zero padding is exact for every kernel here — padded tokens score zero in
the consensus pool, padded channels form all-zero N:M groups against
zero weight rows, and padded output columns are sliced away.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed import tp as tp_mod
from repro.kernels.nm_prune import nm_prune_pallas
from repro.kernels.nm_prune_matmul import nm_prune_matmul_pallas
from repro.kernels.nm_spmm import nm_spmm_pallas
from repro.kernels.osparse_matmul import osparse_matmul_pallas
from repro.kernels.w8a8_matmul import w8a8_matmul_pallas

__all__ = [
    "nm_prune",
    "nm_prune_matmul",
    "nm_spmm",
    "osparse_matmul",
    "w8a8_matmul",
]


def _flatten(x: jax.Array):
    lead = x.shape[:-1]
    t = 1
    for s in lead:
        t *= s
    return x.reshape(t, x.shape[-1]), lead


def _largest_divisor(total: int, target: int,
                     multiple_of: int = 1) -> Optional[int]:
    """Largest divisor of ``total`` that is ≤ target and a multiple of
    ``multiple_of``, or None when no such divisor exists."""
    for cand in range(min(target, total), 0, -1):
        if total % cand == 0 and cand % multiple_of == 0:
            return cand
    return None


def _block_and_pad(total: int, target: int, multiple_of: int = 1):
    """Pick a block size ≤ target (multiple of ``multiple_of``) and the
    padded axis length it divides.

    An axis no longer than ``target`` is one full-extent block.  Otherwise
    prefers an exact divisor of ``total`` (zero padding, full occupancy);
    when only degenerately small divisors exist (e.g. prime token counts)
    or none is a multiple of ``multiple_of``, falls back to a full-size
    block with zero padding up to the next block multiple.
    """
    if total <= target:
        return total, total
    div = _largest_divisor(total, target, multiple_of)
    lim = min(total, target)
    if div is not None and 2 * div >= lim:
        return div, total
    block = max(lim - lim % multiple_of, multiple_of)
    return block, total + (-total) % block


def _rows(total: int, target: int):
    """Block for a sublane (second-to-last) axis: Mosaic tiles 8 rows."""
    return _block_and_pad(total, target, multiple_of=8)


def _lanes(total: int, target: int, m: int = 1):
    """Block for a lane (last) axis: Mosaic tiles 128 lanes; contraction
    blocks also hold whole N:M groups of ``m`` channels."""
    return _block_and_pad(total, target, multiple_of=math.lcm(128, m))


def _pad_to(a: jax.Array, axis: int, new_size: int, value: float = 0.0):
    if a.shape[axis] == new_size:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, new_size - a.shape[axis])
    return jnp.pad(a, widths, constant_values=value)


def _check_groups(d: int, m: int) -> None:
    if d % m != 0:
        raise ValueError(f"last dim {d} not divisible by group size {m}")


def nm_prune(
    x: jax.Array,
    scale: Optional[jax.Array],
    n: int,
    m: int,
    block_t: int = 256,
    block_d: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused Amber prune over any (..., D) tensor."""
    xf, lead = _flatten(x)
    t, d = xf.shape
    _check_groups(d, m)
    bt, tp = _rows(t, block_t)
    bd, dp = _lanes(d, block_d, m)
    xf = _pad_to(_pad_to(xf, 0, tp), 1, dp)
    if scale is not None:
        scale = _pad_to(scale, 0, dp)
    y = nm_prune_pallas(xf, scale, n, m, block_t=bt, block_d=bd,
                        interpret=interpret)
    return y[:t, :d].reshape(*lead, d)


def nm_prune_matmul(
    x: jax.Array,
    w: jax.Array,
    scale: Optional[jax.Array],
    n: int,
    m: int,
    bias: Optional[jax.Array] = None,
    block_t: int = 256,
    block_o: int = 256,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused per-token prune + GEMM over any (..., D) input (one X pass).

    ``bias`` (``(N_out,)``) is folded into the kernel epilogue — the add
    happens on the hot f32 accumulator instead of a separate HBM pass."""
    # tensor parallelism (distributed/tp.py): under an active TP scope the
    # call re-enters itself column-parallel — each device runs this same
    # wrapper on its N_out slice (scope suspended inside the shard body),
    # and the gathered result is bit-identical to the unsharded call
    y = tp_mod.column_parallel(
        lambda w_, b_: nm_prune_matmul(x, w_, scale, n, m, bias=b_,
                                       block_t=block_t, block_o=block_o,
                                       block_k=block_k, interpret=interpret),
        (w, bias))
    if y is not None:
        return y
    xf, lead = _flatten(x)
    t, d = xf.shape
    n_out = w.shape[-1]
    _check_groups(d, m)
    bt, tp = _rows(t, block_t)
    bo, op = _lanes(n_out, block_o)
    bk, dp = _lanes(d, block_k, m)
    xf = _pad_to(_pad_to(xf, 0, tp), 1, dp)
    w = _pad_to(_pad_to(w, 0, dp), 1, op)
    if scale is not None:
        scale = _pad_to(scale, 0, dp)
    if bias is not None:
        bias = _pad_to(bias, 0, op)
    y = nm_prune_matmul_pallas(xf, w, scale, n, m, bias=bias, block_t=bt,
                               block_o=bo, block_k=bk, interpret=interpret)
    return y[:t, :n_out].reshape(*lead, n_out)


def nm_spmm(
    x: jax.Array,
    w: jax.Array,
    scale: Optional[jax.Array],
    n: int,
    m: int,
    tile: int = 256,
    block_o: int = 256,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Tile-consensus compacted matmul over any (..., D) input.

    The token block IS the consensus tile (one shared channel set per bt
    tokens), so ``tile`` is semantic, not a free tiling parameter: the
    block is always ``min(tile, t)`` with zero-padding up to a tile
    multiple — exactly ``pruner.sparse_matmul``'s tiling, never a smaller
    divisor (which would change which tokens vote in each pool).
    """
    # column-parallel TP: the consensus vote runs over the full (replicated)
    # activations/K axis on every device, so sharding N_out cannot change
    # which channels win — outputs stay bit-identical
    y = tp_mod.column_parallel(
        lambda w_: nm_spmm(x, w_, scale, n, m, tile=tile, block_o=block_o,
                           block_k=block_k, interpret=interpret),
        (w,))
    if y is not None:
        return y
    xf, lead = _flatten(x)
    t, d = xf.shape
    n_out = w.shape[-1]
    _check_groups(d, m)
    bt = min(tile, t)
    tp = t + (-t) % bt
    bo, op = _lanes(n_out, block_o)
    bk, dp = _lanes(d, block_k, m)
    xf = _pad_to(_pad_to(xf, 0, tp), 1, dp)
    w = _pad_to(_pad_to(w, 0, dp), 1, op)
    if scale is not None:
        scale = _pad_to(scale, 0, dp)
    y = nm_spmm_pallas(xf, w, scale, n, m, block_t=bt, block_o=bo,
                       block_k=bk, interpret=interpret)
    return y[:t, :n_out].reshape(*lead, n_out)


def osparse_matmul(
    x: jax.Array,
    wq: jax.Array,
    smooth: jax.Array,
    amber: Optional[jax.Array],
    w_scale: jax.Array,
    n: int,
    m: int,
    act_scale: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    prune: bool = True,
    per_token: bool = False,
    block_t: int = 256,
    block_o: int = 256,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused Outstanding-sparse projection over any (..., D) input.

    Returns float32 (dequantized) — callers cast back to the model dtype,
    matching ``quant.quantized_matmul``.  ``bias`` is folded into the
    dequant epilogue; ``prune=False`` skips the N:M selection statically,
    turning the same kernel into the decode-phase smoothed W8A8 GEMM.
    """
    if not prune:
        n = m = 1  # no selection → no channel-group divisibility constraint
    # column-parallel TP: wq/w_scale/bias are N_out-aligned and shard;
    # smooth/amber/act_scale are K- or token-aligned and replicate
    y = tp_mod.column_parallel(
        lambda wq_, ws_, b_: osparse_matmul(
            x, wq_, smooth, amber, ws_, n, m, act_scale=act_scale, bias=b_,
            prune=prune, per_token=per_token, block_t=block_t,
            block_o=block_o, block_k=block_k, interpret=interpret),
        (wq, w_scale, bias))
    if y is not None:
        return y
    xf, lead = _flatten(x)
    t, d = xf.shape
    n_out = wq.shape[-1]
    _check_groups(d, m)
    bt, tp = _rows(t, block_t)
    bo, op = _lanes(n_out, block_o)
    bk, dp = _lanes(d, block_k, m)
    xf = _pad_to(_pad_to(xf, 0, tp), 1, dp)
    wq = _pad_to(_pad_to(wq, 0, dp), 1, op)
    smooth = _pad_to(smooth, 0, dp, value=1.0)  # padded channels: 0/1 = 0
    w_scale = _pad_to(w_scale, 0, op)
    if amber is not None:
        amber = _pad_to(amber, 0, dp)
    if bias is not None:
        bias = _pad_to(bias, 0, op)
    y = osparse_matmul_pallas(xf, wq, smooth, amber, w_scale, act_scale,
                              n, m, bias=bias, prune=prune,
                              per_token=per_token, block_t=bt,
                              block_o=bo, block_k=bk, interpret=interpret)
    return y[:t, :n_out].reshape(*lead, n_out)


def w8a8_matmul(
    xq: jax.Array,
    wq: jax.Array,
    x_scale: jax.Array,
    w_scale: jax.Array,
    block_t: int = 256,
    block_o: int = 256,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    y = tp_mod.column_parallel(
        lambda wq_, ws_: w8a8_matmul(xq, wq_, x_scale, ws_, block_t=block_t,
                                     block_o=block_o, block_k=block_k,
                                     interpret=interpret),
        (wq, w_scale))
    if y is not None:
        return y
    xf, lead = _flatten(xq)
    t, d = xf.shape
    n_out = wq.shape[-1]
    bt, tp = _rows(t, block_t)
    bo, op = _lanes(n_out, block_o)
    bk, dp = _lanes(d, block_k)
    xf = _pad_to(_pad_to(xf, 0, tp), 1, dp)
    wq = _pad_to(_pad_to(wq, 0, dp), 1, op)
    w_scale = _pad_to(w_scale, 0, op)
    y = w8a8_matmul_pallas(xf, wq, x_scale, w_scale, block_t=bt, block_o=bo,
                           block_k=bk, interpret=interpret)
    return y[:t, :n_out].reshape(*lead, n_out)
