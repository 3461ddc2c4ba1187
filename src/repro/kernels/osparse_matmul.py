"""Pallas TPU kernel: fused Outstanding-sparse projection.

The Outstanding-sparse runtime chain (paper §Outstanding-sparse) is

    smooth-divide → N:M prune → int8 quantize → int8 GEMM → dequant

which the jnp path executes as 4-5 separate XLA ops, each a full HBM pass
over a T×D activation tensor (smoothed copy, masked copy, quantized copy,
GEMM read).  This kernel runs the whole chain inside one ``pallas_call``:
every intermediate (smoothed / masked / quantized tile) lives in
registers, and the only HBM write is the T×N_out output.  The GEMM's own
block streaming is the same as a dense tiled matmul's; what the fusion
removes is the three intermediate copies' write+read traffic.

Two quantization modes (matching ``repro.core.quant``):

  * **per-tensor** (static ``act_scale``): classic k-blocked int8 GEMM grid
    (T/bt, N_out/bo, D/bk) with an int32 accumulator scratch; int32 partial
    sums commute, so the result is bit-equal to the jnp oracle.
  * **per-token** (dynamic scales): the row absmax of the *pruned smoothed*
    activations must be known before quantizing, so the k axis runs two
    sweeps — sweep 1 (executed only at the first output block; the scratch
    persists across the sequential j steps) reduces the per-token absmax,
    sweep 2 quantizes with the finished scale and accumulates the int8
    GEMM.  Cost: one extra streaming pass over X and zero intermediate
    writes, vs the jnp path's ~4 reads + 3 writes.

Scoring uses the Amber channel scale on the *smoothed* activations, exactly
as ``layers.linear._quantized`` does; selection is the shared iterative
in-group rank (``nm_prune.nm_rank``), so masks match ``nm.apply_nm``
bit-for-bit.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.nm_prune import nm_rank
from repro.kernels.platform import resolve_interpret

__all__ = ["osparse_matmul_pallas"]

_EPS = 1e-8  # matches repro.core.quant._EPS


def _pruned_smoothed(x, smooth, amber, *, n, m, has_amber, prune=True):
    """smooth-divide + score + N:M mask, all in registers. (bt, bk) f32.

    ``prune=False`` (static) skips scoring/selection entirely — the same
    kernel then runs the plain smoothed W8A8 chain, which is what the
    decode phase uses (the policy gates pruning to prefill)."""
    xs = x.astype(jnp.float32) / smooth.astype(jnp.float32)
    if not prune:
        return xs
    s = jnp.abs(xs)
    if has_amber:
        s = s * amber.astype(jnp.float32)
    return jnp.where(nm_rank(s, m) < n, xs, 0.0)


def _quantize(xp, scale):
    return jnp.clip(jnp.round(xp / scale), -127, 127).astype(jnp.int8)


def _kernel(x_ref, wq_ref, smooth_ref, amber_ref, ws_ref, as_ref, bias_ref,
            o_ref, acc_ref, amax_ref, *, n: int, m: int, has_amber: bool,
            has_bias: bool, prune: bool, per_token: bool, k_steps: int):
    j = pl.program_id(1)
    k = pl.program_id(2)

    def xp():
        return _pruned_smoothed(x_ref[...], smooth_ref[...], amber_ref[...],
                                n=n, m=m, has_amber=has_amber, prune=prune)

    def epilogue(o):  # (bt, bo) f32 dequantized — fold the bias-add in
        if has_bias:
            o = o + bias_ref[...].astype(jnp.float32)
        return o

    if per_token:
        # ---- sweep 1: reduce the per-token absmax of the pruned rows.
        # The scale is independent of the output block, and the grid runs
        # sequentially with j outer / k inner, so the scratch filled at
        # j == 0 stays valid for every later j of the same token block —
        # the sweep (and its smooth+select work) runs once per i, not per j.
        @pl.when((j == 0) & (k == 0))
        def _init_amax():
            amax_ref[...] = jnp.zeros_like(amax_ref)

        @pl.when((j == 0) & (k < k_steps))
        def _scan_amax():
            amax_ref[...] = jnp.maximum(
                amax_ref[...], jnp.abs(xp()).max(axis=-1, keepdims=True))

        # ---- sweep 2: quantize with the finished scale, int8 GEMM ----
        @pl.when(k == k_steps)
        def _init_acc():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(k >= k_steps)
        def _accumulate():
            scale = jnp.maximum(amax_ref[...], _EPS) / 127.0    # (bt, 1)
            acc_ref[...] += jax.lax.dot_general(
                _quantize(xp(), scale), wq_ref[...],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )

        @pl.when(k == 2 * k_steps - 1)
        def _finish():
            scale = jnp.maximum(amax_ref[...], _EPS) / 127.0
            w_scale = ws_ref[...].astype(jnp.float32)
            o_ref[...] = epilogue(acc_ref[...].astype(jnp.float32) * scale
                                  * w_scale).astype(o_ref.dtype)
    else:
        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        act_scale = as_ref[0, 0]
        acc_ref[...] += jax.lax.dot_general(
            _quantize(xp(), act_scale), wq_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

        @pl.when(k == k_steps - 1)
        def _finish():
            w_scale = ws_ref[...].astype(jnp.float32)
            o_ref[...] = epilogue(acc_ref[...].astype(jnp.float32) * act_scale
                                  * w_scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "m", "prune", "per_token",
                                             "block_t", "block_o", "block_k",
                                             "interpret"))
def osparse_matmul_pallas(
    x: jax.Array,                       # (T, D) raw (unsmoothed) activations
    wq: jax.Array,                      # (D, N_out) int8
    smooth: jax.Array,                  # (D,) SmoothQuant divide factor
    amber: Optional[jax.Array],         # (D,) Amber channel scale or None
    w_scale: jax.Array,                 # (N_out,) f32 per-channel dequant
    act_scale: Optional[jax.Array],     # scalar f32, required unless per_token
    n: int,
    m: int,
    bias: Optional[jax.Array] = None,   # (N_out,) or None — epilogue add
    prune: bool = True,                 # False → plain smoothed W8A8 (decode)
    per_token: bool = False,
    block_t: int = 256,
    block_o: int = 256,
    block_k: int = 512,
    interpret: Optional[bool] = None,   # None → chosen by platform
) -> jax.Array:
    t, d = x.shape
    n_out = wq.shape[-1]
    if not prune:
        n = m = 1  # selection is skipped; neutralize the bk % m constraint
    bt = min(block_t, t)
    bo = min(block_o, n_out)
    bk = min(block_k, d)
    assert t % bt == 0 and n_out % bo == 0 and d % bk == 0 and bk % m == 0, (
        t, d, n_out, bt, bo, bk, m)
    k_steps = d // bk
    has_amber = amber is not None
    if not has_amber:
        amber = jnp.ones((d,), jnp.float32)
    has_bias = bias is not None
    if not has_bias:
        bias = jnp.zeros((n_out,), jnp.float32)
    if act_scale is None:
        if not per_token:
            raise ValueError("act_scale is required for per-tensor mode")
        act_scale = jnp.ones((), jnp.float32)  # unused placeholder

    # per-token mode runs the k axis twice: absmax sweep, then GEMM sweep
    k_grid = (2 * k_steps) if per_token else k_steps
    x_block = lambda i, j, k: (i, k % k_steps)
    d_block = lambda i, j, k: (0, k % k_steps)
    o_block = lambda i, j, k: (0, j)

    return pl.pallas_call(
        functools.partial(_kernel, n=n, m=m, has_amber=has_amber,
                          has_bias=has_bias, prune=prune,
                          per_token=per_token, k_steps=k_steps),
        grid=(t // bt, n_out // bo, k_grid),
        in_specs=[
            pl.BlockSpec((bt, bk), x_block),
            pl.BlockSpec((bk, bo), lambda i, j, k: (k % k_steps, j)),
            pl.BlockSpec((1, bk), d_block),
            pl.BlockSpec((1, bk), d_block),
            pl.BlockSpec((1, bo), o_block),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bo), o_block),
        ],
        out_specs=pl.BlockSpec((bt, bo), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, n_out), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bt, bo), jnp.int32),
            pltpu.VMEM((bt, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(x, wq, smooth.reshape(1, d), amber.reshape(1, d),
      w_scale.reshape(1, n_out),
      jnp.asarray(act_scale, jnp.float32).reshape(1, 1),
      bias.reshape(1, n_out))
