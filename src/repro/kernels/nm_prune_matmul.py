"""Pallas TPU kernel: fused per-token N:M prune + GEMM (one X pass).

The naive per-token path (``prune_input`` then ``xp @ w``) materializes the
masked activations: X streams HBM→VMEM for scoring/masking, the masked copy
is written back to HBM, then the dense matmul reads it again — three full
passes over a T×D tensor that exists only to be multiplied once.  This
kernel fuses score → in-group rank select → mask → GEMM into a single
``pallas_call``: the masked copy lives only in registers and is never
materialized in HBM.  The GEMM's own block streaming (each X block is
re-fetched once per output block, as in any tiled matmul — dense included)
is identical in both forms, so the fusion saves exactly the prune stage's
traffic: one full X write plus one full X read per call.

Extra HBM traffic vs the dense GEMM:   none          (fused, this kernel)
                                 vs:   write Xp + read Xp   (jnp path)

The grid is (T/bt, N_out/bo, D/bk) with a float32 accumulator scratch; the
per-token N:M selection is local to each contiguous group of M channels, so
k-blocking (bk % m == 0) is exact — every k-step prunes its own groups and
accumulates its partial product.  Selection is the shared in-group rank of
``nm_prune_pallas`` (lowest index wins on ties), so masks are bit-identical
to the ``nm.apply_nm`` oracle.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.nm_prune import nm_rank
from repro.kernels.platform import contract_precision, resolve_interpret

__all__ = ["nm_prune_matmul_pallas"]


def _kernel(x_ref, w_ref, scale_ref, bias_ref, o_ref, acc_ref, *, n: int,
            m: int, has_scale: bool, has_bias: bool, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                     # (bt, bk)
    s = jnp.abs(x.astype(jnp.float32))
    if has_scale:
        s = s * scale_ref[...].astype(jnp.float32)
    keep = nm_rank(s, m) < n
    xp = jnp.where(keep, x.astype(jnp.float32), 0.0)
    acc_ref[...] += jnp.dot(xp, w_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32,
                            precision=contract_precision(x.dtype, w_ref.dtype))

    @pl.when(k == k_steps - 1)
    def _finish():
        acc = acc_ref[...]
        if has_bias:  # bias-add folded into the epilogue (free: acc is hot)
            acc = acc + bias_ref[...].astype(jnp.float32)
        o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "m", "block_t", "block_o",
                                             "block_k", "interpret"))
def nm_prune_matmul_pallas(
    x: jax.Array,                       # (T, D)
    w: jax.Array,                       # (D, N_out)
    scale: Optional[jax.Array],         # (D,) or None
    n: int,
    m: int,
    bias: Optional[jax.Array] = None,   # (N_out,) or None — epilogue add
    block_t: int = 256,
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
    has_bias = bias is not None
    if not has_bias:
        bias = jnp.zeros((n_out,), jnp.float32)

    out_dtype = jnp.result_type(x.dtype, w.dtype)
    return pl.pallas_call(
        functools.partial(_kernel, n=n, m=m, has_scale=has_scale,
                          has_bias=has_bias, k_steps=k_steps),
        grid=(t // bt, n_out // bo, k_steps),
        in_specs=[
            pl.BlockSpec((bt, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bo), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bk), lambda i, j, k: (0, k)),
            pl.BlockSpec((1, bo), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bt, bo), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, n_out), out_dtype),
        scratch_shapes=[pltpu.VMEM((bt, bo), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, w, scale.reshape(1, d), bias.reshape(1, n_out))
