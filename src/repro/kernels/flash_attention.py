"""Pallas TPU kernel: causal flash attention (online softmax, VMEM tiles).

Why it exists here: the exact-roofline pass (EXPERIMENTS.md §Roofline)
shows every 32k prefill/train cell is MEMORY-bound, and the dominant
traffic is the O(T·S) f32 score/probability tensors the jnp online-softmax
attention materializes in HBM at every chunk (fusion cannot keep a dot's
output resident on CPU/TPU XLA).  Flash attention keeps the (bt × bk)
score tile in VMEM/registers: HBM traffic drops from O(T·S) to
O(T·S/bt · d) operand reads — i.e. the memory term collapses to operand
streaming (napkin math in §Perf A, iteration A4).

Layout: q (B, Hq, T, d), k/v (B, Hkv, S, d) with Hq a multiple of Hkv —
GQA is resolved in the index map (query head h streams KV head
h // (Hq/Hkv)), so grouped KV is never head-repeated in HBM.  Grid
(B, Hq, T/bt, S/bk) with the KV-block axis innermost; scratch (m, l, acc)
carries the running softmax state across KV blocks; finalization divides
on the last block.  Causal masking by absolute block offsets.  MXU
alignment: bt, bk multiples of 128 on real hardware (any value in
interpret mode).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import contract_precision, resolve_interpret

__all__ = ["flash_attention_pallas"]

_NEG = -1e30


# The online-softmax scratch state machine, shared with the paged-attention
# kernel (kernels/paged_attention.py): this is the numerically delicate part
# (fully-masked-row guard, l clamp), so it lives in exactly one place while
# each kernel keeps its own masking and block-walk logic.

def softmax_init(m_ref, l_ref, acc_ref) -> None:
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def softmax_update(s, v, m_ref, l_ref, acc_ref, precision=None) -> None:
    """Fold one KV block into the running (m, l, acc) scratch.

    ``s`` is the already-masked (bq, bk) score tile — invalid lanes hold
    ``_NEG``, which the ``s > _NEG / 2`` guard turns into exactly-zero
    probabilities (a fully-masked row would otherwise yield
    exp(_NEG − _NEG) = 1 per lane).  ``v`` is the (bk, d) value tile;
    ``precision`` that of a float32 ``p @ v`` contraction (``_pv``).
    """
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(s > _NEG / 2, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + _pv(p, v, precision)
    m_ref[...] = m_new


def _pv(p, v, precision):
    """``p @ v``, accumulated in float32.  A bfloat16 ``v`` meets ``p``
    split into two bfloat16 parts, so every product is exact and ``p``
    keeps all but about 2^-17 of itself; one bfloat16 pass (Mosaic's
    default contraction of a float32 operand) would keep 2^-9, an error as
    large as the output itself holds where the weights are near uniform
    and the values cancel."""
    if v.dtype == jnp.bfloat16:
        hi = p.astype(jnp.bfloat16)
        lo = (p - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        return (jnp.dot(hi, v, preferred_element_type=jnp.float32)
                + jnp.dot(lo, v, preferred_element_type=jnp.float32))
    return jnp.dot(p, v, preferred_element_type=jnp.float32,
                   precision=precision)


def softmax_finalize(l_ref, acc_ref, dtype):
    """Normalized (bq, d) output tile; rows that never saw a valid lane
    (l = 0) come out as zeros instead of dividing by zero."""
    return (acc_ref[...] /
            jnp.maximum(l_ref[...], 1e-20)[:, None]).astype(dtype)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            n_kv: int, bq: int, bk: int, causal: bool, scale: float,
            window: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        softmax_init(m_ref, l_ref, acc_ref)

    # block-level band check: a KV block entirely outside
    # (q_lo − window, q_hi] contributes nothing — skip its matmuls (on TPU
    # Mosaic this prunes the MXU work, making SWA prefill O(T·window))
    q_lo, q_hi = qi * bq, qi * bq + bq - 1
    k_lo, k_hi = ki * bk, ki * bk + bk - 1
    visible = jnp.bool_(True)
    if causal:
        visible = visible & (k_lo <= q_hi)
    if window > 0:
        visible = visible & (k_hi > q_lo - window)

    @pl.when(visible)
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)

        prec = contract_precision(q_ref.dtype, k_ref.dtype)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                    precision=prec)                        # (bq, bk)
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if causal:
            s = jnp.where(k_pos <= q_pos, s, _NEG)
        if window > 0:
            s = jnp.where(k_pos > q_pos - window, s, _NEG)

        softmax_update(s, v, m_ref, l_ref, acc_ref, prec)

    @pl.when(ki == n_kv - 1)
    def _finalize():
        o_ref[0, 0] = softmax_finalize(l_ref, acc_ref, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_pallas(
    q: jax.Array,                 # (B, Hq, T, d)
    k: jax.Array,                 # (B, Hkv, S, d); Hq % Hkv == 0
    v: jax.Array,                 # (B, Hkv, S, d)
    causal: bool = True,
    window: int = 0,              # >0 → sliding-window (SWA/local) band
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,  # None → chosen by platform
) -> jax.Array:
    b, h, t, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    g = h // hkv
    bq = min(block_q, t)
    bk = min(block_k, s)
    assert h % hkv == 0 and t % bq == 0 and s % bk == 0, (h, hkv, t, s)
    n_kv = s // bk
    scale = d**-0.5

    # query head hi streams KV head hi // g straight from the grouped
    # layout — no head-repeated KV copy ever lands in HBM
    out = pl.pallas_call(
        functools.partial(_kernel, n_kv=n_kv, bq=bq, bk=bk, causal=causal,
                          scale=scale, window=window),
        grid=(b, h, t // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    return out
