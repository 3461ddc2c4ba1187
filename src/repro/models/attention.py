"""Memory-efficient attention for the model zoo.

Online-softmax (flash-style) attention in pure jnp + ``lax.scan`` so that
32k-token prefill lowers with activation memory linear in sequence length:

  * ``full`` causal / non-causal: scan over KV chunks with running
    (max, denom, acc) statistics — peak live buffer is one (Tq × chunk)
    score tile per head group.
  * ``swa`` / ``local`` prefill: scan over **Q chunks**, each attending a
    static ``window + chunk`` KV slab via ``dynamic_slice`` — HLO FLOPs are
    O(T·window), making sliding-window archs genuinely sub-quadratic in the
    lowered module (this is what long-context roofline cells measure).
  * decode: single-token query against a (possibly ring-buffered) cache
    with a validity length.

GQA is computed in grouped layout (B, T, Hkv, G, hd) — KV is never
materialized repeated.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import opt_barrier

__all__ = ["attention", "gather_kv_blocks", "paged_attention"]

_NEG = -1e30


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _kv_chunk_attention(
    q: jax.Array,          # (B, T, Hkv, G, hd) pre-scaled
    k: jax.Array,          # (B, S, Hkv, hd)
    v: jax.Array,          # (B, S, Hkv, hd)
    q_pos: jax.Array,      # (T,) or (B, T) absolute positions of queries
    causal: bool,
    window: Optional[int],
    kv_len: Optional[jax.Array],  # scalar or (B,) valid-slot counts
    kv_pos_base: jax.Array,  # (S,) absolute positions of cache slots
    chunk: int,
) -> jax.Array:
    B, T, Hkv, G, hd = q.shape
    S = k.shape[1]
    c = min(chunk, S)
    Sp = _ceil_to(S, c)
    if Sp != S:
        pad = Sp - S
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos_base = jnp.pad(kv_pos_base, (0, pad), constant_values=-1)
    n_chunks = Sp // c

    # chunk-level remat = flash-attention backward: scores/probabilities of
    # a chunk are recomputed in its own backward instead of being stacked
    # across the whole scan (which would be O(T·S) live memory in training)
    @jax.checkpoint
    def body(carry, ci):
        # index-based dynamic slices keep the (possibly huge) cache in
        # place — no transposed copy of K/V is ever materialized
        m, l, acc = carry
        start = ci * c
        # the barrier stops XLA commuting convert(f32) past the slice and
        # hoisting a full-cache f32 copy out of the loop (CPU dot lowering)
        kci, vci = opt_barrier((
            jax.lax.dynamic_slice_in_dim(k, start, c, axis=1),
            jax.lax.dynamic_slice_in_dim(v, start, c, axis=1),
        ))
        pci = jax.lax.dynamic_slice_in_dim(kv_pos_base, start, c, axis=0)
        sloti = start + jnp.arange(c)
        s = jnp.einsum(
            "bthgd,bchd->bthgc", q, kci, preferred_element_type=jnp.float32
        )                                                   # (B,T,Hkv,G,c)
        # mask is built in (B', T', c) layout with B'/T' ∈ {1, full} so both
        # scalar (shared) and per-row (slot-batched decode) kv_len / q_pos
        # broadcast against the (B, T, Hkv, G, c) score tile
        qp = q_pos if q_pos.ndim == 2 else q_pos[None, :]     # (B'|1, T)
        mask = (pci >= 0)[None, None, :]                      # (1, 1, c)
        if kv_len is not None:
            kvl = jnp.asarray(kv_len).reshape(-1, 1, 1)       # (B'|1, 1, 1)
            mask = mask & (sloti[None, None, :] < kvl)
        if causal:
            mask = mask & (pci[None, None, :] <= qp[:, :, None])
        if window is not None:
            mask = mask & (pci[None, None, :] > (qp[:, :, None] - window))
        mask = mask[:, :, None, None, :]
        s = jnp.where(mask, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bthgc,bchd->bthgd", p.astype(vci.dtype), vci,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, T, Hkv, G), _NEG, jnp.float32)
    l0 = jnp.zeros((B, T, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, T, Hkv, G, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out


def _banded_attention(
    q: jax.Array,          # (B, T, Hkv, G, hd) pre-scaled; T == S
    k: jax.Array,
    v: jax.Array,
    window: int,
    chunk: int,
) -> jax.Array:
    """Sliding-window causal prefill: Q-chunk scan over a static KV slab."""
    B, T, Hkv, G, hd = q.shape
    cq = min(chunk, T)
    Tp = _ceil_to(T, cq)
    if Tp != T:
        q = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0), (0, 0), (0, 0)))
    nq = Tp // cq
    # front-pad KV by window (and end-pad to Tp) so every slab is in bounds
    end_pad = Tp - k.shape[1]
    kp = jnp.pad(k, ((0, 0), (window, end_pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, end_pad), (0, 0), (0, 0)))
    slab = window + cq

    @jax.checkpoint
    def one_chunk(ci):
        s0 = ci * cq
        qc = jax.lax.dynamic_slice_in_dim(q, s0, cq, axis=1)
        kc = jax.lax.dynamic_slice_in_dim(kp, s0, slab, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(vp, s0, slab, axis=1)
        q_pos = s0 + jnp.arange(cq)                       # absolute
        kv_pos = s0 - window + jnp.arange(slab)           # absolute (may be <0 = pad)
        s = jnp.einsum("bthgd,bchd->bthgc", qc, kc,
                       preferred_element_type=jnp.float32)
        mask = (
            (kv_pos[None, :] >= 0)
            & (kv_pos[None, :] <= q_pos[:, None])
            & (kv_pos[None, :] > q_pos[:, None] - window)
            & (q_pos[:, None] < T)
        )[None, :, None, None, :]
        s = jnp.where(mask, s, _NEG)
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        p = jnp.where(mask, p, 0.0)
        l = p.sum(axis=-1, keepdims=True)
        o = jnp.einsum("bthgc,bchd->bthgd", p.astype(vc.dtype), vc,
                       preferred_element_type=jnp.float32)
        return o / jnp.maximum(l[..., 0], 1e-20)[..., None]

    outs = jax.lax.map(one_chunk, jnp.arange(nq))          # (nq,B,cq,Hkv,G,hd)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Tp, Hkv, G, hd)
    return out[:, :T]


def attention(
    q: jax.Array,              # (B, T, Hq, hd)
    k: jax.Array,              # (B, S, Hkv, hd)
    v: jax.Array,              # (B, S, Hkv, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: jax.Array | int = 0,
    kv_len: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    chunk: int = 1024,
    impl: str = "chunked",
) -> jax.Array:
    """Grouped-query online-softmax attention.  Returns (B, T, Hq, hd).

    Args:
      q_offset:     absolute position of q[0] (decode: current cache length).
                    May be a (B,) vector when every batch row sits at its own
                    position (slot-batched continuous decode).
      kv_len:       number of valid cache slots (decode against padded cache).
                    Scalar or (B,) per-row vector.
      kv_positions: absolute position of every cache slot (ring buffers);
                    defaults to arange(S).
      window:       sliding-window size (swa/local); None = full.
      impl:         "chunked" (jnp scans) or "flash" (Pallas kernel) — the
                    kernel path covers the self-attention prefill/train case
                    (T == S, no kv_len), full **and** sliding-window: the
                    kernel masks the band in-block and skips off-band KV
                    blocks entirely.  Everything else falls back to the jnp
                    scans.
    """
    B, T, Hq, hd = q.shape
    S = k.shape[1]
    Hkv = k.shape[2]
    G = Hq // Hkv

    # static "whole-sequence from position 0" check: offset-prefill /
    # slot-batched callers pass traced (or vector) offsets and must take the
    # masked chunked path
    from_zero = isinstance(q_offset, int) and q_offset == 0

    bqk = min(128, T)
    if (impl == "flash" and T == S and T > 1 and kv_len is None
            and kv_positions is None and from_zero and T % bqk == 0
            and (window is None or causal)):
        from repro.kernels.flash_attention import flash_attention_pallas

        # KV stays in grouped (B, Hkv, S, hd) layout — the kernel's index
        # map resolves each query head to its KV head, so GQA is never
        # head-repeated in HBM (this path is memory-bound; see kernel doc)
        kh = k.transpose(0, 2, 1, 3)                         # (B,Hkv,S,hd)
        vh = v.transpose(0, 2, 1, 3)
        qh = q.transpose(0, 2, 1, 3)
        o = flash_attention_pallas(qh, kh, vh, causal=causal,
                                   window=0 if window is None
                                   else min(window, S),
                                   block_q=bqk, block_k=bqk)
        return o.transpose(0, 2, 1, 3).astype(q.dtype)

    qg = (q * hd**-0.5).reshape(B, T, Hkv, G, hd)
    qo = jnp.asarray(q_offset)
    q_pos = (qo[:, None] + jnp.arange(T)[None, :] if qo.ndim == 1
             else qo + jnp.arange(T))

    if (window is not None and T == S and T > 1 and causal and kv_len is None
            and kv_positions is None and from_zero):
        w = min(window, S)
        out = _banded_attention(qg, k, v, w, chunk)
    else:
        kv_pos = kv_positions if kv_positions is not None else jnp.arange(S)
        out = _kv_chunk_attention(
            qg, k, v, q_pos, causal, window, kv_len, kv_pos, chunk
        )
    return out.reshape(B, T, Hq, hd).astype(q.dtype)


# ------------------------------------------------------------ paged caches

def gather_kv_blocks(pool: jax.Array, block_table: jax.Array) -> jax.Array:
    """Contiguous logical KV view gathered from a pooled cache.

    ``pool`` is head-major ``(num_blocks, Hkv, block_size, hd)`` shared by
    every slot; ``block_table`` is ``(B, max_blocks)`` int32 mapping each
    row's logical block index to its physical block (``-1`` =
    unallocated).  Returns ``(B, max_blocks * block_size, Hkv, hd)``.
    Unallocated entries clip to block 0 for the gather and their rows are
    then **zeroed**: those
    logical positions are ≥ the row's ``pos`` and callers fence them with
    ``kv_len``, but the softmax fence multiplies by probability 0 — which
    is only a fence for *finite* garbage (0·NaN = NaN), so whatever block 0
    happens to hold must never reach the contraction
    (``tests/test_paged_kv.py`` poisons it to pin this).
    """
    nb, hkv, bs, hd = pool.shape
    idx = jnp.clip(block_table, 0, nb - 1)
    g = jnp.take(pool, idx, axis=0)            # (B, max_blocks, Hkv, bs, hd)
    g = jnp.where((block_table >= 0)[:, :, None, None, None], g, 0)
    b, mb = block_table.shape
    return g.transpose(0, 1, 3, 2, 4).reshape(b, mb * bs, hkv, hd)


def paged_kv_update(
    k_pool: jax.Array,             # (num_blocks, Hkv, block_size, hd)
    v_pool: jax.Array,
    k_new: jax.Array,              # (B, T, Hkv, hd) — decode: T == 1
    v_new: jax.Array,
    block_table: jax.Array,        # (B, max_blocks) int32, -1 = unallocated
    pos: jax.Array | int,          # scalar or (B,) absolute pos of row 0
    chunk_len: Optional[jax.Array | int] = None,  # valid rows (default T)
    *,
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array]:
    """Write new K/V rows into the pooled cache through the block table.

    Same dispatch ladder as :func:`paged_attention`: ``use_kernel`` routes
    to :func:`repro.kernels.paged_attention.paged_kv_scatter_pallas`
    (pools aliased in-place, nothing pool-shaped touched outside the
    ``pallas_call``); the jnp scatter below is the bit-exact
    oracle and the fallback.  Rows landing on an unallocated (-1) or
    out-of-range block are dropped — the same fence either way.

    Chunked prefill (``B == 1``, scalar ``pos``, partial ``chunk_len``)
    and slot-batched decode (``T == 1``, vector ``pos``) are the same op.
    """
    b, t = k_new.shape[:2]
    nb, bs = k_pool.shape[0], k_pool.shape[2]
    mb = block_table.shape[1]
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    cl = (jnp.full((b,), t, jnp.int32) if chunk_len is None else
          jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32).reshape(-1),
                           (b,)))
    if use_kernel:
        # chaos-harness injection site — see paged_attention below for the
        # trace-time compile_error / fallback semantics
        from repro.serve.faults import KernelFault, fire as _fire_fault

        kind = _fire_fault("kernel.paged_scatter")
        if kind == "compile_error":
            raise KernelFault(
                "injected paged KV scatter kernel compile failure")
        if kind != "fallback":
            from repro.kernels.paged_attention import paged_kv_scatter_pallas

            def _scatter(kn_, vn_, kp_, vp_, bt_, pos_, cl_):
                return paged_kv_scatter_pallas(kn_, vn_, kp_, vp_, bt_,
                                               pos_, cl_, interpret=interpret)

            # tensor parallelism (distributed/tp.py): under an active TP
            # scope the scatter shards over KV heads; the pools come back
            # gathered so the cache pytree stays replicated between steps
            from repro.distributed import tp as tp_mod
            out = tp_mod.head_sharded_scatter(
                _scatter, k_new, v_new, k_pool, v_pool,
                (block_table, posv, cl))
            if out is not None:
                return out
            return paged_kv_scatter_pallas(k_new, v_new, k_pool, v_pool,
                                           block_table, posv, cl,
                                           interpret=interpret)
    # jnp oracle: scatter each row to (physical block, in-block row)
    i = jnp.arange(t)
    wpos = posv[:, None] + i[None, :]                       # (B, T) abs pos
    blk = block_table[jnp.arange(b)[:, None],
                      jnp.clip(wpos // bs, 0, mb - 1)]
    blk = jnp.where((i[None, :] < cl[:, None]) & (blk >= 0)
                    & (wpos // bs < mb), blk, nb).reshape(-1)  # OOB → dropped
    off = (wpos % bs).reshape(-1)
    fk = k_pool.at[blk, :, off].set(
        k_new.reshape(b * t, *k_new.shape[2:]), mode="drop")
    fv = v_pool.at[blk, :, off].set(
        v_new.reshape(b * t, *v_new.shape[2:]), mode="drop")
    return fk, fv


def paged_attention(
    q: jax.Array,                  # (B, T, Hq, hd)
    k_pool: jax.Array,             # (num_blocks, Hkv, block_size, hd)
    v_pool: jax.Array,
    block_table: jax.Array,        # (B, max_blocks) int32, -1 = unallocated
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: jax.Array | int = 0,
    kv_len: Optional[jax.Array] = None,
    chunk: int = 1024,
    use_kernel: bool = False,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Attention over non-contiguous physical KV blocks.

    Dispatch ladder (the one PR 1 established for the projections):

      1. ``use_kernel`` — the policy flag (``SparsityPolicy
         .use_pallas_kernels``, threaded down by ``models/transformer``)
         routes the call onto :func:`repro.kernels.paged_attention
         .paged_attention_pallas`, which walks the block table in-kernel
         and never materializes the gathered logical view;
      2. the platform (``kernels.platform``) — Mosaic on a TPU, the
         Pallas interpreter elsewhere;
      3. the jnp gather-then-attend path below stays the bit-exact oracle
         and the fallback for shapes the kernel does not cover (sliding
         windows over paged pools, non-tile-divisible query counts).

    Chunked sparse prefill at cache offsets (``q_offset`` scalar) and
    vector-pos decode (``q_offset`` (B,)) both lower to the same kernel:
    masking is by absolute positions either way.
    """
    from repro.kernels.paged_attention import (paged_attention_pallas,
                                               paged_kernel_covers)
    B, T = q.shape[:2]
    if (use_kernel and window is None and kv_len is not None
            and paged_kernel_covers(T)):
        # chaos-harness injection site (serve/faults.py): this dispatch
        # runs at trace time, so a "compile_error" KernelFault aborts the
        # trace cleanly (nothing cached, engine degrades to the oracle jit)
        # and "fallback" silently takes the gather-oracle branch below —
        # either fires only while a trace is actually being built
        from repro.serve.faults import KernelFault, fire as _fire_fault

        kind = _fire_fault("kernel.paged_attention")
        if kind == "compile_error":
            raise KernelFault(
                "injected paged-attention kernel compile failure")
        if kind != "fallback":
            qo = jnp.broadcast_to(
                jnp.asarray(q_offset, jnp.int32).reshape(-1), (B,))
            kvl = jnp.broadcast_to(
                jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))

            def _kern(q_, kp_, vp_, bt_, qo_, kvl_):
                return paged_attention_pallas(q_, kp_, vp_, bt_, qo_, kvl_,
                                              causal=causal,
                                              block_q=min(128, T),
                                              interpret=interpret)

            # tensor parallelism (distributed/tp.py): under an active TP
            # scope the kernel shards over KV heads (heads are independent
            # and the GQA ratio is preserved) — bit-identical outputs
            from repro.distributed import tp as tp_mod
            out = tp_mod.head_sharded_attention(
                _kern, q, k_pool, v_pool, (block_table, qo, kvl))
            if out is not None:
                return out
            return paged_attention_pallas(q, k_pool, v_pool, block_table,
                                          qo, kvl, causal=causal,
                                          block_q=min(128, T),
                                          interpret=interpret)
    k = gather_kv_blocks(k_pool, block_table)
    v = gather_kv_blocks(v_pool, block_table)
    return attention(q, k, v, causal=causal, window=window,
                     q_offset=q_offset, kv_len=kv_len, chunk=chunk)
