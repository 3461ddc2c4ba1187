"""Pallas TPU kernel: paged flash attention — the KV grid axis walks the
per-row block table in-kernel.

Why it exists here: PR 3's paged KV cache made allocation block-granular,
but the serving engine's attention still gathered a dense
``(B, max_blocks * block_size, Hkv, hd)`` logical view into HBM on every
chunked-prefill and decode call (``models/attention.gather_kv_blocks``) —
a full write + re-read of the logical cache view per call, which the
roofline pass shows is the dominant HBM term of paged serving.  This
kernel deletes that view: the innermost grid axis iterates **logical**
block indices, each step resolves ``block_table[b, ki]`` from a
scalar-prefetch argument (``pltpu.PrefetchScalarGridSpec``) and streams
that *physical* block of the shared pool straight through VMEM.  Blocks
that are unallocated (``-1``) or entirely outside the row's valid
``kv_len`` (and, with causal/window masking, outside the query band) are
skipped before their matmuls issue — decode attention is O(pos) per row,
not O(max_blocks * block_size).

Layout: q ``(B, Tq, Hq, hd)`` (viewed head-major inside the wrapper);
pools are head-major ``(num_blocks, Hkv, block_size, hd)`` shared by every
row, so one KV head of one physical block is a ``(block_size, hd)`` tile —
the last two block dims Mosaic tiles on.  GQA is resolved in the index
map: query head ``h`` reads KV head ``h // (Hq // Hkv)`` — the pool is
never head-repeated in HBM.  Grid ``(B, Hq, Tq/bq, max_blocks)`` with the
block axis innermost;
scratch (m, l, acc) carries the online-softmax state across blocks;
finalization divides on the last block.  Masking is by **absolute**
positions: query row r sits at ``q_offset[b] + qi*bq + r`` and block
``ki`` covers positions ``[ki*bs, (ki+1)*bs)``, so chunked prefill at a
cache offset (scalar ``q_offset`` broadcast per row) and vector-position
decode (per-row ``q_offset``) lower to the same kernel.

Mosaic tiling: ``block_size`` must be a multiple of the sublane tile of
the pool dtype (16 for bf16) and ``hd`` a multiple of 128; ``bq`` is 128
or the whole query count (decode runs bq=1).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import (_NEG, softmax_finalize,
                                           softmax_init, softmax_update)
from repro.kernels.platform import contract_precision, resolve_interpret

__all__ = ["paged_attention_pallas", "paged_kv_scatter_pallas",
           "paged_kernel_covers"]


def paged_kernel_covers(t: int) -> bool:
    """Can the kernel serve a ``t``-query call?  The single source of
    truth for the q-tile divisibility rule — the dispatch layer
    (``models/attention.paged_attention``) falls back to the gather oracle
    when this is False, and the serving engine rejects prefill chunk
    buckets that would silently do so while claiming the kernel ran."""
    return t % min(128, t) == 0


def _block_visible(tab_ref, qoff_ref, kvlen_ref, bi, qi, ki, *,
                   bq: int, bs: int, causal: bool, window: int):
    """(physical block id, contributes-anything?) for one grid step.

    The SINGLE definition of the block-level walk: an unallocated (-1)
    table entry, a block entirely past the row's kv_len, or a block
    entirely outside the causal/window band of this q tile contributes
    nothing.  Both the kernel body (to skip the matmuls — on TPU Mosaic
    this prunes the MXU work; decode touches O(pos) rows) and the
    BlockSpec index map (to skip the DMA) consume this predicate; if they
    ever disagreed, the body would accumulate a block the pipeline never
    fetched.
    """
    pb = tab_ref[bi, ki]
    k_lo = ki * bs
    vis = (pb >= 0) & (k_lo < kvlen_ref[bi])
    q_lo = qoff_ref[bi] + qi * bq
    if causal:
        vis = vis & (k_lo <= q_lo + bq - 1)
    if window > 0:
        vis = vis & (k_lo + bs - 1 > q_lo - window)
    return pb, vis


def _kernel(tab_ref, qoff_ref, kvlen_ref,      # scalar prefetch
            q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            mb: int, bq: int, bs: int, causal: bool, window: int,
            scale: float):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        softmax_init(m_ref, l_ref, acc_ref)

    kvl = kvlen_ref[b]
    q_lo = qoff_ref[b] + qi * bq
    k_lo = ki * bs
    _, visible = _block_visible(tab_ref, qoff_ref, kvlen_ref, b, qi, ki,
                                bq=bq, bs=bs, causal=causal, window=window)

    @pl.when(visible)
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32) * scale            # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                    # (bs, hd)
        v = v_ref[0, 0].astype(jnp.float32)

        prec = contract_precision(q_ref.dtype, k_ref.dtype)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                    precision=prec)                        # (bq, bs)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bs), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bs), 1)
        valid = k_pos < kvl
        if causal:
            valid = valid & (k_pos <= q_pos)
        if window > 0:
            valid = valid & (k_pos > q_pos - window)
        s = jnp.where(valid, s, _NEG)
        # rows of a partially-filled physical block past kv_len are
        # unwritten pool memory; zero them so a 0-probability column can
        # never propagate NaN/garbage through the p @ v contraction
        col = jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        v = jnp.where(k_lo + col < kvl, v, 0.0)

        softmax_update(s, v, m_ref, l_ref, acc_ref, prec)

    @pl.when(ki == mb - 1)
    def _finalize():
        o_ref[0, 0] = softmax_finalize(l_ref, acc_ref, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "interpret"))
def paged_attention_pallas(
    q: jax.Array,             # (B, Tq, Hq, hd)
    k_pool: jax.Array,        # (num_blocks, Hkv, block_size, hd)
    v_pool: jax.Array,        # (num_blocks, Hkv, block_size, hd)
    block_table: jax.Array,   # (B, max_blocks) int32, -1 = unallocated
    q_offset: jax.Array,      # (B,) int32 absolute position of q[:, 0]
    kv_len: jax.Array,        # (B,) int32 valid KV rows per table row
    causal: bool = True,
    window: int = 0,          # >0 → sliding-window band by absolute pos
    block_q: int = 128,
    interpret: Optional[bool] = None,  # None → chosen by platform
) -> jax.Array:
    b, t, hq, hd = q.shape
    nb, hkv, bs = k_pool.shape[:3]
    mb = block_table.shape[1]
    g = hq // hkv
    bq = min(block_q, t)
    # non-divisible heads would make the index map read a clamped
    # out-of-range KV head — plausible wrong outputs, so fail fast
    assert hq % hkv == 0 and t % bq == 0, (hq, hkv, t, bq)
    scale = hd**-0.5

    tab = block_table.astype(jnp.int32)
    qoff = q_offset.astype(jnp.int32)
    kvl = kv_len.astype(jnp.int32)

    def k_index(bi, h, qi, ki, tab_ref, qoff_ref, kvlen_ref):
        # physical block for this logical step.  Steps the kernel body will
        # skip (same ``_block_visible`` predicate) resolve to the row's
        # FIRST block instead of their own: consecutive skipped steps then
        # map to an unchanged index, so the pipeline's refetch elision
        # issues no DMA for them and attention traffic is O(kv_len) rows,
        # not O(allocated blocks) (clipped to 0 for fully-empty rows).
        pb, vis = _block_visible(tab_ref, qoff_ref, kvlen_ref, bi, qi, ki,
                                 bq=bq, bs=bs, causal=causal, window=window)
        pb = jnp.where(vis, pb, tab_ref[bi, 0])
        return (jnp.maximum(pb, 0), h // g, 0, 0)

    def q_index(bi, h, qi, ki, *_):
        return (bi, h, qi, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hq, t // bq, mb),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), q_index),
            pl.BlockSpec((1, 1, bs, hd), k_index),
            pl.BlockSpec((1, 1, bs, hd), k_index),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), q_index),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, mb=mb, bq=bq, bs=bs, causal=causal,
                          window=window, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, t, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(tab, qoff, kvl, q.transpose(0, 2, 1, 3), k_pool, v_pool)
    return out.transpose(0, 2, 1, 3)


def _scatter_visible(tab_ref, pos_ref, len_ref, bi, ci, *, bs: int, mb: int):
    """(physical block id, receives-any-row?) for one scatter grid step.

    Logical block ``pos[bi] // bs + ci`` receives rows iff it overlaps the
    row's write span ``[pos, pos + chunk_len)``, sits inside the table, and
    is actually allocated.  Shared by the kernel body and the pool index
    maps — same contract as ``_block_visible`` above: disagreement would
    mean the body merges into a block the pipeline never fetched.
    """
    lb = pos_ref[bi] // bs + ci
    pb = tab_ref[bi, jnp.clip(lb, 0, mb - 1)]
    lo = lb * bs
    p0 = pos_ref[bi]
    vis = ((lb < mb) & (pb >= 0)
           & (lo < p0 + len_ref[bi]) & (lo + bs > p0))
    return lb, pb, vis


def _scatter_kernel(tab_ref, pos_ref, len_ref,          # scalar prefetch
                    kn_ref, vn_ref, kin_ref, vin_ref, ko_ref, vo_ref, *,
                    bs: int, mb: int, t: int, hkv: int):
    bi = pl.program_id(0)
    ci = pl.program_id(1)
    p0 = pos_ref[bi]
    cl = len_ref[bi]
    lb, _, vis = _scatter_visible(tab_ref, pos_ref, len_ref, bi, ci,
                                  bs=bs, mb=mb)
    lo = lb * bs

    # invisible steps write NOTHING: the pool is aliased in-place, so an
    # unwritten output block keeps its current content.  The pool
    # invariant (no physical block reachable from two slots) means each
    # visible step is the sole writer of its block this call, so the
    # input-side fetch is always the correct merge base.
    @pl.when(vis)
    def _merge():
        # row r of this physical block holds absolute position lo + r; it
        # takes chunk token tk iff lo + r == p0 + tk and tk is within the
        # valid span.  The one-hot selection matrix turns the scatter into
        # an MXU contraction against the whole chunk — no per-row dynamic
        # indexing in-kernel.
        row = jax.lax.broadcasted_iota(jnp.int32, (bs, t), 0)
        tok = jax.lax.broadcasted_iota(jnp.int32, (bs, t), 1)
        sel = ((lo + row == p0 + tok) & (tok < cl)).astype(jnp.float32)
        col = lo + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        wr = (col >= p0) & (col < p0 + cl)                        # (bs, 1)

        # one-hot row selection: exact only at full precision for f32 rows
        prec = contract_precision(kn_ref.dtype)
        for new_ref, cur_ref, out_ref in ((kn_ref, kin_ref, ko_ref),
                                          (vn_ref, vin_ref, vo_ref)):
            for h in range(hkv):        # head-major: one (bs, hd) tile each
                new = new_ref[0, h].astype(jnp.float32)           # (t, hd)
                rows = jnp.dot(sel, new, preferred_element_type=jnp.float32,
                               precision=prec)
                cur = cur_ref[0, h]
                out_ref[0, h] = jnp.where(wr, rows.astype(cur.dtype), cur)


# VMEM cap for the resident chunk tile of one scatter call, per K/V leaf.
# The scatter kernel keeps the whole (1, T, Hkv, hd) chunk block in VMEM at
# every grid step; at T=256 an MHA-width cache row (e.g. 64 heads x 64 dims,
# 16 KiB/row f32) makes that 2 x 4 MiB double-buffered — past the ~16 MiB
# per-core budget once the pool blocks ride along (surfaced by
# ``repro.analysis.vmem``).  Chunks whose tile would exceed this split into
# bounded sub-chunk calls below; each sub-call writes a disjoint row span,
# so the result is bit-identical to the single-call form.
_MAX_CHUNK_TILE_BYTES = 2 * 1024 * 1024


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_scatter_pallas(
    k_new: jax.Array,         # (B, T, Hkv, hd) chunk K (decode: T == 1)
    v_new: jax.Array,         # (B, T, Hkv, hd)
    k_pool: jax.Array,        # (num_blocks, Hkv, block_size, hd)
    v_pool: jax.Array,        # (num_blocks, Hkv, block_size, hd)
    block_table: jax.Array,   # (B, max_blocks) int32, -1 = unallocated
    pos: jax.Array,           # (B,) int32 absolute position of k_new[:, 0]
    chunk_len: jax.Array,     # (B,) int32 valid rows of k_new per row
    interpret: Optional[bool] = None,  # None → chosen by platform
) -> tuple[jax.Array, jax.Array]:
    """Write chunk K/V rows into the shared pool through the block table,
    entirely in-kernel: the grid walks the logical blocks the chunk spans,
    resolves each to a physical block via the scalar-prefetched table, and
    merges the chunk rows into that block in VMEM.  The pools are aliased
    input→output (``input_output_aliases``), so nothing pool-shaped is
    gathered or scattered outside the ``pallas_call`` — this replaces the
    host-side flat-index ``.at[].set`` that re-wrote the whole pool view.

    Rows whose target block is unallocated (-1) or out of table range are
    dropped, matching the jnp oracle's ``mode="drop"`` fence.

    Sentinel contract: the LAST pool row (``num_blocks - 1`` of the array,
    i.e. ``serve/paged.device_pool_rows``'s reserved trailing row) is
    where invisible grid steps park their aliased fetch/write-back.  Its
    content is never read for merging and the write-back is the identity,
    but callers must not store live KV there — ``init_paged_cache`` sizes
    device pools with the extra row so allocator block ids never reach it.

    Chunks whose resident tile would blow the static VMEM budget are
    split into sub-chunk calls of at most ``ts`` rows (static Python
    loop, still zero pool-shaped ops outside ``pallas_call``): sub-call
    ``i`` re-bases ``pos``/``chunk_len`` by its row offset and chains the
    aliased pools, so untouched blocks pass through unchanged.
    """
    b, t = k_new.shape[:2]
    nb, hkv, bs, hd = k_pool.shape
    assert v_new.shape == k_new.shape and v_pool.shape == k_pool.shape
    interpret = resolve_interpret(interpret)
    # head-major chunk rows, matching the pool: (B, Hkv, T, hd)
    k_new = k_new.transpose(0, 2, 1, 3)
    v_new = v_new.transpose(0, 2, 1, 3)

    row_bytes = hkv * hd * k_new.dtype.itemsize
    ts = max(1, min(t, _MAX_CHUNK_TILE_BYTES // row_bytes))
    if ts < t:
        posv = pos.astype(jnp.int32)
        cl = chunk_len.astype(jnp.int32)
        for off in range(0, t, ts):
            sl = slice(off, min(off + ts, t))
            k_pool, v_pool = _scatter_call(
                k_new[:, :, sl], v_new[:, :, sl], k_pool, v_pool, block_table,
                posv + off, jnp.clip(cl - off, 0, sl.stop - off), interpret)
        return k_pool, v_pool
    return _scatter_call(k_new, v_new, k_pool, v_pool, block_table,
                         pos, chunk_len, interpret)


def _scatter_call(k_new, v_new, k_pool, v_pool, block_table, pos,
                  chunk_len, interpret):
    """One bounded-tile scatter ``pallas_call`` (see the public wrapper);
    ``k_new``/``v_new`` arrive head-major ``(B, Hkv, T, hd)``."""
    b, hkv, t = k_new.shape[:3]
    nb, _, bs, hd = k_pool.shape
    mb = block_table.shape[1]
    # an unaligned T-row chunk spans at most this many logical blocks
    n_lb = min((t - 1) // bs + 2, t)

    tab = block_table.astype(jnp.int32)
    posv = pos.astype(jnp.int32)
    cl = chunk_len.astype(jnp.int32)

    def pool_index(bi, ci, tab_ref, pos_ref, len_ref):
        _, pb, vis = _scatter_visible(tab_ref, pos_ref, len_ref, bi, ci,
                                      bs=bs, mb=mb)
        # invisible steps park on the SENTINEL block — the pool's reserved
        # trailing row (``serve/paged.device_pool_rows``), never handed out
        # by the allocator and never in any block table.  Consecutive
        # skipped steps keep the index unchanged so refetch elision drops
        # their DMA; the identity write-back lands on a block no other
        # grid step fetches for content.  Parking on a *live* block (the
        # old ``tab[bi, 0]`` remap) is a pipelining RAW hazard: a chunk
        # whose trailing invisible step remapped to its own first block
        # would refetch that block while the earlier step's aliased
        # write-back may still be in flight — surfaced by the ``races``
        # analyzer family (grid_eval checks aliased refetch-after-write).
        pb = jnp.where(vis, pb, nb - 1)
        return (jnp.maximum(pb, 0), 0, 0, 0)

    def new_index(bi, ci, *_):
        return (bi, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_lb),
        in_specs=[
            pl.BlockSpec((1, hkv, t, hd), new_index),   # k_new
            pl.BlockSpec((1, hkv, t, hd), new_index),   # v_new
            pl.BlockSpec((1, hkv, bs, hd), pool_index),  # k_pool (in)
            pl.BlockSpec((1, hkv, bs, hd), pool_index),  # v_pool (in)
        ],
        out_specs=[
            pl.BlockSpec((1, hkv, bs, hd), pool_index),  # k_pool (out)
            pl.BlockSpec((1, hkv, bs, hd), pool_index),  # v_pool (out)
        ],
    )
    return pl.pallas_call(
        functools.partial(_scatter_kernel, bs=bs, mb=mb, t=t, hkv=hkv),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        # operand indices count the scalar-prefetch args: k_pool is
        # operand 5, v_pool operand 6 → outputs 0, 1 (updated in place)
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
    )(tab, posv, cl, k_new, v_new, k_pool, v_pool)
