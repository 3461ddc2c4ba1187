"""Pallas TPU kernel: paged flash attention — each grid step takes one KV
head's group of query heads against a tile of KV pages that the kernel
resolves through the per-row block table.

Why it exists here: PR 3's paged KV cache made allocation block-granular,
but the serving engine's attention still gathered a dense
``(B, max_blocks * block_size, Hkv, hd)`` logical view into HBM on every
chunked-prefill and decode call (``models/attention.gather_kv_blocks``) —
a full write + re-read of the logical cache view per call.  This kernel
deletes that view: the pools stay in HBM (``pl.ANY``), the scalar-
prefetched block table (``pltpu.PrefetchScalarGridSpec``) maps each
logical page of a KV tile to its *physical* block of the shared pool, and
the body copies those blocks into a double-buffered VMEM tile itself, one
DMA per page, fetching the next visible tile while it computes this one.

Grid ``(B, Hkv, ceil(Tq/bq), ceil(max_blocks/P))``, the KV-tile axis
innermost:

* **one step per KV head**, not per query head: the ``g = Hq / Hkv``
  query heads of a GQA group (query head ``h`` reads KV head ``h // g``,
  so a group's heads are contiguous) share one ``(g*bq, hd)`` q tile, so
  a group fetches its K/V pages once instead of ``g`` times.  Row ``r``
  of the tile is query head ``r // bq`` at position
  ``q_offset[b] + qi*bq + r % bq``; decode (``Tq == 1``) is a ``(g, hd)``
  tile.
* **one step per tile of P pages** (about 512 positions): the q·k tile is
  ``(g*bq, P*block_size)``, wide enough to keep the MXU busy and to hide
  the page DMAs behind it.

Tile sizes come from the shapes alone (:func:`paged_tile`).  The visible
KV tiles of each ``(row, q tile)`` form one span (:func:`_tile_span`): a
tile wholly past ``kv_len`` (or past the row's last allocated page), or
wholly outside the causal/window band, copies nothing and skips its
matmuls; a skipped step costs the grid step alone.  (One BlockSpec per
page would pay every page operand's pipeline bookkeeping at every step,
skipped or not: a 512-token chunk at offset 0 took 1.1 ms that way on a
TPU v5e, and 0.16 ms with the body's own copies.)  Inside a visible tile,
pages that are unallocated (``-1``) or past ``kv_len`` are masked and
their V rows zeroed.  Scratch (m, l, acc) carries the online-softmax
state across tiles, in float32; finalization divides on the last tile.

Layout: q ``(B, Tq, Hq, hd)``, viewed group-major inside the wrapper;
pools are head-major ``(num_blocks, Hkv, block_size, hd)`` shared by every
row, so one KV head of one physical block is a ``(block_size, hd)`` tile.
Mosaic tiling: ``block_size`` must be a multiple of the sublane tile of
the pool dtype (16 for bf16) and ``hd`` a multiple of 128.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import (_NEG, softmax_finalize,
                                           softmax_init, softmax_update)
from repro.kernels.platform import contract_precision, resolve_interpret

__all__ = ["paged_attention_pallas", "paged_kv_scatter_pallas",
           "paged_kernel_covers", "paged_tile", "paged_tile_walk"]

# Tile targets: rows of a q tile (a group's query heads x positions) and
# KV positions of a KV tile.  A (1024 x 512) float32 score tile is 2 MiB
# of VMEM; a large GQA group (g = 48) shrinks the positions per q tile
# instead of growing the tile.
_Q_ROWS = 1024
_KV_TOKENS = 512


def paged_kernel_covers(t: int) -> bool:
    """Can the kernel serve a ``t``-query call?  The single source of
    truth for the q-tile divisibility rule — the dispatch layer
    (``models/attention.paged_attention``) falls back to the gather oracle
    when this is False, and the serving engine rejects prefill chunk
    buckets that would silently do so while claiming the kernel ran."""
    return t % min(128, t) == 0


def paged_tile(g: int, t: int, block_size: int, max_blocks: int,
               dtype) -> Tuple[int, int]:
    """``(bq, pages)``: query positions per q tile and KV pages per KV tile
    for a ``t``-query call with GQA group ``g``.  The q tile holds
    ``g * bq`` rows: the whole call when that fits ``_Q_ROWS``, else a
    power of two of positions, at least the dtype's sublane tile (8 rows
    of float32, 16 of bfloat16).  A KV tile holds about ``_KV_TOKENS``
    positions, and never more pages than the table has."""
    sub = 32 // jnp.dtype(dtype).itemsize
    if g * t <= _Q_ROWS:
        bq = t
    else:
        bq = min(t, max(sub, 1 << ((_Q_ROWS // g).bit_length() - 1)))
    return bq, max(1, min(max_blocks, _KV_TOKENS // block_size))


def _tile_span(xp, kv_len, q_lo, *, bq: int, tk: int, causal: bool,
               window: int):
    """``(first, last)`` KV tile visible to the q tile whose first query
    sits at ``q_lo``; ``last < first`` when none is.

    The SINGLE definition of the tile walk: a tile entirely past
    ``kv_len``, or entirely outside the causal/window band of the q tile,
    contributes nothing.  The wrapper evaluates it (``xp = jnp``) into the
    scalar-prefetched span tables the kernel body reads to skip both the
    page copies and the matmuls, and the serving engine's tile counters
    replay it on the host (``xp = np``).
    """
    last = (kv_len + tk - 1) // tk - 1
    if causal:
        last = xp.minimum(last, (q_lo + bq - 1) // tk)
    first = 0 * last
    if window > 0:
        first = xp.maximum(q_lo - window + 1, 0) // tk
    return first, last


def paged_tile_walk(kv_len: int, q_offset: int, *, t: int, bq: int,
                    pages: int, block_size: int, max_blocks: int,
                    causal: bool, window: int = 0) -> Tuple[int, int]:
    """``(tiles walked, tiles visible)`` of one batch row and one KV head:
    the kernel's KV-tile walk replayed in plain integers.  ``kv_len`` is
    the row's effective length, capped at its last allocated page as the
    wrapper caps it."""
    tk = pages * block_size
    nq, nk = -(-t // bq), -(-max_blocks // pages)
    visible = 0
    for qi in range(nq):
        first, last = _tile_span(np, kv_len, q_offset + qi * bq, bq=bq,
                                 tk=tk, causal=causal, window=window)
        visible += max(0, int(last) - int(first) + 1)
    return nq * nk, visible


def _page(tab_ref, b, idx, mb: int, pages: int):
    """Physical block of logical page ``idx`` of row ``b``; -1 past the
    table (the last tile of a table that is not a whole number of
    tiles)."""
    if mb % pages == 0:
        return tab_ref[b, idx]
    return jnp.where(idx < mb, tab_ref[b, jnp.minimum(idx, mb - 1)], -1)


def _kernel(tab_ref, lo_ref, hi_ref, qoff_ref, kvlen_ref,  # scalar prefetch
            q_ref, k_hbm, v_hbm,                            # pools in HBM
            o_ref, m_ref, l_ref, acc_ref, k_buf, v_buf, sems, *,
            pages: int, bq: int, bs: int, mb: int, causal: bool,
            window: int, scale: float):
    b = pl.program_id(0)
    h = pl.program_id(1)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    tk = pages * bs
    lo, hi = lo_ref[b, qi], hi_ref[b, qi]
    visible = (ki >= lo) & (ki <= hi)
    slot = ki % 2
    pools = ((k_hbm, k_buf), (v_hbm, v_buf))

    def page(p):
        return _page(tab_ref, b, ki * pages + p, mb, pages)

    def fetch(kt, into):
        """Start the page DMAs of KV tile ``kt`` into buffer ``into``:
        one per page and pool, an unallocated page reading block 0."""
        def one(p, carry):
            pb = jnp.maximum(_page(tab_ref, b, kt * pages + p, mb, pages), 0)
            for j, (hbm, buf) in enumerate(pools):
                pltpu.make_async_copy(hbm.at[pb, h], buf.at[into, p],
                                      sems.at[j, into]).start()
            return carry
        jax.lax.fori_loop(0, pages, one, 0)

    @pl.when(ki == 0)
    def _init():
        softmax_init(m_ref, l_ref, acc_ref)

    # the first visible tile of a walk fetches itself; every visible tile
    # fetches its successor into the other buffer before it computes
    @pl.when(visible & (ki == lo))
    def _first():
        fetch(ki, slot)

    @pl.when(visible & (ki < hi))
    def _next():
        fetch(ki + 1, 1 - slot)

    @pl.when(visible)
    def _visible():
        def wait(p, carry):
            for j, (hbm, buf) in enumerate(pools):
                pltpu.make_async_copy(hbm.at[0, 0], buf.at[slot, p],
                                      sems.at[j, slot]).wait()
            return carry
        jax.lax.fori_loop(0, pages, wait, 0)

        kvl = kvlen_ref[b]
        k_lo = ki * tk
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        # an unallocated page below kv_len is a hole in the table (serving
        # tables are allocated prefixes, so tiles skip the masking below)
        holes = jax.lax.fori_loop(0, pages, lambda p, n: n | (
            (page(p) < 0) & (k_lo + p * bs < kvl)), False)

        def mask_holes(ok):
            def one(p, ok):
                @pl.when(page(p) < 0)
                def _zero_v():      # the rows of block 0, where holes read
                    v_buf[slot, p] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)
                p_lo = k_lo + p * bs
                return jnp.where((page(p) < 0) & (k_pos >= p_lo)
                                 & (k_pos < p_lo + bs), 0, ok)
            return jax.lax.fori_loop(0, pages, one, ok)

        ok = jax.lax.cond(holes, mask_holes, lambda ok: ok,
                          jnp.ones((1, tk), jnp.int32))
        valid = (k_pos < kvl) & (ok > 0)

        q = q_ref[0, 0]                                         # (rows, hd)
        k = k_buf[slot].reshape(tk, -1)                         # (tk, hd)
        v = v_buf[slot].reshape(tk, -1)       # p @ v: softmax_update
        # bf16 x bf16 products are exact in f32: q.k takes the stored
        # operands; a float32 operand contracts at full precision
        prec = contract_precision(q.dtype, k.dtype)
        if prec is not None:
            q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=prec) * scale      # (rows, tk)
        if causal or window > 0:
            row = jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0)
            row = row & (bq - 1) if bq & (bq - 1) == 0 else row % bq
            q_pos = qoff_ref[b] + qi * bq + row                 # (rows, 1)
            if causal:
                valid = valid & (k_pos <= q_pos)
            if window > 0:
                valid = valid & (k_pos > q_pos - window)
        s = jnp.where(valid, s, _NEG)
        # rows of a partially-filled physical block past kv_len are
        # unwritten pool memory; zero them so a 0-probability column can
        # never propagate NaN/garbage through the p @ v contraction
        col = k_lo + jax.lax.broadcasted_iota(jnp.int32, (tk, 1), 0)
        v = jnp.where(col < kvl, v, 0.0)

        softmax_update(s, v, m_ref, l_ref, acc_ref,
                       contract_precision(q_ref.dtype, k_buf.dtype))

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        o_ref[0, 0] = softmax_finalize(l_ref, acc_ref, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window",
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
    interpret: Optional[bool] = None,  # None → chosen by platform
) -> jax.Array:
    b, t, hq, hd = q.shape
    nb, hkv, bs = k_pool.shape[:3]
    mb = block_table.shape[1]
    # non-divisible heads would make a group read a clamped out-of-range
    # KV head — plausible wrong outputs, so fail fast
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    bq, pages = paged_tile(g, t, bs, mb, q.dtype)
    nq, nk = -(-t // bq), -(-mb // pages)
    rows = g * bq

    tab = block_table.astype(jnp.int32)
    qoff = q_offset.astype(jnp.int32)
    # pages past the row's last allocated one hold nothing: capping
    # kv_len there keeps a free slot's walk (stale position, all -1
    # table) from visiting any tile
    n_alloc = jnp.max(jnp.where(tab >= 0, jnp.arange(1, mb + 1), 0), axis=1)
    kvl = jnp.minimum(kv_len.astype(jnp.int32), n_alloc * bs)
    lo, hi = _tile_span(jnp, kvl[:, None],
                        qoff[:, None] + bq * jnp.arange(nq)[None, :],
                        bq=bq, tk=pages * bs, causal=causal, window=window)
    lo = jnp.broadcast_to(lo, (b, nq)).astype(jnp.int32)
    hi = jnp.broadcast_to(hi, (b, nq)).astype(jnp.int32)

    # group-major q: row r of tile (bi, h, qi) is query head h*g + r // bq
    # at position qi*bq + r % bq
    if nq * bq != t:
        q = jnp.pad(q, ((0, 0), (0, nq * bq - t), (0, 0), (0, 0)))
    qg = (q.reshape(b, nq, bq, hkv, g, hd).transpose(0, 3, 1, 4, 2, 5)
          .reshape(b, hkv, nq * rows, hd))

    def q_index(bi, h, qi, ki, *_):
        return (bi, h, qi, 0)

    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, hkv, nq, nk),
        in_specs=[pl.BlockSpec((1, 1, rows, hd), q_index),
                  pool_spec, pool_spec],
        out_specs=pl.BlockSpec((1, 1, rows, hd), q_index),
        scratch_shapes=[
            pltpu.VMEM((rows,), jnp.float32),
            pltpu.VMEM((rows,), jnp.float32),
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((2, pages, bs, hd), k_pool.dtype),
            pltpu.VMEM((2, pages, bs, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, pages=pages, bq=bq, bs=bs, mb=mb,
                          causal=causal, window=window, scale=hd**-0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, nq * rows, hd), q.dtype),
        interpret=resolve_interpret(interpret),
        name="paged_attention_pallas",
    )(tab, lo, hi, qoff, kvl, qg, k_pool, v_pool)
    out = (out.reshape(b, hkv, nq, g, bq, hd).transpose(0, 2, 4, 1, 3, 5)
           .reshape(b, nq * bq, hq, hd))
    return out[:, :t]


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
