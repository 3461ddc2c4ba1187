"""Pallas TPU kernel: grouped matmul over expert-sorted rows, with the
per-token N:M prune fused onto each LHS tile.

Routed experts contract every row with its own expert's weights:
``y[r] = prune_n:m(x[r]) @ w[g(r)]`` where rows arrive sorted by expert
and ``group_sizes[e]`` counts expert ``e``'s rows (rows past the last
group are left unwritten).  The design follows the megablox ``gmm``
kernel shipped with JAX: the group metadata (where each group starts,
and which (group, row tile) pair each grid step computes) is computed
once in jnp and scalar-prefetched, so the BlockSpec index maps fetch the
row tile and the expert's weight tile each step needs.  A row tile that
straddles a group boundary is visited once per group, on consecutive
grid steps, and each visit stores only its own group's rows; the output
block stays resident in VMEM across those visits.

``w`` may be a scan-stacked ``(L, G, D, N_out)`` with ``layer`` picking
one of its L: the index map reads that layer's blocks in place, where a
layer slice handed to the kernel would first be copied whole by XLA.

The grid is static: ``(N_out/bo, row tiles + groups - 1, D/bk)``.  Steps
past the metadata's tile count repeat the last real step's blocks (no
copy: Pallas elides a fetch whose block index did not change) and skip
their matmul.

N:M selection is row-local over aligned groups of M input channels, so
it runs on the (bt, bk) LHS tile independently of expert boundaries and
k-blocking (bk % M == 0); a row tile's first visit keeps its selected
k blocks in VMEM scratch for the visits of the groups after it.  It is
the shared in-group rank of ``nm_prune_pallas`` on plain ``|x|`` scores
(the paper scores routed experts without Robust-Norm), lowest index
first on ties, so the kept
channels are exactly ``nm.apply_nm``'s.  ``prune`` is static: the same
kernel runs the dense up projection with ``prune=False``.  Operands stay
in their dtype (bf16 on the MXU) with a float32 accumulator.
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

__all__ = ["nm_grouped_matmul_pallas", "group_metadata"]


def group_metadata(group_sizes: jax.Array, rows: int, bt: int):
    """Scalar-prefetch tables for ``rows`` expert-sorted rows in tiles of
    ``bt``: ``(offsets (G+1,), group_ids (S,), tile_ids (S,), num_tiles
    (1,))`` with ``S = rows // bt + G - 1`` grid steps.  Step ``i <
    num_tiles`` computes group ``group_ids[i]`` on row tile
    ``tile_ids[i]``; empty groups take no step, and steps from
    ``num_tiles`` on repeat the last real step."""
    g = group_sizes.shape[0]
    tiles = rows // bt
    step = jnp.arange(tiles + g - 1, dtype=jnp.int32)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends]).astype(
        jnp.int32)
    # tiles a group touches: its first row's tile through its last row's
    group_tiles = jnp.where(group_sizes == 0, 0,
                            (ends + bt - 1) // bt - starts // bt)
    # a tile is visited once by the group owning its first row, plus once
    # by every non-empty group that starts inside it
    inside = (starts % bt != 0) & (group_sizes > 0)
    visits = 1 + jnp.sum(inside[:, None] & (
        starts[:, None] // bt == jnp.arange(tiles)[None, :]), axis=0)

    def owner(counts):
        """Step -> the index whose run of ``counts`` steps holds it."""
        return jnp.sum(jnp.cumsum(counts)[None, :] <= step[:, None],
                       axis=1).astype(jnp.int32)

    num_tiles = jnp.sum(group_tiles).astype(jnp.int32)
    real = step < num_tiles
    group_ids, tile_ids = owner(group_tiles), owner(visits)
    # sorted ascending: the last real step's entries are the largest
    group_ids = jnp.where(real, group_ids,
                          jnp.max(jnp.where(real, group_ids, 0)))
    tile_ids = jnp.where(real, tile_ids,
                         jnp.max(jnp.where(real, tile_ids, 0)))
    return offsets, group_ids, tile_ids, num_tiles.reshape(1)


def _kernel(layer_ref, offsets_ref, gids_ref, tids_ref, ntiles_ref, x_ref,
            w_ref, o_ref, acc_ref, *xp_ref, n: int, m: int, prune: bool,
            k_steps: int):
    i = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(i < ntiles_ref[0])
    def _step():
        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        if prune:
            # a row tile's later visits (the next groups in it) reuse the
            # selection its first visit kept in VMEM
            first = (i == 0) | (tids_ref[i] != tids_ref[jnp.maximum(i - 1,
                                                                    0)])

            @pl.when(first)
            def _select():
                x = x_ref[...]                         # (bt, bk)
                keep = nm_rank(jnp.abs(x.astype(jnp.float32)), m) < n
                xp_ref[0][k] = jnp.where(keep, x, jnp.zeros((), x.dtype))

            x = xp_ref[0][k]
        else:
            x = x_ref[...]
        w = w_ref[...]                                 # (bk, bo)
        acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32,
                                precision=contract_precision(x.dtype,
                                                             w.dtype))

        @pl.when(k == k_steps - 1)
        def _store():
            g = gids_ref[i]
            bt, bo = o_ref.shape
            row = tids_ref[i] * bt + jax.lax.broadcasted_iota(
                jnp.int32, (bt, bo), 0)
            mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
            o_ref[...] = jnp.where(mine, acc_ref[...].astype(o_ref.dtype),
                                   o_ref[...])


@functools.partial(jax.jit, static_argnames=("n", "m", "prune", "block_t",
                                             "block_o", "block_k",
                                             "interpret"))
def nm_grouped_matmul_pallas(
    x: jax.Array,                       # (R, D) rows sorted by group
    w: jax.Array,                       # (G, D, N_out) or (L, G, D, N_out)
    group_sizes: jax.Array,             # (G,) int32, sum <= R
    n: int,
    m: int,
    prune: bool = True,
    block_t: int = 128,
    block_o: int = 2048,
    block_k: int = 512,
    interpret: Optional[bool] = None,   # None → chosen by platform
    layer: Optional[jax.Array] = None,  # () int32: which of L, w 4-D
) -> jax.Array:
    rows, d = x.shape
    if w.ndim == 3:
        w, layer = w[None], jnp.zeros((), jnp.int32)
    groups, n_out = w.shape[1], w.shape[3]
    bt = min(block_t, rows)
    bo = min(block_o, n_out)
    bk = min(block_k, d)
    assert rows % bt == 0 and n_out % bo == 0 and d % bk == 0, (
        rows, d, n_out, bt, bo, bk)
    assert not prune or bk % m == 0, (bk, m)
    k_steps = d // bk
    steps = rows // bt + groups - 1
    meta = (jnp.asarray(layer, jnp.int32).reshape(1),
            *group_metadata(group_sizes.astype(jnp.int32), rows, bt))

    def k_of(i, k, nt):
        # steps past the last real one keep its blocks: no refetch
        return jnp.where(i < nt[0], k, k_steps - 1)

    def x_map(j, i, k, lay, off, gid, tid, nt):
        return tid[i], k_of(i, k, nt)

    def w_map(j, i, k, lay, off, gid, tid, nt):
        return lay[0], gid[i], k_of(i, k, nt), j

    def o_map(j, i, k, lay, off, gid, tid, nt):
        return tid[i], j

    return pl.pallas_call(
        functools.partial(_kernel, n=n, m=m, prune=prune, k_steps=k_steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_out // bo, steps, k_steps),
            in_specs=[pl.BlockSpec((bt, bk), x_map),
                      pl.BlockSpec((None, None, bk, bo), w_map)],
            out_specs=pl.BlockSpec((bt, bo), o_map),
            scratch_shapes=[pltpu.VMEM((bt, bo), jnp.float32)] + (
                [pltpu.VMEM((k_steps, bt, bk), x.dtype)] if prune else [])),
        out_shape=jax.ShapeDtypeStruct((rows, n_out), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="nm_grouped_matmul_pallas",
    )(*meta, x, w)
