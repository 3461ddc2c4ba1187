"""Slot-axis surgery on model KV caches.

The continuous engine keeps ONE cache pytree whose batch axis is the slot
axis (``num_slots`` rows).  The model zoo stacks per-layer caches two ways:

  * ``periods`` (transformer) / ``blocks`` (encdec): leaves are
    ``(n_layers, num_slots, ...)`` — slot axis **1** (layer stacking from
    ``vmap``/``scan`` sits in front);
  * ``tail`` and any other subtree: leaves are ``(num_slots, ...)`` — slot
    axis **0**.

``pos`` is special: the engine stores a ``(num_slots,)`` int32 vector of
per-slot sequence positions where the one-shot engine stores a scalar.

Paged mode (see ``serve/paged.py``) adds two twists, driven by the
optional ``spec`` argument — a bool pytree mirroring the cache subtrees
in which True marks a **pooled** attention K/V leaf:

  * pooled leaves have NO slot axis (they are ``(num_blocks, Hkv,
    block_size, hd)`` shared by every slot), so slicing passes them
    through whole and writing takes the updated pool verbatim — the
    model's block-table scatter already confined the writes to the
    slot's own blocks;
  * ``block_table`` rides in the cache as a ``(num_slots, max_blocks)``
    int32 leaf; slicing extracts the slot's row (kept 2-D so prefill and
    batched decode share the model-side gather code).

All helpers take traced slot indices, so one jitted program serves every
slot (no per-slot retracing).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

__all__ = ["init_slot_cache", "slice_slot", "write_slot", "reset_slot",
           "where_active", "slot_axis"]

_LAYER_STACKED = ("periods", "blocks")   # slot axis 1 under these keys
_tmap = jax.tree_util.tree_map


def slot_axis(key: str) -> int:
    return 1 if key in _LAYER_STACKED else 0


def init_slot_cache(model, num_slots: int, max_seq: int) -> Dict[str, Any]:
    """Model cache with the batch axis as slots and a per-slot pos vector."""
    cache = model.init_cache(num_slots, max_seq)
    cache["pos"] = jnp.zeros((num_slots,), jnp.int32)
    return cache


def slice_slot(cache: Dict[str, Any], slot,
               spec: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Extract slot ``slot`` as a batch-1 cache with a scalar ``pos``."""
    out: Dict[str, Any] = {}
    for key, sub in cache.items():
        if key == "pos":
            out["pos"] = jax.lax.dynamic_index_in_dim(sub, slot, 0,
                                                      keepdims=False)
        elif key == "block_table":
            out[key] = jax.lax.dynamic_slice_in_dim(sub, slot, 1, axis=0)
        else:
            ax = slot_axis(key)

            def sl(a, ax=ax):
                return jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=ax)

            if spec is None:
                out[key] = _tmap(sl, sub)
            else:
                out[key] = _tmap(lambda a, paged: a if paged else sl(a),
                                 sub, spec[key])
    return out


def write_slot(cache: Dict[str, Any], slot, sub: Dict[str, Any],
               spec: Optional[Dict[str, Any]] = None) -> Dict:
    """Write a batch-1 cache (from :func:`slice_slot`) back into the slot."""
    out: Dict[str, Any] = {}
    for key, full in cache.items():
        if key == "pos":
            out["pos"] = jax.lax.dynamic_update_index_in_dim(
                full, sub["pos"].astype(full.dtype), slot, 0)
        elif key == "block_table":
            out[key] = full          # tables are engine-owned, never model-written
        else:
            ax = slot_axis(key)

            def wr(a, u, ax=ax):
                return jax.lax.dynamic_update_slice_in_dim(
                    a, u.astype(a.dtype), slot, axis=ax)

            if spec is None:
                out[key] = _tmap(wr, full, sub[key])
            else:
                out[key] = _tmap(
                    lambda a, u, paged: u.astype(a.dtype) if paged else wr(a, u),
                    full, sub[key], spec[key])
    return out


def reset_slot(cache: Dict[str, Any], slot: int,
               spec: Optional[Dict[str, Any]] = None,
               pos: int = 0) -> Dict[str, Any]:
    """Zero one slot (host-side, static index) before admitting a request.

    Attention rows are already fenced off by kv_len / kv_position masks, but
    recurrent states (rwkv6 S / token shifts, rglru h / conv history) are
    read as the initial state of the next prefill chunk, so they MUST be
    cleared when a slot changes owner.  Pooled leaves are left untouched —
    block ownership is released host-side and stale rows are fenced by the
    block table (-1 rows scatter/gather nowhere live) and kv_len.

    ``pos`` sets the slot's starting sequence position: 0 for a cold
    request, or the number of prefix-cached KV rows when admission matched
    shared blocks (serve/paged.py prefix index) — the first prefill chunk
    then starts mid-sequence and attends over the reused prefix.  Callers
    must separately install the shared block ids in the slot's table row;
    shared blocks themselves are never cleared here (they are full,
    immutable, and possibly read by other slots).
    """
    out: Dict[str, Any] = {}
    for key, sub in cache.items():
        if key == "pos":
            out["pos"] = sub.at[slot].set(pos)
        elif key == "block_table":
            out[key] = sub.at[slot].set(-1)
        else:
            ax = slot_axis(key)

            def zero(a, ax=ax):
                return a.at[(slice(None),) * ax + (slot,)].set(0)

            if spec is None:
                out[key] = _tmap(zero, sub)
            else:
                out[key] = _tmap(lambda a, paged: a if paged else zero(a),
                                 sub, spec[key])
    return out


def where_active(active: jax.Array, new: Dict[str, Any],
                 old: Dict[str, Any],
                 spec: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Per-slot select: keep ``new`` where ``active`` else ``old``.

    Used after a batched decode step so that slots that are empty or still
    prefilling are not advanced or overwritten by the decode's cache writes.
    Pooled leaves take ``new`` verbatim: the paged decode scatter already
    drops inactive rows (empty slots carry -1 block-table entries, which
    map out of bounds), so the pool is correct as written.
    """
    out: Dict[str, Any] = {}
    for key, old_sub in old.items():
        if key == "pos":
            out["pos"] = jnp.where(active, new["pos"], old_sub)
        elif key == "block_table":
            out[key] = old_sub
        else:
            ax = slot_axis(key)

            def sel(n, o, ax=ax):
                shape = [1] * o.ndim
                shape[ax] = active.shape[0]
                return jnp.where(active.reshape(shape), n, o)

            if spec is None:
                out[key] = _tmap(sel, new[key], old_sub)
            else:
                out[key] = _tmap(
                    lambda n, o, paged: n if paged else sel(n, o),
                    new[key], old_sub, spec[key])
    return out
