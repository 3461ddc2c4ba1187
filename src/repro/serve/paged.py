"""Paged KV-cache allocation: a global block pool + per-slot block tables.

The fixed ``max_seq``-per-slot KV slab of the continuous engine reserves
``num_slots * max_seq`` rows per layer even when traffic is mostly short
prompts — memory, not compute, then caps concurrency.  Paged allocation
replaces the slab with a **global pool** of fixed-size KV blocks shared by
every slot:

  * each attention layer's cache leaf becomes a head-major pooled
    ``(num_blocks, n_kv_heads, block_size, head_dim)`` array (one KV head
    of one block is a ``(block_size, head_dim)`` tile, the shape the paged
    Pallas kernels move);
  * each slot holds a **block table** — a ``(max_blocks_per_slot,)`` int32
    row mapping logical block index (``position // block_size``) to a
    physical block id, ``-1`` = unallocated;
  * logical KV row ``p`` of a slot lives at physical flat row
    ``table[p // block_size] * block_size + p % block_size``.

The :class:`BlockPool` is **host-side** (allocation decisions are
scheduler decisions, not traced computation); only the small int32
block-table array crosses to the device, so admission/release never
retraces the jitted phases.  Recurrent state leaves (rwkv6 / rglru) are
position-independent and stay per-slot; sliding-window rings are already
bounded by ``window`` and are not paged (see ``transformer.paged_kv_spec``).

Sizing the pool below ``num_slots * ceil(max_seq / block_size)`` is the
point: the engine admits by block budget instead of free slots alone, and
preempts the youngest request (recompute on re-admission) when the pool
runs dry mid-decode — see ``serve/README.md`` for the policy.

Prefix caching (ISSUE 5)
------------------------

The pool is **refcounted and content-addressed**: a block whose rows are
completely written gets a chain hash ``h_i = hash(h_{i-1}, tokens_i)``
(see :func:`chain_block_hashes`) and is published in ``_index`` so later
requests whose token prefix reproduces the chain can *acquire* the block
(refcount += 1) instead of recomputing its KV.  ``release`` decrements;
at refcount 0 a **registered** block is not freed but parked in an LRU of
zero-ref cached blocks, evicted (index entry dropped) only when ``alloc``
cannot be served from the free list — the pool never reports exhaustion
while evictable cached blocks remain.  Only full, immutable blocks are
ever registered; a request's partially-filled tail block is always a
fresh exclusively-owned allocation, so no shared block is ever writable.
"""
from __future__ import annotations

from collections import Counter, OrderedDict, deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.serve.faults import fire as _fire_fault

# NOTE: no module-level jax import — the Scheduler layer of the serving
# split imports this module for BlockPool + chain hashing, and must stay
# pure host code (pinned by test_sharded_serving).  The one device-side
# helper, init_paged_cache, imports jax lazily.

__all__ = ["BlockPool", "chain_block_hashes", "chain_block_keys",
           "device_pool_rows", "init_paged_cache", "max_blocks_per_slot"]


# Device pool leaves carry ONE reserved row past the allocator's id space:
# the trailing SENTINEL block.  The paged KV scatter kernel's aliased
# index map parks invisible grid steps there (a fixed, never-allocated
# physical block), so a parked write-back can never race a block some
# other grid step legitimately wrote — see
# ``kernels/paged_attention._scatter_call`` and the ``races`` analyzer
# family.  BlockPool itself never hands out the sentinel id; only the
# device-side leaf shape knows about it.
SENTINEL_POOL_ROWS = 1


def device_pool_rows(num_blocks: int) -> int:
    """Rows of a device pool leaf for an allocator of ``num_blocks``
    physical blocks: the allocatable blocks plus the trailing sentinel
    row reserved for the scatter kernel's parked grid steps."""
    return num_blocks + SENTINEL_POOL_ROWS

_HASH_SEED = 0x9E3779B9


def max_blocks_per_slot(max_seq: int, block_size: int) -> int:
    """Width of a slot's block table: logical blocks covering ``max_seq``."""
    return -(-max_seq // block_size)


def chain_block_hashes(tokens, block_size: int,
                       n_blocks: Optional[int] = None,
                       dense_from: Optional[int] = None,
                       start: int = 0,
                       h0: Optional[int] = None) -> List[int]:
    """Chain hashes for full blocks ``start .. n_blocks-1`` of a sequence.

    ``h_i = hash((h_{i-1}, dense_rows_i, token_ids_in_block_i))`` — block
    ``i`` is addressed by its *whole prefix*, not just its own tokens, so
    an index hit guarantees the block's KV (which depends on every earlier
    token through attention) is reusable.

    ``dense_from`` marks the row index from which KV rows were produced by
    the DENSE program (tokens a request *emitted*, first written by the
    dense decode step and replayed dense after preemption) while rows
    before it came from the sparse prefill path.  Under a sparse prefill
    policy the same token ids yield different KV on the two paths, so the
    per-block count of dense rows is folded into the hash: a request whose
    own prompt extends into another request's emitted region hashes those
    blocks differently and correctly misses.  Pass ``None`` when every row
    takes one path (dense policy), which keeps hashes boundary-independent.

    ``start``/``h0`` resume an existing chain incrementally: ``h0`` must
    be the hash of block ``start - 1`` (``None`` = the seed, for
    ``start == 0``) — callers that hash as a sequence grows memoize their
    chain and pay only for the new blocks.

    The block length is folded into the chain seed: the same token stream
    hashed at a different ``block_size`` lands in a disjoint hash space
    (blocks of different geometry must never alias).  Hashes remain
    *probabilistic* identifiers — :meth:`BlockPool.match` additionally
    verifies stored token content (see :func:`chain_block_keys`) so a
    hash collision can never cause false sharing.
    """
    tokens = np.ascontiguousarray(np.asarray(tokens, np.int32))
    if n_blocks is None:
        n_blocks = len(tokens) // block_size
    assert n_blocks * block_size <= len(tokens), \
        "chain hashes cover full blocks only"
    assert (h0 is None) == (start == 0), "h0 must accompany a resume point"
    h = hash((_HASH_SEED, block_size)) if h0 is None else h0
    out: List[int] = []
    for i in range(start, n_blocks):
        lo, hi = i * block_size, (i + 1) * block_size
        dense = 0 if dense_from is None else max(0, hi - max(dense_from, lo))
        h = hash((h, dense, tokens[lo:hi].tobytes()))
        out.append(h)
    return out


def chain_block_keys(tokens, block_size: int,
                     n_blocks: Optional[int] = None,
                     dense_from: Optional[int] = None) -> List[Tuple]:
    """Verification keys ``(dense_rows, token_bytes)`` per full block.

    A chain hash is a probabilistic address; the key is the ground truth
    it stands for.  :meth:`BlockPool.register` stores the key alongside
    the hash and :meth:`BlockPool.match` compares keys block-by-block, so
    a hash collision between different contents is *detected* (counted in
    ``hash_collisions``) instead of silently sharing the wrong KV.
    Verification is inductive: block ``i`` only matches after blocks
    ``0..i-1`` matched with verified keys, so equal per-block keys along
    the chain imply the whole prefix (and its sparse/dense row split) is
    identical."""
    tokens = np.ascontiguousarray(np.asarray(tokens, np.int32))
    if n_blocks is None:
        n_blocks = len(tokens) // block_size
    out: List[Tuple] = []
    for i in range(n_blocks):
        lo, hi = i * block_size, (i + 1) * block_size
        dense = 0 if dense_from is None else max(0, hi - max(dense_from, lo))
        out.append((dense, tokens[lo:hi].tobytes()))
    return out


class BlockPool:
    """Host-side refcounted allocator over ``num_blocks`` fixed-size blocks.

    Every block is in exactly one of three states (asserted by
    :meth:`check_invariants`, exercised by ``tests/test_paged_kv.py`` and
    ``tests/test_prefix_cache.py``):

      * **free** — on the FIFO free list (a deque: reuse sweeps the whole
        pool instead of hammering one block under fragmenting traffic);
      * **allocated** — refcount ≥ 1 in ``_ref``; refcount > 1 means the
        block is a registered prefix block shared read-only by several
        live requests;
      * **cached** — refcount dropped to 0 but the block is registered in
        the prefix index; parked in an LRU and revived by
        :meth:`acquire_cached` or reclaimed (evicted) by :meth:`alloc`.

    ``alloc`` validates the ENTIRE operation before mutating anything
    (ISSUE-5 bugfix: the old free list popped blocks before the
    double-allocation assert could fire, corrupting pool state on the
    failure path).
    """

    def __init__(self, num_blocks: int, block_size: int,
                 prefix_cache: bool = True):
        assert num_blocks > 0 and block_size > 0
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.prefix_cache = prefix_cache
        self._free: Deque[int] = deque(range(num_blocks))
        self._ref: Dict[int, int] = {}           # block id → refcount ≥ 1
        # zero-ref registered blocks, LRU → MRU; value = registered hash
        self._cached: "OrderedDict[int, int]" = OrderedDict()
        self._index: Dict[int, int] = {}         # chain hash → block id
        self._hash_of: Dict[int, int] = {}       # block id → chain hash
        # block id → verification key (chain_block_keys): the content the
        # hash stands for, compared on match to refuse collision aliasing
        self._key_of: Dict[int, Tuple] = {}
        self.peak_in_use = 0
        self.total_allocs = 0                    # fresh allocations only
        self.evictions = 0
        self.hash_collisions = 0                 # matches refused on key skew

    # ------------------------------------------------------------ queries
    @property
    def available(self) -> int:
        """Blocks obtainable without preempting anyone: free + evictable."""
        return len(self._free) + len(self._cached)

    @property
    def in_use(self) -> int:
        """Blocks currently referenced by at least one request."""
        return len(self._ref)

    @property
    def cached_blocks(self) -> int:
        """Zero-ref blocks retained for prefix reuse (evictable)."""
        return len(self._cached)

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` KV rows."""
        return -(-n_tokens // self.block_size)

    def refcount(self, block_id: int) -> int:
        return self._ref.get(block_id, 0)

    def is_registered(self, block_id: int) -> bool:
        return block_id in self._hash_of

    def is_cached(self, block_id: int) -> bool:
        """Zero-ref parked in the LRU (counted in :attr:`available`) —
        reviving it consumes one unit of availability, unlike sharing an
        already-live block."""
        return block_id in self._cached

    # --------------------------------------------------------- allocation
    def alloc(self, n: int) -> List[int]:
        """Hand out ``n`` fresh exclusively-owned blocks (refcount 1).

        Draws from the free list first, then reclaims zero-ref cached
        blocks LRU-first (dropping their prefix-index entries); raises if
        even eviction cannot cover the request — callers check
        :attr:`available` and preempt first.  All validation happens
        before any state is mutated.

        Fault-injection site ``pool.alloc`` (serve/faults.py):
        ``"exhausted"`` raises the real exhaustion error so callers'
        recovery paths (admission retry/backoff, decode-growth preemption)
        are exercised; ``"evict_storm"`` flushes the zero-ref LRU first.
        """
        kind = _fire_fault("pool.alloc")
        if kind == "exhausted":
            raise RuntimeError(
                f"block pool exhausted (injected fault): want {n}, have "
                f"{self.available}")
        if kind == "evict_storm":
            self.flush_cached()
        if n > self.available:
            raise RuntimeError(
                f"block pool exhausted: want {n}, have {self.available} "
                f"({len(self._free)} free + {len(self._cached)} cached)")
        take_free = min(n, len(self._free))
        cand = [self._free[i] for i in range(take_free)]
        evict: List[int] = []
        if take_free < n:                        # LRU → MRU iteration order
            lru = iter(self._cached)
            evict = [next(lru) for _ in range(n - take_free)]
        for i in cand + evict:
            assert i not in self._ref, f"double allocation of block {i}"
        assert len(set(cand + evict)) == n, "free list holds duplicates"
        # ---- validated: now mutate
        for _ in range(take_free):
            self._free.popleft()
        for i in evict:
            h = self._cached.pop(i)
            if self._index.get(h) == i:
                del self._index[h]
            self._hash_of.pop(i, None)
            self._key_of.pop(i, None)
            self.evictions += 1
        ids = cand + evict
        for i in ids:
            self._ref[i] = 1
        self.total_allocs += n
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return ids

    def acquire_cached(self, block_id: int) -> None:
        """Take a reference on a prefix-index hit: revive a zero-ref cached
        block (keeping its registration) or share a live one (refcount+1).
        The caller may only write rows BEYOND the block — registered blocks
        are full and immutable."""
        if block_id in self._cached:
            del self._cached[block_id]
            self._ref[block_id] = 1
        else:
            assert block_id in self._ref, \
                f"acquire_cached of unallocated block {block_id}"
            self._ref[block_id] += 1
        self.peak_in_use = max(self.peak_in_use, self.in_use)

    def release(self, ids: Sequence[int]) -> None:
        """Drop one reference per id; a block reaching refcount 0 is parked
        in the prefix LRU if registered, else returned to the free list."""
        need = Counter(ids)
        for i, k in need.items():                # validate before mutating
            assert self._ref.get(i, 0) >= k, \
                f"release of unallocated block {i}"
        for i in ids:
            self._ref[i] -= 1
            if self._ref[i] == 0:
                del self._ref[i]
                h = self._hash_of.get(i)
                if h is not None and self._index.get(h) == i:
                    self._cached[i] = h          # MRU end of the LRU
                else:
                    self._hash_of.pop(i, None)
                    self._key_of.pop(i, None)
                    self._free.append(i)

    def flush_cached(self) -> int:
        """Evict EVERY zero-ref cached block (index entries dropped, blocks
        freed).  Returns the number evicted.  Used by the ``evict_storm``
        fault and by engine restore after a crash (device KV is gone, so a
        surviving index would advertise garbage blocks)."""
        n = len(self._cached)
        for b, h in self._cached.items():
            if self._index.get(h) == b:
                del self._index[h]
            self._hash_of.pop(b, None)
            self._key_of.pop(b, None)
            self._free.append(b)
            self.evictions += 1
        self._cached.clear()
        return n

    # ------------------------------------------------------- prefix index
    def register(self, block_id: int, chain_hash: int,
                 key: Optional[Tuple] = None) -> bool:
        """Publish a FULL block under its chain hash.  Returns False when
        the hash is already indexed (first copy wins — the duplicate block
        simply stays unregistered and frees normally) or when prefix
        caching is off.

        ``key`` is the block's verification key (:func:`chain_block_keys`)
        — the actual content the hash addresses.  :meth:`match` compares
        it so a hash collision between different token contents is
        refused instead of silently sharing the wrong KV.  ``None``
        registers hash-only (legacy/debug posture: collisions under
        Python's 64-bit tuple hash are ~2^-64 per pair, but a production
        index must not bet correctness on that)."""
        if not self.prefix_cache:
            return False
        assert block_id in self._ref, "register of a block nobody owns"
        if chain_hash in self._index:
            return self._index[chain_hash] == block_id
        prev = self._hash_of.get(block_id)
        assert prev is None or prev == chain_hash, \
            f"block {block_id} re-registered under a different hash"
        self._hash_of[block_id] = chain_hash
        self._index[chain_hash] = block_id
        if key is not None:
            self._key_of[block_id] = key
        return True

    def match(self, chain_hashes: Sequence[int],
              keys: Optional[Sequence[Tuple]] = None) -> List[int]:
        """Longest indexed prefix of a hash chain → block ids (not yet
        acquired; callers :meth:`acquire_cached` each hit).

        With ``keys`` (aligned with ``chain_hashes``), every hash hit is
        verified against the registered block's stored content key; a
        mismatch — a genuine hash collision — stops the match there and
        increments ``hash_collisions``.  A block registered without a key
        matches hash-only."""
        ids: List[int] = []
        for i, h in enumerate(chain_hashes):
            b = self._index.get(h)
            if b is None:
                break
            if keys is not None:
                stored = self._key_of.get(b)
                if stored is not None and stored != keys[i]:
                    self.hash_collisions += 1
                    break
            ids.append(b)
        return ids

    # --------------------------------------------------------- invariants
    def check_invariants(self) -> None:
        """free / allocated / cached partition the pool; the prefix index
        is a bijection onto registered live-or-cached blocks."""
        free, cached, ref = list(self._free), set(self._cached), \
            set(self._ref)
        assert len(free) == len(set(free)), "free list holds duplicates"
        assert not (set(free) & cached) and not (set(free) & ref) \
            and not (cached & ref), "block in two states at once"
        assert len(free) + len(cached) + len(ref) == self.num_blocks, \
            "blocks leaked or conjured"
        assert all(c >= 1 for c in self._ref.values()), "zero-ref in _ref"
        assert set(self._index.values()) == set(self._hash_of), \
            "index/registration skew"
        assert set(self._key_of) <= set(self._hash_of), \
            "verification key for an unregistered block"
        for h, b in self._index.items():
            assert self._hash_of.get(b) == h, f"hash mismatch on block {b}"
            assert b in cached or b in ref, f"indexed block {b} is free"
        for b, h in self._cached.items():
            assert self._index.get(h) == b, f"cached block {b} unreachable"

    # ------------------------------------------------------ snapshot/restore
    def snapshot(self) -> Dict[str, Any]:
        """Copy of the full host-side pool state (free list order, refcounts,
        prefix index, zero-ref LRU order, counters).  Process-local: chain
        hashes use Python's per-process salted ``hash``."""
        return {
            "num_blocks": self.num_blocks,
            "block_size": self.block_size,
            "prefix_cache": self.prefix_cache,
            "free": list(self._free),
            "ref": dict(self._ref),
            "cached": list(self._cached.items()),
            "index": dict(self._index),
            "hash_of": dict(self._hash_of),
            "key_of": dict(self._key_of),
            "peak_in_use": self.peak_in_use,
            "total_allocs": self.total_allocs,
            "evictions": self.evictions,
            "hash_collisions": self.hash_collisions,
        }

    def restore(self, snap: Dict[str, Any]) -> None:
        """Rebuild the pool exactly as snapshotted (same process, and only
        meaningful when the device-side KV the blocks point at is intact —
        the engine's crash-recovery path resets a FRESH pool instead)."""
        assert snap["num_blocks"] == self.num_blocks \
            and snap["block_size"] == self.block_size, \
            "snapshot geometry mismatch"
        self.prefix_cache = snap["prefix_cache"]
        self._free = deque(snap["free"])
        self._ref = dict(snap["ref"])
        self._cached = OrderedDict(snap["cached"])
        self._index = dict(snap["index"])
        self._hash_of = dict(snap["hash_of"])
        self._key_of = dict(snap["key_of"])
        self.peak_in_use = snap["peak_in_use"]
        self.total_allocs = snap["total_allocs"]
        self.evictions = snap["evictions"]
        self.hash_collisions = snap["hash_collisions"]
        self.check_invariants()


def init_paged_cache(model, num_slots: int, max_seq: int, block_size: int,
                     num_blocks: int, spec: Dict[str, Any]) -> Dict[str, Any]:
    """Slot cache with paged attention leaves.

    ``spec`` is the bool pytree from ``model.paged_kv_spec()``: leaves
    marked True swap their ``(..., num_slots, max_seq, Hkv, hd)`` axes for
    head-major pooled ``(..., device_pool_rows(num_blocks), Hkv,
    block_size, hd)`` —
    ``num_blocks`` allocatable blocks plus the trailing sentinel row the
    scatter kernel parks invisible grid steps on (never referenced by any
    block table); everything else keeps the slot axis.  Adds the per-slot
    ``pos`` vector and the ``-1``-filled ``block_table``.
    """
    # shapes only — materializing the dense slab just to discard its paged
    # leaves would transiently cost dense + pool memory, exactly the
    # footprint paging exists to avoid
    import jax
    import jax.numpy as jnp

    from repro.serve.slots import slot_axis
    shapes = jax.eval_shape(lambda: model.init_cache(num_slots, max_seq))
    mb = max_blocks_per_slot(max_seq, block_size)
    out: Dict[str, Any] = {
        "pos": jnp.zeros((num_slots,), jnp.int32),
        "block_table": jnp.full((num_slots, mb), -1, jnp.int32),
    }
    for key, sub in shapes.items():
        if key == "pos":
            continue
        ax = slot_axis(key)

        def pool_leaf(a, paged, ax=ax):
            if paged:
                hkv, hd = a.shape[ax + 2:]
                shape = (a.shape[:ax]
                         + (device_pool_rows(num_blocks), hkv, block_size, hd))
                return jnp.zeros(shape, a.dtype)
            return jnp.zeros(a.shape, a.dtype)

        out[key] = jax.tree_util.tree_map(pool_leaf, sub, spec[key])
    return out
