"""Unified decoder-only LM covering dense / GQA / MoE / SSM / hybrid archs.

A model is a sequence of blocks whose kinds follow ``cfg.block_pattern``
(period-tiled), e.g. ``("attn",)`` for LLaMA-likes, ``("rwkv6",)`` for
RWKV6, ``("rglru", "rglru", "attn")`` for RecurrentGemma.  Homogeneous
periods are **scan-stacked** (params carry a leading ``n_periods`` axis and
the forward runs ``lax.scan`` over them) so 88-layer models compile in
bounded time; leftover layers (n_layers % period) live in an unrolled tail.

Three execution phases share one block implementation:
  * ``train`` / ``prefill`` without cache — full-sequence causal pass;
  * ``prefill`` with cache — same pass + cache population (serving);
  * ``decode`` — single-token step against the cache.

Sliding-window attention layers keep a **ring-buffer** cache of exactly
``window`` slots, which is what makes the ``long_500k`` decode cells cheap
for SWA archs.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.policy import SparsityPolicy
from repro.layers.linear import init_linear, sparse_linear
from repro.models import common
from repro.models.attention import (attention, paged_attention,
                                    paged_kv_update)
from repro.models.mlp import init_mlp, mlp
from repro.models.moe import init_moe, moe
from repro.models.rglru import init_rglru_block, init_rglru_state, rglru_block
from repro.models.rwkv6 import init_rwkv6_block, init_rwkv6_state, rwkv6_block

__all__ = [
    "init_params",
    "forward",
    "init_cache",
    "prefill",
    "prefill_chunk",
    "decode_step",
    "layer_kinds",
    "paged_kv_spec",
]


# --------------------------------------------------------------------- utils

def layer_kinds(cfg: ModelConfig):
    return [cfg.block_pattern[i % len(cfg.block_pattern)] for i in range(cfg.n_layers)]


def _n_periods(cfg: ModelConfig) -> Tuple[int, int]:
    p = len(cfg.block_pattern)
    return cfg.n_layers // p, cfg.n_layers % p


def _apply_rope(cfg: ModelConfig, x: jax.Array, positions, positions_3d):
    if cfg.rope_variant == "default":
        return common.apply_rope(x, positions, cfg.rope_theta)
    if cfg.rope_variant == "2d":
        return common.apply_rope_2d(x, positions, cfg.rope_theta)
    if cfg.rope_variant == "mrope":
        return common.apply_mrope(x, positions_3d, cfg.rope_theta)
    return x  # none | sinusoidal (added at embedding)


# --------------------------------------------------------------- block init

def _init_attn_block(cfg: ModelConfig, rng: jax.Array, dtype) -> Dict:
    r = jax.random.split(rng, 6)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p = {
        "ln1": common.init_norm(d, cfg.norm, dtype),
        "q_proj": init_linear(r[0], d, qd, bias=cfg.qkv_bias, dtype=dtype),
        "k_proj": init_linear(r[1], d, kvd, bias=cfg.qkv_bias, dtype=dtype),
        "v_proj": init_linear(r[2], d, kvd, bias=cfg.qkv_bias, dtype=dtype),
        "o_proj": init_linear(r[3], qd, d, dtype=dtype),
        "ln2": common.init_norm(d, cfg.norm, dtype),
    }
    if cfg.n_experts:
        p["moe"] = init_moe(r[4], d, cfg.moe_d_ff, cfg.n_experts,
                            cfg.shared_expert, dtype)
    else:
        p["mlp"] = init_mlp(r[4], d, cfg.d_ff, dtype)
    return p


def _init_block(cfg: ModelConfig, kind: str, rng: jax.Array, dtype) -> Dict:
    if kind == "attn":
        return _init_attn_block(cfg, rng, dtype)
    if kind == "rwkv6":
        return {"rwkv": init_rwkv6_block(rng, cfg.d_model, cfg.d_ff,
                                         cfg.n_heads, dtype)}
    if kind == "rglru":
        r1, r2 = jax.random.split(rng)
        return {
            "ln1": common.init_norm(cfg.d_model, cfg.norm, dtype),
            "rglru": init_rglru_block(r1, cfg.d_model,
                                      cfg.rnn_width or cfg.d_model,
                                      cfg.conv_width, dtype),
            "ln2": common.init_norm(cfg.d_model, cfg.norm, dtype),
            "mlp": init_mlp(r2, cfg.d_model, cfg.d_ff, dtype),
        }
    raise ValueError(f"unknown block kind {kind}")


def init_params(cfg: ModelConfig, rng: jax.Array) -> Dict:
    dtype = common.dtype_of(cfg)
    n_per, tail = _n_periods(cfg)
    r_embed, r_blocks, r_tail, r_head = jax.random.split(rng, 4)
    params: Dict[str, Any] = {
        "embed": common.init_embedding(r_embed, cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": common.init_norm(cfg.d_model, cfg.norm, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_linear(r_head, cfg.d_model, cfg.vocab_size,
                                        dtype=dtype)

    def period_init(rng_i):
        keys = jax.random.split(rng_i, len(cfg.block_pattern))
        return {f"b{j}": _init_block(cfg, kind, keys[j], dtype)
                for j, kind in enumerate(cfg.block_pattern)}

    if n_per:
        params["periods"] = jax.vmap(period_init)(jax.random.split(r_blocks, n_per))
    if tail:
        keys = jax.random.split(r_tail, tail)
        params["tail"] = {
            f"t{j}": _init_block(cfg, cfg.block_pattern[j], keys[j], dtype)
            for j in range(tail)
        }
    return params


# ------------------------------------------------------------------- caches

def _attn_cache_len(cfg: ModelConfig, max_seq: int) -> int:
    if cfg.attn_type in ("swa", "local"):
        return min(cfg.window, max_seq)
    return max_seq


def _init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_seq: int,
                      dtype) -> Dict:
    if kind == "attn":
        s = _attn_cache_len(cfg, max_seq)
        return {
            "k": jnp.zeros((batch, s, cfg.n_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((batch, s, cfg.n_kv_heads, cfg.head_dim), dtype),
        }
    if kind == "rwkv6":
        return init_rwkv6_state(batch, cfg.d_model, cfg.n_heads, dtype)
    if kind == "rglru":
        return init_rglru_state(batch, cfg.rnn_width or cfg.d_model,
                                cfg.conv_width, dtype)
    raise ValueError(kind)


def paged_kv_spec(cfg: ModelConfig) -> Dict:
    """Bool pytree over ``init_cache``'s layer subtrees: True marks the
    attention K/V leaves that move into the global block pool under paged
    serving (``serve/paged.py``).

    Sliding-window rings are excluded — they are already bounded by
    ``window`` and their in-ring wraparound does not compose with block
    tables; recurrent states (rwkv6 / rglru) are position-independent
    per-slot state.  Callers check ``any(leaves)`` to decide whether
    paging buys anything for the arch.
    """
    n_per, tail = _n_periods(cfg)

    def block_spec(kind):
        tmpl = _init_block_cache(cfg, kind, 1, 1, jnp.float32)
        paged = kind == "attn" and cfg.attn_type not in ("swa", "local")
        return jax.tree_util.tree_map(lambda _: paged, tmpl)

    spec: Dict[str, Any] = {}
    if n_per:
        spec["periods"] = {f"b{j}": block_spec(kind)
                           for j, kind in enumerate(cfg.block_pattern)}
    if tail:
        spec["tail"] = {f"t{j}": block_spec(cfg.block_pattern[j])
                        for j in range(tail)}
    return spec


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=None) -> Dict:
    dtype = dtype or common.dtype_of(cfg)
    n_per, tail = _n_periods(cfg)
    cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}

    def one_period(_):
        return {f"b{j}": _init_block_cache(cfg, kind, batch, max_seq, dtype)
                for j, kind in enumerate(cfg.block_pattern)}

    if n_per:
        cache["periods"] = jax.vmap(one_period)(jnp.arange(n_per))
    if tail:
        cache["tail"] = {
            f"t{j}": _init_block_cache(cfg, cfg.block_pattern[j], batch,
                                       max_seq, dtype)
            for j in range(tail)
        }
    return cache


# -------------------------------------------------------------- block apply

def _attn_block_apply(
    cfg: ModelConfig,
    h: jax.Array,
    p: Dict,
    policy: SparsityPolicy,
    phase: str,
    cache: Optional[Dict],
    pos,
    positions,
    positions_3d,
    flags,
    chunk_len=None,
    block_table=None,
) -> Tuple[jax.Array, Optional[Dict]]:
    b, t, d = h.shape
    fl = flags or {}
    x = common.norm_apply(h, p["ln1"], cfg.norm)
    q = sparse_linear(x, p["q_proj"], "q_proj", policy, phase, None,
                      fl.get("q_proj"))
    k = sparse_linear(x, p["k_proj"], "k_proj", policy, phase, None,
                      fl.get("k_proj"))
    v = sparse_linear(x, p["v_proj"], "v_proj", policy, phase, None,
                      fl.get("v_proj"))
    q = q.reshape(b, t, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    q = _apply_rope(cfg, q, positions, positions_3d)
    k = _apply_rope(cfg, k, positions, positions_3d)
    # pin attention sharding: heads on "model" when divisible, otherwise
    # replicated head compute — NEVER a head_dim-split contraction, which
    # would all-reduce the O(T·S) score tensor (measured on qwen2.5's 40
    # heads @ 16-way TP; EXPERIMENTS.md §Perf iteration 2)
    from repro.distributed.sharding import maybe_shard
    q = maybe_shard(q, "dp", None, "model", None)
    k = maybe_shard(k, "dp", None, "model", None)
    v = maybe_shard(v, "dp", None, "model", None)

    window = cfg.window if cfg.attn_type in ("swa", "local") else None
    new_cache = None

    if cache is None:
        o = attention(q, k, v, causal=True, window=window, q_offset=0,
                      chunk=cfg.attn_chunk, impl=cfg.attn_impl)
    elif block_table is not None:
        # paged cache: K/V live in a head-major pooled (num_blocks, Hkv,
        # block_size, hd) array shared by every slot; logical row p of a
        # slot maps to physical block table[p // bs], in-block row p % bs.
        # Writes scatter through the table (unallocated / pad rows drop)
        # and reads fence stale or unallocated positions with kv_len; both
        # dispatch through the
        # kernel ladder in models/attention — with kernels on, neither
        # direction touches a pool-shaped array outside a pallas_call.
        assert window is None, "paged KV does not cover sliding-window rings"
        bs_ = cache["k"].shape[2]
        mb = block_table.shape[1]
        # same policy flag that routes projections onto the fused kernels
        # sends the KV scatter AND the attention through the in-kernel
        # block-table walk (pool aliased in-place, no gathered logical
        # view); the jnp flat-index scatter / gather stay the oracles
        use_kernel = bool(policy.use_pallas_kernels)
        if t == 1:  # vector-pos decode: every row writes at its own depth
            posv = pos if jnp.ndim(pos) == 1 else jnp.broadcast_to(pos, (b,))
            ck, cv = paged_kv_update(cache["k"], cache["v"], k, v,
                                     block_table, posv,
                                     use_kernel=use_kernel)
            o = paged_attention(q, ck, cv, block_table, causal=False,
                                q_offset=posv,
                                kv_len=jnp.minimum(posv + 1, mb * bs_),
                                chunk=cfg.attn_chunk,
                                use_kernel=use_kernel)
        else:  # chunked prefill at offset ``pos`` (batch-1 slot path)
            # ``pos`` may be nonzero on a request's FIRST chunk: with
            # block-level prefix caching (serve/paged.py) admission maps
            # already-computed blocks into the table and starts the slot at
            # the first non-cached token.  Writes only ever target
            # wpos >= pos, and a cached prefix is always a whole number of
            # blocks, so shared (refcount > 1) blocks — table indices
            # < pos // bs — are read-only here by construction.
            assert b == 1, "paged chunked prefill is per-slot (batch 1)"
            cl = (chunk_len if chunk_len is not None
                  else jnp.asarray(t, jnp.int32))
            ck, cv = paged_kv_update(cache["k"], cache["v"], k, v,
                                     block_table, pos, cl,
                                     use_kernel=use_kernel)
            o = paged_attention(q, ck, cv, block_table, causal=True,
                                q_offset=pos, kv_len=pos + cl,
                                chunk=cfg.attn_chunk,
                                use_kernel=use_kernel)
        new_cache = {"k": ck, "v": cv}
    else:
        s_c = cache["k"].shape[1]
        if t == 1:  # decode step: write slot, then attend over valid slots
            if jnp.ndim(pos) == 1:  # slot-batched: every row at its own pos
                slot = pos % s_c if window is not None else pos
                bidx = jnp.arange(b)
                ck = cache["k"].at[bidx, slot].set(k[:, 0], mode="drop")
                cv = cache["v"].at[bidx, slot].set(v[:, 0], mode="drop")
            else:
                slot = pos % s_c if window is not None else pos
                ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot,
                                                         axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot,
                                                         axis=1)
            kv_len = jnp.minimum(pos + 1, s_c)
            o = attention(q, ck, cv, causal=False, window=None,
                          q_offset=pos, kv_len=kv_len, chunk=cfg.attn_chunk)
            new_cache = {"k": ck, "v": cv}
        elif chunk_len is not None:
            # chunked prefill at offset ``pos``: write the chunk's valid rows
            # into the cache first, then attend the chunk queries over the
            # whole cached history (earlier chunks + this one).  Rows past
            # ``chunk_len`` (padding) scatter out of bounds and are dropped;
            # stale rows from a previous slot occupant are excluded by the
            # kv_len / kv_positions masks.
            i = jnp.arange(t)
            valid_i = i < chunk_len
            wpos = pos + i                      # absolute token positions
            if window is not None:
                # Ring buffer: writing the chunk first would evict older
                # rows still inside the window of this chunk's early queries
                # (ring size == window), so attend over [old ring ∥ fresh
                # chunk] with explicit absolute positions, then scatter the
                # chunk into the ring for later chunks / decode.  Row r of
                # the old ring holds the latest position ≤ pos-1 congruent
                # to r mod s_c (-1 = never written).
                r_ = jnp.arange(s_c)
                p_old = pos - 1 - ((pos - 1 - r_) % s_c)
                kv_pos = jnp.concatenate(
                    [jnp.where(p_old >= 0, p_old, -1),
                     jnp.where(valid_i, wpos, -1)])
                k_att = jnp.concatenate([cache["k"], k], axis=1)
                v_att = jnp.concatenate([cache["v"], v], axis=1)
                o = attention(q, k_att, v_att, causal=True, window=window,
                              q_offset=pos, kv_positions=kv_pos,
                              chunk=cfg.attn_chunk)
                idx = jnp.where(valid_i, wpos % s_c, s_c)
                ck = cache["k"].at[:, idx].set(k, mode="drop")
                cv = cache["v"].at[:, idx].set(v, mode="drop")
            else:
                # full cache: write the valid rows at their absolute offsets
                # (pad rows scatter out of bounds → dropped), then attend the
                # chunk queries over the whole cached prefix + chunk
                idx = jnp.where(valid_i, wpos, s_c)
                ck = cache["k"].at[:, idx].set(k, mode="drop")
                cv = cache["v"].at[:, idx].set(v, mode="drop")
                o = attention(q, ck, cv, causal=True, window=None,
                              q_offset=pos, kv_len=pos + chunk_len,
                              chunk=cfg.attn_chunk)
            new_cache = {"k": ck, "v": cv}
        else:  # prefill: full attention, then populate the cache
            o = attention(q, k, v, causal=True, window=window, q_offset=0,
                          chunk=cfg.attn_chunk, impl=cfg.attn_impl)
            if s_c >= t:
                ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, axis=1)
            else:  # ring buffer smaller than the prompt: keep last s_c
                tail_k = k[:, t - s_c:]
                tail_v = v[:, t - s_c:]
                idx = (jnp.arange(t - s_c, t) % s_c)
                ck = cache["k"].at[:, idx].set(tail_k)
                cv = cache["v"].at[:, idx].set(tail_v)
            new_cache = {"k": ck, "v": cv}

    o = o.reshape(b, t, cfg.q_dim)
    o = sparse_linear(o, p["o_proj"], "o_proj", policy, phase, None,
                      fl.get("o_proj"))
    h = h + o
    x2 = common.norm_apply(h, p["ln2"], cfg.norm)
    if cfg.n_experts:
        ff = moe(x2, p["moe"], policy, phase, cfg.top_k, cfg.act_fn,
                 cfg.moe_impl, fl)
    else:
        ff = mlp(x2, p["mlp"], policy, phase, cfg.act_fn, None, fl)
    return h + ff, new_cache


def _block_apply(cfg, kind, h, p, policy, phase, cache, pos, positions,
                 positions_3d, flags, chunk_len=None, block_table=None):
    if kind == "attn":
        return _attn_block_apply(cfg, h, p, policy, phase, cache, pos,
                                 positions, positions_3d, flags, chunk_len,
                                 block_table)
    if kind == "rwkv6":
        y, st = rwkv6_block(h, p["rwkv"], policy, phase, cfg.n_heads,
                            cache, flags)
        return y, st
    if kind == "rglru":
        x = common.norm_apply(h, p["ln1"], cfg.norm)
        y, st = rglru_block(x, p["rglru"], policy, phase, cache, flags)
        h = h + y
        x2 = common.norm_apply(h, p["ln2"], cfg.norm)
        h = h + mlp(x2, p["mlp"], policy, phase, cfg.act_fn, None, flags)
        return h, st
    raise ValueError(kind)


# ------------------------------------------------------------ layer skipping

def _build_flags(cfg: ModelConfig, policy: SparsityPolicy):
    """Per-period boolean prune-flags for modules with layer-dependent skips.

    Returns (period_flags, tail_flags):
      period_flags: {"b{j}": {module: (n_periods,) bool}} scanned as xs;
      tail_flags:   {"t{j}": {module: bool scalar}}.
    None / missing module ⇒ no layer dependence (prune whenever the module
    is prunable).
    """
    if not policy.enabled or not policy.skip_layers:
        return None, None
    has_any = any(len(idxs) for _, idxs in policy.skip_layers)  # type: ignore
    if not has_any:
        return None, None
    n_per, tail = _n_periods(cfg)
    plen = len(cfg.block_pattern)
    modules = [name for name, idxs in policy.skip_layers if len(idxs)]  # type: ignore

    period_flags = {}
    for j in range(plen):
        fl = {}
        for mname in modules:
            vec = np.array(
                [policy.should_prune(mname, i * plen + j) for i in range(n_per)],
                dtype=bool,
            )
            fl[mname] = jnp.asarray(vec)
        period_flags[f"b{j}"] = fl
    tail_flags = {}
    for j in range(tail):
        li = n_per * plen + j
        tail_flags[f"t{j}"] = {
            m: jnp.asarray(bool(policy.should_prune(m, li))) for m in modules
        }
    return (period_flags if n_per else None), (tail_flags if tail else None)


# ------------------------------------------------------------------ forward

def _embed_inputs(cfg: ModelConfig, params, batch) -> jax.Array:
    from repro.distributed.sharding import maybe_shard

    tokens = batch["tokens"]
    h = common.embed(tokens, params["embed"])
    h = maybe_shard(h, "dp", None, None)
    if cfg.vision_stub and "pixel_embeds" in batch:
        pe = batch["pixel_embeds"].astype(h.dtype)
        h = jax.lax.dynamic_update_slice(h, pe, (0, 0, 0))
    if cfg.rope_variant == "sinusoidal":
        pos = batch.get("positions", jnp.arange(tokens.shape[1])[None, :])
        h = h + common.sinusoidal_positions(pos, cfg.d_model).astype(h.dtype)
    return h


def _run_blocks(cfg, params, h, policy, phase, cache, positions, positions_3d,
                chunk_len=None):
    n_per, tail = _n_periods(cfg)
    pos = cache["pos"] if cache is not None else jnp.zeros((), jnp.int32)
    btab = cache.get("block_table") if cache is not None else None
    period_flags, tail_flags = _build_flags(cfg, policy)
    new_cache: Dict[str, Any] = {} if cache is not None else None

    if n_per:
        def run_period(h_c, pp, cc, fl):
            cc_new = {}
            hh = h_c
            for j, kind in enumerate(cfg.block_pattern):
                blk_cache = cc[f"b{j}"] if cc is not None else None
                blk_flags = fl[f"b{j}"] if fl is not None else None
                hh, c_out = _block_apply(cfg, kind, hh, pp[f"b{j}"], policy,
                                         phase, blk_cache, pos, positions,
                                         positions_3d, blk_flags, chunk_len,
                                         btab)
                if cc is not None:
                    cc_new[f"b{j}"] = c_out
            return hh, cc_new

        if cache is None and not cfg.scan_layers:
            # unrolled layers: FSDP param gathers sit at their natural use
            # sites (a lax.scan would let LICM hoist one whole-stack gather
            # of the loop-invariant xs out of the loop — n_layers× the
            # per-layer working set)
            from repro.distributed.sharding import maybe_shard

            body_fn = run_period
            if cfg.remat and phase == "train":
                body_fn = jax.checkpoint(
                    lambda h_c, pp, fl: run_period(h_c, pp, None, fl)[0],
                    static_argnums=())
            for i in range(n_per):
                pp = jax.tree_util.tree_map(lambda x: x[i],
                                            params["periods"])
                fl = (jax.tree_util.tree_map(lambda x: x[i], period_flags)
                      if period_flags is not None else None)
                if cfg.remat and phase == "train":
                    h = body_fn(h, pp, fl)
                else:
                    h, _ = run_period(h, pp, None, fl)
                h = maybe_shard(h, "dp", None, None)
        elif cache is None:
            # stateless pass: params (and optional flags) ride as scan xs
            from repro.distributed.sharding import maybe_shard

            def body(h_c, xs):
                pp, fl = xs if period_flags is not None else (xs, None)
                # barrier pins the FSDP param all-gather INSIDE the loop:
                # without it LICM hoists a whole-stack (n_layers×) gather of
                # the loop-invariant xs out of the scan
                pp = common.opt_barrier(pp)
                hh, _ = run_period(h_c, pp, None, fl)
                # keep the residual carry batch-sharded (GSPMD propagation
                # through the recurrent scan sometimes drops it)
                hh = maybe_shard(hh, "dp", None, None)
                return hh, None

            if cfg.remat and phase == "train":
                body = jax.checkpoint(body)
            xs = (params["periods"], period_flags) \
                if period_flags is not None else params["periods"]
            h, _ = jax.lax.scan(body, h, xs)
        elif not cfg.scan_layers:
            # unrolled cached path (analysis mode: exact per-layer cost
            # accounting — while bodies are counted once by HLO cost
            # analysis, so roofline extraction unrolls)
            cstack = cache["periods"]
            new_stack = cstack
            for i in range(n_per):
                pp = jax.tree_util.tree_map(lambda x: x[i], params["periods"])
                fl = (jax.tree_util.tree_map(lambda x: x[i], period_flags)
                      if period_flags is not None else None)
                cc = jax.tree_util.tree_map(lambda x: x[i], cstack)
                h, cc_new = run_period(h, pp, cc, fl)
                new_stack = jax.tree_util.tree_map(
                    lambda c, u: c.at[i].set(u.astype(c.dtype)),
                    new_stack, cc_new)
            new_cache["periods"] = new_stack
        else:
            # cache rides in the CARRY (not xs): scan xs are loop-invariant,
            # and XLA's float-normalization + LICM on CPU would hoist a full
            # f32 copy of an xs cache out of the loop — as loop-varying
            # state it is sliced/updated in place per period
            cstack = cache["periods"]

            def body(carry, xs):
                h_c, cs = carry
                if period_flags is not None:
                    pp, fl, idx = xs
                else:
                    (pp, idx), fl = xs, None
                cc = jax.tree_util.tree_map(
                    lambda c: jax.lax.dynamic_index_in_dim(
                        c, idx, 0, keepdims=False), cs)
                hh, cc_new = run_period(h_c, pp, cc, fl)
                cs = jax.tree_util.tree_map(
                    lambda c, u: jax.lax.dynamic_update_index_in_dim(
                        c, u.astype(c.dtype), idx, 0), cs, cc_new)
                return (hh, cs), None

            idxs = jnp.arange(n_per)
            xs = (params["periods"], period_flags, idxs) \
                if period_flags is not None else (params["periods"], idxs)
            (h, cstack), _ = jax.lax.scan(body, (h, cstack), xs)
            new_cache["periods"] = cstack

    if tail:
        base = n_per * len(cfg.block_pattern)
        for j in range(tail):
            kind = cfg.block_pattern[j]
            blk_cache = cache["tail"][f"t{j}"] if cache is not None else None
            blk_flags = tail_flags[f"t{j}"] if tail_flags is not None else None
            h, c_out = _block_apply(cfg, kind, h, params["tail"][f"t{j}"],
                                    policy, phase, blk_cache, pos, positions,
                                    positions_3d, blk_flags, chunk_len, btab)
            if cache is not None:
                new_cache.setdefault("tail", {})[f"t{j}"] = c_out

    return h, new_cache


def _lm_logits(cfg, params, h):
    from repro.distributed.sharding import maybe_shard

    h = common.norm_apply(h, params["final_norm"], cfg.norm)
    if cfg.tie_embeddings:
        logits = h @ params["embed"]["w"].T
    else:
        logits = h @ params["lm_head"]["w"]
    # keep the vocab dim model-sharded: (B, T, V) or (B, V)
    if logits.ndim == 3:
        return maybe_shard(logits, "dp", None, "model")
    return maybe_shard(logits, "dp", "model")


def forward(
    cfg: ModelConfig,
    params: Dict,
    batch: Dict,
    *,
    policy: SparsityPolicy,
    phase: str = "train",
) -> jax.Array:
    """Full-sequence pass (train / prefill-without-cache).  → (B, T, V)."""
    tokens = batch["tokens"]
    b, t = tokens.shape
    positions = batch.get("positions", jnp.broadcast_to(jnp.arange(t), (b, t)))
    positions_3d = batch.get(
        "positions_3d",
        jnp.broadcast_to(jnp.arange(t), (3, b, t)) if cfg.rope_variant == "mrope"
        else None,
    )
    h = _embed_inputs(cfg, params, batch)
    h, _ = _run_blocks(cfg, params, h, policy, phase, None, positions,
                       positions_3d)
    return _lm_logits(cfg, params, h)


def prefill(
    cfg: ModelConfig,
    params: Dict,
    batch: Dict,
    cache: Dict,
    *,
    policy: SparsityPolicy,
) -> Tuple[jax.Array, Dict]:
    """Prompt ingestion: returns (last-token logits (B, V), filled cache)."""
    tokens = batch["tokens"]
    b, t = tokens.shape
    positions = batch.get("positions", jnp.broadcast_to(jnp.arange(t), (b, t)))
    positions_3d = batch.get(
        "positions_3d",
        jnp.broadcast_to(jnp.arange(t), (3, b, t)) if cfg.rope_variant == "mrope"
        else None,
    )
    h = _embed_inputs(cfg, params, batch)
    h, new_cache = _run_blocks(cfg, params, h, policy, "prefill", cache,
                               positions, positions_3d)
    new_cache["pos"] = cache["pos"] + t
    logits = _lm_logits(cfg, params, h[:, -1:])[:, 0]
    return logits, new_cache


def prefill_chunk(
    cfg: ModelConfig,
    params: Dict,
    batch: Dict,
    cache: Dict,
    *,
    policy: SparsityPolicy,
) -> Tuple[jax.Array, Dict]:
    """One fixed-shape prefill chunk written at the cache offset ``pos``.

    ``batch["tokens"]`` is ``(B, C)``; ``batch["chunk_len"]`` (traced scalar,
    default C) marks how many leading tokens are valid — the padded tail is
    masked out of both the KV writes and the attention.  The chunk attends
    causally over everything the cache already holds — earlier chunks of
    the same request, or (paged caches) prefix blocks another request
    computed that admission mapped into this slot's table with ``pos``
    advanced past them — so feeding a prompt through in C-token chunks,
    from any starting offset with valid cached KV below it, reproduces the
    one-shot prefill.  Recurrent blocks (rwkv6 / rglru) carry their state
    through the cache but cannot mask padded tokens out of their scans — for
    those archs the caller must send fully-valid chunks (chunk_len == C; the
    serving engine decomposes prompts dyadically to guarantee it).

    Returns (logits of the last *valid* token (B, V), cache with
    ``pos += chunk_len``).
    """
    tokens = batch["tokens"]
    b, t = tokens.shape
    pos = cache["pos"]
    chunk_len = batch.get("chunk_len")
    if chunk_len is None:
        chunk_len = jnp.asarray(t, jnp.int32)
    positions = pos + jnp.broadcast_to(jnp.arange(t), (b, t))
    positions_3d = (
        pos + jnp.broadcast_to(jnp.arange(t), (3, b, t))
        if cfg.rope_variant == "mrope" else None
    )
    if cfg.rope_variant == "sinusoidal":
        batch = dict(batch, positions=positions)
    h = _embed_inputs(cfg, params, batch)
    h, new_cache = _run_blocks(cfg, params, h, policy, "prefill", cache,
                               positions, positions_3d, chunk_len=chunk_len)
    new_cache["pos"] = pos + chunk_len
    if "block_table" in cache:
        new_cache["block_table"] = cache["block_table"]
    h_last = jax.lax.dynamic_slice_in_dim(h, chunk_len - 1, 1, axis=1)
    logits = _lm_logits(cfg, params, h_last)[:, 0]
    return logits, new_cache


def decode_step(
    cfg: ModelConfig,
    params: Dict,
    tokens: jax.Array,        # (B, 1)
    cache: Dict,
    *,
    policy: SparsityPolicy,
) -> Tuple[jax.Array, Dict]:
    """One decode step.  → ((B, V) logits, updated cache).

    ``cache["pos"]`` may be a scalar (whole batch in lockstep, legacy
    one-shot engine) or a (B,) vector of per-slot positions (continuous
    batching: every slot decodes at its own depth).
    """
    b, t = tokens.shape
    pos = cache["pos"]
    if jnp.ndim(pos) == 1:
        positions = jnp.broadcast_to(pos[:, None], (b, t))
        positions_3d = (
            jnp.broadcast_to(pos[None, :, None], (3, b, t))
            if cfg.rope_variant == "mrope" else None
        )
    else:
        positions = jnp.broadcast_to(pos, (b, t))
        positions_3d = (
            jnp.broadcast_to(pos, (3, b, t)) if cfg.rope_variant == "mrope"
            else None
        )
    batch = {"tokens": tokens, "positions": positions}
    h = _embed_inputs(cfg, params, batch)
    h, new_cache = _run_blocks(cfg, params, h, policy, "decode", cache,
                               positions, positions_3d)
    new_cache["pos"] = pos + 1
    if "block_table" in cache:
        new_cache["block_table"] = cache["block_table"]
    logits = _lm_logits(cfg, params, h)[:, 0]
    return logits, new_cache
