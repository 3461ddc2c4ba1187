"""Routed mixture-of-experts with a sort-based grouped matmul.

Dispatch is dropless: tokens are argsorted by expert id and contracted with
``jax.lax.ragged_dot`` (grouped matmul — FLOPs ∝ top_k, not n_experts).
An einsum-based dense fallback (``moe_impl='dense'``) exists for platforms
where ragged_dot does not lower.

Amber Pruner inside experts: the paper disables Robust-Norm scoring for MoE
(tokens are dynamically routed → per-expert weight statistics are unstable),
so expert-FFN inputs are pruned with plain |X| scores (``moe_plain_score``);
the per-token N:M mode is used even under tile-consensus because expert
groups don't align with token tiles.  Router projections stay dense (tiny).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import pruner
from repro.core.policy import SparsityPolicy
from repro.layers.linear import init_linear
from repro.models.mlp import _act, init_mlp, mlp

__all__ = ["init_moe", "moe"]


def init_moe(
    rng: jax.Array,
    d_model: int,
    moe_d_ff: int,
    n_experts: int,
    shared_expert: bool,
    dtype=jnp.float32,
) -> Dict:
    r = jax.random.split(rng, 5)
    std = d_model**-0.5
    fstd = moe_d_ff**-0.5
    p = {
        "router": init_linear(r[0], d_model, n_experts, dtype=jnp.float32),
        "experts": {
            "gate_proj": {"w": (jax.random.normal(r[1], (n_experts, d_model, moe_d_ff)) * std).astype(dtype)},
            "up_proj": {"w": (jax.random.normal(r[2], (n_experts, d_model, moe_d_ff)) * std).astype(dtype)},
            "down_proj": {"w": (jax.random.normal(r[3], (n_experts, moe_d_ff, d_model)) * fstd).astype(dtype)},
        },
    }
    if shared_expert:
        p["shared"] = init_mlp(r[4], d_model, moe_d_ff, dtype)
    return p


def _maybe_prune(x: jax.Array, module: str, policy: SparsityPolicy,
                 phase: str) -> jax.Array:
    if policy.active(phase) and policy.should_prune(module, None):
        return pruner.prune_input(x, None, policy)  # naive |X| inside experts
    return x


def moe(
    x: jax.Array,                      # (..., T, D) — flattened internally
    p: Dict,
    policy: SparsityPolicy,
    phase: str,
    top_k: int,
    act_fn: str = "silu",
    impl: str = "ragged",
    flags: Optional[Dict[str, jax.Array]] = None,
) -> jax.Array:
    # Under a multi-device mesh, GSPMD partitions ragged_dot by expanding
    # the expert dim into dense masked ops over the GLOBAL token axis
    # (O(E·T·d) buffers).  Dispatch must be token-local: shard_map keeps the
    # sort/bincount/ragged_dot per data shard, with TP over d_ff and one
    # explicit psum for the row-parallel down projection.
    from repro.distributed.sharding import _context_mesh, data_axes

    mesh = _context_mesh()
    if (impl == "ragged" and mesh is not None and mesh.size > 1
            and "model" in mesh.axis_names and x.ndim == 3):
        dp_size = 1
        for a in data_axes(mesh):
            dp_size *= mesh.shape[a]
        # shard_map needs the batch divisible by DP; tiny batches (e.g. the
        # long-context decode cell, B=1) go through the local path — the
        # token count there is trivial so the portable ragged decomposition
        # is harmless
        if x.shape[0] % dp_size == 0 and x.shape[0] >= dp_size:
            return _moe_shard_map(mesh, x, p, policy, phase, top_k, act_fn,
                                  flags)
    return _moe_local(x, p, policy, phase, top_k, act_fn, impl, flags)


def _moe_local(
    x: jax.Array,
    p: Dict,
    policy: SparsityPolicy,
    phase: str,
    top_k: int,
    act_fn: str = "silu",
    impl: str = "ragged",
    flags: Optional[Dict[str, jax.Array]] = None,
) -> jax.Array:
    orig_shape = x.shape
    d = orig_shape[-1]
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    n_experts = p["router"]["w"].shape[-1]

    logits = (xt.astype(jnp.float32) @ p["router"]["w"])        # (T, E)
    gate_vals, expert_ids = jax.lax.top_k(logits, top_k)        # (T, k)
    gates = jax.nn.softmax(gate_vals, axis=-1)                  # renorm over top-k

    wg = p["experts"]["gate_proj"]["w"]
    wu = p["experts"]["up_proj"]["w"]
    wd = p["experts"]["down_proj"]["w"]

    if impl == "dense":
        # weighted all-expert compute (compile-anywhere fallback)
        combine = jnp.zeros((t, n_experts), jnp.float32)
        combine = jax.vmap(lambda c, i, g: c.at[i].add(g))(combine, expert_ids, gates)
        xin = _maybe_prune(xt, "gate_proj", policy, phase)
        xup = _maybe_prune(xt, "up_proj", policy, phase)
        h = _act(jnp.einsum("td,edf->tef", xin, wg), act_fn)
        h = h * jnp.einsum("td,edf->tef", xup, wu)
        h = _maybe_prune(h.reshape(t * n_experts, -1), "down_proj", policy, phase
                         ).reshape(t, n_experts, -1)
        y_e = jnp.einsum("tef,efd->ted", h, wd)
        y = jnp.einsum("ted,te->td", y_e, combine.astype(y_e.dtype))
    else:
        flat_e = expert_ids.reshape(-1)                         # (T*k,)
        flat_t = jnp.repeat(jnp.arange(t), top_k)               # (T*k,)
        order = jnp.argsort(flat_e, stable=True)
        inv = jnp.argsort(order)
        xs = jnp.take(xt, jnp.take(flat_t, order), axis=0)      # (T*k, D)
        group_sizes = jnp.bincount(flat_e, length=n_experts).astype(jnp.int32)

        xg = _maybe_prune(xs, "gate_proj", policy, phase)
        xu = _maybe_prune(xs, "up_proj", policy, phase)
        hg = jax.lax.ragged_dot(xg, wg, group_sizes)
        hu = jax.lax.ragged_dot(xu, wu, group_sizes)
        h = _act(hg, act_fn) * hu
        h = _maybe_prune(h, "down_proj", policy, phase)
        ys = jax.lax.ragged_dot(h, wd, group_sizes)             # (T*k, D)
        y_flat = jnp.take(ys, inv, axis=0).reshape(t, top_k, d)
        y = jnp.einsum("tkd,tk->td", y_flat, gates.astype(y_flat.dtype))

    y = y.astype(x.dtype)
    if "shared" in p:
        y = y + mlp(xt, p["shared"], policy, phase, act_fn, None, flags)
    return y.reshape(orig_shape)


def _moe_shard_map(
    mesh,
    x: jax.Array,                      # (B, T, D)
    p: Dict,
    policy: SparsityPolicy,
    phase: str,
    top_k: int,
    act_fn: str,
    flags: Optional[Dict[str, jax.Array]],
) -> jax.Array:
    """Token-local routed experts under shard_map.

    Layout: batch over the DP axes, expert weights TP-sharded on d_ff over
    "model" (column-parallel gate/up, row-parallel down + psum).  Routing,
    argsort, bincount and both ragged_dots see only LOCAL shapes — the
    collective footprint is exactly one psum of the (local tokens, d_model)
    output, matching a Megatron MLP.

    N:M note: inside the experts the groups-of-M run over each device's
    contiguous d_ff shard — identical semantics to the unsharded op for
    gate/up (d_model unsharded); for the down projection the group
    boundaries align with the weight shard, which is also how a sparse
    tensor core would see the operand.
    """
    from jax.sharding import PartitionSpec as P

    dp = data_axes_tuple(mesh)
    wg, wu, wd = (p["experts"]["gate_proj"]["w"], p["experts"]["up_proj"]["w"],
                  p["experts"]["down_proj"]["w"])
    router = p["router"]["w"]

    def body(xb, router_l, wg_l, wu_l, wd_l):
        b, t, d = xb.shape
        n_exp = router_l.shape[-1]
        xt = xb.reshape(b * t, d)
        logits = xt.astype(jnp.float32) @ router_l
        gate_vals, expert_ids = jax.lax.top_k(logits, top_k)
        gates = jax.nn.softmax(gate_vals, axis=-1)

        # sort-by-expert, then FIXED-CAPACITY batched matmuls.  ragged_dot
        # would be the native TPU op, but its portable decomposition dense-
        # expands the expert dim (O(E·T·d)); capacity slots keep every
        # shape static and partitioner-friendly at topk·cf× dense FLOPs.
        flat_e = expert_ids.reshape(-1)                      # (t*k,)
        flat_t = jnp.repeat(jnp.arange(b * t), top_k)
        order = jnp.argsort(flat_e, stable=True)
        tok_sorted = jnp.take(flat_t, order)
        xs = jnp.take(xt, tok_sorted, axis=0)                # (t*k, D)
        counts = jnp.bincount(flat_e, length=n_exp)
        offsets = jnp.cumsum(counts) - counts

        cap = int(-(-(b * t * top_k) // n_exp) * 1.25)
        cap = max(8, -(-cap // 8) * 8)
        slot = jnp.arange(cap)
        idx = offsets[:, None] + slot[None, :]               # (E, C)
        valid = slot[None, :] < counts[:, None]
        idx_c = jnp.clip(idx, 0, b * t * top_k - 1)
        xe = jnp.take(xs, idx_c.reshape(-1), axis=0).reshape(
            n_exp, cap, d)                                   # (E, C, D)

        xg = _maybe_prune(xe, "gate_proj", policy, phase)
        xu = _maybe_prune(xe, "up_proj", policy, phase)
        hg = jnp.einsum("ecd,edf->ecf", xg, wg_l)
        hu = jnp.einsum("ecd,edf->ecf", xu, wu_l)
        h = _act(hg, act_fn) * hu
        h = _maybe_prune(h, "down_proj", policy, phase)
        ye = jnp.einsum("ecf,efd->ecd", h, wd_l)             # partial over F
        ye = ye * valid[..., None]

        ys = jnp.zeros((b * t * top_k, d), ye.dtype).at[
            idx_c.reshape(-1)].add(ye.reshape(-1, d))
        y = jnp.take(ys, jnp.argsort(order), axis=0).reshape(
            b * t, top_k, d)
        y = jnp.einsum("tkd,tk->td", y, gates.astype(y.dtype))
        y = jax.lax.psum(y, "model")                         # row-parallel sum
        return y.reshape(b, t, d).astype(xb.dtype)

    dp_entry = dp if len(dp) > 1 else dp[0]
    y = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(dp_entry, None, None),          # x: batch over DP
            P(None, None),                    # router replicated
            P(None, None, "model"),           # gate (E, D, F/model)
            P(None, None, "model"),           # up
            P(None, "model", None),           # down (E, F/model, D)
        ),
        out_specs=P(dp_entry, None, None),
        check_vma=False,
    )(x, router, wg, wu, wd)

    if "shared" in p:
        y = y + mlp(x, p["shared"], policy, phase, act_fn, None, flags)
    return y


def data_axes_tuple(mesh):
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
