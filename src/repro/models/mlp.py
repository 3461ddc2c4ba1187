"""Gated MLP (SwiGLU / GeGLU) with Amber-prunable projections."""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.policy import SparsityPolicy
from repro.layers.linear import init_linear, sparse_linear

__all__ = ["init_mlp", "mlp"]


def _act(x: jax.Array, kind: str) -> jax.Array:
    return jax.nn.gelu(x) if kind == "gelu" else jax.nn.silu(x)


def glu(g: jax.Array, u: jax.Array, kind: str) -> jax.Array:
    """``act(g) * u``, computed in float32 and stored once in ``g``'s
    dtype."""
    return (_act(g.astype(jnp.float32), kind)
            * u.astype(jnp.float32)).astype(g.dtype)


def init_mlp(rng: jax.Array, d_model: int, d_ff: int, dtype=jnp.float32) -> Dict:
    r1, r2, r3 = jax.random.split(rng, 3)
    return {
        "gate_proj": init_linear(r1, d_model, d_ff, dtype=dtype),
        "up_proj": init_linear(r2, d_model, d_ff, dtype=dtype),
        "down_proj": init_linear(r3, d_ff, d_model, dtype=dtype),
    }


def mlp(
    x: jax.Array,
    p: Dict,
    policy: SparsityPolicy,
    phase: str,
    act_fn: str = "silu",
    layer_idx: Optional[int] = None,
    flags: Optional[Dict[str, jax.Array]] = None,
) -> jax.Array:
    """SwiGLU: down( act(gate(x)) * up(x) ).

    The paper's policy: ``up_proj`` is skipped (sensitive), ``down_proj`` is
    always pruned (lowest sensitivity), ``gate_proj`` selectively pruned.
    """
    fl = flags or {}
    g = sparse_linear(x, p["gate_proj"], "gate_proj", policy, phase,
                      layer_idx, fl.get("gate_proj"))
    u = sparse_linear(x, p["up_proj"], "up_proj", policy, phase,
                      layer_idx, fl.get("up_proj"))
    h = glu(g, u, act_fn)
    return sparse_linear(h, p["down_proj"], "down_proj", policy, phase,
                         layer_idx, fl.get("down_proj"))
