"""Shared model-zoo pieces: norms, embeddings, positional encodings."""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

__all__ = [
    "rms_norm",
    "layer_norm",
    "norm_apply",
    "init_norm",
    "init_embedding",
    "embed",
    "sinusoidal_positions",
    "rope_freqs",
    "apply_rope",
    "apply_rope_2d",
    "apply_mrope",
    "dtype_of",
    "opt_barrier",
]


def dtype_of(cfg) -> Any:
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]


# ---------------------------------------------------------------- barrier

@jax.custom_vjp
def opt_barrier(x):
    """``lax.optimization_barrier`` with an identity gradient.

    The primitive has no differentiation rule on the pinned jax version,
    which breaks training through any scan body that uses the barrier to
    fence LICM; the barrier is the identity, so the cotangent routes
    straight through.
    """
    return jax.lax.optimization_barrier(x)


def _opt_barrier_fwd(x):
    return opt_barrier(x), None


def _opt_barrier_bwd(_, g):
    return (g,)


opt_barrier.defvjp(_opt_barrier_fwd, _opt_barrier_bwd)


# --------------------------------------------------------------------- norms

def init_norm(d: int, kind: str, dtype=jnp.float32) -> Dict[str, jax.Array]:
    p = {"w": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["b"] = jnp.zeros((d,), dtype)
    return p


def rms_norm(x: jax.Array, p: Dict[str, jax.Array], eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["w"].astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: jax.Array, p: Dict[str, jax.Array], eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["w"].astype(jnp.float32)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y.astype(x.dtype)


def norm_apply(x: jax.Array, p: Dict[str, jax.Array], kind: str) -> jax.Array:
    return layer_norm(x, p) if kind == "layernorm" else rms_norm(x, p)


# ---------------------------------------------------------------- embeddings

def init_embedding(rng: jax.Array, vocab: int, d: int, dtype=jnp.float32):
    return {"w": (jax.random.normal(rng, (vocab, d)) * 0.02).astype(dtype)}


def embed(tokens: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
    return jnp.take(p["w"], tokens, axis=0)


def sinusoidal_positions(positions: jax.Array, d: int) -> jax.Array:
    """Classic transformer sinusoidal embedding for arbitrary positions."""
    half = d // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------- RoPE

def rope_freqs(head_dim: int, theta: float, rotary_dim: int | None = None):
    rd = rotary_dim or head_dim
    return 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))


def _rotate(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs laid out as [x0..x_{d/2-1} | x_{d/2}..] (neox style)."""
    d2 = x.shape[-1] // 2
    xf, cf, sf = (a.astype(jnp.float32) for a in (x, cos, sin))
    x1, x2 = xf[..., :d2], xf[..., d2:]
    return jnp.concatenate([x1 * cf - x2 * sf, x2 * cf + x1 * sf],
                           axis=-1).astype(x.dtype)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Standard RoPE.  x: (B, T, H, hd); positions: (B, T) absolute."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)                                # (hd/2,)
    ang = positions.astype(jnp.float32)[..., None] * inv        # (B, T, hd/2)
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    return _rotate(x, cos, sin)


def apply_rope_2d(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """ChatGLM-style: rotary on the first half of head_dim only."""
    hd = x.shape[-1]
    rd = hd // 2
    xr, xp = x[..., :rd], x[..., rd:]
    inv = rope_freqs(hd, theta, rotary_dim=rd)
    ang = positions.astype(jnp.float32)[..., None] * inv
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([_rotate(xr, cos, sin), xp], axis=-1)


def apply_mrope(
    x: jax.Array, positions_3d: jax.Array, theta: float,
    sections=(0.25, 0.375, 0.375),
) -> jax.Array:
    """Qwen2-VL M-RoPE: head_dim frequency bands split across (t, h, w)
    position streams.  positions_3d: (3, B, T)."""
    hd = x.shape[-1]
    half = hd // 2
    inv = rope_freqs(hd, theta)                                 # (half,)
    n_t = int(half * sections[0])
    n_h = int(half * sections[1])
    sel = jnp.zeros((half,), jnp.int32)
    sel = sel.at[n_t : n_t + n_h].set(1).at[n_t + n_h :].set(2)
    pos = positions_3d.astype(jnp.float32)                      # (3, B, T)
    ang_all = pos[..., None] * inv                              # (3, B, T, half)
    # per-frequency selection of the (t|h|w) position stream
    onehot = jax.nn.one_hot(sel, 3, dtype=jnp.float32)          # (half, 3)
    ang = jnp.einsum("sbth,hs->bth", ang_all, onehot)
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    return _rotate(x, cos, sin)
