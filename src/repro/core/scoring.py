"""Activation-importance scoring for Amber Pruner.

Three scoring modes, in increasing fidelity (paper §Methodology):

  * ``naive``  — ``S_ij = |X_ij|``  (the Naïve top-k baseline).
  * ``wanda``  — ``S_ij = |X_ij| · ‖W_:,j‖₂ / min_k ‖W_:,k‖₂``  (Eq. 2;
                 min-normalized so low-dynamic-range channels cannot
                 underflow in low-precision inference).
  * ``robust`` — Robust-Norm Scoring (Eqs. 3-5): winsorize weights to the
                 [0.5%, 99.5%] percentile band, standardize by the global
                 mean/variance of the surviving weights, then take channel
                 L2 norms (min-normalized like ``wanda``).

Weight convention throughout the code base: ``W`` has shape
``(d_in, d_out)`` so an input channel j is the **row** ``W[j, :]`` — this is
the transpose of the paper's ``(d_out, d_in)`` layout; the channel norms are
identical.

Scales depend only on the weights, so they are precomputed offline
(:func:`precompute_scale`) and stored as auxiliary parameters (<0.05% of
model size).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "channel_norm_scale",
    "kth_smallest",
    "quantile",
    "robust_norm_scale",
    "precompute_scale",
    "score_activations",
    "SCORE_MODES",
]

SCORE_MODES = ("naive", "wanda", "robust")

_EPS = 1e-12


def _min_normalize(norms: jax.Array) -> jax.Array:
    """``f(W_:,j) = ‖W_:,j‖ / min_k ‖W_:,k‖`` (Eq. 2 / Appendix B Eq. 5)."""
    return norms / (jnp.min(norms) + _EPS)


def channel_norm_scale(w: jax.Array) -> jax.Array:
    """Wanda-like per-input-channel scale from raw weight column norms.

    Args:
      w: ``(d_in, d_out)`` weight matrix.
    Returns:
      ``(d_in,)`` float32 scale.
    """
    norms = jnp.linalg.norm(w.astype(jnp.float32), axis=-1)
    return _min_normalize(norms)


def _order_key(x: jax.Array) -> jax.Array:
    """float32 → uint32 whose unsigned order is the float order."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def _from_order_key(key: jax.Array) -> jax.Array:
    u = jnp.where(key >> 31 == 1, key & jnp.uint32(0x7FFFFFFF), ~key)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def kth_smallest(x: jax.Array, k: int) -> jax.Array:
    """The ``k``-th smallest (0-based) element of a finite float32 array.

    Builds the answer's 32-bit order key from the top bit down, one counting
    pass over ``x`` per bit: the largest key with at most ``k`` elements
    below it.  Streams the data 32 times instead of sorting it — a sort of
    the 68 M weights of one Qwen2-7B MLP matrix is the slow part of
    :func:`robust_norm_scale` on a TPU."""
    key = _order_key(x.reshape(-1))

    def bit(i, ans):
        cand = ans | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(jnp.sum(key < cand) <= k, cand, ans)

    return _from_order_key(jax.lax.fori_loop(0, 32, bit, jnp.uint32(0)))


def quantile(x: jax.Array, q: float) -> jax.Array:
    """``jnp.quantile(x, q)`` (linear interpolation) of a finite float32
    array — the same value in the same compiled program — from two order
    statistics instead of a sort."""
    n = x.size
    pos = np.float32(q) * (np.float32(n) - np.float32(1))
    low, high = np.floor(pos), np.ceil(pos)
    hw = pos - low
    lw = np.float32(1) - hw
    lo_v = kth_smallest(x, int(min(max(low, 0), n - 1)))
    hi_v = kth_smallest(x, int(min(max(high, 0), n - 1)))
    return lo_v * lw + hi_v * hw


def robust_norm_scale(
    w: jax.Array, q_low: float = 0.005, q_high: float = 0.995
) -> jax.Array:
    """Robust-Norm Scoring scale (paper Eqs. 3-5).

    1. Outlier removal: weights outside the [q_low, q_high] percentile band
       are winsorized to the band edge (the paper "discards" them; clamping
       keeps per-channel element counts equal, which the channel norm in
       step 3 requires — the contribution of a clamped outlier saturates at
       the band edge either way).
    2. Standardize by the global mean/std of the winsorized tensor.
    3. Channel-wise L2 norm, min-normalized.

    Args:
      w: ``(d_in, d_out)`` weight matrix.
    Returns:
      ``(d_in,)`` float32 scale.
    """
    wf = w.astype(jnp.float32)
    lo = quantile(wf, q_low)
    hi = quantile(wf, q_high)
    wc = jnp.clip(wf, lo, hi)
    mu = jnp.mean(wc)
    sd = jnp.sqrt(jnp.var(wc) + _EPS)
    wn = (wc - mu) / sd
    norms = jnp.linalg.norm(wn, axis=-1)
    return _min_normalize(norms)


def precompute_scale(w: jax.Array, mode: str) -> jax.Array | None:
    """Offline per-channel scale for a linear's weight, or None for naive."""
    if mode == "naive":
        return None
    if mode == "wanda":
        return channel_norm_scale(w)
    if mode == "robust":
        return robust_norm_scale(w)
    raise ValueError(f"unknown score mode {mode!r}; expected one of {SCORE_MODES}")


def score_activations(x: jax.Array, scale: jax.Array | None) -> jax.Array:
    """``S_ij = |X_ij| · scale_j`` (scale None → naive |X|). float32 output."""
    s = jnp.abs(x.astype(jnp.float32))
    if scale is not None:
        s = s * scale.astype(jnp.float32)
    return s
