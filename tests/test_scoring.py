"""Scoring (naive / Wanda-like / Robust-Norm) unit + property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import scoring


def test_channel_norm_scale_min_normalized(rng):
    w = jax.random.normal(rng, (32, 64))
    s = scoring.channel_norm_scale(w)
    assert s.shape == (32,)
    assert float(jnp.min(s)) == pytest.approx(1.0, rel=1e-5)


def test_robust_norm_scale_clips_outliers(rng):
    w = jax.random.normal(rng, (64, 128))
    # inject a huge outlier into channel 0 — robust scale must not explode
    w_out = w.at[0, 0].set(1e6)
    s_plain = scoring.channel_norm_scale(w_out)
    s_robust = scoring.robust_norm_scale(w_out)
    ratio_plain = float(s_plain[0] / jnp.median(s_plain))
    ratio_robust = float(s_robust[0] / jnp.median(s_robust))
    assert ratio_robust < ratio_plain / 100  # outlier influence crushed


@pytest.mark.parametrize("shape,dtype", [((64, 48), jnp.bfloat16),
                                         ((257, 33), jnp.float32),
                                         ((1000, 7), jnp.float32),
                                         ((1, 1), jnp.float32)])
def test_quantile_matches_jnp_quantile(shape, dtype):
    """The counting-pass quantile gives jnp.quantile's value (bf16-cast
    weights are full of ties; signed zeros compare equal)."""
    w = (jax.random.normal(jax.random.PRNGKey(shape[0]), shape) * 0.02)
    w = w.astype(dtype).astype(jnp.float32).at[:3].set(0.0).at[3:5].set(-0.0)
    for q in (0.0, 0.005, 0.25, 0.5, 0.995, 1.0):
        want = jax.jit(lambda x: jnp.quantile(x, q))(w)
        got = jax.jit(lambda x: scoring.quantile(x, q))(w)
        assert got == want, (q, got, want)
    srt = np.sort(np.asarray(w).ravel())
    for k in (0, w.size // 2, w.size - 1):
        assert float(scoring.kth_smallest(w, k)) == srt[k]


def test_score_activations_naive_vs_scaled(rng):
    x = jax.random.normal(rng, (8, 32))
    s_naive = scoring.score_activations(x, None)
    np.testing.assert_allclose(np.asarray(s_naive),
                               np.abs(np.asarray(x)), rtol=1e-6)
    scale = jnp.full((32,), 2.0)
    s2 = scoring.score_activations(x, scale)
    np.testing.assert_allclose(np.asarray(s2), 2 * np.abs(np.asarray(x)),
                               rtol=1e-6)


def test_precompute_scale_modes(rng):
    w = jax.random.normal(rng, (16, 8))
    assert scoring.precompute_scale(w, "naive") is None
    assert scoring.precompute_scale(w, "wanda").shape == (16,)
    assert scoring.precompute_scale(w, "robust").shape == (16,)
    with pytest.raises(ValueError):
        scoring.precompute_scale(w, "bogus")


@settings(max_examples=30, deadline=None)
@given(
    din=st.integers(4, 64),
    dout=st.integers(4, 64),
    seed=st.integers(0, 2**30),
)
def test_property_scales_positive_finite(din, dout, seed):
    w = jax.random.normal(jax.random.PRNGKey(seed), (din, dout))
    for mode in ("wanda", "robust"):
        s = np.asarray(scoring.precompute_scale(w, mode))
        assert np.isfinite(s).all()
        assert (s > 0).all()
        assert s.min() >= 1.0 - 1e-4  # min-normalization
