"""The served program stores a narrow (bfloat16) value once, where the
plain references of ``bench/references/`` store it: RoPE and the gated
product compute in float32 and round once, a dense projection rounds once
after its bias, and every serving program is compiled with XLA's excess
precision off (``executor.EXACT_STORES``), so no fused chain carries a
stored value wider than its dtype."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.layers.linear import dense_linear
from repro.models import common
from repro.models.mlp import glu

BF16 = jnp.bfloat16


def _rand(key, shape, scale=1.0):
    return (jax.random.normal(jax.random.PRNGKey(key), shape) * scale
            ).astype(BF16)


@pytest.mark.parametrize("kind", ["silu", "gelu"])
def test_gated_product_rounds_once(kind):
    g, u = _rand(0, (64, 96), 3.0), _rand(1, (64, 96), 3.0)
    act = jax.nn.silu if kind == "silu" else jax.nn.gelu
    want = (act(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(BF16)
    got = glu(g, u, kind)
    assert got.dtype == BF16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_rope_rounds_once():
    x = _rand(2, (1, 40, 4, 32), 2.0)
    pos = jnp.arange(40)[None] + 1000
    inv = common.rope_freqs(32, 1e6)
    ang = pos.astype(jnp.float32)[..., None] * inv
    c = jnp.cos(ang)[:, :, None, :].astype(BF16).astype(jnp.float32)
    s = jnp.sin(ang)[:, :, None, :].astype(BF16).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :16], xf[..., 16:]
    want = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           -1).astype(BF16)
    got = common.apply_rope(x, pos, 1e6)
    assert got.dtype == BF16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_dense_projection_rounds_once_after_its_bias():
    x, w = _rand(3, (8, 64)), _rand(4, (64, 16), 0.125)
    b = _rand(5, (16,), 0.02)
    acc = jnp.matmul(x, w, preferred_element_type=jnp.float32)
    got = dense_linear(x, {"w": w, "b": b})
    assert got.dtype == BF16
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray((acc + b.astype(jnp.float32)).astype(BF16), np.float32))
    # float32 stays float32
    assert dense_linear(x.astype(jnp.float32),
                        {"w": w.astype(jnp.float32)}).dtype == jnp.float32


def test_serving_programs_compile_with_exact_stores():
    from repro.serve import executor
    assert executor.EXACT_STORES == {"xla_allow_excess_precision": False}
    # the option reaches XLA: a stored bfloat16 sum is rounded before the
    # float32 work that reads it
    f = jax.jit(lambda a, b: (a + b).astype(jnp.float32) * 3.0,
                compiler_options=executor.EXACT_STORES)
    a = jnp.full((4,), 1.0, BF16)
    b = jnp.full((4,), 2.0 ** -9, BF16)
    np.testing.assert_array_equal(np.asarray(f(a, b)), np.full((4,), 3.0))
