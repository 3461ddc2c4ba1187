"""Fused Pallas sparse-projection kernels vs the jnp oracles.

Covers the ISSUE-1 kernel family: ``nm_prune_matmul`` (score + N:M select +
mask + GEMM in one pallas_call), ``osparse_matmul`` (the Outstanding-sparse
smooth→prune→int8→GEMM→dequant chain), the k-blocked ``nm_spmm``, the
padding fallback in ``kernels.ops``, and the dispatch layer
(``use_pallas_kernels`` on the policy → exactly one pallas_call per
projection, jnp fallback for ``layer_flag`` models).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.core.policy import SparsityPolicy
from repro.core.pruner import SCALE_KEY, sparse_matmul
from repro.kernels import ops, ref
from repro.kernels.nm_spmm import nm_spmm_pallas
from repro.layers.linear import sparse_linear

PATTERNS = [(2, 4), (4, 8), (8, 16)]
DTYPES = [jnp.float32, jnp.bfloat16]
# (t, d, n_out): the last two force the token/odd-shape padding fallback
SHAPES = [(32, 64, 48), (128, 256, 128), (97, 160, 100), (33, 96, 130)]


def _tol(dtype):
    return dict(rtol=5e-2, atol=5e-1) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=1e-3)


def _policy(n, m, **kw):
    return SparsityPolicy(n=n, m=m, score_mode="naive", skip_modules=(),
                          skip_layers={}, **kw)


# --------------------------------------------------------- nm_prune_matmul

@pytest.mark.parametrize("t,d,no", SHAPES)
@pytest.mark.parametrize("n,m", PATTERNS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_nm_prune_matmul_parity(t, d, no, n, m, dtype, rng):
    if d % m:
        pytest.skip(f"d={d} not a multiple of m={m}")
    k1, k2, k3 = jax.random.split(rng, 3)
    x = jax.random.normal(k1, (t, d), dtype=dtype)
    w = jax.random.normal(k2, (d, no), dtype=dtype)
    scale = jax.random.uniform(k3, (d,)) + 0.5
    got = ops.nm_prune_matmul(x, w, scale, n, m)
    want = ref.nm_prune_matmul_ref(x, w, scale, n, m)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_nm_prune_matmul_no_scale_batched(rng):
    x = jax.random.normal(rng, (2, 16, 128))
    w = jax.random.normal(jax.random.fold_in(rng, 1), (128, 64))
    got = ops.nm_prune_matmul(x, w, None, 4, 8)
    want = ref.nm_prune_matmul_ref(x.reshape(32, 128), w, None, 4, 8)
    np.testing.assert_allclose(np.asarray(got).reshape(32, 64),
                               np.asarray(want), rtol=2e-5, atol=1e-3)


def test_nm_prune_matmul_kblock_invariance(rng):
    """Per-token selection is local to each M-group → k-blocking is exact."""
    k1, k2 = jax.random.split(rng)
    x = jax.random.normal(k1, (64, 512))
    w = jax.random.normal(k2, (512, 128))
    a = ops.nm_prune_matmul(x, w, None, 8, 16, block_k=128)
    b = ops.nm_prune_matmul(x, w, None, 8, 16, block_k=512)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-4)


# ---------------------------------------------------------- osparse_matmul

@pytest.mark.parametrize("t,d,no", [(32, 64, 48), (97, 160, 100)])
@pytest.mark.parametrize("n,m", PATTERNS)
@pytest.mark.parametrize("per_token", [False, True])
def test_osparse_matmul_parity(t, d, no, n, m, per_token, rng):
    if d % m:
        pytest.skip(f"d={d} not a multiple of m={m}")
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    x = jax.random.normal(k1, (t, d))
    w = jax.random.normal(k2, (d, no))
    smooth = jax.random.uniform(k3, (d,)) + 0.5
    amber = jax.random.uniform(k4, (d,)) + 0.5
    wq, w_scale = quant.quantize_weight_per_channel(w)
    act_scale = None if per_token else jnp.float32(0.05)
    got = ops.osparse_matmul(x, wq, smooth, amber, w_scale, n, m,
                             act_scale=act_scale, per_token=per_token)
    want = ref.osparse_matmul_ref(x, wq, smooth, amber, w_scale, n, m,
                                  act_scale=act_scale, per_token=per_token)
    # int32 partial sums commute → bit-equal up to f32 dequant rounding
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-5)


def test_osparse_per_tensor_requires_scale(rng):
    x = jax.random.normal(rng, (8, 32))
    wq = jnp.ones((32, 16), jnp.int8)
    with pytest.raises(ValueError):
        ops.osparse_matmul(x, wq, jnp.ones((32,)), None, jnp.ones((16,)),
                           2, 4, act_scale=None, per_token=False)


# ----------------------------------------------------- k-blocked nm_spmm

@pytest.mark.parametrize("dtype", DTYPES)
def test_nm_spmm_kblock_matches_single_block(dtype, rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    x = jax.random.normal(k1, (64, 512), dtype=dtype)
    w = jax.random.normal(k2, (512, 128), dtype=dtype)
    scale = jax.random.uniform(k3, (512,)) + 0.5
    blocked = nm_spmm_pallas(x, w, scale, 4, 8, block_t=32, block_o=64,
                             block_k=128)
    single = nm_spmm_pallas(x, w, scale, 4, 8, block_t=32, block_o=64,
                            block_k=512)
    tol = dict(rtol=5e-2, atol=5e-1) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(blocked, np.float32),
                               np.asarray(single, np.float32), **tol)


def test_nm_spmm_d16384_tiles(rng):
    """Reduction depth the seed kernel's full-D BlockSpec could not tile:
    VMEM residency is now per k-block, so D = 16384 runs with bk = 2048."""
    d = 16384
    k1, k2 = jax.random.split(rng)
    x = jax.random.normal(k1, (8, d))
    w = jax.random.normal(k2, (d, 128)) * d**-0.5
    got = ops.nm_spmm(x, w, None, 8, 16, tile=8, block_k=2048)
    want = ref.nm_spmm_ref(x, w, None, 8, 16, tile=8)
    assert got.shape == (8, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-2)


# ----------------------------------------------- ops padding / divisor fix

def test_largest_divisor_never_returns_non_divisor():
    # seed bug: total=80, multiple_of=32 → returned 32, which 80 % 32 != 0
    assert ops._largest_divisor(80, 512, multiple_of=32) is None
    assert ops._largest_divisor(96, 512, multiple_of=16) == 96
    assert ops._largest_divisor(7, 256) == 7


def test_block_and_pad_covers_awkward_axes():
    for total, target, mult in [(80, 512, 32), (997, 256, 1), (7, 256, 1),
                                (300, 256, 1), (96, 512, 16)]:
        block, padded = ops._block_and_pad(total, target, mult)
        assert padded >= total and padded % block == 0
        # one full-extent block (always tileable), or aligned blocks
        assert (block == padded == total <= target
                or block % mult == 0 and block <= max(target, mult))


def test_ops_wrappers_survive_padding_shapes(rng):
    """Shapes with no valid block divisor used to trip the shape asserts."""
    k1, k2 = jax.random.split(rng)
    x = jax.random.normal(k1, (97, 96))          # 97 prime tokens
    got = ops.nm_prune(x, None, 8, 32, block_d=64)   # no divisor mult of 32
    want = ref.nm_prune_ref(x, None, 8, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)

    xq = jax.random.randint(k1, (33, 80), -127, 128).astype(jnp.int8)
    wq = jax.random.randint(k2, (80, 130), -127, 128).astype(jnp.int8)
    ws = jax.random.uniform(k2, (130,)) * 0.02
    got = ops.w8a8_matmul(xq, wq, jnp.float32(0.01), ws)
    want = ref.w8a8_matmul_ref(xq, wq, jnp.float32(0.01), ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


# ----------------------------------------------------------- dispatch layer

from repro.analysis.jaxpr_utils import (  # noqa: E402
    count_pallas_calls as _count_pallas_calls)


def test_sparse_linear_lowers_to_single_pallas_call(rng):
    """ISSUE-1 acceptance: with use_pallas_kernels=True a per-token sparse
    projection is ONE fused pallas_call — no separate nm_prune pass."""
    k1, k2 = jax.random.split(rng)
    x = jax.random.normal(k1, (32, 128))
    p = {"w": jax.random.normal(k2, (128, 64))}
    pol = _policy(8, 16, use_pallas_kernels=True)

    fn = lambda x, w: sparse_linear(x, {"w": w}, "down_proj", pol, "prefill")
    closed = jax.make_jaxpr(fn)(x, p["w"])
    assert _count_pallas_calls(closed.jaxpr) == 1

    # jnp oracle path stays pallas-free
    pol_jnp = _policy(8, 16)
    fn2 = lambda x, w: sparse_linear(x, {"w": w}, "down_proj", pol_jnp,
                                     "prefill")
    assert _count_pallas_calls(jax.make_jaxpr(fn2)(x, p["w"]).jaxpr) == 0


def test_quantized_sparse_linear_single_pallas_call(rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    x = jax.random.normal(k1, (16, 64))
    w = jax.random.normal(k2, (64, 32))
    wq, w_scale = quant.quantize_weight_per_channel(w)
    p = {"wq": wq, "w_scale": w_scale,
         "smooth": jax.random.uniform(k3, (64,)) + 0.5,
         "act_scale": jnp.float32(0.05)}
    pol = _policy(4, 8, use_pallas_kernels=True)
    fn = lambda x: sparse_linear(x, p, "q_proj", pol, "prefill")
    assert _count_pallas_calls(jax.make_jaxpr(fn)(x).jaxpr) == 1


@pytest.mark.parametrize("tile_consensus", [False, True])
def test_sparse_matmul_dispatch_parity(tile_consensus, rng):
    k1, k2 = jax.random.split(rng)
    x = jax.random.normal(k1, (64, 128))
    w = jax.random.normal(k2, (128, 96))
    pol = _policy(4, 8, tile_consensus=tile_consensus, tile_size=32)
    want = sparse_matmul(x, w, None, pol)
    got = sparse_matmul(x, w, None, pol.with_(use_pallas_kernels=True))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


def test_nm_spmm_consensus_tile_is_semantic(rng):
    """Token counts not divisible by tile_size must not shrink the
    consensus tile (regression: bt=150 divisor vs the oracle's padded
    256-token tiles silently changed which tokens vote in each pool)."""
    k1, k2 = jax.random.split(rng)
    x = jax.random.normal(k1, (300, 64))
    w = jax.random.normal(k2, (64, 32))
    pol = _policy(2, 4, tile_consensus=True, tile_size=256)
    want = sparse_matmul(x, w, None, pol)
    got = sparse_matmul(x, w, None, pol.with_(use_pallas_kernels=True))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


def test_tile_consensus_honors_layer_flag(rng):
    """tile_consensus + layer_flag: flagged-off layers must stay dense,
    and with kernels on the flag path lowers to the kernel (one
    pallas_call, in the pruned branch of the flag's ``lax.cond``)."""
    k1, k2 = jax.random.split(rng)
    x = jax.random.normal(k1, (32, 64))
    p = {"w": jax.random.normal(k2, (64, 32))}
    pol = _policy(2, 4, tile_consensus=True, tile_size=16,
                  use_pallas_kernels=True)
    dense = x @ p["w"]
    sparse = sparse_matmul(x, p["w"], None,
                           pol.with_(use_pallas_kernels=False))
    got_off = sparse_linear(x, p, "down_proj", pol, "prefill",
                            layer_flag=jnp.array(False))
    got_on = sparse_linear(x, p, "down_proj", pol, "prefill",
                           layer_flag=jnp.array(True))
    np.testing.assert_allclose(np.asarray(got_off), np.asarray(dense),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_on), np.asarray(sparse),
                               rtol=1e-6, atol=1e-6)
    fn = lambda x: sparse_linear(x, p, "down_proj", pol, "prefill",
                                 layer_flag=jnp.array(True))
    assert _count_pallas_calls(jax.make_jaxpr(fn)(x).jaxpr) == 1


def test_sparse_linear_pallas_matches_jnp_end_to_end(rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    x = jax.random.normal(k1, (33, 96))          # padding path too
    p = {"w": jax.random.normal(k2, (96, 100)),
         "b": jax.random.normal(k3, (100,)),
         SCALE_KEY: jax.random.uniform(k3, (96,)) + 0.5}
    pol = _policy(8, 16)
    want = sparse_linear(x, p, "down_proj", pol, "prefill")
    got = sparse_linear(x, p, "down_proj",
                        pol.with_(use_pallas_kernels=True), "prefill")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


def test_quantized_sparse_linear_pallas_matches_jnp(rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    x = jax.random.normal(k1, (16, 64))
    w = jax.random.normal(k2, (64, 32))
    wq, w_scale = quant.quantize_weight_per_channel(w)
    base = {"wq": wq, "w_scale": w_scale,
            "smooth": jax.random.uniform(k3, (64,)) + 0.5,
            "act_scale": jnp.float32(0.05),
            SCALE_KEY: jax.random.uniform(k3, (64,)) + 0.5}
    pol = _policy(4, 8)
    for extra in ({}, {"per_token": True}):
        p = dict(base, **extra)
        want = sparse_linear(x, p, "q_proj", pol, "prefill")
        got = sparse_linear(x, p, "q_proj",
                            pol.with_(use_pallas_kernels=True), "prefill")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_layer_flag_models_fall_back_to_mask_select(rng):
    """Scan-stacked models pick pruned vs dense per layer.  With kernels on
    the flag is a ``lax.cond`` between the fused kernel and the dense
    projection; it must agree with the jnp mask-select form."""
    k1, k2 = jax.random.split(rng)
    x = jax.random.normal(k1, (16, 64))
    p = {"w": jax.random.normal(k2, (64, 32))}
    pol = _policy(4, 8, use_pallas_kernels=True)
    for flag in (jnp.array(True), jnp.array(False)):
        got = sparse_linear(x, p, "down_proj", pol, "prefill",
                            layer_flag=flag)
        want = sparse_linear(x, p, "down_proj", _policy(4, 8), "prefill",
                             layer_flag=flag)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        # the flag path lowers to the kernel: one pallas_call
        fn = lambda x: sparse_linear(x, p, "down_proj", pol, "prefill",
                                     layer_flag=flag)
        assert _count_pallas_calls(jax.make_jaxpr(fn)(x).jaxpr) == 1
