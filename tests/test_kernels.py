"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SHAPES = [(32, 64, 64), (64, 128, 96), (128, 256, 128), (256, 512, 256)]
PATTERNS = [(2, 4), (4, 8), (8, 16)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("t,d,no", SHAPES)
@pytest.mark.parametrize("n,m", PATTERNS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_nm_prune_kernel(t, d, no, n, m, dtype, rng):
    k1, k2 = jax.random.split(rng)
    x = jax.random.normal(k1, (t, d), dtype=dtype)
    scale = jax.random.uniform(k2, (d,)) + 0.5
    got = ops.nm_prune(x, scale, n, m)
    want = ref.nm_prune_ref(x, scale, n, m)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
    # structural check: valid N:M sparsity
    groups = np.asarray(got != 0, np.int32).reshape(t, d // m, m).sum(-1)
    assert (groups <= n).all()


@pytest.mark.parametrize("t,d,no", SHAPES)
@pytest.mark.parametrize("n,m", PATTERNS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_nm_spmm_kernel(t, d, no, n, m, dtype, rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    x = jax.random.normal(k1, (t, d), dtype=dtype)
    w = jax.random.normal(k2, (d, no), dtype=dtype)
    scale = jax.random.uniform(k3, (d,)) + 0.5
    tile = min(32, t)
    got = ops.nm_spmm(x, w, scale, n, m, tile=tile)
    want = ref.nm_spmm_ref(x, w, scale, n, m, tile=tile)
    tol = dict(rtol=5e-2, atol=5e-1) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("t,d,no", SHAPES)
def test_w8a8_kernel(t, d, no, rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    xq = jax.random.randint(k1, (t, d), -127, 128).astype(jnp.int8)
    wq = jax.random.randint(k2, (d, no), -127, 128).astype(jnp.int8)
    xs = jnp.float32(0.013)
    ws = jax.random.uniform(k3, (no,)) * 0.02
    got = ops.w8a8_matmul(xq, wq, xs, ws)
    want = ref.w8a8_matmul_ref(xq, wq, xs, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_nm_prune_no_scale_matches_naive(rng):
    x = jax.random.normal(rng, (64, 128))
    got = ops.nm_prune(x, None, 2, 4)
    want = ref.nm_prune_ref(x, None, 2, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-7)


def test_kernel_batched_inputs(rng):
    x = jax.random.normal(rng, (2, 16, 128))
    got = ops.nm_prune(x, None, 4, 8)
    want = ref.nm_prune_ref(x.reshape(32, 128), None, 4, 8).reshape(2, 16, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-7)


@pytest.mark.parametrize("b,h,t,s,d,causal", [
    (2, 4, 64, 64, 32, True),
    (1, 2, 128, 128, 64, True),
    (2, 2, 64, 128, 32, False),
    (1, 8, 256, 256, 128, True),
])
def test_flash_attention_kernel(b, h, t, s, d, causal, rng):
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.ref import flash_attention_ref
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (b, h, t, d))
    k = jax.random.normal(k2, (b, h, s, d))
    v = jax.random.normal(k3, (b, h, s, d))
    got = flash_attention_pallas(q, k, v, causal=causal, block_q=32,
                                 block_k=32)
    want = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t,d,w", [(128, 32, 32), (256, 64, 64),
                                   (128, 32, 96)])
def test_flash_attention_sliding_window(t, d, w, rng):
    """SWA band variant (mixtral/recurrentgemma prefill) vs oracle —
    off-band KV blocks are skipped at block granularity (O(T·window))."""
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.ref import flash_attention_ref
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (1, 2, t, d))
    k = jax.random.normal(k2, (1, 2, t, d))
    v = jax.random.normal(k3, (1, 2, t, d))
    got = flash_attention_pallas(q, k, v, causal=True, window=w,
                                 block_q=32, block_k=32)
    want = flash_attention_ref(q, k, v, causal=True, window=w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", DTYPES)
def test_flash_attention_dtypes(dtype, rng):
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.ref import flash_attention_ref
    q = jax.random.normal(rng, (1, 2, 64, 32), dtype=dtype)
    got = flash_attention_pallas(q, q, q, block_q=32, block_k=32)
    want = flash_attention_ref(q, q, q)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


# -------------------------------------------------------- paged attention

def _paged_inputs(rng, nb=10, bs=8, mb=6, B=3, Hkv=2, hd=16,
                  dtype=jnp.float32):
    """Pool + permuted per-row block tables with -1 tails (physical block 0
    left unreferenced so the fencing tests can poison it)."""
    k1, k2 = jax.random.split(rng)
    kp = jax.random.normal(k1, (nb, Hkv, bs, hd), dtype=dtype)
    vp = jax.random.normal(k2, (nb, Hkv, bs, hd), dtype=dtype)
    tab = np.full((B, mb), -1, np.int32)
    perm = np.random.default_rng(7).permutation(np.arange(1, nb))
    tab[0, :3] = perm[:3]
    tab[1, :5] = perm[3:8]
    tab[2, :2] = perm[8:10][:2] if len(perm) > 9 else perm[-2:]
    return kp, vp, jnp.asarray(tab)


@pytest.mark.parametrize("dtype", DTYPES)
def test_paged_attention_kernel_prefill_parity(dtype, rng):
    """Chunked-prefill shape (scalar q_offset, causal) through the
    in-kernel block-table walk vs the jnp gather oracle."""
    from repro.models.attention import paged_attention
    kp, vp, tab = _paged_inputs(rng, dtype=dtype)
    q = jax.random.normal(jax.random.fold_in(rng, 1), (3, 8, 4, 16),
                          dtype=dtype)
    kvl = jnp.asarray([21, 38, 13], jnp.int32)
    kw = dict(causal=True, q_offset=jnp.asarray(13, jnp.int32),
              kv_len=kvl, chunk=32)
    got = paged_attention(q, kp, vp, tab, use_kernel=True, interpret=True,
                          **kw)
    want = paged_attention(q, kp, vp, tab, use_kernel=False, **kw)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_paged_attention_kernel_decode_parity(rng):
    """Vector-position decode (per-row q_offset / kv_len, non-causal
    single-query) through the kernel vs the gather oracle."""
    from repro.models.attention import paged_attention
    kp, vp, tab = _paged_inputs(rng)
    q = jax.random.normal(jax.random.fold_in(rng, 2), (3, 1, 4, 16))
    posv = jnp.asarray([20, 37, 10], jnp.int32)
    kw = dict(causal=False, q_offset=posv, kv_len=posv + 1, chunk=32)
    got = paged_attention(q, kp, vp, tab, use_kernel=True, interpret=True,
                          **kw)
    want = paged_attention(q, kp, vp, tab, use_kernel=False, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_kernel_window_parity(rng):
    """The kernel's sliding-window band (the hook for paging SWA caches —
    not yet reachable through the model dispatch, which keeps windowed
    paged shapes on the oracle) vs the gather oracle with the same
    window."""
    from repro.kernels.paged_attention import paged_attention_pallas
    from repro.models.attention import paged_attention
    kp, vp, tab = _paged_inputs(rng)
    q = jax.random.normal(jax.random.fold_in(rng, 3), (3, 8, 4, 16))
    qoff = jnp.asarray([13, 30, 5], jnp.int32)
    kvl = jnp.asarray([21, 38, 13], jnp.int32)
    w = 6
    got = paged_attention_pallas(q, kp, vp, tab, qoff, kvl, causal=True,
                                 window=w, block_q=4, interpret=True)
    want = paged_attention(q, kp, vp, tab, causal=True, window=w,
                           q_offset=qoff, kv_len=kvl, chunk=32,
                           use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_kernel_gqa_and_single_head(rng):
    from repro.models.attention import paged_attention
    for hq in (2, 8):                       # G = 1 and G = 4
        kp, vp, tab = _paged_inputs(rng)
        q = jax.random.normal(jax.random.fold_in(rng, hq), (3, 4, hq, 16))
        kvl = jnp.asarray([17, 33, 9], jnp.int32)
        kw = dict(causal=True, q_offset=jnp.asarray(5, jnp.int32),
                  kv_len=kvl, chunk=32)
        got = paged_attention(q, kp, vp, tab, use_kernel=True,
                              interpret=True, **kw)
        want = paged_attention(q, kp, vp, tab, use_kernel=False, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=f"Hq={hq}")


# --------------------------------------------- windowed flash self-attention

from repro.analysis.jaxpr_utils import (  # noqa: E402
    has_pallas_call as _has_pallas_call)


@pytest.mark.parametrize("t,d,w", [(128, 32, 32), (256, 64, 96)])
def test_windowed_self_attention_routes_through_flash(t, d, w, rng):
    """ISSUE-4 satellite: ``attention(impl="flash")`` with a sliding window
    used to fall back to the jnp scans even though the kernel implements
    windowed masking + KV-block skipping — the windowed T == S case must
    now lower a pallas_call and match the ``_banded_attention`` path
    (``impl="chunked"`` routes there for exactly this shape)."""
    from repro.models.attention import attention
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (2, t, 4, d))
    k = jax.random.normal(k2, (2, t, 2, d))      # GQA
    v = jax.random.normal(k3, (2, t, 2, d))

    flash = lambda q, k, v: attention(q, k, v, causal=True, window=w,
                                      impl="flash")
    banded = lambda q, k, v: attention(q, k, v, causal=True, window=w,
                                       impl="chunked", chunk=64)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(banded(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    # the windowed branch is no longer dead code …
    assert _has_pallas_call(jax.make_jaxpr(flash)(q, k, v).jaxpr)
    # … and the banded jnp oracle stays kernel-free
    assert not _has_pallas_call(jax.make_jaxpr(banded)(q, k, v).jaxpr)


def test_nm_spmm_flop_advantage_structure(rng):
    """The compacted contraction must touch exactly D·n/m weight rows/tile."""
    from repro.core import nm as nmod
    from repro.core import scoring
    x = jax.random.normal(rng, (32, 64))
    s = scoring.score_activations(x, None)
    chans = nmod.tile_consensus_channels(s, 2, 4)
    assert chans.shape == (16, 2)        # D/m groups × n survivors
    xc = nmod.compact_columns(x, chans)
    assert xc.shape == (32, 32)          # D·n/m = 64·2/4
