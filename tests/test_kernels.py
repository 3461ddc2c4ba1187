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
                                 window=w, interpret=True)
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


# The tiled walk: q tiles of a KV group's heads, KV tiles of 32 pages.
# Tables hold 80 entries of 16 positions, so the walk has three KV tiles
# of 512 positions and the last is partial (80 is not a multiple of 32).
_BS, _MB = 16, 80


def _tiled_inputs(rng, hkv, kv_lens, hd=16, dtype=jnp.float32):
    """Pool + per-row tables allocated through each row's kv_len, from a
    permutation of the pool (block 0 left free)."""
    need = [-(-k // _BS) for k in kv_lens]
    nb = sum(need) + 1
    k1, k2 = jax.random.split(rng)
    kp = jax.random.normal(k1, (nb, hkv, _BS, hd), dtype=dtype)
    vp = jax.random.normal(k2, (nb, hkv, _BS, hd), dtype=dtype)
    perm = np.random.default_rng(11).permutation(np.arange(1, nb))
    tab = np.full((len(kv_lens), _MB), -1, np.int32)
    for b, n in enumerate(need):
        tab[b, :n] = perm[sum(need[:b]):sum(need[:b]) + n]
    return kp, vp, tab


# (g, Hkv, T, q offsets, kv lens): q tiles split wherever g * T > 1024;
# chunks end mid-page and mid-tile; offsets sit off page and tile edges
_GROUP_CASES = {
    "g1_two_rows": (1, 2, 8, [300, 517], [308, 525]),
    "g5_two_q_tiles": (5, 1, 256, [600], [856]),
    "g7_qwen2_ratio": (7, 2, 256, [0, 700], [256, 956]),
    "g16_chatglm3_ratio": (16, 1, 128, [900], [1028]),
    "g48_granite_mqa": (48, 1, 32, [1000, 37], [1032, 69]),
    "g48_padded_tail": (48, 1, 24, [100], [124]),  # 24 = 16 + a padded 8
}


@pytest.mark.parametrize("case", sorted(_GROUP_CASES))
def test_paged_attention_kernel_group_parity(case, rng):
    """One KV head's group of query heads per grid step, at GQA ratios 1
    to 48, against the gather oracle: every query head must still read
    its own group's KV head, and every query row its own position."""
    from repro.models.attention import paged_attention
    g, hkv, t, qoff, kvl = _GROUP_CASES[case]
    kp, vp, tab = _tiled_inputs(rng, hkv, kvl)
    q = jax.random.normal(jax.random.fold_in(rng, g), (len(kvl), t,
                                                       g * hkv, 16))
    kw = dict(causal=True, q_offset=jnp.asarray(qoff, jnp.int32),
              kv_len=jnp.asarray(kvl, jnp.int32), chunk=64)
    got = paged_attention(q, kp, vp, jnp.asarray(tab), use_kernel=True,
                          interpret=True, **kw)
    want = paged_attention(q, kp, vp, jnp.asarray(tab), use_kernel=False,
                           **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_kernel_decode_idle_slot(rng):
    """Decode rows across the tiled walk, one of them an idle slot (kv_len
    0, all -1 table row, stale position): the idle row comes out zero and
    the live rows match the oracle."""
    from repro.models.attention import paged_attention
    kvl = [1100, 0, 517]
    kp, vp, tab = _tiled_inputs(rng, 2, kvl)
    q = jax.random.normal(jax.random.fold_in(rng, 5), (3, 1, 14, 16))
    kw = dict(causal=False, q_offset=jnp.asarray([1099, 900, 516]),
              kv_len=jnp.asarray(kvl, jnp.int32), chunk=64)
    got = paged_attention(q, kp, vp, jnp.asarray(tab), use_kernel=True,
                          interpret=True, **kw)
    want = paged_attention(q, kp, vp, jnp.asarray(tab), use_kernel=False,
                           **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(got[1]).any()


def _masked_reference(q, kp, vp, tab, qoff, kvl, causal):
    """float64 attention over each row's allocated pages only: a position
    counts iff its page is allocated, it lies below kv_len and (causal)
    at or before the query."""
    q, kp, vp = (np.asarray(a, np.float64) for a in (q, kp, vp))
    b, t, hq, hd = q.shape
    hkv = kp.shape[1]
    out = np.zeros_like(q)
    pos = np.arange(tab.shape[1] * _BS)
    for bi in range(b):
        page = tab[bi][pos // _BS]
        k = kp[np.maximum(page, 0), :, pos % _BS]          # (S, Hkv, hd)
        v = vp[np.maximum(page, 0), :, pos % _BS]
        for i in range(t):
            ok = (page >= 0) & (pos < kvl[bi])
            if causal:
                ok &= pos <= qoff[bi] + i
            if not ok.any():
                continue
            for h in range(hq):
                s = k[ok, h // (hq // hkv)] @ q[bi, i, h] * hd**-0.5
                p = np.exp(s - s.max())
                out[bi, i, h] = p @ v[ok, h // (hq // hkv)] / p.sum()
    return out


@pytest.mark.parametrize("causal", [True, False])
def test_paged_attention_kernel_table_holes(causal, rng):
    """Unallocated (-1) pages below kv_len, inside and across KV tiles,
    are masked and their V rows never reach the output — even when the
    block they park on holds NaN.  (The gather oracle zero-fills such
    pages instead of masking them; serving tables never have them, so
    the reference here is a plain masked attention.)"""
    from repro.kernels.paged_attention import paged_attention_pallas
    kvl = [1100, 600]
    kp, vp, tab = _tiled_inputs(rng, 2, kvl)
    tab[0, [3, 31, 32, 50]] = -1         # in tile 0, both sides of 0|1
    tab[1, 0] = -1                       # the first page
    kp, vp = np.array(kp), np.array(vp)
    kp[0], vp[0] = np.nan, np.nan        # where -1 pages park
    t = 1 if not causal else 64
    qoff = [1100 - t, 600 - t] if causal else [1099, 599]
    q = np.asarray(jax.random.normal(jax.random.fold_in(rng, 9),
                                     (2, t, 6, 16)))
    got = paged_attention_pallas(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tab),
        jnp.asarray(qoff, jnp.int32), jnp.asarray(kvl, jnp.int32),
        causal=causal, interpret=True)
    want = _masked_reference(q, kp, vp, tab, qoff, kvl, causal)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("g,t", [(7, 512), (7, 1), (48, 512), (1, 2048)])
def test_paged_attention_grid_walks_kv_heads_and_page_tiles(g, t):
    """The call's grid is (B, Hkv, ceil(T/bq), ceil(max_blocks/P)): one
    step per KV head and tile of pages, never per query head or page."""
    from repro.analysis.jaxpr_utils import pallas_call_eqns
    from repro.kernels.paged_attention import (paged_attention_pallas,
                                               paged_tile)
    b, hkv, hd, bs, mb = 2, 4, 128, 16, 512
    q = jax.ShapeDtypeStruct((b, t, g * hkv, hd), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((b * mb + 1, hkv, bs, hd), jnp.bfloat16)
    tab = jax.ShapeDtypeStruct((b, mb), jnp.int32)
    vec = jax.ShapeDtypeStruct((b,), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda *a: paged_attention_pallas(
        *a, causal=t > 1, interpret=True))(q, pool, pool, tab, vec, vec)
    (eqn,) = list(pallas_call_eqns(jaxpr.jaxpr))
    bq, pages = paged_tile(g, t, bs, mb, jnp.bfloat16)
    grid = tuple(eqn.params["grid_mapping"].grid)
    assert grid == (b, hkv, -(-t // bq), -(-mb // pages))
    assert g * bq <= max(1024, 16 * g) and pages * bs == 512


@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0),
                                           (True, 700)])
def test_paged_tile_walk_matches_brute_force(causal, window):
    """The host replay of the tile walk (``paged_tile_walk``, behind the
    ``paged.attention_tiles_*`` counters) agrees with a position-by-
    position count of the tiles that hold any unmasked score."""
    from repro.kernels.paged_attention import paged_tile_walk
    bq, pages, bs, mb = 64, 4, 16, 40          # 64-position KV tiles
    tk, nk = pages * bs, -(-mb // pages)
    for t, q_off, kv_len in [(256, 0, 256), (256, 300, 556), (1, 70, 71),
                             (1, 5, 0), (129, 511, 640)]:
        nq = -(-t // bq)
        want = 0
        for qi in range(nq):
            q = np.arange(q_off + qi * bq, q_off + (qi + 1) * bq)[:, None]
            for ki in range(nk):
                k = np.arange(ki * tk, (ki + 1) * tk)[None, :]
                ok = k < kv_len
                if causal:
                    ok = ok & (k <= q)
                if window:
                    ok = ok & (k > q - window)
                want += bool(ok.any())
        got = paged_tile_walk(kv_len, q_off, t=t, bq=bq, pages=pages,
                              block_size=bs, max_blocks=mb, causal=causal,
                              window=window)
        assert got == (nq * nk, want), (t, q_off, kv_len)


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
