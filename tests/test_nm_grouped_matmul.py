"""Routed experts at smoke size: the grouped N:M kernel against its jnp
oracle (``_maybe_prune`` + ``ragged_dot``), the layer's q/gate skip flag
inside the experts, the per-head q/k RMSNorm against a hand-written one,
and the host's routed-row counters."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.qwen3_30b_a3b import smoke
from repro.core import nm
from repro.core.policy import paper_policy
from repro.kernels import ops
from repro.kernels.nm_grouped_matmul import group_metadata
from repro.models import build_model, moe

POL = paper_policy(8, 16)


def _oracle(x, w, sizes, prune):
    xp = moe._maybe_prune(x, "gate_proj" if prune else "up_proj", POL,
                          "prefill")
    return jax.lax.ragged_dot(xp, w, sizes,
                              precision=jax.lax.Precision.HIGHEST)


# (rows, d_in, d_out, group sizes, row tile): empty groups first, last and
# between; boundaries inside a row tile; rows not a multiple of the tile;
# rows past the last group (the padded tail of a chunk)
CASES = {
    "empty_groups": (48, 64, 32, [0, 5, 0, 12, 3, 0, 10, 18, 0], 16),
    "inside_tiles": (64, 128, 48, [7, 9, 1, 30, 17], 16),
    "ragged_rows": (37, 64, 40, [10, 0, 20, 7], 16),
    "unfilled_tail": (40, 64, 32, [3, 0, 11, 6], 8),
    "one_group": (24, 32, 16, [24], 8),
}


@pytest.mark.parametrize("prune", [True, False])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_prune_then_ragged_dot(case, prune):
    rows, d, n_out, sizes, bt = CASES[case]
    rng = np.random.default_rng(len(sizes) * rows)
    x = jnp.asarray(rng.standard_normal((rows, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((len(sizes), d, n_out)),
                    jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    got = ops.nm_grouped_matmul(x, w, gs, 8, 16, prune=prune, block_t=bt,
                                block_o=128, block_k=128)
    want = _oracle(x, w, gs, prune)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    assert not np.any(np.asarray(got)[sum(sizes):])


@pytest.mark.parametrize("prune", [True, False])
def test_layer_of_a_stack_reads_as_its_slice(prune):
    """A scan-stacked (L, G, D, N) weight with ``layer`` gives what the
    layer's own (G, D, N) slice gives."""
    rows, d, n_out, sizes, bt = CASES["inside_tiles"]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((rows, d)), jnp.float32)
    stack = jnp.asarray(rng.standard_normal((3, len(sizes), d, n_out)),
                        jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    kw = dict(prune=prune, block_t=bt, block_o=128, block_k=128)
    for i in range(3):
        got = ops.nm_grouped_matmul(x, stack, gs, 8, 16, layer=jnp.int32(i),
                                    **kw)
        want = ops.nm_grouped_matmul(x, stack[i], gs, 8, 16, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_selection_is_bit_identical_with_ties():
    """Integer activations full of ties and integer weights: every product
    is exact, so any channel kept differently shows."""
    rng = np.random.default_rng(0)
    rows, d, n_out, sizes = 40, 64, 24, [9, 0, 17, 14]
    x = jnp.asarray(rng.integers(-2, 3, (rows, d)), jnp.float32)
    w = jnp.asarray(rng.integers(-3, 4, (len(sizes), d, n_out)),
                    jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    got = ops.nm_grouped_matmul(x, w, gs, 8, 16, block_t=8, block_k=32)
    kept = nm.apply_nm(x, jnp.abs(x), 8, 16)
    want = jax.lax.ragged_dot(kept, w, gs,
                              precision=jax.lax.Precision.HIGHEST)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_bf16_operands_accumulate_in_float32():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((64, 256)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((3, 256, 128)) / 16, jnp.bfloat16)
    gs = jnp.asarray([20, 30, 14], jnp.int32)
    got = ops.nm_grouped_matmul(x, w, gs, 8, 16, block_t=16, block_k=128)
    assert got.dtype == jnp.bfloat16
    want = _oracle(x.astype(jnp.float32), w.astype(jnp.float32), gs, True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("sizes,bt", [([0, 5, 0, 12, 3, 0, 10, 18, 0], 16),
                                      ([7, 9, 1, 30, 17], 16),
                                      ([3, 0, 11, 6], 8), ([0, 0, 8], 8)])
def test_group_metadata_visits_every_row_once(sizes, bt):
    rows = -(-max(sum(sizes), 1) // bt) * bt
    off, gid, tid, nt = (np.asarray(a) for a in group_metadata(
        jnp.asarray(sizes, jnp.int32), rows, bt))
    nt = int(nt[0])
    assert len(gid) == rows // bt + len(sizes) - 1
    covered = np.zeros(rows, int)
    for g, t in zip(gid[:nt], tid[:nt]):
        r = np.arange(t * bt, (t + 1) * bt)
        covered[r[(r >= off[g]) & (r < off[g + 1])]] += 1
    assert covered[:sum(sizes)].tolist() == [1] * sum(sizes)
    assert not covered[sum(sizes):].any()
    # an output tile's visits are consecutive; padding repeats the last
    assert all(np.diff(tid[:nt]) >= 0)
    assert set(gid[nt:]) <= {gid[max(nt - 1, 0)]}
    assert set(tid[nt:]) <= {tid[max(nt - 1, 0)]}


# ------------------------------------------------------- the skip flag

@pytest.mark.parametrize("kernels", [False, True])
def test_routed_gate_honours_the_layer_skip_flag(kernels):
    """Flag False (a skip layer) leaves the routed experts' gate input
    dense, as a policy that never prunes gate_proj does; down stays
    pruned."""
    cfg = smoke()
    model = build_model(dataclasses.replace(cfg, dtype="float32"))
    p = jax.tree_util.tree_map(lambda a: a[0], model.init(
        jax.random.PRNGKey(0))["periods"]["b0"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, cfg.d_model))
    pol = POL.with_(use_pallas_kernels=kernels)
    no_gate = pol.with_(skip_modules=pol.skip_modules + ("gate_proj",))

    def run(policy, flag):
        flags = None if flag is None else {"gate_proj": jnp.asarray(flag)}
        return np.asarray(moe.moe(x, p, policy, "prefill", cfg.top_k,
                                  flags=flags))

    np.testing.assert_allclose(run(pol, False), run(no_gate, None),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(run(pol, True), run(pol, None),
                               rtol=1e-5, atol=1e-5)
    assert np.abs(run(pol, True) - run(pol, False)).max() > 1e-3


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_layer_stack_and_kernels_match_the_ragged_path(phase):
    """The experts as the serving layer scan calls them (the whole stack
    and the layer index, kernels on) against a layer's slice through
    ``ragged_dot`` with kernels off: decode runs the kernel dense."""
    cfg = smoke()
    model = build_model(dataclasses.replace(cfg, dtype="float32"))
    stack = model.init(jax.random.PRNGKey(0))["periods"]["b0"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, cfg.d_model))
    kernels = POL.with_(use_pallas_kernels=True)
    for i in range(cfg.n_layers):
        p = jax.tree_util.tree_map(lambda a: a[i], stack)
        layer = dict(p, experts=stack["experts"], layer=jnp.int32(i))
        got = moe.moe(x, layer, kernels, phase, cfg.top_k)
        want = moe.moe(x, p, POL, phase, cfg.top_k)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_padded_chunk_rows_route_nowhere():
    """Rows at or past chunk_len leave no expert work and read zero; the
    valid rows are what an unpadded call gives."""
    cfg = smoke()
    model = build_model(dataclasses.replace(cfg, dtype="float32"))
    p = jax.tree_util.tree_map(lambda a: a[0], model.init(
        jax.random.PRNGKey(0))["periods"]["b0"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, cfg.d_model))
    pol = POL.with_(use_pallas_kernels=True)
    got = moe.moe(x, p, pol, "prefill", cfg.top_k, chunk_len=jnp.int32(11))
    want = moe.moe(x[:, :11], p, pol, "prefill", cfg.top_k)
    np.testing.assert_allclose(np.asarray(got[:, :11]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[:, 11:]).any()


# ------------------------------------------------------- q/k RMSNorm

def test_qk_norm_is_a_per_head_rmsnorm_before_rope(monkeypatch):
    from repro.core.policy import DENSE
    from repro.models import common, transformer
    cfg = dataclasses.replace(smoke(), dtype="float32", scan_layers=False,
                              n_layers=1)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    layer = jax.tree_util.tree_map(lambda a: a[0], params["periods"]["b0"])
    assert layer["q_norm"]["w"].shape == (cfg.head_dim,)
    assert layer["k_norm"]["w"].shape == (cfg.head_dim,)
    # non-trivial norm weights, so a missing multiply shows
    for i, name in enumerate(("q_norm", "k_norm")):
        params["periods"]["b0"][name]["w"] = jax.random.uniform(
            jax.random.PRNGKey(i + 1), (1, cfg.head_dim), minval=0.5,
            maxval=1.5)
    layer = jax.tree_util.tree_map(lambda a: a[0], params["periods"]["b0"])
    seen = []
    rope = transformer._apply_rope

    def recording(cfg_, x, *a):
        seen.append(np.asarray(x))
        return rope(cfg_, x, *a)

    monkeypatch.setattr(transformer, "_apply_rope", recording)
    tokens = jnp.asarray([[3, 17, 5, 250, 9, 1]])
    model.forward(params, {"tokens": tokens}, policy=DENSE, phase="prefill")
    x = common.norm_apply(common.embed(tokens, params["embed"]),
                          layer["ln1"], cfg.norm)

    def by_hand(w, heads, norm_w):
        y = np.asarray(x @ w).reshape(1, 6, heads, cfg.head_dim)
        ms = (y * y).mean(-1, keepdims=True)
        return y / np.sqrt(ms + 1e-6) * np.asarray(norm_w)

    np.testing.assert_allclose(
        seen[0], by_hand(layer["q_proj"]["w"], cfg.n_heads,
                         layer["q_norm"]["w"]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        seen[1], by_hand(layer["k_proj"]["w"], cfg.n_kv_heads,
                         layer["k_norm"]["w"]), rtol=1e-5, atol=1e-5)


def test_other_architectures_have_no_qk_norm():
    from repro.configs.base import get_smoke_config
    for arch in ("qwen2_7b", "llama31_8b", "mixtral_8x7b"):
        shapes = jax.eval_shape(build_model(get_smoke_config(arch)).init,
                                jax.random.PRNGKey(0))
        paths = [jax.tree_util.keystr(k) for k, _ in
                 jax.tree_util.tree_flatten_with_path(shapes)[0]]
        assert not [p for p in paths if "q_norm" in p or "k_norm" in p]


# ------------------------------------------------------- host counters

def test_routed_rows_counted_from_the_plan():
    from repro.serve.api import Engine, EngineConfig
    from repro.serve.continuous import ContinuousConfig
    cfg = dataclasses.replace(smoke(), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine.from_config(model, EngineConfig(serving=ContinuousConfig(
        max_seq=64, num_slots=2, chunk_size=16, block_size=8, paged=True)),
        policy=POL)
    for n in (21, 9):
        eng.submit(np.arange(n, dtype=np.int32) % cfg.vocab_size,
                   max_new_tokens=3)
    eng.run(params)
    rows = eng.metrics.moe.routed_rows
    assert rows["prefill"] == (21 + 9) * cfg.top_k
    assert rows["decode"] == 2 * 2 * cfg.top_k     # 2 decode steps each
    assert eng.metrics.to_dict()["moe"]["routed_rows"] == rows
