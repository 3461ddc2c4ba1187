"""Partition rules: divisibility guarantees + lowering on a tiny mesh."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ARCH_IDS, get_config, get_smoke_config
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh_auto
from repro.models import build_model


def _mesh(shape=(2, 4), axes=("data", "model")):
    # tests run on 1 device; abstract mesh via make_mesh requires devices —
    # use the AbstractMesh to validate specs without hardware
    return jax.sharding.AbstractMesh(shape, axes)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_divisible(arch):
    """Every sharded dim must divide the mesh axis — for the FULL configs
    on the production 16×16 mesh (the dry-run contract)."""
    cfg = get_config(arch)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
    specs = shd.param_specs(params, mesh, cfg.n_experts)

    def check(path, leaf, spec):
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            assert dim % size == 0, (path, leaf.shape, spec)

    jax.tree_util.tree_map_with_path(check, params, specs)


def test_known_rules():
    mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
    cfg = get_config("qwen2_5_32b")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = shd.param_specs(params, mesh, 0)
    blk = specs["periods"]["b0"]
    # column-parallel q: (L, d, qd) → model on last dim
    assert tuple(blk["q_proj"]["w"])[-1] == "model"
    # row-parallel o: model on d_in
    assert tuple(blk["o_proj"]["w"])[-2] == "model"
    assert tuple(blk["mlp"]["down_proj"]["w"])[-2] == "model"
    # norms replicated
    assert all(s is None for s in tuple(blk["ln1"]["w"]))
    # vocab sharding on embed + lm_head
    assert "model" in tuple(specs["embed"]["w"])
    assert tuple(specs["lm_head"]["w"])[-1] == "model"


def test_whisper_odd_vocab_replicates():
    """vocab 51865 is not divisible by 16 → embedding must not shard it."""
    mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
    cfg = get_config("whisper_medium")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = shd.param_specs(params, mesh, 0)
    emb_spec = tuple(specs["embed"]["w"])
    assert emb_spec[0] is None  # 51865 % 16 != 0
    # d_model 1024 divisible → second dim may shard
    assert emb_spec[1] == "model"


def test_batch_and_cache_specs():
    mesh = jax.sharding.AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    assert shd.data_axes(mesh) == ("pod", "data")
    assert tuple(shd.batch_spec(mesh))[0] == ("pod", "data")

    cfg = get_config("granite_34b")  # kv_heads=1 → heads must NOT shard
    model = build_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(128, 1024))
    cspecs = shd.cache_specs(cache, cfg, mesh)
    k_spec = tuple(cspecs["periods"]["b0"]["k"])
    assert k_spec[-2] is None          # 1 kv head — replicate heads
    assert k_spec[-4] == ("pod", "data")


@pytest.mark.slow
def test_smoke_cell_lowers_on_multidevice_mesh():
    """End-to-end pjit lowering of a smoke config on an 8-way mesh shape
    (validates sharding rules agree with GSPMD propagation)."""
    if len(jax.devices()) < 2:
        mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    from repro.launch.cells import build_cell
    mesh_c = make_mesh_auto((1, 1), ("data", "model"))
    cell = build_cell("llama31_8b", "train_4k", mesh_c,
                      cfg=dataclasses.replace(get_smoke_config("llama31_8b")))
    lowered = cell.lower(mesh_c)
    compiled = lowered.compile()
    assert compiled.cost_analysis() is not None
