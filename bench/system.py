"""The system under test, built from a configuration file: the program's
model at the file's sizes, its Amber policy, the seeded weights with the
program's own offline Amber scales, and a serving engine through
``repro.serve.api`` shaped as the traffic mix says."""
from __future__ import annotations

import dataclasses

import jax

from bench import weights

# configuration-file key -> the program's ModelConfig field
_SIZES = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "attention_bias": "qkv_bias",
}


def model_config(conf: dict):
    """The program's config for ``program_arch`` with every size the file
    states; the file's values win over the program's."""
    from repro.configs.base import get_config
    cfg = get_config(conf["program_arch"])
    sizes = {field: conf[key] for key, field in _SIZES.items()}
    amber = conf["amber"]
    return dataclasses.replace(
        cfg, head_dim=0, dtype=conf["torch_dtype"],
        qgate_skip_layers=tuple(amber["qgate_skip_layers"]), **sizes)


def policy(conf: dict):
    from repro.core.policy import paper_policy
    a = conf["amber"]
    return paper_policy(a["n"], a["m"], tuple(a["qgate_skip_layers"]),
                        score_mode=a["score_mode"],
                        use_pallas_kernels=a["kernels"])


def build(conf: dict, seed: int):
    """(model, params): the benchmark's weights in the program's layout,
    then the program's offline Amber scales, one layer at a time so the
    float32 temporaries of the widest matrices fit beside the weights."""
    from repro.core.pruner import SCALE_KEY, precompute_scales
    from repro.models import build_model
    cfg = model_config(conf)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = weights.build_like(shapes, seed)
    pol = policy(conf)

    def scales_only(tree):
        out = {}
        for k, v in tree.items():
            if k == SCALE_KEY:
                out[k] = v
            elif isinstance(v, dict) and (sub := scales_only(v)):
                out[k] = sub
        return out

    @jax.jit
    def layer_scales(stack, i):
        one = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, i, 1), stack)
        return scales_only(precompute_scales(one, pol))

    def merge(tree, scales):
        return {k: merge(v, scales[k]) if k in scales else v
                for k, v in tree.items()} | (
            {SCALE_KEY: scales[SCALE_KEY]} if SCALE_KEY in scales else {})

    stack = params["periods"]
    per = [layer_scales(stack, i) for i in range(cfg.n_layers)]
    params["periods"] = merge(stack, jax.tree_util.tree_map(
        lambda *xs: jax.numpy.concatenate(xs), *per))
    jax.block_until_ready(params)
    return model, params, pol


def engine(model, pol, shape: dict):
    """A serving engine for the mix's ``engine`` block."""
    from repro.serve.api import Engine, EngineConfig
    from repro.serve.continuous import ContinuousConfig
    return Engine.from_config(model, EngineConfig(
        dp=shape.get("dp", 1), serving=ContinuousConfig(
            max_seq=shape["max_seq"], num_slots=shape["slots"],
            chunk_size=shape["chunk"], block_size=shape["block_size"],
            paged=True, prefix_cache=True, validate_pool=False)),
        policy=pol)
