"""The system under test, built from a configuration file: the program's
model at the file's sizes, its Amber policy, the seeded weights with the
program's own offline Amber scales, and a serving engine through
``repro.serve.api`` shaped as the traffic mix says.

The configuration file is the one statement of the model's shape.  Every
key it states is one of four kinds, and ``model_config`` raises
``BenchError`` naming any key that is none of them, so a width the file
states is never dropped for the program's default:

* applied: set on the program's ``ModelConfig`` as stated (``APPLIED``);
  the head width and the routed experts through ``bench/sizes.py``, so an
  unstated head width is ``hidden_size // num_attention_heads`` and
  unstated experts are none;
* checked: the program has no setting for it, and the file's value must be
  what the program does (``checked``): ``rms_norm_eps`` the program's
  RMSNorm epsilon, ``norm_topk_prob`` true (the gates are a softmax over
  the top-k logits), ``decoder_sparse_step`` 1 and ``mlp_only_layers`` []
  (every layer of an expert model routes);
* metadata, which the program never sees: ``source``, ``program_arch``,
  ``reference``, ``reduced``, ``published_*``, ``amber`` (read by
  ``policy``), ``deployment``, ``assumed``;
* refused: any other key.

What no key of a published config carries (the attention kind, norms
inside attention, the RoPE variant) comes from the program's config for
``program_arch``.
"""
from __future__ import annotations

import dataclasses

import jax

from bench import sizes, weights
from bench.errors import BenchError

# configuration-file key -> the program's ModelConfig field
APPLIED = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "num_hidden_layers": "n_layers",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "attention_bias": "qkv_bias",
    "num_experts": "n_experts",
    "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "moe_d_ff",
    "hidden_act": "act_fn",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}
# published activation name -> the program's (its gelu is the tanh form)
ACTIVATIONS = {"silu": "silu", "gelu_pytorch_tanh": "gelu"}
METADATA = ("source", "program_arch", "reference", "reduced", "amber",
            "deployment", "assumed")


def checked(cfg) -> dict:
    """Each key the program has no setting for -> the value that states
    what the program does."""
    import inspect

    from repro.models.common import rms_norm
    eps = inspect.signature(rms_norm).parameters["eps"].default
    return {"rms_norm_eps": eps if cfg.norm == "rmsnorm" else None,
            "norm_topk_prob": True, "decoder_sparse_step": 1,
            "mlp_only_layers": []}


def model_config(conf: dict):
    """The program's config for ``program_arch`` with every size the file
    states; the file's values win over the program's."""
    from repro.configs.base import get_config
    cfg = get_config(conf["program_arch"])
    does = checked(cfg)
    for key, value in conf.items():
        if key in does and value != does[key]:
            raise BenchError(f"{key}: the file states {value!r}, the "
                             f"program does {does[key]!r}")
        if not (key in does or key in APPLIED or key in METADATA
                or key.startswith("published_")):
            raise BenchError(f"{key}: the benchmark neither applies nor "
                             "checks this configuration key")
    fields = {field: conf[key] for key, field in APPLIED.items()
              if key in conf}
    if "hidden_act" in conf:
        if conf["hidden_act"] not in ACTIVATIONS:
            raise BenchError(f"hidden_act: the program has no "
                             f"{conf['hidden_act']!r}")
        fields["act_fn"] = ACTIVATIONS[conf["hidden_act"]]
    e = sizes.experts(conf) or sizes.Experts(0, 0, 0)
    fields.update(head_dim=sizes.head_dim(conf), n_experts=e.count,
                  top_k=e.top_k, moe_d_ff=e.width)
    return dataclasses.replace(
        cfg, qgate_skip_layers=tuple(conf["amber"]["qgate_skip_layers"]),
        **fields)


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
