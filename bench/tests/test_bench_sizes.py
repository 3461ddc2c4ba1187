"""The configuration file as the one statement of the model's shape: the
sizes derived from it (``bench/sizes.py``), the keys ``system.model_config``
applies, checks or refuses, an expert model built at the file's widths,
and the qwen2_7b cell's program, weights and readings left as they were
before the file's head width and experts were read."""
import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run, sizes, system, weights
from bench.drive import Iteration
from bench.errors import BenchError
from bench.measure import Run, reader, work
from bench.tests import smoke

# the program's ModelConfig for bench/configs/qwen2_7b.json, and the
# sha256 of the smoke-size weights of two seeds, recorded before the
# harness read head widths and experts from the file
QWEN2_7B = {
    "name": "qwen2-7b", "family": "dense", "n_layers": 8, "d_model": 3584,
    "n_heads": 28, "n_kv_heads": 4, "d_ff": 18944, "vocab_size": 152064,
    "head_dim": 128, "attn_type": "full", "window": 4096,
    "rope_variant": "default", "rope_theta": 1000000.0, "qkv_bias": True,
    "attn_chunk": 1024, "attn_impl": "chunked", "n_experts": 0, "top_k": 0,
    "moe_d_ff": 0, "shared_expert": False, "moe_impl": "ragged",
    "block_pattern": ("attn",), "rnn_width": 0, "conv_width": 4,
    "is_encdec": False, "n_encoder_layers": 0, "encoder_seq": 1500,
    "vision_stub": False, "n_patches": 64, "act_fn": "silu",
    "norm": "rmsnorm", "tie_embeddings": False, "dtype": "bfloat16",
    "sub_quadratic": False, "remat": True, "scan_layers": True,
    "qgate_skip_layers": (0, 6)}
WEIGHT_DIGESTS = [
    ("bfloat16", 2 ** 31 + 5,
     "f9606856082237944da50c14689c6b9bc1ebdfd61ff3749364351ae56ac1d29f"),
    ("float32", 7,
     "1d0926af21afbe0bd001f7ed9a0627aea38ce1b14aa5aba43a1ee68a8008e3fd"),
]
# the readings of a hand-made traced qwen2_7b run (_qwen2_run), as the
# harness gave them before
READINGS = {"nm_prune_matmul_roofline.prefill": 2.8647494017094015,
            "paged_attention_roofline.prefill": 2.966509778546327,
            "mfu.prefill": 2.2815068970883248}
STEP_OPS = 839277379584.0


def _file(name: str) -> dict:
    with open(os.path.join(run.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def qwen3_moe(**over) -> dict:
    """A Qwen3-30B-A3B-shaped file (head width twice hidden / heads, every
    layer routed) at smoke size."""
    c = {"source": "https://huggingface.co/Qwen/Qwen3-30B-A3B/blob/main/"
                   "config.json",
         "program_arch": "qwen3_30b_a3b",
         "reduced": ["hidden_size", "num_hidden_layers"],
         "published_num_hidden_layers": 48,
         "hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
         "num_hidden_layers": 2, "vocab_size": 256, "num_experts": 8,
         "num_experts_per_tok": 2, "moe_intermediate_size": 32,
         "norm_topk_prob": True, "decoder_sparse_step": 1,
         "mlp_only_layers": [], "rms_norm_eps": 1e-06,
         "rope_theta": 1000000.0, "hidden_act": "silu",
         "tie_word_embeddings": False, "attention_bias": False,
         "torch_dtype": "bfloat16",
         "amber": {"n": 8, "m": 16, "score_mode": "robust",
                   "qgate_skip_layers": [1], "kernels": True}}
    c.update(over)
    return c


# ------------------------------------------------------------ head width

@pytest.mark.parametrize("stated,want", [(None, 64), (128, 128), (48, 48)])
def test_head_dim_is_the_files_or_the_convention(stated, want):
    conf = {"hidden_size": 2048, "num_attention_heads": 32,
            "num_key_value_heads": 4}
    if stated is not None:
        conf["head_dim"] = stated
    assert sizes.head_dim(conf) == want
    assert sizes.q_dim(conf) == 32 * want
    assert sizes.kv_dim(conf) == 4 * want


def test_published_qwen3_widths_reach_the_program():
    """At Qwen3-30B-A3B's widths, q is 4096 wide, not 2048."""
    conf = qwen3_moe(hidden_size=2048, intermediate_size=6144,
                     num_attention_heads=32, num_key_value_heads=4,
                     head_dim=128, num_hidden_layers=6, vocab_size=151936,
                     num_experts=128, num_experts_per_tok=8,
                     moe_intermediate_size=768)
    cfg = system.model_config(conf)
    assert (cfg.head_dim, cfg.q_dim, cfg.kv_dim) == (128, 4096, 512)
    assert (cfg.n_experts, cfg.top_k, cfg.moe_d_ff) == (128, 8, 768)
    assert (cfg.d_model, cfg.n_layers, cfg.qkv_bias) == (2048, 6, False)


# --------------------------------------------------------- the key contract

@pytest.mark.parametrize("key,value", [
    ("norm_topk_prob", False),
    ("mlp_only_layers", [1]),
    ("decoder_sparse_step", 2),
    ("rms_norm_eps", 1e-05),
    ("q_lora_rank", 512),
    ("hidden_act", "gelu"),
])
def test_a_key_the_program_does_not_do_is_refused(key, value):
    with pytest.raises(BenchError, match=key):
        system.model_config(qwen3_moe(**{key: value}))


def test_experts_are_stated_together():
    conf = qwen3_moe()
    del conf["num_experts_per_tok"]
    with pytest.raises(BenchError, match="num_experts_per_tok"):
        system.model_config(conf)


def test_every_committed_configuration_states_only_known_keys():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        with open(os.path.join(run.ROOT, c["file"])) as f:
            system.model_config(json.load(f))


# ------------------------------------------------------ an expert model

def test_expert_file_builds_at_its_own_widths():
    conf = qwen3_moe()
    model, params, _ = system.build(conf, 2 ** 31 + 3)
    cfg = model.cfg
    assert (cfg.head_dim, cfg.q_dim) == (32, 128)
    assert (cfg.n_experts, cfg.top_k, cfg.moe_d_ff) == (8, 2, 32)
    layer = params["periods"]["b0"]
    assert layer["q_proj"]["w"].shape == (2, 64, 128)
    assert layer["o_proj"]["w"].shape == (2, 128, 64)
    assert layer["k_proj"]["w"].shape == (2, 64, 64)
    assert "b" not in layer["q_proj"] and "mlp" not in layer
    moe = layer["moe"]
    assert moe["router"]["w"].shape == (2, 64, 8)
    for name, shape in (("gate_proj", (2, 8, 64, 32)),
                        ("up_proj", (2, 8, 64, 32)),
                        ("down_proj", (2, 8, 32, 64))):
        assert moe["experts"][name]["w"].shape == shape
    # every projection the sizes list is a leaf of the program's layer
    for p in sizes.projections(conf, 0):
        leaf = layer
        for part in p.name.split("/"):
            leaf = leaf[part]
        assert leaf["w"].shape[-2:] == (p.d_in, p.d_out)


def test_norm_leaves_are_ones():
    key = weights.base_key(11)
    for path in ("periods/b0/ln1/w", "final_norm/w", "periods/b0/q_norm/w",
                 "periods/b0/k_norm/w"):
        got = weights.leaf(key, path, (2, 32), jnp.bfloat16)
        assert np.all(np.asarray(got, np.float32) == 1.0), path
    other = weights.leaf(key, "periods/b0/q_proj/w", (2, 32, 32),
                         jnp.bfloat16)
    assert not np.all(np.asarray(other, np.float32) == 1.0)


def test_model_step_counts_routed_experts_by_hand():
    conf = {"hidden_size": 16, "intermediate_size": 32,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 8, "num_hidden_layers": 2, "vocab_size": 10,
            "num_experts": 4, "num_experts_per_tok": 2,
            "moe_intermediate_size": 8,
            "amber": {"n": 8, "m": 16, "qgate_skip_layers": [0]}}
    # q is 4 x 8 = 32 wide, k and v 2 x 8 = 16; the router is dense; each
    # of the 2 experts a token takes runs gate (pruned outside layer 0),
    # up (dense) and down (pruned)
    attn_proj = 2 * 16 * 32 + 2 * 2 * 16 * 16 + 2 * 32 * 16
    router = 2 * 16 * 4
    l0 = attn_proj + router + 2 * (2 * 16 * 8 + 2 * 16 * 8 + 2 * 8 * 16 / 2)
    l1 = (attn_proj - 2 * 16 * 32 / 2 + router
          + 2 * (2 * 16 * 8 / 2 + 2 * 16 * 8 + 2 * 8 * 16 / 2))
    attn = 2 * 4 * 32 * 3                       # 2 layers, 3 visible scores
    head = 2 * 16 * 10
    fn = work("model_step")
    assert fn(conf, [("prefill", 2, 0, True)]) == 2 * (l0 + l1) + attn + head
    dense = 2 * (attn_proj + router + 2 * 3 * 2 * 16 * 8)
    assert fn(conf, [("decode", 1, 2)]) == dense + attn + head


# --------------------------------------------------- qwen2_7b unchanged

def test_qwen2_7b_program_config_is_unchanged():
    cfg = system.model_config(_file("qwen2_7b"))
    assert dataclasses.asdict(cfg) == QWEN2_7B


@pytest.mark.parametrize("dtype,seed,digest", WEIGHT_DIGESTS)
def test_qwen2_7b_smoke_weights_are_unchanged(dtype, seed, digest):
    from repro.models import build_model
    model = build_model(system.model_config(smoke.conf(dtype)))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = weights.build_like(shapes, seed)
    h = hashlib.sha256()
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for p, leaf in sorted(flat, key=lambda x: weights.path_str(x[0])):
        a = np.asarray(leaf)
        for part in (weights.path_str(p), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    assert h.hexdigest() == digest


def _qwen2_run() -> Run:
    with open(os.path.join(run.HERE, "traffic", "prefill_pool.json")) as f:
        mix = json.load(f)
    iters = [Iteration(0, 0.0, 0.1, [("prefill", 512, 0, False)]),
             Iteration(1, 0.1, 0.2, [("prefill", 300, 512, True),
                                     ("decode", 1, 2047)]),
             Iteration(2, 0.2, 0.3, [("decode", 1, 812), ("decode", 1, 2048)])]
    trace = {"window_s": 0.5, "busy_s": 0.3,
             "kernel_s": {"nm_prune_matmul_pallas": 0.2,
                          "paged_attention_pallas": 0.01}}
    return Run(conf=_file("qwen2_7b"), mix=mix, peaks=smoke.peaks(), chips=1,
               setup_s=1.0, window=(0, 1), tracks=[], iters=iters,
               trace=trace, trace_span=(0.0, 1.0))


def test_qwen2_7b_readings_are_unchanged():
    r = _qwen2_run()
    for name, want in READINGS.items():
        assert reader(name)(r) == want, name
    assert work("model_step")(r.conf, r.iters[1].items) == STEP_OPS
