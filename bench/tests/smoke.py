"""A cell at smoke size for the CPU tests: the real configuration file
with every width cut, and small traffic mixes of the same shape."""
import copy
import json
import os

import jax

from bench import run

# sound bfloat16 runs at this size read 0 (four seeds on the CPU); the
# int8 control of test_bench_checks reads 0.0072
LIMITS = {"mean_logit_gap_decode": 0.002}


def conf(dtype: str = "bfloat16") -> dict:
    with open(os.path.join(run.HERE, "configs", "qwen2_7b.json")) as f:
        c = json.load(f)
    c.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=2, vocab_size=256,
             torch_dtype=dtype)
    c["amber"] = dict(c["amber"], qgate_skip_layers=[0])
    return c


OPEN = {"rate_per_s": 4.0,
        "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                   "min": 8, "max": 120},
        "output": {"dist": "fixed", "value": 4},
        "engine": {"slots": 2, "max_seq": 128, "chunk": 32, "block_size": 8,
                   "dp": 1},
        "check_requests": 4}

E2E = ["ttft_p50_ms", "ttft_p90_ms", "setup_s"]
PER_LAYER = ["queue_wait_ms_p50.prefill", "nm_prune_matmul_roofline.prefill",
             "paged_attention_roofline.prefill", "mfu.prefill",
             "idle_share.prefill"]


def resolved(cell: str, mix: dict, dtype: str = "bfloat16") -> dict:
    """What ``run.resolve`` gives for a cell of ``mix``, at smoke sizes."""
    return {"cell": {"name": cell, "config": "qwen2_7b", "chips": 1},
            "conf": conf(dtype), "mix": copy.deepcopy(mix),
            "limits": dict(LIMITS),
            "end_to_end": [{"name": n, "unit": "x"} for n in E2E],
            "per_layer": [{"name": n, "unit": "x"} for n in PER_LAYER]}


def peaks() -> dict:
    with open(os.path.join(run.HERE, "peaks.json")) as f:
        return json.load(f)["devices"]["TPU v5 lite"]


def execute(res: dict, seed: int, seconds: float, traced: bool = False):
    """The run's own path, on the CPU: test use only."""
    return run.execute(res, seed, seconds, traced, jax.devices(), peaks())
