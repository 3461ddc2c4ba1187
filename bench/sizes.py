"""A model's shape, read from its configuration file: the one place the
benchmark derives a width.

Everything that builds the system (``bench/system.py``), counts its work
(``bench/work/model_step.py``, ``Run.attention_least_s``, the roofline
readers) or computes its plain reference (``bench/references/``) takes
the model's sizes from here, so a width the file states reaches all of
them alike.

* The head width is the file's ``head_dim``; where the file states none,
  the published convention ``hidden_size // num_attention_heads``.
* Routed experts are the file's ``num_experts``, ``num_experts_per_tok``
  and ``moe_intermediate_size``, stated together; where it states none,
  every layer has a dense MLP of ``intermediate_size``.  A file that
  states experts has them in every layer.
* Amber-P (paper's deployment) prunes in prefill the inputs of
  ``q_proj`` and ``gate_proj``, except in the file's
  ``amber.qgate_skip_layers``, and of ``down_proj`` in every layer; in an
  expert layer, of each routed expert's ``gate_proj`` and ``down_proj``
  by the same rule.  The router, k, v, o and up projections run dense.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

from bench.errors import BenchError

EXPERT_KEYS = ("num_experts", "num_experts_per_tok", "moe_intermediate_size")


@dataclasses.dataclass(frozen=True)
class Experts:
    count: int          # routed experts in each layer
    top_k: int          # experts each token goes through
    width: int          # one expert's intermediate width


@dataclasses.dataclass(frozen=True)
class Projection:
    name: str           # the program's parameter path inside a layer
    d_in: int
    d_out: int
    pruned: bool        # Amber prunes its input in prefill
    routed: bool = False    # a routed expert's: top_k of them per token


def head_dim(conf: dict) -> int:
    if "head_dim" in conf:
        return conf["head_dim"]
    return conf["hidden_size"] // conf["num_attention_heads"]


def q_dim(conf: dict) -> int:
    return conf["num_attention_heads"] * head_dim(conf)


def kv_dim(conf: dict) -> int:
    return conf["num_key_value_heads"] * head_dim(conf)


def experts(conf: dict) -> Optional[Experts]:
    """The routed experts the file states, or ``None`` for a dense MLP."""
    stated = [k for k in EXPERT_KEYS if k in conf]
    if not stated:
        return None
    if len(stated) < len(EXPERT_KEYS):
        missing = [k for k in EXPERT_KEYS if k not in conf]
        raise BenchError(f"{', '.join(missing)}: the file states "
                         f"{', '.join(stated)} but not this")
    return Experts(*(conf[k] for k in EXPERT_KEYS))


def projections(conf: dict, layer: int) -> List[Projection]:
    """Every projection of ``layer``, in the order the layer runs them."""
    d, qd, kvd = conf["hidden_size"], q_dim(conf), kv_dim(conf)
    qgate = layer not in conf["amber"]["qgate_skip_layers"]
    out = [Projection("q_proj", d, qd, qgate),
           Projection("k_proj", d, kvd, False),
           Projection("v_proj", d, kvd, False),
           Projection("o_proj", qd, d, False)]
    e = experts(conf)
    if e is None:
        f = conf["intermediate_size"]
        return out + [Projection("mlp/gate_proj", d, f, qgate),
                      Projection("mlp/up_proj", d, f, False),
                      Projection("mlp/down_proj", f, d, True)]
    p = "moe/experts/"
    return out + [Projection("moe/router", d, e.count, False),
                  Projection(p + "gate_proj", d, e.width, qgate, True),
                  Projection(p + "up_proj", d, e.width, False, True),
                  Projection(p + "down_proj", e.width, d, True, True)]
