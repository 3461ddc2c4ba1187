"""Operations the whole model needs for the work of one serving iteration,
as the algorithm states it (Amber-P's pruned projections at n/m).

``items`` are the harness's records of one iteration: ``("prefill", q,
offset, final)`` for a prompt chunk of ``q`` tokens at ``offset`` (``final``
when it completes the prompt, whose last row alone needs the LM head), and
``("decode", 1, kv_len)`` for one decoded row attending ``kv_len``
positions.  Prefill rows are pruned as the configuration's Amber policy
says (``bench/sizes.py``); decode rows run dense.  A token goes through
the router dense and through ``top_k`` routed experts.
"""
from bench import sizes
from bench.work import paged_attention


def _linear(conf: dict, prefill: bool) -> float:
    """Operations of every projection of every layer, for one row."""
    a = conf["amber"]
    credit = a["n"] / a["m"] if prefill else 1.0
    e = sizes.experts(conf)
    total = 0.0
    for i in range(conf["num_hidden_layers"]):
        for p in sizes.projections(conf, i):
            rows = e.top_k if p.routed else 1
            total += 2 * p.d_in * p.d_out * rows * (credit if p.pruned
                                                    else 1.0)
    return total


def work(conf: dict, items) -> float:
    attn = conf["num_hidden_layers"] * 4 * sizes.q_dim(conf)
    head = 2 * conf["hidden_size"] * conf["vocab_size"]
    total = 0.0
    for item in items:
        q, off = item[1], item[2]
        prefill = item[0] == "prefill"
        total += q * _linear(conf, prefill)
        total += attn * paged_attention.visible(q, off)
        if not prefill or item[3]:
            total += head
    return total
