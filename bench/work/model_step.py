"""Operations the whole model needs for the work of one serving iteration,
as the algorithm states it (Amber-P's pruned projections at n/m).

``items`` are the harness's records of one iteration: ``("prefill", q,
offset, final)`` for a prompt chunk of ``q`` tokens at ``offset`` (``final``
when it completes the prompt, whose last row alone needs the LM head), and
``("decode", 1, kv_len)`` for one decoded row attending ``kv_len``
positions.  Prefill rows are pruned as the configuration's Amber policy
says; decode rows run dense.
"""
from bench.work import paged_attention


def _linear(conf: dict, prefill: bool) -> float:
    d, f = conf["hidden_size"], conf["intermediate_size"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = d // hq
    a = conf["amber"]
    credit = a["n"] / a["m"] if prefill else 1.0
    total = 0.0
    for i in range(conf["num_hidden_layers"]):
        qg = credit if i not in a["qgate_skip_layers"] else 1.0
        total += 2 * d * hq * hd * qg + 2 * 2 * d * hkv * hd + 2 * hq * hd * d
        total += 2 * d * f * qg + 2 * d * f + 2 * f * d * credit
    return total


def work(conf: dict, items) -> float:
    d, hq = conf["hidden_size"], conf["num_attention_heads"]
    hd, n = d // hq, conf["num_hidden_layers"]
    head = 2 * d * conf["vocab_size"]
    total = 0.0
    for item in items:
        q, off = item[1], item[2]
        prefill = item[0] == "prefill"
        total += q * _linear(conf, prefill)
        total += n * 4 * hd * hq * paged_attention.visible(q, off)
        if not prefill or item[3]:
            total += head
    return total
