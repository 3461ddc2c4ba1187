"""Work causal attention over a paged KV cache needs, for ``q`` query rows
at absolute positions ``offset .. offset + q - 1`` (decode: ``q = 1``).

Operations count the causally visible scores only, ``offset + i + 1`` for
query ``i``, times 2 for q.k and 2 for p.v per head dimension, per query
head.  Bytes: q read and o written once; each KV head's visible blocks of K
and V read once (not once per query head of its group).
"""


def visible(q: int, offset: int) -> int:
    return q * offset + q * (q + 1) // 2


def work(q: int, offset: int, heads_q: int, heads_kv: int, head_dim: int,
         block: int, itemsize: int = 2):
    flops = 4 * head_dim * heads_q * visible(q, offset)
    rows = -(-(offset + q) // block) * block
    nbytes = (2 * q * heads_q + 2 * rows * heads_kv) * head_dim * itemsize
    return flops, nbytes
