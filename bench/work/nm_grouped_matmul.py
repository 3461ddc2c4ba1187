"""Work one routed-expert projection needs over one call of ``t`` tokens
(a prefill chunk's valid tokens, or a decode batch's rows): ``y[r] =
prune_n:m(x[r]) @ w[expert(r)]`` for the ``rows = t * top_k`` rows the
router sends to its experts.

Operations: ``2 * rows * d_in * d_out``, times ``n / m`` for a pruned
input (the algorithm multiplies only the kept channels), whatever kernel
runs it.  Bytes: every row read and written once, plus the weights of
``top_k`` experts, read once: the fewest a call can touch, since each
token picks ``top_k`` distinct experts.  How many a call really touches
depends on the routing, which the trace does not show (PERF.md);
counting the fewest keeps the share a lower bound of the kernel's true
share of its roofline, never above it.
"""


def work(t: int, d_in: int, d_out: int, experts: int, top_k: int, n: int,
         m: int, pruned: bool, itemsize: int = 2):
    rows = t * top_k
    flops = 2 * rows * d_in * d_out * (n / m if pruned else 1.0)
    nbytes = (rows * (d_in + d_out)
              + min(top_k, experts) * d_in * d_out) * itemsize
    return flops, nbytes
