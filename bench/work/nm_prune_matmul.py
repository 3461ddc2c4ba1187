"""Work an N:M-pruned projection needs: ``y = prune_n:m(x) @ w``.

The algorithm multiplies only the kept n of every m input channels, so it
needs ``2 * t * d * n_out * (n / m)`` operations, whatever kernel runs it.
Its bytes are x, w and y, each read or written once.
"""


def work(t: int, d: int, n_out: int, n: int, m: int, itemsize: int = 2):
    flops = 2 * t * d * n_out * n / m
    nbytes = (t * d + d * n_out + t * n_out) * itemsize
    return flops, nbytes
