"""Share of the roofline reached by ``nm_prune_matmul_pallas`` in the
traced window: the least time the chip needs for every pruned projection
of every prefill chunk (``bench/work/nm_prune_matmul.py``, valid rows only)
over the device time of the kernel's events.  Routed experts' projections
are not the kernel's work: their pruning runs in the expert layer's own
path, which a kernel of its own would report."""
from bench import sizes
from bench.measure import work


def read(run):
    c, a = run.conf, run.conf["amber"]
    shapes = [(p.d_in, p.d_out) for i in range(c["num_hidden_layers"])
              for p in sizes.projections(c, i) if p.pruned and not p.routed]
    fn = work("nm_prune_matmul")
    least = sum(run.min_time(*fn(item[1], din, dout, a["n"], a["m"]))
                for it in run.traced_iters() for item in it.items
                if item[0] == "prefill" for din, dout in shapes)
    return run.roofline("nm_prune_matmul_pallas", least)
