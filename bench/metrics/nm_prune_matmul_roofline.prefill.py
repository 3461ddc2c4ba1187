"""Share of the roofline reached by ``nm_prune_matmul_pallas`` in the
traced window: the least time the chip needs for every pruned projection
of every prefill chunk (``bench/work/nm_prune_matmul.py``, valid rows only)
over the device time of the kernel's events."""
from bench.measure import work


def read(run):
    c, a = run.conf, run.conf["amber"]
    d, f = c["hidden_size"], c["intermediate_size"]
    shapes = []
    for i in range(c["num_hidden_layers"]):
        if i not in a["qgate_skip_layers"]:
            shapes += [(d, d), (d, f)]
        shapes.append((f, d))
    fn = work("nm_prune_matmul")
    least = sum(run.min_time(*fn(item[1], din, dout, a["n"], a["m"]))
                for it in run.traced_iters() for item in it.items
                if item[0] == "prefill" for din, dout in shapes)
    return run.roofline("nm_prune_matmul_pallas", least)
