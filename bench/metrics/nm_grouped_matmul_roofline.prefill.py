"""Share of the roofline reached by ``nm_grouped_matmul_pallas`` in the
traced window: the least time the chip needs for every routed-expert
projection (gate, up and down) of every layer and call, over the device
time of the kernel's events.  A call is a prefill chunk (its valid
tokens) or an iteration's decode batch (its decoded rows), each counted
by ``bench/work/nm_grouped_matmul.py``, whose weight bytes are the
fewest a call can touch (``top_k`` experts), so the share never reads
above the kernel's true share; Amber prunes gate and down in prefill
only.  Nothing to read in a model without routed experts, or in a
program that runs them in no such kernel."""
from bench import sizes
from bench.measure import work


def read(run):
    c, a = run.conf, run.conf["amber"]
    e = sizes.experts(c)
    if e is None:
        return None
    shapes = [(p.d_in, p.d_out, p.pruned)
              for i in range(c["num_hidden_layers"])
              for p in sizes.projections(c, i) if p.routed]
    fn = work("nm_grouped_matmul")

    def least(t, prefill):
        return sum(run.min_time(*fn(t, din, dout, e.count, e.top_k, a["n"],
                                    a["m"], pruned and prefill))
                   for din, dout, pruned in shapes)

    total = 0.0
    for it in run.traced_iters():
        total += sum(least(item[1], True) for item in it.items
                     if item[0] == "prefill")
        rows = sum(item[1] for item in it.items if item[0] == "decode")
        if rows:
            total += least(rows, False)
    return run.roofline("nm_grouped_matmul_pallas", total)
