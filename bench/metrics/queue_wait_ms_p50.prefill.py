"""Median wait in the scheduler's queue: from a request's due time to the
top of the iteration that admitted it (the program's ``admitted_iter``),
over every request due in the window; one never admitted counts as
missing."""
import math

from bench.measure import percentile


def read(run):
    top = {i.it: i.t0 for i in run.iters}
    waits = [(top[t.admitted_iter] - t.due_t) * 1e3
             if t.admitted_iter in top else math.inf
             for t in run.window_tracks()]
    return percentile(waits, 50)
