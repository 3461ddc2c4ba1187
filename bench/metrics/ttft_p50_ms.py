"""Median time to first token over every request due in the window (see
``Run.ttft_ms``)."""
from bench.measure import percentile


def read(run):
    return percentile(run.ttft_ms(), 50)
