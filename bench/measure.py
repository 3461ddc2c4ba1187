"""What a run measured, handed to every metric reader.

A metric is a file ``bench/metrics/<name>.py`` with ``read(run)``, which
returns a number or ``None`` where the run holds nothing for it to read
(the harness then leaves the metric out of the line).  A kernel's work
is a file ``bench/work/<kernel>.py`` with ``work(...) -> (ops, bytes)``.
Both are found by name.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import math
import os
from typing import List, Optional

import numpy as np

from bench import sizes

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    return _load("metrics", name).read


def work(kernel: str):
    return _load("work", kernel).work


def percentile(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (linear interpolation) of all values;
    ``math.inf`` stands for a request that never answered."""
    if not len(values):
        return None
    v = np.sort(np.asarray(values, dtype=float))
    pos = q / 100 * (len(v) - 1)
    lo, hi = math.floor(pos), math.ceil(pos)
    if math.isinf(v[hi]):
        return math.inf
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


@dataclasses.dataclass
class Run:
    conf: dict                  # configuration file
    mix: dict                   # traffic file
    peaks: dict                 # the device's row of peaks.json
    chips: int
    setup_s: float
    window: tuple               # (start, end) on the host clock
    tracks: list                # drive.Track, every request of the run
    iters: list                 # drive.Iteration, every iteration
    trace: Optional[dict] = None        # trace.reduce(), traced runs
    trace_span: tuple = (0.0, 0.0)      # traced span on the host clock
    memory_peak_bytes: int = 0

    # ------------------------------------------------------------ windows
    def window_tracks(self) -> List:
        """Requests due inside the window."""
        w0, w1 = self.window
        return [t for t in self.tracks if w0 <= t.due_t <= w1]

    def ttft_ms(self) -> List[float]:
        """Per request due in the window: due time to the iteration
        boundary at which its first token was on the host; ``inf`` for one
        that never got it."""
        return [(t.times[0] - t.due_t) * 1e3 if t.times else math.inf
                for t in self.window_tracks()]

    def traced_iters(self) -> List:
        a, b = self.trace_span
        return [i for i in self.iters if i.t0 >= a and i.t1 <= b]

    # ------------------------------------------------------------ kernels
    def peak_flops(self) -> float:
        return self.peaks["flops_per_s"][self.conf["torch_dtype"]]

    def min_time(self, flops: float, nbytes: float) -> float:
        """The least time the chip needs: the larger of the two bounds."""
        return max(flops / self.peak_flops(),
                   nbytes / self.peaks["hbm_bytes_per_s"])

    def attention_least_s(self) -> float:
        """Least time for every paged-attention call of the traced
        window: each prefill chunk and each decoded row, in every layer
        (``bench/work/paged_attention.py``)."""
        c = self.conf
        hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
        hd = sizes.head_dim(c)
        block = self.mix["engine"]["block_size"]
        fn = work("paged_attention")
        per_layer = sum(self.min_time(*fn(item[1], item[2], hq, hkv, hd,
                                          block))
                        for it in self.traced_iters() for item in it.items)
        return per_layer * c["num_hidden_layers"]

    def mfu(self) -> Optional[float]:
        if self.trace is None:
            return None
        fn = work("model_step")
        ops = sum(fn(self.conf, it.items) for it in self.traced_iters())
        if ops <= 0:
            return None
        return 100.0 * ops / (self.trace["window_s"] * self.chips
                              * self.peak_flops())

    def roofline(self, kernel: str, least_s: float) -> Optional[float]:
        """Share (%) of the kernel's roofline: least time over the device
        time of its events in the traced window."""
        if self.trace is None:
            return None
        spent = self.trace["kernel_s"].get(kernel, 0.0)
        if spent <= 0 or least_s <= 0:
            return None
        return 100.0 * least_s / spent
