"""Reduction of a profiler trace to device metrics.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a plain
dict: per device the operations that ran (HLO instruction name, start,
duration in ns, from the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane) and the harness's own host spans (``bench.*`` events of the host
planes, on the same clock).  ``reduce`` turns that dict into

* ``kernel_s``: self seconds per operation name, summed over devices.  The
  name drops the suffixes the compiler appends (``%nm_prune_matmul_pallas.3``
  and ``%cond.5.clone.6`` become ``nm_prune_matmul_pallas`` and ``cond``).
  The line nests a loop or conditional around the operations it runs, so a
  container's own time is its span less its children's;
* ``busy_s``: the union of operation intervals, averaged over devices;
* ``window_s``: the length of the ``bench.window`` span;
* ``idle_gaps``: the ten longest gaps between busy intervals, each
  labelled by the host span that overlaps it most (what the harness was
  doing meanwhile).

Everything is clipped to the ``bench.window`` span.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
SUFFIX = re.compile(r"(\.\d+|\.clone)+$")
WINDOW = "bench.window"


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    devices: Dict[str, List[list]] = {}
    spans: List[list] = []
    for path in files:
        for plane in ProfileData.from_file(path).planes:
            if DEVICE.match(plane.name):
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        devices.setdefault(plane.name, []).extend(
                            [e.name.split(" = ")[0], e.start_ns,
                             e.duration_ns] for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans.extend([e.name, e.start_ns, e.duration_ns]
                                 for e in line.events
                                 if e.name.startswith("bench."))
    return {"devices": devices, "spans": spans}


def base(name: str) -> str:
    return SUFFIX.sub("", name.split(" = ")[0].lstrip("%"))


def _self_times(ops):
    """(name, self seconds) of intervals on one line, where an interval
    inside another is its child."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    own = [o[2] - o[1] for o in ops]
    stack = []
    for i, (_, s, e) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(o[0], t / 1e9) for o, t in zip(ops, own)]


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(events: dict) -> dict:
    windows = [s for s in events["spans"] if s[0] == WINDOW]
    if not windows:
        raise ValueError("trace holds no bench.window span")
    _, w0, wd = max(windows, key=lambda s: s[2])
    w1 = w0 + wd
    kernel: Dict[str, float] = {}
    busy, gaps = [], []
    spans = [s for s in events["spans"] if s[0] != WINDOW]
    for ops in events["devices"].values():
        iv = [(base(name), max(s, w0), min(s + d, w1)) for name, s, d in ops
              if min(s + d, w1) > max(s, w0)]
        for name, t in _self_times(iv):
            kernel[name] = kernel.get(name, 0.0) + t
        merged = _union([(s, e) for _, s, e in iv])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s))
    gaps.sort(reverse=True)
    return {"kernel_s": kernel,
            "busy_s": sum(busy) / max(len(busy), 1),
            "window_s": wd / 1e9,
            "devices": len(events["devices"]),
            "idle_gaps": [[_label(spans, s, s + d), d / 1e9]
                          for d, s in gaps[:10]]}


def _label(spans, s, e) -> str:
    best, name = 0, "none"
    for n, a, d in spans:
        ov = min(e, a + d) - max(s, a)
        if ov > best:
            best, name = ov, n
    return name


def breakdown(red: dict, top: int = 10) -> dict:
    ops = sorted(red["kernel_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": red["idle_gaps"][:top]}
