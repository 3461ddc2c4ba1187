"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` at the checkout's root) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); its limits are in
``bench/limits/<cell>.json``.  The run builds the system from the seed,
warms up the step programs the mix uses (set-up), serves the mix for
``--seconds`` through ``repro.serve.api``, then frees the program's state
and compares a sample of what it served with the configuration's plain
reference.  The last line of standard output is one JSON object; with
``--trace 0`` its metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  Without a TPU, or with fewer chips than the cell asks for, it
prints no result and exits non-zero.  JAX's persistent compilation cache
lives in ``bench/.out/jax_cache`` inside the checkout.

``--control 1`` also runs the reference one precision step down (int8) at
the same positions and prints every number of both sides on standard
error: the readings a cell's limits are set from.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(HERE, ".out", "jax_cache")
TRACE_DIR = os.path.join(HERE, ".out", "trace")
# the checkout's root holds the harness, its src/ the program
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                if p not in sys.path]

from bench.errors import BenchError  # noqa: E402


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(name: str) -> dict:
    """The cell's entry, configuration, mix, limits and metrics, each
    found by name."""
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError(f"no {path}")
    spec = _json(path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}")
    cell = cells[name]
    confs = {c["name"]: c for c in spec["configs"]}
    if cell["config"] not in confs:
        raise BenchError(f"unknown configuration {cell['config']!r}")
    conf = confs[cell["config"]]

    def wanted(m):
        return name in m.get("workloads", cells)

    return {"cell": cell,
            "conf": _json(os.path.join(ROOT, conf["file"])),
            "mix": _json(os.path.join(HERE, "traffic",
                                      cell["traffic"] + ".json")),
            "limits": _json(os.path.join(HERE, "limits", name + ".json")),
            "end_to_end": [m for m in spec["end_to_end"] if wanted(m)],
            "per_layer": [m for m in spec["per_layer"] if wanted(m)]}


def _profile_options():
    """The profiler without its Python tracer: host spans come from the
    harness's ``TraceAnnotation``s alone."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def setup_jax():
    """Persistent compilation cache at a fixed path inside the checkout,
    every program cached."""
    os.makedirs(CACHE, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def chips_or_fail(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs


def peaks_for(kind: str) -> dict:
    table = _json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise BenchError(f"device {kind!r} is not in bench/peaks.json")
    return table[kind]


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def serve(res: dict, seed: int, seconds: float, traced: bool, devs,
          peaks: dict, t_start: float = T_START):
    """Set up and serve the window; returns (what the run measured, the
    sampled requests' (prompt, served tokens), the window's request
    records).  The program's state is freed before it returns."""
    import jax

    from bench import check, drive, generator, system, trace
    from bench.measure import Run
    cell, conf, mix = res["cell"], res["conf"], res["mix"]
    chips = cell["chips"]
    cache = {}

    def count(event, **kw):
        cache[event] = cache.get(event, 0) + 1
    jax.monitoring.register_event_listener(count)
    phases = [("start", time.perf_counter() - t_start)]
    model, params, pol = system.build(conf, seed)
    phases.append(("weights and Amber scales", time.perf_counter() - t_start))
    eng = system.engine(model, pol, mix["engine"])
    vocab = conf["vocab_size"]
    tracing = {}

    OPTIONS = _profile_options()

    def open_trace():
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR, profiler_options=OPTIONS)
        tracing["span"] = jax.profiler.TraceAnnotation("bench.window")
        tracing["span"].__enter__()
        tracing["t0"] = time.perf_counter()

    gen = generator.for_mix(mix)
    loop = getattr(gen, "Loop", drive.OpenLoop)
    d = loop(eng, mix, seconds, gen.requests(mix, seed, seconds, vocab),
             annotate=traced)
    d.warm_up(params, gen.warm_up(mix, vocab))
    traces0 = dict(eng.replicas[0].trace_counts)
    setup_s = time.perf_counter() - t_start
    phases.append(("engine and warm-up", setup_s))
    jax.monitoring.unregister_event_listener(count)
    log("set-up (s since process start): " + ", ".join(
        f"{k} {v:.2f}" for k, v in phases) + "; persistent cache: hits "
        f"{cache.get('/jax/compilation_cache/cache_hits', 0)}, misses "
        f"{cache.get('/jax/compilation_cache/cache_misses', 0)}")
    if traced:
        open_trace()
    d.serve(params)
    red, span = None, (0.0, 0.0)
    if traced:
        tracing["span"].__exit__(None, None, None)
        span = (tracing["t0"], time.perf_counter())
        jax.profiler.stop_trace()
        red = trace.reduce(trace.load(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    traces1 = dict(eng.replicas[0].trace_counts)
    memory = max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for x in devs[:chips])
    run = Run(conf=conf, mix=mix, peaks=peaks, chips=chips,
              setup_s=setup_s, window=d.window,
              tracks=list(d.tracks.values()), iters=d.iters, trace=red,
              trace_span=span, memory_peak_bytes=int(memory))
    picked = check.sample(d.tracks.values(), seed,
                          mix.get("check_requests", 8))
    served = [(t.gen.prompt, list(d.rep.requests[t.rid].out))
              for t in picked]
    late = sorted(d.late)
    log(f"window: {len(run.window_tracks())} requests due, {len(d.iters)} "
        f"iterations in the run; compiles in window {d.compiles.count}; "
        f"step programs traced before/after the window {traces0} / "
        f"{traces1}")
    if late:
        log(f"generator late (submit - due, s): median "
            f"{late[len(late) // 2]!r} max {late[-1]!r}; sleep overshoot "
            f"max {max(d.sleep_late, default=0.0)!r}")
    del d, eng, params, model
    gc.collect()
    return run, served


def execute(res: dict, seed: int, seconds: float, traced: bool, devs,
            peaks: dict, control: bool = False) -> dict:
    """Serve the window, read the metrics, compare; the result line."""
    import numpy as np

    from bench import check, trace
    from bench.measure import reader
    run, served = serve(res, seed, seconds, traced, devs, peaks)
    window = run.window_tracks()
    # a request due in the window must answer in full
    failed = sum(t.state != "done" or t.n_out < t.gen.max_new
                 for t in window)
    n = len(window)
    log(f"requests due in the window: {n}, failed {failed}, tokens "
        f"{sum(len(t.times) for t in window)}")
    for q in (50, 90, 95):
        log(f"samples beyond p{q}: {n - 1 - int(q / 100 * (n - 1))} of {n}")
    metrics = {}
    for m in res["per_layer" if traced else "end_to_end"]:
        v = reader(m["name"])(run)
        if v is not None and not np.isfinite(v):
            log(f"{m['name']}: {v!r} (requests that never answered), "
                "left out")
        elif v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    checks, tokens, ctl = check.compare(run.conf, seed, served,
                                        res["limits"], control)
    log(f"compared {len(served)} requests, {tokens} served tokens")
    for side, got in (ctl or {}).items():
        for k, v in got.items():
            log(f"reading {side} {k} {v!r}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": None, "attempted": n,
           "failed": int(failed), "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = trace.breakdown(run.trace)
    ok = check.passed(checks)
    out["checks"] = {k: {"value": c["value"] if np.isfinite(c["value"])
                         else None, "limit": c["limit"]}
                     for k, c in checks.items()}
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    out["correct"] = ok
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the numbers of the int8 control on the "
                    "same served tokens (stderr; for setting limits)")
    args = ap.parse_args(argv)
    try:
        res = resolve(args.workload)
        jax = setup_jax()
        devs = chips_or_fail(jax, res["cell"]["chips"])
        peaks = peaks_for(devs[0].device_kind)
        out = execute(res, args.seed, args.seconds, bool(args.trace), devs,
                      peaks, bool(args.control))
    except (BenchError, OSError) as e:
        log(f"bench: {type(e).__name__}: {e}")
        return 2
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
