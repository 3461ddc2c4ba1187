"""The trace reduction: on hand-made events, on a small trace recorded on
a TPU v5e (``bench/testdata/``), and ``load`` on a trace the CPU records."""
import gzip
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "testdata",
                    "trace_v5e_prefill.json.gz")


def _events():
    ms = 1_000_000
    return {"devices": {"/device:TPU:0": [
        ["%while.1", 0 * ms, 5 * ms],            # a loop around the next two
        ["%nm_prune_matmul_pallas.1", 1 * ms, 1 * ms],
        ["%paged_attention_pallas.3", 2 * ms, 3 * ms],
        ["%fusion.7.clone", 5 * ms, 1 * ms],
        ["%nm_prune_matmul_pallas.2", 9 * ms, 4 * ms],   # clipped at 10
    ]}, "spans": [
        ["bench.window", 1 * ms, 9 * ms],
        ["bench.iteration", 1 * ms, 4 * ms],
        ["bench.wait_arrival", 5 * ms, 4 * ms],
    ]}


def test_reduce_by_hand():
    red = trace.reduce(_events())
    ms = 1e-3
    assert red["window_s"] == pytest.approx(9 * ms)
    k = red["kernel_s"]
    assert k["nm_prune_matmul_pallas"] == pytest.approx(1 * ms + 1 * ms)
    assert k["paged_attention_pallas"] == pytest.approx(3 * ms)
    assert k["fusion"] == pytest.approx(1 * ms)
    assert k["while"] == pytest.approx(0.0)         # its children's time
    assert red["busy_s"] == pytest.approx(6 * ms)     # [1,6) and [9,10)
    assert red["idle_gaps"] == [["bench.wait_arrival", pytest.approx(3 * ms)]]
    b = trace.breakdown(red)
    assert b["device_ops"][0] == ["paged_attention_pallas",
                                  pytest.approx(3 * ms)]


def test_reduce_needs_a_window():
    ev = _events()
    ev["spans"] = ev["spans"][1:]
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(ev)


def test_recorded_v5e_trace():
    with gzip.open(DATA, "rt") as f:
        events = json.load(f)
    red = trace.reduce(events)
    assert red["devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    for k in ("nm_prune_matmul_pallas", "paged_attention_pallas",
              "paged_kv_scatter_pallas"):
        assert red["kernel_s"][k] > 0
    total = sum(red["kernel_s"].values())
    assert red["busy_s"] <= total + 1e-9
    b = trace.breakdown(red)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert all(g[0].startswith("bench.") for g in b["idle_gaps"])


def test_load_reads_host_spans(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.iteration"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.load(str(tmp_path))
    names = [s[0] for s in ev["spans"]]
    assert "bench.window" in names and "bench.iteration" in names
    assert ev["devices"] == {}                  # no TPU plane on the CPU
    red = trace.reduce(ev)
    assert red["busy_s"] == 0 and red["window_s"] > 0
