"""The harness end to end on the CPU at smoke size (a test-only path: the
measurement path refuses a CPU), the last line's schema, and the refusals."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run
from bench.tests import smoke

SEED = 2 ** 31 + 77


def _check_schema(out, traced):
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert isinstance(out["correct"], bool)
    assert out["attempted"] > 0 and out["failed"] == 0
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    dev = out["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if traced:
        assert dev["window_s"] > 0 and 0 <= dev["busy_s"] <= dev["window_s"]
        for key in ("device_ops", "idle_gaps"):
            assert len(out["breakdown"][key]) <= 10
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}
    json.loads(json.dumps(out, allow_nan=False))


def test_open_loop_cell_end_to_end():
    res = smoke.resolved("qwen2_7b.prefill_pool", smoke.OPEN)
    out = smoke.execute(res, SEED, 1.5)
    _check_schema(out, traced=False)
    assert out["correct"]
    assert set(out["metrics"]) == {"ttft_p50_ms", "ttft_p90_ms", "setup_s"}


def test_open_loop_cell_traced():
    res = smoke.resolved("qwen2_7b.prefill_pool", smoke.OPEN)
    out = smoke.execute(res, SEED + 1, 1.0, traced=True)
    _check_schema(out, traced=True)
    assert {"queue_wait_ms_p50.prefill", "mfu.prefill",
            "idle_share.prefill"} <= set(out["metrics"])


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         "qwen2_7b.prefill_pool", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=run.ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_refuses_alone_in_a_directory(tmp_path):
    """Only BENCHMARK.json and bench/: no program to serve, no result."""
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".out"))
    code = ("import sys; sys.path.insert(0, '.'); "
            "from bench import run, system; "
            "res = run.resolve('qwen2_7b.prefill_pool'); "
            "system.model_config(res['conf'])")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=tmp_path, timeout=300,
                       env=dict(os.environ, PYTHONPATH=""))
    assert p.returncode != 0 and "repro" in p.stderr


def test_unknown_device_is_an_error():
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(run.BenchError, match="not in bench/peaks.json"):
        run.peaks_for("TPU v99")


def test_every_cell_and_metric_resolves_by_name():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    from bench.measure import reader
    for w in spec["workloads"]:
        res = run.resolve(w["name"])
        from bench import check
        assert res["limits"] and all(
            k in check.NUMBERS and v > 0 for k, v in res["limits"].items())
        assert res["end_to_end"] and res["per_layer"]
        assert any(m["name"] == "setup_s" for m in res["end_to_end"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(reader(m["name"]))
