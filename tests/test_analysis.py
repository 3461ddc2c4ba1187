"""The analyzer analyzed: ``repro.analysis`` (ISSUE 9) must catch each
seeded violation class through the real CLI (non-zero exit + structured
JSON finding), and its building blocks (jaxpr walk, VMEM estimator,
purity AST pass, trace-key declaration) must hold on known inputs.

The CLI tests run narrow rule selections so none of them pays for the
full engine-shaped sweeps; the full-repo clean run is CI's
``static-analysis`` job, not a test here.
"""
import json
import os
import subprocess
import sys

import pytest

_HERE = os.path.dirname(__file__)
_SRC = os.path.join(_HERE, "..", "src")
_FIX = os.path.join(_HERE, "fixtures", "analysis")


def _run_cli(*args, json_name="out.json", tmp_path=None):
    out = os.path.join(str(tmp_path), json_name)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args,
         "--json-out", out],
        env={**os.environ, "PYTHONPATH": _SRC}, capture_output=True,
        text=True)
    doc = None
    if os.path.exists(out):
        with open(out) as fh:
            doc = json.load(fh)
    return proc, doc


def _errors(doc, rule):
    return [f for f in doc["findings"]
            if f["rule"] == rule and f["severity"] == "error"]


# ------------------------------------------------------------ CLI, seeded


def test_cli_flags_oversized_kernel(tmp_path):
    """A kernel whose BlockSpec blows the per-core VMEM budget must fail
    the vmem.budget rule through the CLI."""
    proc, doc = _run_cli(
        "--rules", "vmem.budget", "--configs", "llama31_8b",
        "--vmem-extra", os.path.join(_FIX, "bad_kernel.py"),
        tmp_path=tmp_path)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert doc["failed"] is True
    hits = _errors(doc, "vmem.budget")
    assert any(f["obj"] == "oversized_copy" for f in hits), doc["findings"]
    (bad,) = [f for f in hits if f["obj"] == "oversized_copy"]
    assert bad["data"]["vmem_bytes"] > 16 * 2**20


def test_cli_flags_poisoned_scheduler(tmp_path):
    """A jax import in the scheduler host layer must fail the purity
    rule, with the offending chain reported."""
    proc, doc = _run_cli(
        "--rules", "purity",
        "--purity-root", os.path.join(_FIX, "poisoned_src"),
        tmp_path=tmp_path)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    hits = _errors(doc, "purity.scheduler-jax-free")
    assert hits and hits[0]["obj"] == "repro.serve.scheduler"
    assert hits[0]["data"]["chain"][-1] == "jax"


def test_cli_flags_pool_gather_step(tmp_path):
    """A step with a pool-shaped gather outside pallas_call must fail
    the jaxpr containment pin."""
    proc, doc = _run_cli(
        "--rules", "jaxpr.extra-entries",
        "--jaxpr-extra", os.path.join(_FIX, "pool_gather_step.py"),
        tmp_path=tmp_path)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    hits = _errors(doc, "jaxpr.extra-entries")
    assert hits and hits[0]["data"]["prim"] == "gather"


def test_cli_purity_clean_on_repo(tmp_path):
    """The shipped tree passes the purity family (exit 0, no errors) —
    the same pass CI runs over all families."""
    proc, doc = _run_cli("--rules", "purity", tmp_path=tmp_path)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert doc["failed"] is False
    assert doc["summary"].get("error", 0) == 0


def test_cli_rejects_unknown_family(tmp_path):
    proc, _ = _run_cli("--rules", "nonsense", tmp_path=tmp_path)
    assert proc.returncode == 2


# ------------------------------------------------------- library pieces


def test_vmem_estimator_flags_oversized_kernel():
    from repro.analysis.vmem import estimate_call
    sys.path.insert(0, _FIX)
    try:
        import bad_kernel
    finally:
        sys.path.pop(0)
    (name, fn, args), = bad_kernel.TRACE_ENTRIES
    (fp,) = estimate_call(fn, *args)
    assert fp.vmem_bytes > 16 * 2**20
    assert fp.double_buffered and fp.grid == (2,)


def test_vmem_estimator_shipped_kernels_fit():
    """In-process version of the budget rule on one config — the zoo
    entries must all lower a pallas_call and fit 16 MiB."""
    from repro.analysis import Context, run_rules
    findings = run_rules(Context(configs=("llama31_8b",)),
                         families=["vmem"])
    errs = [f for f in findings if f.severity == "error"]
    assert not errs, [f.message for f in errs]


def test_purity_layering_poisoned_vs_clean():
    from repro.analysis.purity import run_layering
    bad = run_layering(os.path.join(_FIX, "poisoned_src"))
    assert any(f.rule == "purity.scheduler-jax-free"
               and f.severity == "error" for f in bad)
    clean = run_layering(_SRC)
    assert not [f for f in clean if f.severity == "error"], \
        [f.message for f in clean]


def test_purity_lazy_contract_tracks_function_scope():
    from repro.analysis.purity import check_lazy_import, scan_tree
    tree = scan_tree(_SRC)
    paged = tree["repro.serve.paged"]
    assert not check_lazy_import(paged, "jax", ("init_paged_cache",))
    # the contract bites: pretend the allowance list is empty
    assert check_lazy_import(paged, "jax", ())


def test_pool_eqn_count_and_pallas_walk():
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr_utils import (count_pallas_calls,
                                            pool_eqn_count)
    pool = jax.ShapeDtypeStruct((8, 4, 2, 2), jnp.float32)
    idx = jax.ShapeDtypeStruct((3,), jnp.int32)

    def gather_in_scan(pool, idx):
        # nested under scan so the recursive walk is exercised
        def body(c, i):
            return c + jnp.take(pool, idx, axis=0).sum(), None
        out, _ = jax.lax.scan(body, jnp.float32(0.0), jnp.arange(2))
        return out

    closed = jax.make_jaxpr(gather_in_scan)(pool, idx)
    assert pool_eqn_count(closed, (8, 4, 2, 2), "gather") >= 1
    assert count_pallas_calls(closed) == 0


def test_declared_trace_keys_cover_buckets():
    from repro.serve.executor import STEP_BUCKETS, declared_trace_keys
    keys = declared_trace_keys()
    for name in STEP_BUCKETS.values():
        assert name in keys and name + "_oracle" in keys
    for legacy in ("prefill", "decode", "prefill_replay"):
        assert legacy in keys and legacy + "_oracle" in keys


def test_findings_json_schema():
    from repro.analysis import Finding, findings_to_json
    doc = json.loads(findings_to_json([
        Finding("vmem.budget", "error", "k", "boom", {"x": 1}),
        Finding("vmem.budget", "info", "k2", "fine"),
    ]))
    assert doc["schema_version"] == 1
    assert doc["failed"] is True
    assert doc["summary"] == {"error": 1, "info": 1}
    assert doc["findings"][0]["data"] == {"x": 1}


# ------------------------------------------------- races/hbm/numerics


@pytest.mark.parametrize("fixture,kind", [
    ("race_write_write", "aliased-raw"),
    ("race_oob_index", "oob"),
    ("race_discontiguous", "out-revisit"),
])
def test_cli_flags_seeded_grid_race(tmp_path, fixture, kind):
    """Each seeded racy grid yields EXACTLY ONE structured finding of
    its hazard class through the real CLI."""
    proc, doc = _run_cli(
        "--rules", "races.extra-entries",
        "--grid-extra", os.path.join(_FIX, fixture + ".py"),
        tmp_path=tmp_path)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    hits = _errors(doc, "races.extra-entries")
    assert len(hits) == 1, doc["findings"]
    assert hits[0]["obj"] == fixture
    assert hits[0]["data"]["kind"] == kind


def test_cli_flags_int8_accumulator(tmp_path):
    """int8×int8 dot_general without preferred_element_type must fail
    the numerics lint with exactly one finding."""
    proc, doc = _run_cli(
        "--rules", "numerics.extra-entries",
        "--numerics-extra", os.path.join(_FIX, "bad_int8_accum.py"),
        tmp_path=tmp_path)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    hits = _errors(doc, "numerics.extra-entries")
    assert len(hits) == 1, doc["findings"]
    assert hits[0]["data"]["kind"] == "int8-accum"


def test_cli_flags_stale_cost_model(tmp_path):
    """A cost formula 10x off its kernel's measured bytes must fail the
    hbm divergence check with exactly one finding."""
    proc, doc = _run_cli(
        "--rules", "hbm.extra-entries",
        "--hbm-extra", os.path.join(_FIX, "stale_cost_model.py"),
        tmp_path=tmp_path)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    hits = _errors(doc, "hbm.extra-entries")
    assert len(hits) == 1, doc["findings"]
    assert hits[0]["obj"] == "stale_cost_model"
    assert hits[0]["data"]["divergence"] > 0.10


def test_cli_baseline_demotes_known_error(tmp_path):
    """A (rule, obj) suppression in the baseline turns the error into a
    tracked warning: exit 0, finding kept with data.baselined."""
    base = os.path.join(str(tmp_path), "baseline.json")
    with open(base, "w") as fh:
        json.dump({"suppressions": [
            {"rule": "races.extra-entries", "obj": "race_oob_index",
             "reason": "tracked for the test"}]}, fh)
    proc, doc = _run_cli(
        "--rules", "races.extra-entries",
        "--grid-extra", os.path.join(_FIX, "race_oob_index.py"),
        "--baseline", base, tmp_path=tmp_path)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert doc["failed"] is False
    warns = [f for f in doc["findings"]
             if f["rule"] == "races.extra-entries"
             and f["severity"] == "warning"]
    assert len(warns) == 1 and warns[0]["data"]["baselined"] is True


def test_cli_severity_filters_report_not_exit(tmp_path):
    """--severity error hides info rows from the report; errors still
    fail and a clean run still exits 0."""
    proc, doc = _run_cli(
        "--rules", "hbm.doc-sync", "--severity", "error",
        tmp_path=tmp_path)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "[INFO " not in proc.stdout
    assert doc["summary"].get("info", 0) >= 1  # JSON keeps everything


def test_cli_rule_globs(tmp_path):
    """fnmatch globs select rules; a glob matching nothing is a usage
    error (a typo must not silently select zero checks)."""
    proc, doc = _run_cli(
        "--rules", "races.extra-*",
        "--grid-extra", os.path.join(_FIX, "race_oob_index.py"),
        tmp_path=tmp_path)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert _errors(doc, "races.extra-entries")
    proc, _ = _run_cli("--rules", "races.nomatch*", tmp_path=tmp_path)
    assert proc.returncode == 2


def test_races_coverage_spans_zoo_and_buckets():
    """The races sweep enumerates every kernel-zoo entry point AND every
    STEP_BUCKETS step program — the coverage counts are part of the
    contract, so a silently skipped kernel breaks this test."""
    from repro.analysis import Context
    from repro.analysis.grid_eval import (rule_races_kernel_zoo,
                                          rule_races_step_buckets)
    from repro.analysis.vmem import kernel_zoo_entries
    from repro.configs.base import get_smoke_config
    from repro.serve.executor import STEP_BUCKETS

    ctx = Context()
    zoo = rule_races_kernel_zoo(ctx)
    assert not [f for f in zoo if f.severity == "error"], \
        [f.message for f in zoo]
    (cov,) = [f for f in zoo if f.severity == "info"
              and "coverage" in f.data]
    required = {name for name, _ in
                kernel_zoo_entries(get_smoke_config(ctx.arch))}
    assert set(cov.data["coverage"]) == required
    assert all(n >= 1 for n in cov.data["coverage"].values())

    buckets = rule_races_step_buckets(ctx)
    assert not [f for f in buckets if f.severity == "error"], \
        [f.message for f in buckets]
    (bcov,) = [f for f in buckets if f.severity == "info"]
    assert set(bcov.data["coverage"]) == set(STEP_BUCKETS.values())
    assert all(n >= 1 for n in bcov.data["coverage"].values())


def test_grid_eval_sentinel_exemption():
    """The scatter kernel's parked steps (sentinel row) are exempt; the
    legacy park-on-live-block remap is precisely what gets flagged (the
    race_write_write fixture covers the flagged side)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis.grid_eval import (check_grid, eval_pallas_eqn,
                                          trace_and_collect)
    from repro.kernels.paged_attention import paged_kv_scatter_pallas
    from repro.serve.paged import device_pool_rows

    bs, mb, nb, hkv, hd, t = 8, 8, 16, 2, 16, 16
    pool = jnp.zeros((device_pool_rows(nb), hkv, bs, hd), jnp.float32)
    tab = np.full((2, mb), -1, np.int32)
    tab[0, :2] = [1, 2]
    tab[1, 1:4] = [5, 6, 7]
    knew = jnp.zeros((2, t, hkv, hd), jnp.float32)
    calls = trace_and_collect(
        lambda *a: paged_kv_scatter_pallas(*a, interpret=True),
        knew, knew, pool, pool, jnp.asarray(tab),
        jnp.asarray([0, 12], jnp.int32), jnp.asarray([t, t], jnp.int32))
    assert len(calls) == 1
    ge = eval_pallas_eqn(calls[0].eqn, calls[0].invals)
    assert not isinstance(ge, str), ge
    issues = check_grid(ge)
    assert not [i for i in issues if not i.get("info")], issues
    # row 0's chunk [0,16) spans 3 logical steps but only 2 allocated
    # blocks — the third parks on the sentinel and is reported as info
    assert any(i["kind"] == "sentinel-parked" for i in issues)


def test_hbm_measured_matches_cost_model():
    """In-process version of hbm.cost-model: zero errors, and every
    COST_MODEL entry was exercised."""
    from repro.analysis import Context
    from repro.analysis.hbm import rule_hbm_cost_model
    from repro.kernels import COST_MODEL

    findings = rule_hbm_cost_model(Context())
    errs = [f for f in findings if f.severity == "error"]
    assert not errs, [f.message for f in errs]
    checked = {f.obj for f in findings if f.severity == "info"}
    assert checked == set(COST_MODEL)


@pytest.mark.parametrize("shapes", [(8, 4), [(8, 4), (32,)]])
def test_pool_shape_normalization(shapes):
    """pool_eqn_count accepts one shape tuple or an iterable of them."""
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr_utils import pool_eqn_count
    x = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    i = jax.ShapeDtypeStruct((2,), jnp.int32)
    closed = jax.make_jaxpr(lambda x, i: jnp.take(x, i, axis=0))(x, i)
    assert pool_eqn_count(closed, shapes, "gather") == 1
