"""CI gate over a ``BENCH_*.json`` trajectory: the latest run must carry
every expected kernel row with a finite, positive wall-time, and no row
may regress beyond the threshold against the previous run.

    PYTHONPATH=src python benchmarks/check_bench.py bench_ci.json \
        [--threshold 0.5] [--no-regress-gate]

A kernel that stops lowering in the CPU interpreter (or starts
returning NaN timings) would otherwise just drop out of the trajectory and
the regression would go unnoticed until someone eyeballed the JSON —
``benchmarks/run.py`` only exits non-zero on ordering-claim FAILs, not on
missing rows.

The regression compare is latest-vs-PREVIOUS trajectory entry, per row
name: a row whose ``us_per_call`` grew by more than ``threshold``
(fractional, default 0.5 — interpret-mode CPU timings are noisy) fails
the gate unless ``--no-regress-gate`` demotes regressions to warnings.
Rows present in only one of the two runs are never regression-compared
(the required-row scan already catches disappearances).

Latest-vs-previous alone lets slow drift compound: N consecutive +40%
steps each pass the 50% gate while the cumulative cost explodes (the
ROADMAP notes ~25% interpret-mode drift already).  ``--since-seed
BENCH_seed_cpu.json`` additionally gates the latest run's ``kernel/*``
rows against the FIRST entry of the seed trajectory — the repo's
original baseline — with a wider ``--seed-threshold`` (default 2.0,
i.e. 3x the seed timing) that absorbs noise but caps total drift.
Kernel rows added after the seed have no baseline and are skipped.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from typing import List

DEFAULT_REGRESS_THRESHOLD = 0.5
DEFAULT_SEED_THRESHOLD = 2.0

# one prefix per fused-kernel hot path benchmarked by kernel_bench.run()
REQUIRED_KERNEL_ROWS = (
    "kernel/nm_prune/",
    "kernel/nm_prune_matmul/",
    "kernel/nm_spmm/",
    "kernel/w8a8/",
    "kernel/osparse_matmul/",
    "kernel/paged_attention/",
)
# scheduler-level rows gated by bench-smoke (serving table): prefix_reuse
# embeds its own hit-rate / skip-fraction / token-identity PASS gate in
# the derived column, which the FAIL scan below enforces
REQUIRED_SERVING_ROWS = (
    "serving/prefix_reuse",
    # fused one-dispatch step vs the legacy two-program split; derived
    # embeds the token-identity verdict and dispatches_per_iteration
    "serving/one_dispatch",
    # dp=2 router-sharded serving: derived embeds per-replica dpi and the
    # token-identity-vs-dp1 verdict
    "serving/sharded_dp2",
)
REQUIRED_ROWS = REQUIRED_KERNEL_ROWS + REQUIRED_SERVING_ROWS


def check_trajectory(path: str,
                     required=REQUIRED_ROWS) -> List[str]:
    """Returns a list of problems with the LATEST run in the trajectory
    (empty = healthy)."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path}: unreadable trajectory ({e})"]
    if not isinstance(data, list) or not data:
        return [f"{path}: not a non-empty trajectory list"]
    run = data[-1]
    rows = run.get("rows", [])
    errors = []
    for prefix in required:
        matches = [r for r in rows if str(r.get("name", "")).startswith(prefix)]
        if not matches:
            errors.append(f"missing required row {prefix}*")
        for r in matches:
            derived = str(r.get("derived", ""))
            # a required scenario that self-reports SKIP (e.g. paging
            # auto-disabled for the bench arch) still fails the gate, but
            # with the real cause instead of a bogus 0.0-timing complaint
            if "SKIP" in derived:
                errors.append(
                    f"{r['name']}: required row was skipped ({derived})")
                continue
            us = r.get("us_per_call")
            if not (isinstance(us, (int, float)) and math.isfinite(us)
                    and us > 0):
                errors.append(
                    f"{r['name']}: non-finite us_per_call {us!r}")
            # required rows embed their correctness claims (ordering,
            # token-identity, reuse rates) as PASS/FAIL in derived —
            # a FAIL must fail the artifact gate, not just run.py's exit
            if "FAIL" in derived:
                errors.append(f"{r['name']}: derived claims FAIL "
                              f"({derived})")
    return errors


def _finite_timings(run) -> dict:
    out = {}
    for r in run.get("rows", []):
        us = r.get("us_per_call")
        if (isinstance(us, (int, float)) and math.isfinite(us) and us > 0):
            out[str(r.get("name", ""))] = float(us)
    return out


def check_regressions(path: str,
                      threshold: float = DEFAULT_REGRESS_THRESHOLD
                      ) -> List[str]:
    """Latest-vs-previous per-row wall-time compare over the trajectory.

    Returns one message per row whose ``us_per_call`` grew by more than
    ``threshold`` (fractional) since the previous run.  Trajectories with
    fewer than two runs (fresh artifacts) have nothing to compare and
    return [] — the health scan in ``check_trajectory`` still applies.
    """
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return []   # unreadable is check_trajectory's complaint, not ours
    if not isinstance(data, list) or len(data) < 2:
        return []
    prev, cur = _finite_timings(data[-2]), _finite_timings(data[-1])
    problems = []
    for name in sorted(set(prev) & set(cur)):
        if cur[name] > prev[name] * (1.0 + threshold):
            pct = 100.0 * (cur[name] / prev[name] - 1.0)
            problems.append(
                f"{name}: {prev[name]:.1f} -> {cur[name]:.1f} us/call "
                f"(+{pct:.0f}% > {threshold:.0%} threshold)")
    return problems


def check_since_seed(path: str, seed_path: str,
                     threshold: float = DEFAULT_SEED_THRESHOLD
                     ) -> List[str]:
    """Latest run's ``kernel/*`` rows vs the FIRST entry of the seed
    trajectory — the anti-compounding gate.  Returns one message per
    kernel row whose ``us_per_call`` grew past ``threshold`` (fractional)
    since the seed; seed-less rows (added later) are skipped, but an
    unreadable/empty seed file is an error (a silently absent baseline
    would turn the gate off)."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        return []   # unreadable is check_trajectory's complaint, not ours
    if not isinstance(data, list) or not data:
        return []
    try:
        with open(seed_path) as f:
            seed_data = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"{seed_path}: unreadable seed baseline ({e})"]
    if not isinstance(seed_data, list) or not seed_data:
        return [f"{seed_path}: not a non-empty seed trajectory"]
    seed = {n: us for n, us in _finite_timings(seed_data[0]).items()
            if n.startswith("kernel/")}
    if not seed:
        return [f"{seed_path}: seed entry has no finite kernel/* rows"]
    cur = _finite_timings(data[-1])
    problems = []
    for name in sorted(set(seed) & set(cur)):
        if cur[name] > seed[name] * (1.0 + threshold):
            pct = 100.0 * (cur[name] / seed[name] - 1.0)
            problems.append(
                f"{name}: seed {seed[name]:.1f} -> {cur[name]:.1f} us/call "
                f"(+{pct:.0f}% > {threshold:.0%} since-seed threshold)")
    return problems


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv[1:])
    ap = argparse.ArgumentParser()
    ap.add_argument("path", nargs="?", default="bench_ci.json")
    ap.add_argument("--threshold", type=float,
                    default=DEFAULT_REGRESS_THRESHOLD,
                    help="max fractional us_per_call growth vs the previous "
                         "trajectory entry before the gate fails")
    ap.add_argument("--no-regress-gate", action="store_true",
                    help="report regressions as warnings instead of "
                         "failing the gate")
    ap.add_argument("--since-seed", default=None, metavar="SEED_JSON",
                    help="also gate kernel/* rows of the latest run "
                         "against the FIRST entry of this seed "
                         "trajectory (anti-compounding drift gate)")
    ap.add_argument("--seed-threshold", type=float,
                    default=DEFAULT_SEED_THRESHOLD,
                    help="max fractional us_per_call growth vs the seed "
                         "baseline (wider than --threshold: cumulative)")
    args = ap.parse_args(argv)
    errors = check_trajectory(args.path)
    regressions = check_regressions(args.path, args.threshold)
    if args.since_seed:
        regressions += check_since_seed(args.path, args.since_seed,
                                        args.seed_threshold)
    for e in errors:
        print(f"BENCH CHECK FAIL: {e}")
    for r in regressions:
        tag = "WARN" if args.no_regress_gate else "FAIL"
        print(f"BENCH REGRESSION {tag}: {r}")
    if errors or (regressions and not args.no_regress_gate):
        return 1
    with open(args.path) as f:
        run = json.load(f)[-1]
    print(f"bench check OK: {len(run.get('rows', []))} rows "
          f"@ {run.get('utc', '?')} "
          f"(tables: {','.join(run.get('tables', []))})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
