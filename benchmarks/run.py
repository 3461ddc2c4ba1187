"""Benchmark harness entry point — one module per paper table.

    PYTHONPATH=src python -m benchmarks.run [--only table1,...] \
        [--json-out BENCH_kernels.json]

Prints ``name,us_per_call,derived`` CSV rows.  Ordering-claim checks embed
PASS/FAIL in the derived column; a FAIL exits non-zero.

``--json-out`` appends this run to a ``BENCH_*.json`` trajectory file: the
file holds a list of run records ``{"utc", "tables", "rows": [{"name",
"us_per_call", "derived"}, ...]}`` so successive sessions can track kernel
regressions across PRs without re-parsing CSV logs.
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import time

TABLES = ("coverage", "table1", "table2", "table3", "appendix_a",
          "sensitivity", "kernels", "serving")


def _parse_row(row: str):
    name, us, derived = row.split(",", 2)
    return {"name": name, "us_per_call": float(us), "derived": derived}


def _append_trajectory(path: str, tables, rows) -> None:
    trajectory = []
    if os.path.exists(path):
        with open(path) as f:
            trajectory = json.load(f)
        if not isinstance(trajectory, list):
            raise ValueError(f"{path} is not a BENCH trajectory (list)")
    trajectory.append({
        "utc": datetime.datetime.utcnow().isoformat(timespec="seconds"),
        "tables": list(tables),
        "rows": [_parse_row(r) for r in rows],
    })
    with open(path, "w") as f:
        json.dump(trajectory, f, indent=1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help=f"comma list from {TABLES}")
    ap.add_argument("--json-out", default=None, metavar="BENCH_*.json",
                    help="append this run's rows to a JSON trajectory file")
    args = ap.parse_args(argv)
    selected = args.only.split(",") if args.only else list(TABLES)

    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()

    from benchmarks import (appendix_a_weight_vs_act, coverage, kernel_bench,
                            sensitivity_scan, serving, table1_amber,
                            table2_osparse, table3_generation)

    runners = {
        "coverage": coverage.run,
        "table1": table1_amber.run,
        "table2": table2_osparse.run,
        "table3": table3_generation.run,
        "appendix_a": appendix_a_weight_vs_act.run,
        "sensitivity": sensitivity_scan.run,
        "kernels": kernel_bench.run,
        "serving": serving.run,
    }

    print("name,us_per_call,derived")
    failures = 0
    all_rows = []
    for name in selected:
        t0 = time.time()
        rows = runners[name]()
        all_rows.extend(rows)
        for r in rows:
            print(r, flush=True)
            if r.rstrip().endswith("FAIL"):
                failures += 1
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if args.json_out:
        _append_trajectory(args.json_out, selected, all_rows)
        print(f"# appended {len(all_rows)} rows to {args.json_out}")
    if failures:
        print(f"# {failures} ordering-claim check(s) FAILED")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
