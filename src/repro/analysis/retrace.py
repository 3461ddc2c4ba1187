"""Trace-budget rules — the ``retrace`` family.

One-dispatch serving only pays off if every shape bucket compiles ONCE.
Two rules guard that:

  * ``retrace.trace-budget``  drives a real (tiny, CPU-interpret) engine
    run end-to-end and compares the executor's observed ``trace_counts``
    against :func:`repro.serve.executor.declared_trace_keys`: every
    observed key must be declared, and every declared-and-hit bucket must
    have traced exactly once.  An undeclared key is an unbounded bucket
    (something is keying traces on a value, not a shape class); a count
    > 1 is a retrace — both error.
  * ``retrace.closure-captures``  inspects the raw step programs'
    closures (``__closure__`` cells, recursively through nested
    functions): a captured jax/numpy array or mutable container would
    either bake silently-stale data into the trace or defeat jit caching
    — the step programs may close over static config objects and the
    executor only.
"""
from __future__ import annotations

from typing import Any, List, Set

from repro.analysis import Context, Finding, rule

__all__ = []


def _err(rule_name, obj, msg, **data):
    return Finding(rule=rule_name, severity="error", obj=obj, message=msg,
                   data=data)


# ------------------------------------------------------ dry-run budget

def _dry_run(ctx: Context):
    """Serve a few tiny prompts through the serving engine and return it
    (cached — the jaxpr rules' fixture engine is separate on purpose:
    this one must actually execute)."""
    if "dry_engine" in ctx._cache:
        return ctx._cache["dry_engine"]
    import jax
    import numpy as np

    from repro.core.policy import DENSE
    from repro.serve.continuous import (ContinuousConfig,
                                        ContinuousServingEngine)

    cfg, model, params = ctx.smoke_model()
    pol = DENSE.with_(use_pallas_kernels=True)
    eng = ContinuousServingEngine(model, pol, ContinuousConfig(
        max_seq=64, num_slots=2, chunk_size=8, block_size=8))
    # staggered arrivals so prefill-only, hybrid, and decode-only buckets
    # all occur; lengths force multi-chunk prefill
    prompts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(i), (l,), 0, cfg.vocab_size))
        for i, l in enumerate((9, 17, 12))]
    for i, (p, a) in enumerate(zip(prompts, (0, 0, 3))):
        eng.submit(p, max_new_tokens=5, arrival=a)
    res = eng.run(params)
    ctx._cache["dry_engine"] = (eng, res)
    return ctx._cache["dry_engine"]


@rule("retrace.trace-budget", family="retrace")
def rule_trace_budget(ctx: Context) -> List[Finding]:
    """Observed trace_counts from a dry run ⊆ declared buckets, each
    traced exactly once."""
    from repro.serve.executor import declared_trace_keys

    eng, _res = _dry_run(ctx)
    declared = set(declared_trace_keys())
    findings: List[Finding] = []
    for key, n in sorted(eng.trace_counts.items()):
        if key not in declared:
            findings.append(_err(
                "retrace.trace-budget", key,
                f"undeclared trace bucket {key!r} (observed {n} traces); "
                "declare it in executor.STEP_BUCKETS/declared_trace_keys",
                count=n))
        elif n != 1:
            findings.append(_err(
                "retrace.trace-budget", key,
                f"bucket {key!r} traced {n} times — a retrace means some "
                "operand is keying compilation on a value", count=n))
    if not eng.trace_counts:
        findings.append(_err(
            "retrace.trace-budget", "engine",
            "dry run recorded no trace_counts — the probe is broken"))
    if not findings:
        findings.append(Finding(
            rule="retrace.trace-budget", severity="info", obj="engine",
            message=f"{len(eng.trace_counts)} buckets, one trace each "
                    f"({sorted(eng.trace_counts)})",
            data={"trace_counts": dict(eng.trace_counts)}))
    return findings


# ------------------------------------------------- closure-capture lint

_BAD_CAPTURE_TYPES = (dict, list, set, bytearray)


def _is_array(obj: Any) -> bool:
    # duck-typed: jax.Array and np.ndarray both carry shape+dtype
    return hasattr(obj, "shape") and hasattr(obj, "dtype")


def _scan_closure(fn, path: str, seen: Set[int], findings: List[Finding],
                  rule_name: str) -> None:
    if not callable(fn) or id(fn) in seen:
        return
    seen.add(id(fn))
    closure = getattr(fn, "__closure__", None) or ()
    names = getattr(getattr(fn, "__code__", None), "co_freevars", ())
    for name, cell in zip(names, closure):
        try:
            val = cell.cell_contents
        except ValueError:          # empty cell
            continue
        where = f"{path} captures {name!r}"
        if _is_array(val):
            findings.append(_err(
                rule_name, path,
                f"{where}: a {type(val).__name__} array — traced programs "
                "must take arrays as operands, not closure state",
                capture=name))
        elif isinstance(val, _BAD_CAPTURE_TYPES):
            findings.append(_err(
                rule_name, path,
                f"{where}: a mutable {type(val).__name__} — step closures "
                "may hold only static config/callables", capture=name))
        elif callable(val) and getattr(val, "__closure__", None):
            _scan_closure(val, f"{path}.{name}", seen, findings, rule_name)


@rule("retrace.closure-captures", family="retrace")
def rule_closure_captures(ctx: Context) -> List[Finding]:
    """No raw step program closes over arrays or mutable containers."""
    from repro.serve.executor import STEP_BUCKETS

    eng, _res = _dry_run(ctx)
    findings: List[Finding] = []
    seen: Set[int] = set()
    for bucket, name in STEP_BUCKETS.items():
        for oracle in (False, True):
            label = name + ("_oracle" if oracle else "")
            _scan_closure(eng.exec.step_program(bucket, oracle=oracle),
                          label, seen, findings,
                          "retrace.closure-captures")
    if not findings:
        findings.append(Finding(
            rule="retrace.closure-captures", severity="info",
            obj="executor",
            message=f"{2 * len(STEP_BUCKETS)} step closures clean"))
    return findings
