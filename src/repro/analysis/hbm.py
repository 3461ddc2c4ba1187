"""Machine-verified HBM cost model.

``repro.kernels.COST_MODEL`` documents, as closed-form formulas, how
many HBM bytes each kernel call moves under Mosaic's pipelined
fetch/write semantics.  Docs rot; this module makes them a checked
contract by MEASURING the same quantity from the kernels' real grids:

    measured = Σ_inputs  (fetch runs  × block bytes)
             + Σ_outputs (write runs × block bytes)
             + Σ_DMAs    (bytes a kernel body copies itself)

where "runs" is the maximal-constant-run compression of each operand's
per-grid-step block-index sequence, obtained by concretely evaluating
every BlockSpec index map over the enumerated grid
(:mod:`repro.analysis.grid_eval`) — i.e. exactly the refetch/write-back
elision Mosaic's pipeline performs.  An operand left in HBM (``pl.ANY``)
has no pipeline; its traffic is the DMAs the body starts, found by the
same walk evaluating the body's scalar control flow at every step.  The formulas are an independent
re-derivation from the documented contract, so >10% divergence means a
kernel's grid/BlockSpecs changed without the cost model (or the model
was wrong all along) — either way, CI fails until they agree.

Three rules:

  ``hbm.cost-model``  measured vs formula for every grid-zoo entry, both
                      directions of coverage (a zoo entry without a
                      formula and a formula without a zoo entry are
                      errors — silent gaps would fake a green run).
  ``hbm.doc-sync``    the generated table in the ``repro.kernels``
                      docstring must equal ``cost_model_doc()``.
  ``hbm.extra-entries``  fixture hook (``--hbm-extra``): COST_ENTRIES
                      ``(name, fn, args, bytes_fn, dims)`` tuples get
                      the same measured-vs-formula treatment.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

from repro.analysis import Context, Finding, rule
from repro.analysis.grid_eval import (GridEval, _runs, eval_pallas_eqn,
                                      trace_and_collect)

__all__ = ["measured_call_bytes", "DIVERGENCE_TOLERANCE"]

DIVERGENCE_TOLERANCE = 0.10


def measured_call_bytes(ge: GridEval) -> Tuple[int, Dict[str, Any]]:
    """(total bytes, per-operand breakdown) for one evaluated grid."""
    total = 0
    detail: Dict[str, Any] = {}
    for og in ge.inputs + ge.outputs:
        if og.manual:
            dmas = [d for d in ge.dmas if d.operand == og.idx]
            byts = sum(d.nbytes for d in dmas)
            detail[og.label] = {"dmas": len(dmas), "bytes": byts}
        else:
            runs = len(_runs(og.indices))
            byts = runs * og.block_bytes
            detail[og.label] = {"runs": runs,
                                "block_bytes": og.block_bytes,
                                "bytes": byts}
        total += byts
    return total, detail


def _measure_traced(name: str, fn, args) -> Any:
    """Measured bytes summed over every pallas_call the trace contains,
    or an error-message string."""
    calls = trace_and_collect(fn, *args)
    if not calls:
        return f"{name}: traced zero pallas_calls — nothing to measure"
    total = 0
    details = []
    for call in calls:
        ge = eval_pallas_eqn(call.eqn, call.invals)
        if isinstance(ge, str):
            return f"{name}: {ge}"
        byts, detail = measured_call_bytes(ge)
        total += byts
        details.append({"kernel": ge.kernel, "grid": list(ge.grid),
                        "bytes": byts, "operands": detail})
    return total, details


def _compare(name: str, rule_name: str, measured, predicted: int,
             details) -> Finding:
    denom = max(measured, 1)
    div = abs(measured - predicted) / denom
    if div > DIVERGENCE_TOLERANCE:
        return Finding(
            rule=rule_name, severity="error", obj=name,
            message=(f"{name}: measured {measured} B vs COST_MODEL "
                     f"{predicted} B — {div:.1%} divergence (> "
                     f"{DIVERGENCE_TOLERANCE:.0%}); the kernel's "
                     "grid/BlockSpecs and its documented cost formula "
                     "disagree"),
            data={"measured": measured, "predicted": predicted,
                  "divergence": div, "calls": details})
    return Finding(
        rule=rule_name, severity="info", obj=name,
        message=(f"{name}: measured {measured} B, model {predicted} B "
                 f"({div:.1%} divergence)"),
        data={"measured": measured, "predicted": predicted,
              "divergence": div})


@rule("hbm.cost-model", family="hbm")
def rule_hbm_cost_model(ctx: Context) -> List[Finding]:
    """Measured HBM bytes (block footprints × grid fetch/write runs,
    refetch elision modelled) vs ``repro.kernels.COST_MODEL`` for every
    grid-zoo entry, with two-directional coverage."""
    from repro.analysis.vmem import grid_zoo_entries
    from repro.configs.base import get_smoke_config
    from repro.kernels import COST_MODEL

    cfg = get_smoke_config(ctx.arch)
    entries = grid_zoo_entries(cfg)
    findings: List[Finding] = []
    seen = set()
    for e in entries:
        seen.add(e.name)
        if e.name not in COST_MODEL:
            findings.append(Finding(
                rule="hbm.cost-model", severity="error", obj=e.name,
                message=f"{e.name} has no COST_MODEL entry — its HBM "
                "traffic is undocumented"))
            continue
        res = _measure_traced(e.name, e.fn, e.args)
        if isinstance(res, str):
            findings.append(Finding(rule="hbm.cost-model",
                                    severity="error", obj=e.name,
                                    message=res))
            continue
        measured, details = res
        predicted = int(COST_MODEL[e.name]["bytes"](e.dims))
        findings.append(_compare(e.name, "hbm.cost-model", measured,
                                 predicted, details))
    for name in sorted(set(COST_MODEL) - seen):
        findings.append(Finding(
            rule="hbm.cost-model", severity="error", obj=name,
            message=f"COST_MODEL documents {name} but grid_zoo_entries "
            "has no such kernel — stale model entry"))
    return findings


@rule("hbm.doc-sync", family="hbm")
def rule_hbm_doc_sync(ctx: Context) -> List[Finding]:
    """The marker-delimited table in the ``repro.kernels`` docstring is
    generated from ``COST_MODEL`` — drift means someone edited one
    without the other (regenerate: ``python -m repro.analysis
    --hbm-table``)."""
    import repro.kernels as kernels_mod

    want = kernels_mod.cost_model_doc()
    doc = kernels_mod.__doc__ or ""
    start = want.splitlines()[0]
    end = want.splitlines()[-1]
    i, j = doc.find(start), doc.find(end)
    if i < 0 or j < 0:
        return [Finding(
            rule="hbm.doc-sync", severity="error", obj="repro.kernels",
            message="kernels/__init__.py docstring lost the generated "
            "HBM table markers")]
    got = doc[i:j + len(end)]
    if got != want:
        return [Finding(
            rule="hbm.doc-sync", severity="error", obj="repro.kernels",
            message="kernels/__init__.py HBM table drifted from "
            "COST_MODEL — regenerate with `python -m repro.analysis "
            "--hbm-table`",
            data={"want": want, "got": got})]
    return [Finding(rule="hbm.doc-sync", severity="info",
                    obj="repro.kernels",
                    message="generated HBM table matches COST_MODEL")]


@rule("hbm.extra-entries", family="hbm")
def rule_hbm_extra(ctx: Context) -> List[Finding]:
    """Fixture hook: ``--hbm-extra`` module's ``COST_ENTRIES``
    ``(name, fn, args, bytes_fn, dims)`` get measured-vs-model checks —
    the analyzer's own tests seed a deliberately stale formula here."""
    if not ctx.hbm_extra:
        return [Finding(rule="hbm.extra-entries", severity="info",
                        obj="fixtures", message="no extra cost entries")]
    mod = ctx.load_extra(ctx.hbm_extra)
    findings: List[Finding] = []
    for name, fn, args, bytes_fn, dims in mod.COST_ENTRIES:
        res = _measure_traced(name, fn, args)
        if isinstance(res, str):
            findings.append(Finding(rule="hbm.extra-entries",
                                    severity="error", obj=name,
                                    message=res))
            continue
        measured, details = res
        f = _compare(name, "hbm.extra-entries", measured,
                     int(bytes_fn(dims)), details)
        findings.append(f)
    return findings
