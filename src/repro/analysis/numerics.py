"""Quantization/softmax numerics lints over Pallas kernel bodies.

Interpret-mode CPU tests run the kernels through XLA, which hides a
class of numerics bugs that only bite on real hardware or at real model
scale: an int8×int8 ``dot_general`` without ``preferred_element_type``
accumulates in int8 on the MXU (wraps at ±127 — CPU interpret happily
widens), a quant-scale divide by an unguarded computed amax produces
inf/NaN exactly when a block is all zeros, and an online-softmax body
that reinvents the running-max update with literal ``-inf`` produces
NaN (``-inf - -inf``) for fully-masked rows.  These are properties of
the kernel JAXPR, so they are lintable statically.

Lints (each aggregated to at most one finding per kernel):

  ``int8-accum``     every dot_general whose operands are both int8 must
                     set ``preferred_element_type`` to int32/float32.
  ``div-guard``      float divides whose divisor is COMPUTED inside the
                     body (not a ref load / input) must have a
                     ``max``/``clamp`` in the divisor's def-chain —
                     ``jnp.maximum(amax, eps)`` style.  Ref-load
                     divisors are exempt: ``x / smooth`` is the
                     SmoothQuant input contract.
  ``softmax-guard``  bodies containing ``exp`` must carry the shared
                     online-softmax guard shape (a running ``max``
                     reduction and a ``select``/``where`` rescue) and no
                     ``±inf`` literals — the shared helpers use a finite
                     ``_NEG`` sentinel for exactly this reason.
  ``f64``            no float64 anywhere in a kernel body (TPU has no
                     f64; interpret mode silently does).
  ``cast-roundtrip`` no lossy dtype round-trip ``a → b → a`` with
                     ``b`` narrower than ``a`` (precision silently
                     dropped and re-widened).
"""
from __future__ import annotations

from typing import Any, Dict, List, Set, Tuple

from repro.analysis import Context, Finding, rule

__all__ = ["lint_kernel_body"]

_ACCUM_OK = ("int32", "float32")
_GUARD_PRIMS = {"max", "clamp"}
_LOAD_PRIMS = {"get", "masked_load", "load", "swap", "masked_swap"}
# pure data movement: a value that is just a moved ref-load stays exempt
_MOVE_PRIMS = {"broadcast_in_dim", "reshape", "squeeze", "slice",
               "dynamic_slice", "transpose", "convert_element_type",
               "expand_dims"}


def _all_eqns(jaxpr) -> List[Any]:
    """Flatten a kernel jaxpr including sub-jaxprs (``pl.when`` lowers
    to ``cond``; loops carry bodies in params)."""
    from jax.extend import core as jax_core

    out = []
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            out.append(eqn)
            for v in eqn.params.values():
                vs = v if isinstance(v, (tuple, list)) else (v,)
                for s in vs:
                    if isinstance(s, jax_core.ClosedJaxpr):
                        stack.append(s.jaxpr)
                    elif isinstance(s, jax_core.Jaxpr):
                        stack.append(s)
    return out


def _def_chain_has(var, defs: Dict[Any, Any], prims: Set[str],
                   stop: Set[str]) -> bool:
    """BFS the def-chain of ``var``: True iff some defining primitive is
    in ``prims`` before hitting one in ``stop``."""
    seen: Set[int] = set()
    frontier = [var]
    while frontier:
        v = frontier.pop()
        if hasattr(v, "val") or id(v) in seen:
            continue
        seen.add(id(v))
        eqn = defs.get(v)
        if eqn is None:
            continue
        if eqn.primitive.name in prims:
            return True
        if eqn.primitive.name in stop:
            continue
        frontier.extend(eqn.invars)
    return False


def _is_loaded(var, defs: Dict[Any, Any]) -> bool:
    """Is ``var`` a ref load / kernel input (possibly through pure data
    movement)?  Such values are inputs by contract, not computed."""
    v = var
    while True:
        if hasattr(v, "val"):
            return False
        eqn = defs.get(v)
        if eqn is None:
            return True                      # invar / constvar
        nm = eqn.primitive.name
        if nm in _LOAD_PRIMS:
            return True
        if nm in _MOVE_PRIMS:
            v = eqn.invars[0]
            continue
        return False


def lint_kernel_body(name: str, jaxpr) -> List[Dict[str, Any]]:
    """All numerics lint hits for one kernel-body jaxpr, aggregated to
    one issue dict per lint kind."""
    import numpy as np

    eqns = _all_eqns(jaxpr)
    defs: Dict[Any, Any] = {}
    for eqn in eqns:
        for ov in eqn.outvars:
            defs[ov] = eqn

    hits: Dict[str, Dict[str, Any]] = {}

    def hit(kind: str, detail: str):
        h = hits.setdefault(kind, {"kind": kind, "kernel": name,
                                   "count": 0, "detail": detail})
        h["count"] += 1

    has_exp = False
    has_reduce_max = False
    has_select = False
    inf_literals = 0

    for eqn in eqns:
        nm = eqn.primitive.name
        if nm in ("exp", "exp2"):
            has_exp = True
        if nm in ("reduce_max", "cummax", "argmax"):
            has_reduce_max = True
        if nm in ("select_n", "select"):
            has_select = True
        for iv in eqn.invars:
            if hasattr(iv, "val"):
                val = np.asarray(iv.val)
                if val.dtype.kind == "f" and val.size and np.isinf(val).any():
                    inf_literals += 1

        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and str(getattr(aval, "dtype", "")
                                        ) == "float64":
                hit("f64", f"{nm} touches float64")
                break

        if nm == "dot_general":
            lhs, rhs = eqn.invars[0].aval.dtype, eqn.invars[1].aval.dtype
            if str(lhs) == "int8" and str(rhs) == "int8":
                pet = eqn.params.get("preferred_element_type")
                if pet is None or str(np.dtype(pet)) not in _ACCUM_OK:
                    hit("int8-accum",
                        f"int8xint8 dot_general accumulates in "
                        f"{pet or 'int8 (default)'} — must set "
                        "preferred_element_type to int32/float32")

        if nm == "div" and eqn.invars[0].aval.dtype.kind == "f":
            divisor = eqn.invars[1]
            if hasattr(divisor, "val"):
                val = np.asarray(divisor.val)
                if (val == 0).any():
                    hit("div-guard", "literal zero divisor")
            elif not _is_loaded(divisor, defs):
                if not _def_chain_has(divisor, defs, _GUARD_PRIMS,
                                      _LOAD_PRIMS):
                    hit("div-guard",
                        "computed divisor has no max/clamp guard in its "
                        "def-chain — divide-by-zero on all-zero blocks")

        if nm == "convert_element_type":
            inner = defs.get(eqn.invars[0])
            if inner is not None and \
                    inner.primitive.name == "convert_element_type":
                src = inner.invars[0].aval.dtype
                mid = inner.outvars[0].aval.dtype
                dst = eqn.outvars[0].aval.dtype
                if (str(src) == str(dst) and str(mid) != str(src)
                        and np.dtype(mid).itemsize
                        < np.dtype(src).itemsize):
                    hit("cast-roundtrip",
                        f"{src}->{mid}->{dst} round-trip silently drops "
                        "precision")

    if has_exp:
        if not (has_reduce_max and has_select):
            hit("softmax-guard",
                "body computes exp without the shared online-softmax "
                "guard shape (running max reduction + select rescue)")
        if inf_literals:
            hit("softmax-guard",
                f"{inf_literals} ±inf literal(s) in an exp-carrying body "
                "— use the finite _NEG sentinel (softmax helpers) so "
                "fully-masked rows don't produce -inf - -inf = NaN")

    return list(hits.values())


def _lint_traced(name: str, fn, args) -> Tuple[int, List[Dict[str, Any]]]:
    """(bodies linted, issues) over every pallas_call in a trace."""
    from repro.analysis.grid_eval import trace_and_collect

    issues: List[Dict[str, Any]] = []
    calls = trace_and_collect(fn, *args)
    for call in calls:
        body = call.eqn.params["jaxpr"]
        kernel = getattr(call.eqn.params.get("name_and_src_info"),
                         "name", None) or name
        issues.extend(lint_kernel_body(f"{name}:{kernel}", body.jaxpr
                      if hasattr(body, "jaxpr") else body))
    return len(calls), issues


def _issues_to_findings(rule_name: str, obj: str,
                        issues: List[Dict[str, Any]]) -> List[Finding]:
    return [Finding(
        rule=rule_name, severity="error", obj=obj,
        message=(f"{issue['kernel']}: [{issue['kind']}] "
                 f"{issue['detail']} (x{issue['count']})"),
        data=issue) for issue in issues]


@rule("numerics.kernel-zoo", family="numerics")
def rule_numerics_kernel_zoo(ctx: Context) -> List[Finding]:
    """Every kernel-zoo entry's pallas bodies pass the numerics lints;
    an entry with zero linted bodies is an error (silent fallback)."""
    from repro.analysis.vmem import grid_zoo_entries
    from repro.configs.base import get_smoke_config

    cfg = get_smoke_config(ctx.arch)
    findings: List[Finding] = []
    linted = 0
    for e in grid_zoo_entries(cfg):
        n, issues = _lint_traced(e.name, e.fn, e.args)
        if n == 0:
            findings.append(Finding(
                rule="numerics.kernel-zoo", severity="error", obj=e.name,
                message=f"{e.name}: zero pallas bodies to lint — the "
                "dispatch silently fell back"))
        linted += n
        findings.extend(_issues_to_findings("numerics.kernel-zoo",
                                            e.name, issues))
    findings.append(Finding(
        rule="numerics.kernel-zoo", severity="info", obj="kernel-zoo",
        message=f"linted {linted} kernel bodies"))
    return findings


@rule("numerics.extra-entries", family="numerics")
def rule_numerics_extra(ctx: Context) -> List[Finding]:
    """Fixture hook: ``--numerics-extra`` module's ``NUMERICS_ENTRIES``
    ``(name, fn, args)`` bodies get the same lints."""
    if not ctx.numerics_extra:
        return [Finding(rule="numerics.extra-entries", severity="info",
                        obj="fixtures", message="no extra bodies")]
    mod = ctx.load_extra(ctx.numerics_extra)
    findings: List[Finding] = []
    for name, fn, args in mod.NUMERICS_ENTRIES:
        _, issues = _lint_traced(name, fn, args)
        findings.extend(_issues_to_findings("numerics.extra-entries",
                                            name, issues))
    if not findings:
        findings.append(Finding(
            rule="numerics.extra-entries", severity="info", obj="fixtures",
            message=f"{len(mod.NUMERICS_ENTRIES)} extra bodies clean"))
    return findings
