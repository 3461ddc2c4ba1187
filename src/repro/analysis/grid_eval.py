"""Symbolic grid evaluator: race/aliasing/bounds checks for Pallas grids.

Interpret-mode CPU tests execute every grid step sequentially over one
shared buffer, which MASKS the two hazards Mosaic's pipelined lowering
actually has:

  * **discontiguous output revisit** — Mosaic keeps an output block
    resident in VMEM across *consecutive* grid steps that map to it and
    writes it back when the index changes.  A block revisited after the
    pipeline moved off it is write-after-write through a stale copy.
  * **aliased refetch-after-write** — with ``input_output_aliases`` the
    input side re-FETCHES a block from HBM at the start of each of its
    runs.  If an earlier grid step already wrote that block, the fetch
    races the in-flight write-back (RAW) — exactly the hazard a wrong
    scalar-prefetch index remap creates in ``paged_kv_scatter_pallas``.
  * **out-of-bounds block indices** — Pallas clamps them silently, so a
    table bug reads/writes the wrong block instead of failing.

None of this needs hardware to check: grids are static, and every
BlockSpec index map is a tiny jaxpr we can evaluate CONCRETELY for all
grid steps once the scalar-prefetch operands (block tables, positions,
lengths) are known.  This module

  1. traces a callable and walks its jaxpr with a constant-propagation
     pass (:func:`trace_and_collect`) that resolves small operand values
     through ``pjit``/``scan``/``cond``/... down to each ``pallas_call``
     equation — so the *serving step programs'* kernels are checked with
     their real block tables, not hand-built ones;
  2. enumerates the grid row-major (last axis innermost, the sequential
     order Mosaic pipelines in) and evaluates every index map for every
     step (:func:`eval_pallas_eqn`), via ``discharge_state`` + vmap;
  3. checks bounds / revisit-contiguity / aliased-RAW over the resulting
     per-step block-index sequences (:func:`check_grid`).

An operand left in HBM (``pl.ANY``) has no index map: the kernel body
copies from it with its own DMAs.  For such kernels the body itself is
walked at every step with the same concrete scalars (program ids,
scalar-prefetch loads, scalar arithmetic, ``pl.when`` branches and static
loops), recording each ``dma_start``'s source window
(:class:`DmaStart`), which the bounds check and the ``hbm`` rules read.

Skipped-step index remaps (PR 4's refetch-elision trick) are covered by
the same two write checks: a remap that parks on a block some other step
writes shows up as a discontiguous revisit or an aliased refetch of a
written block.  The one legal parking target is the pool's SENTINEL row
(``serve/paged.device_pool_rows``): the trailing block the allocator
never hands out.  Scalar-dependent aliased operands may park there
freely (content is never consumed), and the checker exempts exactly
that — last axis-0 block, aliased, scalar-fed — reporting the parked
step count as an ``info`` datum instead.

The ``races`` rules sweep the concrete kernel zoo
(:func:`repro.analysis.vmem.grid_zoo_entries` — coverage is derived
from ``kernel_zoo_entries``, so new kernels cannot silently skip) and
every ``STEP_BUCKETS`` step program of ``serve/executor.py``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.analysis import Context, Finding, rule

__all__ = [
    "UNKNOWN",
    "ResolvedCall",
    "trace_and_collect",
    "OperandGrid",
    "GridEval",
    "eval_pallas_eqn",
    "check_grid",
]


class _Unknown:
    """Sentinel for values the const-prop pass could not resolve."""

    def __repr__(self):
        return "UNKNOWN"


UNKNOWN = _Unknown()

# Propagate only smallish values: block tables / positions / smoke-model
# tensors resolve; nothing big enough to make eager evaluation costly.
_MAX_PROP_ELEMS = 1 << 16


@dataclasses.dataclass
class ResolvedCall:
    """One ``pallas_call`` equation with const-propagated operand values
    (``UNKNOWN`` where resolution failed) and the jaxpr path to it."""
    eqn: Any
    invals: List[Any]
    path: str


def _aval_small(aval) -> bool:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return False
    try:
        return math.prod(int(d) for d in shape) <= _MAX_PROP_ELEMS
    except (TypeError, ValueError):
        return False


def _closed(j):
    """(jaxpr, consts) from a ClosedJaxpr or open Jaxpr param value."""
    from jax.extend import core as jax_core
    if isinstance(j, jax_core.ClosedJaxpr):
        return j.jaxpr, list(j.consts)
    return j, []


def trace_and_collect(fn, *args) -> List[ResolvedCall]:
    """Trace ``fn(*args)`` and return every ``pallas_call`` equation in
    the program (recursing through pjit/scan/while/cond/custom_*), with
    operand values constant-propagated from the concrete ``args``."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    flat = jax.tree_util.tree_leaves(args)
    invals: List[Any] = list(flat)
    if len(invals) != len(closed.jaxpr.invars):
        invals = [UNKNOWN] * len(closed.jaxpr.invars)
    calls: List[ResolvedCall] = []
    _eval_jaxpr(closed.jaxpr, list(closed.consts), invals, calls, "")
    return calls


def _eval_jaxpr(jaxpr, consts, invals, calls: List[ResolvedCall],
                path: str) -> List[Any]:
    """Mixed concrete/abstract evaluation: known small values propagate
    through first-order primitives eagerly; higher-order primitives are
    recursed for ``pallas_call`` collection.  Returns outvar values
    (``UNKNOWN``-filled where resolution stopped)."""
    from jax.extend import core as jax_core

    env: Dict[Any, Any] = {}

    def read(v):
        if isinstance(v, jax_core.Literal):
            return v.val
        return env.get(v, UNKNOWN)

    def write(vs, vals):
        for v, val in zip(vs, vals):
            env[v] = val

    write(jaxpr.constvars, consts)
    write(jaxpr.invars, invals)

    for eqn in jaxpr.eqns:
        p = eqn.primitive
        vals = [read(v) for v in eqn.invars]
        known = all(not isinstance(v, _Unknown) for v in vals)
        name = p.name
        outs: List[Any] = [UNKNOWN] * len(eqn.outvars)

        if name == "pallas_call":
            calls.append(ResolvedCall(eqn, vals, path))
        elif name in ("pjit", "jit"):
            j, c = _closed(eqn.params["jaxpr"])
            outs = _eval_jaxpr(j, c, vals, calls, path + "/" + name)
        elif name in ("custom_jvp_call", "custom_vjp_call"):
            j, c = _closed(eqn.params["call_jaxpr"])
            outs = _eval_jaxpr(j, c, vals, calls, path + "/" + name)
        elif name in ("remat", "checkpoint", "remat2", "core_call",
                      "closed_call", "call"):
            j, c = _closed(eqn.params.get("jaxpr")
                           or eqn.params.get("call_jaxpr"))
            outs = _eval_jaxpr(j, c, vals, calls, path + "/" + name)
        elif name == "scan":
            # one body pass: consts + INITIAL carry are seeded (block
            # tables / positions are loop-invariant in the step
            # programs), per-iteration xs slices stay UNKNOWN.  Loop
            # outputs are not short-circuited.
            j, c = _closed(eqn.params["jaxpr"])
            nc = eqn.params["num_consts"]
            ncar = eqn.params["num_carry"]
            body_in = (vals[:nc + ncar]
                       + [UNKNOWN] * (len(j.invars) - nc - ncar))
            _eval_jaxpr(j, c, body_in, calls, path + "/scan")
        elif name == "while":
            j, c = _closed(eqn.params["body_jaxpr"])
            cn = eqn.params["cond_nconsts"]
            bn = eqn.params["body_nconsts"]
            body_in = vals[cn:cn + bn] + vals[cn + bn:]
            body_in = body_in[:len(j.invars)] + [UNKNOWN] * max(
                0, len(j.invars) - len(body_in))
            _eval_jaxpr(j, c, body_in, calls, path + "/while")
        elif name == "cond":
            branches = eqn.params["branches"]
            pred, ops = vals[0], vals[1:]
            for bi, br in enumerate(branches):
                j, c = _closed(br)
                bouts = _eval_jaxpr(j, c, list(ops), calls,
                                    path + f"/cond[{bi}]")
                if not isinstance(pred, _Unknown) and int(pred) == bi:
                    outs = bouts
        else:
            sub = [v for v in eqn.params.values()
                   if isinstance(v, (jax_core.Jaxpr, jax_core.ClosedJaxpr))]
            if sub:
                for s in sub:  # unknown higher-order: collect, no values
                    j, c = _closed(s)
                    _eval_jaxpr(j, c, [UNKNOWN] * len(j.invars), calls,
                                path + "/" + name)
            elif known and all(_aval_small(v.aval) for v in eqn.outvars):
                try:
                    res = p.bind(*vals, **eqn.params)
                    outs = list(res) if p.multiple_results else [res]
                except Exception:  # noqa: BLE001 — resolution is optional
                    outs = [UNKNOWN] * len(eqn.outvars)
        write(eqn.outvars, outs)

    return [read(v) for v in jaxpr.outvars]


# ------------------------------------------------------- grid evaluation

@dataclasses.dataclass
class OperandGrid:
    """Per-grid-step block indices for one blocked operand."""
    role: str                       # "in" | "out"
    idx: int                        # index within role ordering
    block_shape: Tuple[int, ...]
    array_shape: Tuple[int, ...]
    block_bytes: int
    indices: Any                    # (steps, ndim) int ndarray
    scalar_dependent: bool
    manual: bool = False            # left in HBM (pl.ANY): the kernel
                                    # copies from it with its own DMAs

    @property
    def label(self) -> str:
        return f"{self.role}[{self.idx}]"

    def nblocks(self) -> Tuple[int, ...]:
        return tuple(-(-d // b) for d, b in
                     zip(self.array_shape, self.block_shape))


@dataclasses.dataclass
class GridEval:
    """Fully-enumerated grid semantics of one ``pallas_call``."""
    kernel: str
    grid: Tuple[int, ...]
    steps: int
    inputs: List[OperandGrid]
    outputs: List[OperandGrid]
    aliases: List[Tuple[int, int]]   # (input idx, output idx), bm-relative
    dmas: List["DmaStart"] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class DmaStart:
    """One DMA a kernel body starts itself from an operand left in HBM."""
    operand: int                     # input index, bm-relative
    step: int                        # grid step (row-major)
    index: Tuple[int, ...]           # first source element, per dim
    shape: Tuple[int, ...]           # source window extents, per dim
    nbytes: int


def _block_dims(block_shape) -> Tuple[int, ...]:
    """Block extents: ints, or ``pl.Blocked``/``pl.Element`` wrappers
    (their ``block_size``); squeezed dims count as 1 row."""
    return tuple(int(d) if isinstance(d, int)
                 else int(getattr(d, "block_size", 1)) for d in block_shape)


def _scalar_dependent(index_map_jaxpr, n_grid: int) -> bool:
    """Does the (undischarged) index-map jaxpr's output depend on its
    scalar-prefetch ref arguments?"""
    jx = index_map_jaxpr.jaxpr
    marked = set(jx.invars[n_grid:])
    if not marked:
        return False
    for eqn in jx.eqns:
        if any(v in marked for v in eqn.invars
               if not hasattr(v, "val")):
            marked.update(eqn.outvars)
    return any(v in marked for v in jx.outvars if not hasattr(v, "val"))


def eval_pallas_eqn(eqn, invals: Sequence[Any]):
    """Evaluate every BlockSpec index map of one ``pallas_call`` equation
    over its full (static) grid.  Returns a :class:`GridEval`, or an
    error string when the grid/scalars cannot be resolved statically."""
    import jax
    import numpy as np
    from jax.extend import core as jax_core
    from jax._src import state
    try:
        from jax._src.state import discharge as state_discharge
    except ImportError:  # pragma: no cover - layout varies across versions
        state_discharge = state.discharge  # type: ignore[attr-defined]

    gm = eqn.params["grid_mapping"]
    name_info = eqn.params.get("name_and_src_info")
    kernel = getattr(name_info, "name", None) or "pallas_call"
    try:
        grid = tuple(int(g) for g in gm.grid)
    except (TypeError, ValueError):
        return f"{kernel}: dynamic grid {gm.grid!r} — cannot enumerate"
    if getattr(gm, "num_dynamic_grid_bounds", 0):
        return f"{kernel}: dynamic grid bounds — cannot enumerate"

    n_idx = gm.num_index_operands
    scalars = list(invals[:n_idx])
    if any(isinstance(s, _Unknown) for s in scalars):
        return (f"{kernel}: {sum(isinstance(s, _Unknown) for s in scalars)}"
                f"/{n_idx} scalar-prefetch operand(s) unresolved — index "
                "maps cannot be evaluated")
    scalars = [np.asarray(s) for s in scalars]

    naxes = len(grid)
    steps = int(math.prod(grid)) if grid else 1
    if grid:
        mesh = np.meshgrid(*[np.arange(g, dtype=np.int32) for g in grid],
                           indexing="ij")
        grid_idx = np.stack(mesh, axis=-1).reshape(steps, naxes)
    else:
        grid_idx = np.zeros((1, 0), np.int32)

    inputs: List[OperandGrid] = []
    outputs: List[OperandGrid] = []
    for bi, bm in enumerate(gm.block_mappings):
        is_out = bi >= gm.num_inputs
        cj = bm.index_map_jaxpr
        dis_jaxpr, dis_consts = state_discharge.discharge_state(
            cj.jaxpr, cj.consts)
        fn = jax_core.jaxpr_as_fun(
            jax_core.ClosedJaxpr(dis_jaxpr, dis_consts))
        n_ref = len(cj.jaxpr.invars) - naxes
        ref_args = tuple(scalars[:n_ref])
        axes = (0,) * naxes + (None,) * n_ref
        vm = jax.vmap(fn, in_axes=axes if (naxes + n_ref) else None)
        call_args = tuple(grid_idx[:, i] for i in range(naxes)) + ref_args
        outs = vm(*call_args) if call_args else fn()
        bdims = _block_dims(bm.block_shape)
        nd = len(bdims)
        idx = np.stack([np.broadcast_to(np.asarray(o), (steps,))
                        for o in outs[:nd]], axis=-1).astype(np.int64)
        arr = bm.array_aval
        og = OperandGrid(
            role="out" if is_out else "in",
            idx=(bi - gm.num_inputs) if is_out else bi,
            block_shape=bdims,
            array_shape=tuple(int(d) for d in arr.shape),
            block_bytes=(math.prod(bdims)
                         * np.dtype(arr.dtype).itemsize),
            indices=idx,
            scalar_dependent=_scalar_dependent(cj, naxes),
            manual=_in_hbm(bm))
        (outputs if is_out else inputs).append(og)

    aliases: List[Tuple[int, int]] = []
    for op_idx, out_idx in tuple(eqn.params.get("input_output_aliases",
                                                ()) or ()):
        aliases.append((int(op_idx) - n_idx, int(out_idx)))

    dmas: List[DmaStart] = []
    if any(og.manual for og in inputs):
        body = eqn.params["jaxpr"]
        refs = [np.asarray(x) for x in scalars] + [
            _OperandRef(i) for i in range(len(body.invars) - n_idx)]
        for step in range(steps):
            pos = [int(x) for x in grid_idx[step]]
            found: List[Tuple[int, Tuple[int, ...], Tuple[int, ...]]] = []
            if _walk_body(body, [], refs, pos, grid, found) is None:
                return (f"{kernel}: a DMA of the kernel body depends on "
                        "values the scalar walk cannot resolve")
            for op, start, shape in found:
                if op >= len(inputs):      # a copy out of scratch
                    continue
                arr = gm.block_mappings[op].array_aval
                dmas.append(DmaStart(
                    op, step, start, shape,
                    math.prod(shape) * np.dtype(arr.dtype).itemsize))

    return GridEval(kernel=kernel, grid=grid, steps=steps, inputs=inputs,
                    outputs=outputs, aliases=aliases, dmas=dmas)


def _in_hbm(bm) -> bool:
    space = getattr(bm.transformed_block_aval, "memory_space", None)
    return str(space).lower().endswith("any")


class _OperandRef:
    """A kernel operand ref in the body walk (its position after the
    scalar-prefetch refs)."""

    def __init__(self, pos: int):
        self.pos = pos


def _walk_body(jaxpr, consts, invals, pos, grid, found):
    """Scalar walk of a kernel body for one grid step: program ids, loads
    from the concrete scalar-prefetch refs and scalar arithmetic are
    evaluated, ``pl.when`` branches followed, and every ``dma_start`` from
    an operand records ``(operand, first source index, window shape)``
    into ``found``.  Vector work stays unevaluated.  Returns the jaxpr's
    output values, or None when a branch holding a DMA, or a DMA's source
    window, cannot be resolved."""
    import numpy as np
    from jax import tree_util
    from jax.extend import core as jax_core
    from jax._src.pallas.mosaic.primitives import _dma_unflatten
    from jax._src.state.indexing import NDIndexer, Slice

    env: Dict[Any, Any] = {}

    def read(v):
        if isinstance(v, jax_core.Literal):
            return v.val
        return env.get(v, UNKNOWN)

    def scalar(x):
        return (not isinstance(x, (_Unknown, _OperandRef))
                and getattr(x, "shape", ()) == ())

    env.update(zip(jaxpr.constvars, consts))
    env.update(zip(jaxpr.invars, invals))
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        vals = [read(v) for v in eqn.invars]
        outs: Optional[List[Any]] = [UNKNOWN] * len(eqn.outvars)
        if name == "program_id":
            outs = [np.int32(pos[eqn.params["axis"]])]
        elif name == "num_programs":
            outs = [np.int32(grid[eqn.params["axis"]])]
        elif name == "get" and isinstance(vals[0], np.ndarray):
            (idx,) = tree_util.tree_unflatten(eqn.params["tree"], vals[1:])
            if all(scalar(i) for i in idx.indices):
                outs = [vals[0][tuple(int(i) for i in idx.indices)]]
        elif name == "cond" and scalar(vals[0]):
            j, c = _closed(eqn.params["branches"][int(vals[0])])
            outs = _walk_body(j, c, vals[1:], pos, grid, found)
        elif name == "cond" and _has_dma(eqn):
            return None
        elif name in ("pjit", "jit", "closed_call"):
            j, c = _closed(eqn.params.get("jaxpr")
                           or eqn.params.get("call_jaxpr"))
            outs = _walk_body(j, c, vals, pos, grid, found)
        elif name == "scan" and not eqn.params["reverse"]:
            # a fori_loop with static bounds: walk every iteration
            j, c = _closed(eqn.params["jaxpr"])
            nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
            carry = vals[nc:nc + ncar]
            xs = [UNKNOWN] * (len(vals) - nc - ncar)
            for _ in range(eqn.params["length"]):
                res = _walk_body(j, c, vals[:nc] + carry + xs, pos, grid,
                                 found)
                if res is None:
                    return None
                carry = res[:ncar]
            outs = carry + [UNKNOWN] * (len(eqn.outvars) - ncar)
        elif name in ("scan", "while") and _has_dma(eqn):
            return None
        elif name == "dma_start":
            src, src_tr = _dma_unflatten(eqn.params["tree"], vals)[:2]
            if isinstance(src, _OperandRef):
                window = _dma_window(src_tr, eqn.invars[0].aval.shape,
                                     scalar, NDIndexer, Slice)
                if window is None:
                    return None
                found.append((src.pos,) + window)
        elif all(scalar(x) for x in vals) and all(
                getattr(v.aval, "shape", None) == () for v in eqn.outvars):
            try:
                res = eqn.primitive.bind(*vals, **eqn.params)
                res = res if eqn.primitive.multiple_results else [res]
                outs = [np.asarray(o) for o in res]
            except Exception:  # noqa: BLE001 — stays unevaluated
                pass
        if outs is None:
            return None
        env.update(zip(eqn.outvars, outs))
    return [read(v) for v in jaxpr.outvars]


def _dma_window(transforms, shape, scalar, NDIndexer, Slice):
    """``(first index, extents)`` per dim of a ref of ``shape`` that a
    chain of ``.at[...]`` indexers selects (extent 1 where an integer
    index drops the dim); None if unresolved."""
    start, ext = [0] * len(shape), [int(d) for d in shape]
    dims = list(range(len(shape)))          # dims the next indexer sees
    for tr in transforms:
        if not isinstance(tr, NDIndexer):
            return None
        kept = []
        for d, ix in zip(dims, tr.indices):
            if isinstance(ix, Slice):
                if not scalar(ix.start) or ix.stride != 1:
                    return None
                start[d] += int(ix.start)
                ext[d] = int(ix.size)
                kept.append(d)
            elif scalar(ix):
                start[d] += int(ix)
                ext[d] = 1
            else:
                return None
        dims = kept + dims[len(tr.indices):]
    return tuple(start), tuple(ext)


def _has_dma(eqn) -> bool:
    from jax.extend import core as jax_core
    for v in eqn.params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(j, (jax_core.Jaxpr, jax_core.ClosedJaxpr)):
                jj, _ = _closed(j)
                if any(e.primitive.name == "dma_start" or _has_dma(e)
                       for e in jj.eqns):
                    return True
    return False


def _runs(indices) -> List[Tuple[Tuple[int, ...], int, int]]:
    """Run-length compress per-step block tuples: maximal runs of equal
    consecutive indices, as ``(block, first_step, last_step)`` — the
    granularity Mosaic's pipeline fetches/writes blocks at (consecutive
    equal indices elide the refetch/write-back)."""
    out: List[Tuple[Tuple[int, ...], int, int]] = []
    prev: Optional[Tuple[int, ...]] = None
    start = 0
    for s in range(indices.shape[0]):
        cur = tuple(int(x) for x in indices[s])
        if cur != prev:
            if prev is not None:
                out.append((prev, start, s - 1))
            prev, start = cur, s
    if prev is not None:
        out.append((prev, start, indices.shape[0] - 1))
    return out


def _is_sentinel(og: OperandGrid, block: Tuple[int, ...],
                 aliased: bool) -> bool:
    """The one legal parked target: scalar-fed aliased operands may map
    skipped steps onto the LAST axis-0 block — the reserved sentinel row
    of the paged pool (``serve/paged.device_pool_rows``), which the
    allocator never hands out and no table references."""
    return (aliased and og.scalar_dependent
            and block[0] == og.nblocks()[0] - 1)


def check_grid(ge: GridEval) -> List[Dict[str, Any]]:
    """Race/aliasing/bounds issues for one evaluated grid; one aggregated
    issue dict per (kind, operand)."""
    issues: List[Dict[str, Any]] = []
    aliased_out = {o for _, o in ge.aliases}
    aliased_in = {i for i, _ in ge.aliases}

    # (c/d) every computed block index in-bounds — OOB is silently
    # clamped at runtime, which turns table bugs into wrong-block I/O
    for og in ge.inputs + ge.outputs:
        nblk = og.nblocks()
        bad = [(s, tuple(int(x) for x in og.indices[s]))
               for s in range(ge.steps)
               if any(x < 0 or x >= n
                      for x, n in zip(og.indices[s], nblk))]
        if bad:
            issues.append({
                "kind": "oob", "operand": og.label, "kernel": ge.kernel,
                "count": len(bad), "nblocks": list(nblk),
                "first": {"step": bad[0][0], "block": list(bad[0][1])}})

    # a body-issued DMA must read inside its HBM operand, too
    for og in ge.inputs:
        if not og.manual:
            continue
        bad = [d for d in ge.dmas if d.operand == og.idx and any(
            i < 0 or i + n > a
            for i, n, a in zip(d.index, d.shape, og.array_shape))]
        if bad:
            issues.append({
                "kind": "dma-oob", "operand": og.label, "kernel": ge.kernel,
                "count": len(bad),
                "first": {"step": bad[0].step, "index": list(bad[0].index)}})

    # (a) non-aliased outputs: a block revisited in >1 run is written
    # back through a stale VMEM copy (WAW) under Mosaic pipelining
    for oi, og in enumerate(ge.outputs):
        if oi in aliased_out:
            continue
        runs = _runs(og.indices)
        seen: Dict[Tuple[int, ...], int] = {}
        racy: List[Tuple[int, ...]] = []
        for block, _, _ in runs:
            seen[block] = seen.get(block, 0) + 1
        racy = [b for b, n in seen.items() if n > 1
                and not _is_sentinel(og, b, aliased=False)]
        if racy:
            issues.append({
                "kind": "out-revisit", "operand": og.label,
                "kernel": ge.kernel, "blocks": [list(b) for b in racy[:8]],
                "count": len(racy)})

    # (b) aliased pairs: the input side re-fetches at every run start; a
    # fetch of a block an EARLIER run already wrote races the in-flight
    # aliased write-back (RAW)
    for ii, oi in ge.aliases:
        if ii >= len(ge.inputs) or oi >= len(ge.outputs):
            continue
        og_in, og_out = ge.inputs[ii], ge.outputs[oi]
        write_end: Dict[Tuple[int, ...], int] = {}
        for block, _, last in _runs(og_out.indices):
            if block not in write_end:
                write_end[block] = last
        racy = []
        parked = 0
        for block, first, _ in _runs(og_in.indices):
            if _is_sentinel(og_in, block, aliased=True):
                parked += 1
                continue
            if block in write_end and write_end[block] < first:
                racy.append(block)
        if racy:
            issues.append({
                "kind": "aliased-raw",
                "operand": f"{og_in.label}->{og_out.label}",
                "kernel": ge.kernel,
                "blocks": [list(b) for b in racy[:8]], "count": len(racy)})
        elif parked:
            issues.append({
                "kind": "sentinel-parked", "info": True,
                "operand": og_in.label, "kernel": ge.kernel,
                "count": parked})
    return issues


# ---------------------------------------------------------------- rules

def _check_calls(obj: str, calls: List[ResolvedCall],
                 findings: List[Finding]) -> int:
    """Evaluate+check every collected call; append error findings.
    Returns the number of calls successfully enumerated."""
    ok = 0
    for call in calls:
        ge = eval_pallas_eqn(call.eqn, call.invals)
        if isinstance(ge, str):
            findings.append(Finding(
                rule="races", severity="error", obj=obj,
                message=f"{obj}: {ge} (at {call.path or '<top>'})"))
            continue
        ok += 1
        for issue in check_grid(ge):
            if issue.get("info"):
                continue
            findings.append(Finding(
                rule="races", severity="error", obj=obj,
                message=(f"{obj}: kernel {ge.kernel} grid {ge.grid} "
                         f"{issue['kind']} on {issue['operand']} "
                         f"({issue['count']} block(s)/step(s))"),
                data=issue))
    return ok


@rule("races.kernel-zoo", family="races")
def rule_races_kernel_zoo(ctx: Context) -> List[Finding]:
    """Every kernel-zoo entry point, at concrete non-degenerate geometry:
    enumerate each pallas_call's grid, evaluate all index maps, check
    bounds / output-revisit contiguity / aliased RAW.  Coverage is pinned
    against ``kernel_zoo_entries`` — a kernel in the vmem zoo without a
    grid-zoo twin is an error, and an entry tracing zero pallas_calls is
    an error (a silent fallback would fake a green run)."""
    from repro.analysis.vmem import grid_zoo_entries, kernel_zoo_entries
    from repro.configs.base import get_smoke_config

    cfg = get_smoke_config(ctx.arch)
    entries = grid_zoo_entries(cfg)
    required = {name for name, _ in kernel_zoo_entries(cfg)}
    findings: List[Finding] = []
    coverage: Dict[str, int] = {}
    for e in entries:
        fname = f"races.kernel-zoo:{e.name}"
        calls = trace_and_collect(e.fn, *e.args)
        if not calls:
            findings.append(Finding(
                rule="races.kernel-zoo", severity="error", obj=e.name,
                message=f"{e.name}: traced ZERO pallas_calls — the "
                "dispatch silently fell back"))
            continue
        errs: List[Finding] = []
        _check_calls(e.name, calls, errs)
        for f in errs:
            f.rule = "races.kernel-zoo"
        findings.extend(errs)
        coverage[e.name] = len(calls)
    for missing in sorted(required - {e.name for e in entries}):
        findings.append(Finding(
            rule="races.kernel-zoo", severity="error", obj=missing,
            message=f"{missing} is in kernel_zoo_entries but has no "
            "grid_zoo_entries twin — grid semantics unchecked"))
    errors = any(f.severity == "error" for f in findings)
    findings.append(Finding(
        rule="races.kernel-zoo",
        severity="info", obj="kernel-zoo",
        message=(f"enumerated {sum(coverage.values())} pallas_call(s) "
                 f"across {len(coverage)} zoo entries"
                 + ("" if not errors else " (with errors)")),
        data={"coverage": coverage, "required": sorted(required)}))
    return findings


@rule("races.step-buckets", family="races")
def rule_races_step_buckets(ctx: Context) -> List[Finding]:
    """Every ``STEP_BUCKETS`` step program: const-propagate the fixture's
    real block tables / positions through the traced program and check
    every pallas_call's grid semantics.  Buckets must enumerate ≥ 1
    pallas_call (kernels-on programs with none mean the dispatch fell
    back) and no kernel may be skipped as unresolvable."""
    from repro.analysis.jaxpr_rules import _step_fixture

    eng, _, args = _step_fixture(ctx)
    findings: List[Finding] = []
    coverage: Dict[str, int] = {}
    for bucket, name, step in eng.exec.step_programs():
        calls = trace_and_collect(step, *args)
        if not calls:
            findings.append(Finding(
                rule="races.step-buckets", severity="error", obj=name,
                message=f"{name}: traced ZERO pallas_calls — kernels-on "
                "step program fell back to the oracle"))
            continue
        errs: List[Finding] = []
        _check_calls(name, calls, errs)
        for f in errs:
            f.rule = "races.step-buckets"
        findings.extend(errs)
        coverage[name] = len(calls)
    errors = any(f.severity == "error" for f in findings)
    findings.append(Finding(
        rule="races.step-buckets", severity="info", obj="executor",
        message=(f"enumerated {sum(coverage.values())} pallas_call(s) "
                 f"across {len(coverage)} step buckets"
                 + ("" if not errors else " (with errors)")),
        data={"coverage": coverage}))
    return findings


@rule("races.extra-entries", family="races")
def rule_races_extra(ctx: Context) -> List[Finding]:
    """Fixture hook: ``--grid-extra`` module's ``GRID_ENTRIES`` (name,
    fn, args) triples get the same enumerate+check treatment — the
    analyzer's own tests seed known-racy grids here."""
    if not ctx.grid_extra:
        return [Finding(rule="races.extra-entries", severity="info",
                        obj="fixtures", message="no extra grid entries")]
    mod = ctx.load_extra(ctx.grid_extra)
    findings: List[Finding] = []
    for name, fn, fargs in mod.GRID_ENTRIES:
        errs: List[Finding] = []
        _check_calls(name, trace_and_collect(fn, *fargs), errs)
        for f in errs:
            f.rule = "races.extra-entries"
        findings.extend(errs)
    if not findings:
        findings.append(Finding(
            rule="races.extra-entries", severity="info", obj="fixtures",
            message=f"{len(mod.GRID_ENTRIES)} extra entries clean"))
    return findings
