"""Static per-core VMEM/SMEM budget estimator for every Pallas kernel.

The ROADMAP's standing gap: kernels validated in interpret mode can
still die at Mosaic lowering on a real TPU when their working set
exceeds VMEM (~16 MB/core — pallas guide "Memory Hierarchy").  Nothing
about that failure needs hardware to predict: the working set is fully
determined by the traced program's BlockSpecs, grid, and scratch shapes.
This module walks each ``pallas_call`` equation of a traced call and
computes a worst-case footprint:

    vmem  =  2 x (sum of in/out block bytes)   # double-buffered pipeline
           + vmem scratch bytes                # single-buffered
    smem  =  scalar-prefetch operands + smem scratch

The x2 models Mosaic's pipelined double buffering of every streamed
block (see pallas guide "Patterns: Double Buffering"); scratch buffers
persist across grid steps and are not double-buffered.  Grids with a
single step skip the x2.  The estimate is deliberately conservative —
it does not model Mosaic's own temporaries, so a kernel near the budget
is already a finding.

``kernel_zoo_entries`` builds one representative traced call per kernel
in ``repro.kernels`` (nm_prune, nm_prune_matmul, nm_spmm,
osparse_matmul prefill + its static ``prune=False`` decode form,
w8a8_matmul, flash attention, paged attention, paged_kv_scatter) from a
``ModelConfig``'s real dims, so the ``vmem.budget`` rule sweeps the
whole shipped config zoo without materializing a single array
(``jax.make_jaxpr`` over ``ShapeDtypeStruct``s — no TPU, no FLOPs).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.analysis import Context, Finding, rule
from repro.analysis.jaxpr_utils import pallas_call_eqns

__all__ = [
    "PallasFootprint",
    "estimate_jaxpr",
    "estimate_call",
    "kernel_zoo_entries",
    "GridZooEntry",
    "grid_zoo_entries",
    "footprint_table",
]


@dataclasses.dataclass
class PallasFootprint:
    """Static memory footprint of ONE ``pallas_call`` equation."""
    kernel: str                    # inner kernel function name
    grid: Tuple[int, ...]
    block_bytes: int               # one copy of every in/out block
    vmem_scratch_bytes: int
    smem_bytes: int                # scalar prefetch + smem scratch
    double_buffered: bool

    @property
    def vmem_bytes(self) -> int:
        mult = 2 if self.double_buffered else 1
        return mult * self.block_bytes + self.vmem_scratch_bytes

    def to_dict(self) -> Dict[str, Any]:
        return {"kernel": self.kernel, "grid": list(self.grid),
                "block_bytes": self.block_bytes,
                "vmem_scratch_bytes": self.vmem_scratch_bytes,
                "smem_bytes": self.smem_bytes,
                "vmem_bytes": self.vmem_bytes,
                "double_buffered": self.double_buffered}


def _itemsize(dtype) -> int:
    import numpy as np
    return np.dtype(dtype).itemsize


def _block_numel(block_shape) -> int:
    from repro.analysis.grid_eval import _block_dims
    return math.prod(_block_dims(block_shape))


def _ref_space_and_bytes(aval) -> Tuple[str, int]:
    """(memory space, bytes) of a kernel ref aval (AbstractMemoryRef)."""
    inner = getattr(aval, "inner_aval", aval)
    shape = getattr(inner, "shape", getattr(aval, "shape", ()))
    dtype = getattr(inner, "dtype", getattr(aval, "dtype", None))
    space = getattr(aval, "memory_space", None)
    space = str(space).lower() if space is not None else "vmem"
    nbytes = math.prod(int(d) for d in shape) * _itemsize(dtype)
    return ("smem" if "smem" in space else "vmem"), nbytes


def estimate_jaxpr(jaxpr) -> List[PallasFootprint]:
    """Footprints for every ``pallas_call`` in a (Closed)Jaxpr."""
    out: List[PallasFootprint] = []
    for eqn in pallas_call_eqns(jaxpr):
        gm = eqn.params["grid_mapping"]
        name_info = eqn.params.get("name_and_src_info")
        name = getattr(name_info, "name", None) or "pallas_call"
        grid = tuple(int(g) for g in gm.grid)

        block_bytes = 0
        for bm in gm.block_mappings:
            arr = bm.array_aval
            block_bytes += _block_numel(bm.block_shape) * _itemsize(arr.dtype)

        inner = eqn.params["jaxpr"]
        invars = inner.jaxpr.invars if hasattr(inner, "jaxpr") \
            else inner.invars
        n_idx = gm.num_index_operands
        n_scratch = gm.num_scratch_operands
        smem_bytes = 0
        vmem_scratch = 0
        for v in invars[:n_idx]:               # scalar prefetch (SMEM)
            _, nb = _ref_space_and_bytes(v.aval)
            smem_bytes += nb
        if n_scratch:
            for v in invars[len(invars) - n_scratch:]:
                space, nb = _ref_space_and_bytes(v.aval)
                if space == "smem":
                    smem_bytes += nb
                else:
                    vmem_scratch += nb

        out.append(PallasFootprint(
            kernel=name, grid=grid, block_bytes=block_bytes,
            vmem_scratch_bytes=vmem_scratch, smem_bytes=smem_bytes,
            double_buffered=math.prod(grid) > 1 if grid else False))
    return out


def estimate_call(fn, *args, **kwargs) -> List[PallasFootprint]:
    """Trace ``fn(*args)`` abstractly and estimate every pallas_call in
    it.  ``args`` may be ``jax.ShapeDtypeStruct``s — nothing is ever
    computed or materialized."""
    import jax
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    return estimate_jaxpr(closed)


# --------------------------------------------------------------- kernel zoo

def _nm_for(d: int) -> Tuple[int, int]:
    """An N:M pattern whose group size divides the channel axis."""
    for m in (16, 8, 4, 2):
        if d % m == 0:
            return m // 2, m
    return 1, 1


def kernel_zoo_entries(cfg, *, chunk: int = 256, decode_slots: int = 8,
                       max_seq: int = 4096, block_size: int = 16):
    """``(entry_name, thunk)`` pairs, one per kernel entry point, with
    shapes drawn from ``cfg``'s real dims (a ``ModelConfig``).  Each
    thunk returns the footprint list for one representative call."""
    return _zoo(cfg, chunk, decode_slots, max_seq, block_size)


def _zoo(cfg, chunk, decode_slots, max_seq, block_size):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.paged_attention import (paged_attention_pallas,
                                               paged_kv_scatter_pallas)

    S = jax.ShapeDtypeStruct
    d = cfg.d_model
    n_out = max(cfg.d_ff, cfg.q_dim, cfg.moe_d_ff or 0)
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    n, m = _nm_for(d)

    x = S((chunk, d), jnp.float32)
    xd = S((decode_slots, d), jnp.float32)
    w = S((d, n_out), jnp.float32)
    wq = S((d, n_out), jnp.int8)
    scale = S((d,), jnp.float32)
    w_scale = S((n_out,), jnp.float32)
    bias = S((n_out,), jnp.float32)
    act = S((1,), jnp.float32)

    entries = [
        ("nm_prune", lambda: estimate_call(
            lambda x_, s_: ops.nm_prune(x_, s_, n, m), x, scale)),
        ("nm_prune_matmul", lambda: estimate_call(
            lambda x_, w_, s_, b_: ops.nm_prune_matmul(
                x_, w_, s_, n, m, bias=b_), x, w, scale, bias)),
        ("nm_spmm", lambda: estimate_call(
            lambda x_, w_, s_: ops.nm_spmm(x_, w_, s_, n, m), x, w, scale)),
        # prefill Outstanding-sparse with per-token scales (the extra
        # absmax sweep is the worst case of the two scale modes)
        ("osparse_matmul", lambda: estimate_call(
            lambda x_, wq_, sm_, am_, ws_, b_: ops.osparse_matmul(
                x_, wq_, sm_, am_, ws_, n, m, bias=b_, per_token=True),
            x, wq, scale, scale, w_scale, bias)),
        # decode-phase W8A8: same kernel, static prune=False
        ("osparse_w8a8_decode", lambda: estimate_call(
            lambda x_, wq_, sm_, ws_, a_, b_: ops.osparse_matmul(
                x_, wq_, sm_, None, ws_, n, m, act_scale=a_, bias=b_,
                prune=False), xd, wq, scale, w_scale, act, bias)),
        ("w8a8_matmul", lambda: estimate_call(
            lambda xq_, wq_, a_, ws_: ops.w8a8_matmul(xq_, wq_, a_, ws_),
            S((chunk, d), jnp.int8), wq, act, w_scale)),
    ]

    # attention kernels: one batch row of a 1024-token self-attn tile is
    # representative — block sizes are clamped at 128 so longer sequences
    # only grow the grid, never the VMEM working set
    t_attn = 1024
    q4 = S((1, hq, t_attn, hd), jnp.float32)
    kv4 = S((1, hkv, t_attn, hd), jnp.float32)
    entries.append(("flash_attention", lambda: estimate_call(
        lambda q_, k_, v_: flash_attention_pallas(
            q_, k_, v_, causal=True, interpret=True), q4, kv4, kv4)))

    mb = max_seq // block_size
    nb = decode_slots * mb
    qp = S((decode_slots, chunk, hq, hd), jnp.float32)
    pool = S((nb, hkv, block_size, hd), jnp.float32)
    tab = S((decode_slots, mb), jnp.int32)
    vec = S((decode_slots,), jnp.int32)
    entries.append(("paged_attention", lambda: estimate_call(
        lambda q_, k_, v_, t_, o_, l_: paged_attention_pallas(
            q_, k_, v_, t_, o_, l_, interpret=True),
        qp, pool, pool, tab, vec, vec)))

    knew = S((decode_slots, chunk, hkv, hd), jnp.float32)
    entries.append(("paged_kv_scatter", lambda: estimate_call(
        lambda kn_, vn_, kp_, vp_, t_, p_, c_: paged_kv_scatter_pallas(
            kn_, vn_, kp_, vp_, t_, p_, c_, interpret=True),
        knew, knew, pool, pool, tab, vec, vec)))
    return entries


@dataclasses.dataclass
class GridZooEntry:
    """One CONCRETE small-geometry kernel call for the grid-semantics
    (``races``) and HBM cost-model (``hbm``) rules.

    Unlike the abstract ``kernel_zoo_entries`` sweep (ShapeDtypeStructs,
    full-config dims), these entries carry real operand values — the
    scalar-prefetched block tables / positions / lengths must be concrete
    so every BlockSpec index map can be *evaluated* over the enumerated
    grid.  Geometry is chosen so every grid axis has ≥ 2 steps (tiled
    matmuls get I, J, K ≥ 2): degenerate single-step grids would make the
    revisit/elision checks and the closed-form byte model vacuously agree.

    ``dims`` feeds ``repro.kernels.COST_MODEL[name]["bytes"]`` — logical
    quantities (t, d, n_out, tile sizes, tables) the documented formulas
    are written in.  Entry names MUST mirror ``kernel_zoo_entries`` —
    the races rule derives its required coverage set from the vmem zoo,
    so a kernel added there without a grid-zoo twin is an error finding,
    not a silent skip.
    """
    name: str
    fn: Any
    args: Tuple[Any, ...]
    dims: Dict[str, Any]


def grid_zoo_entries(cfg) -> List[GridZooEntry]:
    """Concrete-operand kernel calls over ``cfg``'s dims at a small,
    non-degenerate geometry (see :class:`GridZooEntry`).  Paged entries
    follow the serving pool convention: device pools carry the trailing
    sentinel row (``serve/paged.device_pool_rows``) and block tables
    never reference it."""
    import functools

    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.paged_attention import (paged_attention_pallas,
                                               paged_kv_scatter_pallas)
    from repro.serve.paged import device_pool_rows

    # projections: two 128-lane blocks per axis (the wrappers pad smaller
    # or unaligned blocks up to Mosaic's lane tile, and the formulas speak
    # of the kernel's own dims)
    d = n_out = 256
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    n, m = _nm_for(d)

    t, bt = 32, 16                                    # I = 2
    bo = n_out // 2 if n_out % 2 == 0 else n_out      # J = 2
    bk = d // 2 if d % 2 == 0 and (d // 2) % m == 0 else d   # K = 2
    bk8 = d // 2 if d % 2 == 0 else d                 # no %m constraint

    x = jnp.zeros((t, d), jnp.float32)
    xd = jnp.zeros((16, d), jnp.float32)             # 8-row blocks: I = 2
    w = jnp.zeros((d, n_out), jnp.float32)
    wq = jnp.zeros((d, n_out), jnp.int8)
    vec_d = jnp.ones((d,), jnp.float32)
    vec_o = jnp.ones((n_out,), jnp.float32)
    act = jnp.float32(1.0)

    mm = dict(t=t, d=d, n_out=n_out, bt=bt, bo=bo, bk=bk)
    entries = [
        GridZooEntry(
            "nm_prune",
            lambda x_, s_: ops.nm_prune(x_, s_, n, m, block_t=bt,
                                        block_d=bk),
            (x, vec_d), dict(t=t, d=d, bt=bt, bd=bk)),
        GridZooEntry(
            "nm_prune_matmul",
            lambda x_, w_, s_, b_: ops.nm_prune_matmul(
                x_, w_, s_, n, m, bias=b_, block_t=bt, block_o=bo,
                block_k=bk),
            (x, w, vec_d, vec_o), dict(mm)),
        GridZooEntry(
            "nm_spmm",
            lambda x_, w_, s_: ops.nm_spmm(x_, w_, s_, n, m, tile=bt,
                                           block_o=bo, block_k=bk),
            (x, w, vec_d), dict(mm)),
        GridZooEntry(
            "osparse_matmul",
            lambda x_, wq_, sm_, am_, ws_, b_: ops.osparse_matmul(
                x_, wq_, sm_, am_, ws_, n, m, bias=b_, per_token=True,
                block_t=bt, block_o=bo, block_k=bk),
            (x, wq, vec_d, vec_d, vec_o, vec_o), dict(mm)),
        GridZooEntry(
            "osparse_w8a8_decode",
            lambda x_, wq_, sm_, ws_, a_, b_: ops.osparse_matmul(
                x_, wq_, sm_, None, ws_, n, m, act_scale=a_, bias=b_,
                prune=False, block_t=8, block_o=bo, block_k=bk),
            (xd, wq, vec_d, vec_o, act, vec_o),
            dict(mm, t=16, bt=8)),
        GridZooEntry(
            "w8a8_matmul",
            lambda xq_, wq_, a_, ws_: ops.w8a8_matmul(
                xq_, wq_, a_, ws_, block_t=bt, block_o=bo, block_k=bk8),
            (jnp.zeros((t, d), jnp.int8), wq, act, vec_o),
            dict(mm, bk=bk8)),
    ]

    t_attn, ba = 64, 16
    q4 = jnp.zeros((1, hq, t_attn, hd), jnp.float32)
    kv4 = jnp.zeros((1, hkv, t_attn, hd), jnp.float32)
    entries.append(GridZooEntry(
        "flash_attention",
        functools.partial(flash_attention_pallas, causal=True, block_q=ba,
                          block_k=ba, interpret=True),
        (q4, kv4, kv4),
        dict(b=1, h=hq, hkv=hkv, t=t_attn, s_kv=t_attn, bq=ba, bk=ba,
             hd=hd)))

    # paged pool: 2 rows, 16 allocatable blocks + the trailing sentinel
    # row (never in any table).  Row 0 is a from-zero prefill (kv_len =
    # its chunk); row 1 sits mid-sequence at pos 12/16.
    bs, mb, nb = 8, 8, 16
    rows = device_pool_rows(nb)
    pool = jnp.zeros((rows, hkv, bs, hd), jnp.float32)
    atab = np.full((2, mb), -1, np.int32)
    atab[0, :4] = [1, 2, 3, 4]
    atab[1, :6] = [5, 6, 7, 8, 9, 10]
    tq = 32
    qoff = np.asarray([0, 16], np.int32)
    kvl = np.asarray([tq, 16 + tq], np.int32)
    entries.append(GridZooEntry(
        "paged_attention",
        functools.partial(paged_attention_pallas, causal=True, block_q=16,
                          interpret=True),
        (jnp.zeros((2, tq, hq, hd), jnp.float32), pool, pool,
         jnp.asarray(atab), jnp.asarray(qoff), jnp.asarray(kvl)),
        dict(b=2, h=hq, hkv=hkv, t=tq, bq=16, bs=bs, mb=mb, rows=rows,
             hd=hd, tab=atab, qoff=qoff, kvl=kvl)))

    stab = np.full((2, mb), -1, np.int32)
    stab[0, :2] = [1, 2]
    stab[1, 1:4] = [5, 6, 7]
    ts = 16
    pos = np.asarray([0, 12], np.int32)
    cl = np.asarray([ts, ts], np.int32)
    knew = jnp.zeros((2, ts, hkv, hd), jnp.float32)
    entries.append(GridZooEntry(
        "paged_kv_scatter",
        functools.partial(paged_kv_scatter_pallas, interpret=True),
        (knew, knew, pool, pool, jnp.asarray(stab), jnp.asarray(pos),
         jnp.asarray(cl)),
        dict(b=2, t=ts, bs=bs, mb=mb, rows=rows, hkv=hkv, hd=hd, tab=stab,
             pos=pos, cl=cl)))
    return entries


def kernel_zoo_footprints(cfg, *, chunk: int = 256, decode_slots: int = 8,
                          max_seq: int = 4096, block_size: int = 16
                          ) -> Dict[str, List[PallasFootprint]]:
    """Footprints for every kernel entry point under ``cfg``'s dims."""
    out: Dict[str, List[PallasFootprint]] = {}
    for name, thunk in _zoo(cfg, chunk, decode_slots, max_seq, block_size):
        out[name] = thunk()
    return out


def footprint_table(config_names: Sequence[str],
                    **zoo_kw) -> List[Dict[str, Any]]:
    """Per-kernel worst-case rows across ``config_names`` (full, non-smoke
    configs): the table ``kernels/__init__.py`` documents and the CLI
    emits under ``vmem_table``."""
    from repro.configs.base import get_config

    worst: Dict[str, Dict[str, Any]] = {}
    for cname in config_names:
        cfg = get_config(cname)
        for entry, fps in kernel_zoo_footprints(cfg, **zoo_kw).items():
            for fp in fps:
                row = worst.get(entry)
                if row is None or fp.vmem_bytes > row["vmem_bytes"]:
                    worst[entry] = {"entry": entry, "config": cname,
                                    **fp.to_dict()}
    return [worst[k] for k in sorted(worst)]


# ------------------------------------------------------------------- rule

def _mib(b: int) -> float:
    return b / (1024.0 * 1024.0)


@rule("vmem.budget", family="vmem")
def rule_vmem_budget(ctx: Context) -> List[Finding]:
    """Every kernel's static VMEM footprint, across the shipped config
    zoo, must fit the per-core budget (default 16 MiB); SMEM usage
    (scalar-prefetch tables) must stay tiny."""
    findings: List[Finding] = []
    budget, sbudget = ctx.vmem_budget_bytes, ctx.smem_budget_bytes

    def check(entry: str, where: str, fps: List[PallasFootprint]):
        if not fps:
            findings.append(Finding(
                rule="vmem.budget", severity="error", obj=entry,
                message=f"{entry} ({where}) lowered no pallas_call — "
                "the kernel dispatch silently fell back"))
            return
        for fp in fps:
            data = {"where": where, **fp.to_dict(),
                    "budget_bytes": budget}
            if fp.vmem_bytes > budget:
                findings.append(Finding(
                    rule="vmem.budget", severity="error", obj=entry,
                    message=(f"{entry} ({where}): static VMEM "
                             f"{_mib(fp.vmem_bytes):.2f} MiB exceeds the "
                             f"{_mib(budget):.0f} MiB per-core budget "
                             f"(kernel {fp.kernel}, grid {fp.grid})"),
                    data=data))
            elif fp.smem_bytes > sbudget:
                findings.append(Finding(
                    rule="vmem.budget", severity="error", obj=entry,
                    message=(f"{entry} ({where}): SMEM "
                             f"{fp.smem_bytes} B exceeds the "
                             f"{sbudget} B scalar budget"),
                    data=data))

    for cname in ctx.config_zoo():
        from repro.configs.base import get_config
        cfg = get_config(cname)
        for entry, fps in kernel_zoo_footprints(cfg).items():
            check(entry, cname, fps)

    if ctx.vmem_extra:
        mod = ctx.load_extra(ctx.vmem_extra)
        for entry_name, fn, args in mod.TRACE_ENTRIES:
            check(entry_name, ctx.vmem_extra, estimate_call(fn, *args))

    if not any(f.severity == "error" for f in findings):
        findings.append(Finding(
            rule="vmem.budget", severity="info", obj="kernels",
            message=(f"all kernels fit {_mib(budget):.0f} MiB across "
                     f"{len(ctx.config_zoo())} configs")))
    return findings
