"""Pallas TPU kernels for the Amber Pruner hot paths.

Kernel family (each is ONE ``pallas_call`` — every intermediate lives in
VMEM/registers, never HBM):

  nm_prune         — fused scoring + per-token N:M top-k + mask
  nm_prune_matmul  — fused prune + GEMM (the per-token projection itself)
  nm_grouped_matmul — routed experts: grouped GEMM over expert-sorted
                     rows with the per-token N:M prune (plain |x|) fused
                     onto each row tile; scalar-prefetched group
                     metadata picks each step's row tile and expert
                     weight tile (megablox ``gmm``'s design), static
                     ``prune`` (the dense up projection and decode run
                     it too); reads one layer of a scan-stacked weight
                     in place
  nm_spmm          — tile-consensus compacted matmul (TPU-native SpMM),
                     k-blocked over D with an f32 accumulator scratch
  osparse_matmul   — Outstanding-sparse chain: smooth-divide → prune →
                     int8 quantize (static or per-token) → int8 GEMM →
                     dequant
  w8a8_matmul      — plain int8×int8→int32 GEMM with SmoothQuant dequant
  flash_attention  — causal online-softmax attention, VMEM score tiles
                     (full and sliding-window self-attention; off-band KV
                     blocks are skipped at block granularity)
  paged_attention  — flash attention over the paged KV pool: one grid
                     step takes a KV head's group of query heads
                     against a tile of ~512 positions of pages, each
                     page resolved through the scalar-prefetched block
                     table (``pltpu.PrefetchScalarGridSpec``) and
                     streamed through VMEM; tiles past ``kv_len`` or
                     off-band skip their matmuls and DMAs, ``-1`` pages
                     are masked — chunked prefill at cache offsets and
                     vector-position decode share one kernel
  paged_kv_scatter — the write side of the paged pool (same module): per
                     logical block the scalar-prefetched table picks the
                     physical block, a one-hot selection matmul merges the
                     chunk's rows into it, and ``input_output_aliases``
                     updates the pool in place — invisible grid steps
                     write nothing, so untouched blocks keep their
                     content.  Replaces the host-side flat-index
                     ``.at[].set`` scatter in the serving hot path.

Dispatch order for model projections (``layers.linear.sparse_linear``):

  1. ``SparsityPolicy.use_pallas_kernels`` — the policy flag routes each
     prunable linear onto the fused kernel for its mode (per-token →
     ``nm_prune_matmul``; tile-consensus → ``nm_spmm``; Outstanding-sparse
     W8A8 → ``osparse_matmul``; decode-phase W8A8 → ``osparse_matmul``
     with static ``prune=False``, skipping selection in-kernel).  A
     projection bias rides the kernels' f32 dequant/accumulator epilogue
     instead of a separate HBM pass.  Scan-stacked ``layer_flag`` models
     pick the kernel or the dense projection per layer with ``lax.cond``.
  2. The platform (``kernels.platform``): Mosaic compiles the kernels on
     a TPU backend; anywhere else (the CPU test hosts) the same BlockSpecs
     run through the Pallas interpreter.
  3. The pure-jnp implementations in ``repro.core`` remain the bit-exact
     oracles (``kernels.ref`` wraps them per kernel for the test sweeps).

HBM cost model — ``COST_MODEL`` below is the machine-readable contract:
exact bytes moved per kernel call under Mosaic's pipelined fetch/write
semantics (a block is fetched at the start of each maximal RUN of grid
steps mapping to it, written back once per output run — consecutive
equal block indices elide the refetch/write-back).  The ``hbm`` analyzer
family enumerates every kernel's real grid + index maps and fails CI
when the measured bytes diverge >10% from these formulas, so the table
below cannot rot.  Versus the jnp oracles: the fused projections spend
ZERO extra X passes (the mask/quantized copies live in registers; the
jnp chains write + re-read them), the gather oracle round-trips the full
(B, mb·bs, Hkv, hd) logical view per call while ``paged_attention``
streams O(kv_len) rows, and the flat-index scatter round-trips the whole
pool per leaf while ``paged_kv_scatter`` touches only the blocks a chunk
overlaps.

--- HBM bytes per call (generated from COST_MODEL; do not edit) ---
  flash_attention      s*(2*B*H*T*hd + 2*B*H*(T/bq)*S*hd)
  nm_grouped_matmul    s*(bt*bk*runs(x) + bk*bo*runs(w) + bt*bo*runs(out))
  nm_prune             s*(2*T*D + I*D)
  nm_prune_matmul      s*(T*D + I*D*N + I*D + I*N + T*N)
  nm_spmm              s*(J*T*D + I*D*N + I*J*D + T*N)
  osparse_matmul       s*(2*J*T*D + 4*I*J*D + 2*I*N + T*N) + 2*I*D*N
  osparse_w8a8_decode  s*(J*T*D + 2*I*J*D + 2*I*N + T*N + 1) + I*D*N
  paged_attention      s*(2*B*H*T*hd + 2*P*bs*hd*tiles(visible walk))
  paged_kv_scatter     s*(2*B*T*r + runs(pool walk)*4*bs*r)
  w8a8_matmul          J*T*D + I*D*N + s*(I*N + 1 + T*N)
--- end generated table ---

Symbols: T tokens, D in-features, N out-features, s dtype bytes (f32:
4); grid extents I = T/bt, J = N/bo, K = D/bk (a tiled matmul refetches
X once per output tile and W once per token tile, hence J·T·D and I·D·N;
``nm_prune_matmul`` keeps its selected X in VMEM across output tiles, so
X streams once, T·D); grouped: bt/bk/bo
its row, k and output tiles, ``runs(·)`` over its (group, row tile)
walk; attention B, H, hd,
query tile bq, KV length S; paged r = Hkv·hd, P pages of bs rows per
KV tile, T padded to whole q tiles.  The paged formulas replay the
documented table/visibility contract over the real block table:
``tiles(·)`` counts the KV tiles paged attention copies page by page
itself (visible ones only), ``runs(·)`` the maximal constant runs of the
scatter's scalar-prefetched block walk (skipped steps park on the
sentinel block, so consecutive skips fetch nothing).

Dispatch for the paged pool (``models/attention.paged_attention`` reads,
``models/attention.paged_kv_update`` writes) runs the same ladder as the
projections: ``SparsityPolicy.use_pallas_kernels`` →
the platform (Mosaic vs interpreter) → the jnp
gather-then-attend / flat-index-scatter oracles (the gather oracle is
always used for windowed paged shapes and non-tile-divisible query
counts).  Both directions carry chaos-harness sites
(``kernel.paged_attention``, ``kernel.paged_scatter``).

Static VMEM footprints (worst case across the shipped config zoo, from
``PYTHONPATH=src python -m repro.analysis --vmem-table`` — regenerate
after changing any BlockSpec/grid/scratch; the ``vmem.budget`` analyzer
rule fails CI past 16 MiB/core).  The estimate is 2x the in/out block
bytes (Mosaic double buffering) + VMEM scratch; SMEM carries the
scalar-prefetched block tables:

  kernel               VMEM       SMEM     worst config, grid
  flash_attention       1.13 MiB     0 B   recurrentgemma_2b (1,10,8,8)
  nm_grouped_matmul    14.50 MiB   116 B   granite_34b       (12,9,12)
  nm_prune              2.00 MiB     0 B   llama31_8b        (1,8)
  nm_prune_matmul       8.76 MiB     0 B   recurrentgemma_2b (1,10,5)
  nm_spmm               2.75 MiB     0 B   llama31_8b        (1,56,8)
  osparse_matmul        2.01 MiB     0 B   llama31_8b        (1,56,16)
  osparse_w8a8_decode   0.32 MiB     0 B   llama31_8b        (1,56,8)
  paged_attention       5.13 MiB  8512 B   recurrentgemma_2b (8,1,4,8)
  paged_kv_scatter     10.00 MiB  8256 B   rwkv6_7b          (8,9)
  w8a8_matmul           1.25 MiB     0 B   llama31_8b        (1,56,8)

(``paged_kv_scatter``'s bound holds because the wrapper splits chunks
whose resident tile would exceed ~2 MiB/leaf into sub-chunk calls —
MHA-width caches at chunk 256 used to hit 18 MiB.)

``ops``  — jit'd wrappers (batched, padded, interpret-mode switch)
``ref``  — pure-jnp oracles used by the allclose test sweeps
"""
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import (paged_attention_pallas,
                                           paged_kv_scatter_pallas)
from repro.kernels.ops import (
    nm_grouped_matmul,
    nm_prune,
    nm_prune_matmul,
    nm_spmm,
    osparse_matmul,
    w8a8_matmul,
)

__all__ = [
    "nm_grouped_matmul",
    "nm_prune",
    "nm_prune_matmul",
    "nm_spmm",
    "osparse_matmul",
    "w8a8_matmul",
    "flash_attention_pallas",
    "paged_attention_pallas",
    "paged_kv_scatter_pallas",
    "COST_MODEL",
    "cost_model_doc",
]


# --------------------------------------------------------------------------
# COST_MODEL: closed-form HBM bytes per kernel call.
#
# Each entry maps a kernel-zoo name to {"formula": <doc string — MUST match
# the generated table in the module docstring>, "bytes": fn(dims) -> int}.
# ``dims`` is the geometry dict a ``grid_zoo_entries`` entry carries
# (tokens/features/block sizes, and for the paged kernels the concrete
# block table / positions / lengths).  The formulas model Mosaic's
# pipelined traffic: one fetch per maximal RUN of grid steps mapping an
# operand to the same block (row-major grid order, last axis innermost),
# one write-back per output run.  ``repro.analysis.hbm`` measures the same
# quantity from the kernels' REAL BlockSpec index maps and fails on >10%
# divergence — these formulas are the independent re-derivation from the
# documented contract, not a transcription of the measurement.
#
# Pure Python on purpose (no jax/numpy): the model is consultable from
# host-only contexts and the purity rules keep this module import-light.

def _run_count(seq) -> int:
    """Maximal constant runs in a sequence — the number of block
    fetches Mosaic's refetch elision leaves in a grid walk."""
    runs, prev = 0, object()
    for item in seq:
        if item != prev:
            runs, prev = runs + 1, item
    return runs


def _mm_dims(d):
    s = d.get("s", 4)
    t, dd, n = d["t"], d["d"], d["n_out"]
    i, j = t // d["bt"], n // d["bo"]
    return s, t, dd, n, i, j


def _nm_prune_bytes(d):
    s = d.get("s", 4)
    i = d["t"] // d["bt"]
    return s * (2 * d["t"] * d["d"] + i * d["d"])


def _nm_prune_matmul_bytes(d):
    # X and the channel scale stream once per token block (the X block
    # parks on its last k block after the first output tile, whose
    # selection the later tiles reuse from VMEM); weights once per token
    # block, bias once per output run
    s, t, dd, n, i, j = _mm_dims(d)
    return s * (t * dd + i * dd * n + i * dd + i * n + t * n)


def _nm_spmm_bytes(d):
    s, t, dd, n, i, j = _mm_dims(d)
    return s * (j * t * dd + i * dd * n + i * j * dd + t * n)


def _osparse_matmul_bytes(d):
    # per-token scale: the k axis runs twice (absmax pass + GEMM pass),
    # doubling X/weight/channel-vector traffic; wq is int8 (1 byte)
    s, t, dd, n, i, j = _mm_dims(d)
    return (s * (2 * j * t * dd + 4 * i * j * dd + 2 * i * n + t * n)
            + 2 * i * dd * n)


def _osparse_w8a8_decode_bytes(d):
    # static scale (prune=False decode form): single k pass, scalar
    # act-scale is one 4-byte fetch for the whole grid.  The amber
    # channel vector streams even when unused (the kernel's operand list
    # is static — a ones placeholder rides next to smooth), hence 2·I·J·D
    s, t, dd, n, i, j = _mm_dims(d)
    return (s * (j * t * dd + 2 * i * j * dd + 2 * i * n + t * n + 1)
            + i * dd * n)


def _w8a8_matmul_bytes(d):
    # xq/wq int8; w_scale f32 per output run; x_scale one scalar fetch
    s, t, dd, n, i, j = _mm_dims(d)
    return j * t * dd + i * dd * n + s * (i * n + 1 + t * n)


def _grouped_steps(sizes, bt):
    """(group, row tile) of each grid step that computes: every non-empty
    group, in order, visits each row tile its rows overlap."""
    steps, start = [], 0
    for g, n in enumerate(sizes):
        if n:
            steps += [(g, t) for t in range(start // bt,
                                            (start + n - 1) // bt + 1)]
        start += n
    return steps


def _nm_grouped_matmul_bytes(d):
    # grid (J, row tiles + G - 1, K); the steps past the computing ones
    # hold the last one's blocks at its last k (nothing fetched or
    # written).  X and W blocks are fetched per run of equal indices,
    # each output tile written once per run of visits
    s, bt, bk, bo = d.get("s", 4), d["bt"], d["bk"], d["bo"]
    kk, jj = d["d"] // bk, d["n_out"] // bo
    steps = _grouped_steps(d["sizes"], bt)
    idle = d["rows"] // bt + len(d["sizes"]) - 1 - len(steps)
    xs, ws, outs = [], [], []
    for j in range(jj):
        walk = [(g, t, k) for g, t in steps for k in range(kk)]
        walk += [(steps[-1][0], steps[-1][1], kk - 1)] * idle
        xs += [(t, k) for _, t, k in walk]
        ws += [(g, k, j) for g, _, k in walk]
        outs += [(t, j) for _, t, _ in walk]
    return s * (bt * bk * _run_count(xs) + bk * bo * _run_count(ws)
                + bt * bo * _run_count(outs))


def _flash_attention_bytes(d):
    # q/out resident across the KV axis (1 run per (b,h,q-tile)); k/v
    # blocks are fetched every step — causal masking skips the COMPUTE
    # of future blocks, not their DMA (the index map is unconditional)
    s = d.get("s", 4)
    b, h, t, skv, bq, hd = d["b"], d["h"], d["t"], d["s_kv"], d["bq"], d["hd"]
    return s * (2 * b * h * t * hd + 2 * b * h * (t // bq) * skv * hd)


def _paged_attention_bytes(d):
    # replay the documented walk: grid (B, Hkv, ceil(T/bq), ceil(mb/P)),
    # KV tiles innermost.  A (b, KV head, q tile) reads its g*bq-row q
    # tile once and writes its output tile once (T padded to whole q
    # tiles).  K and V stay in HBM: the body copies the P pages of each
    # VISIBLE tile once, page by page (a tile in [0, last], ``last`` the
    # last tile below kv_len — capped at the row's last allocated page —
    # and, causally, at or before the q tile's last query); skipped tiles
    # copy nothing.
    s = d.get("s", 4)
    b, h, hkv, t, hd = d["b"], d["h"], d["hkv"], d["t"], d["hd"]
    bq, pages, bs, mb = d["bq"], d["pages"], d["bs"], d["mb"]
    tk = pages * bs
    nq = -(-t // bq)
    tiles = 0
    for bi in range(b):
        row = [int(x) for x in d["tab"][bi]]
        n_alloc = max([i + 1 for i, pb in enumerate(row) if pb >= 0],
                      default=0)
        kv = min(int(d["kvl"][bi]), n_alloc * bs)
        for qi in range(nq):
            q_hi = int(d["qoff"][bi]) + qi * bq + bq - 1
            tiles += hkv * max(0, min(-(-kv // tk), q_hi // tk + 1))
    return s * (2 * b * h * nq * bq * hd + 2 * pages * bs * hd * tiles)


def _paged_kv_scatter_bytes(d):
    # grid (B, n_lb) over the ≤ ceil(T/bs)+1 logical blocks a chunk can
    # overlap; visible steps resolve table[pos//bs + ci], invisible ones
    # park on the pool's reserved SENTINEL row (rows-1).  Each pool run
    # costs a fetch AND an aliased write-back, for K and V (×4); k_new /
    # v_new are resident per batch row (×2 fetches of T rows).
    s = d.get("s", 4)
    b, t, bs, mb, rows = d["b"], d["t"], d["bs"], d["mb"], d["rows"]
    r = d["hkv"] * d["hd"]
    tab, pos, cl = d["tab"], d["pos"], d["cl"]
    n_lb = min((t - 1) // bs + 2, t)
    walk = []
    for bi in range(b):
        for ci in range(n_lb):
            lb = int(pos[bi]) // bs + ci
            pb = int(tab[bi][min(max(lb, 0), mb - 1)])
            lo = lb * bs
            vis = (lb < mb and pb >= 0 and lo < int(pos[bi]) + int(cl[bi])
                   and lo + bs > int(pos[bi]))
            walk.append(max(pb, 0) if vis else rows - 1)
    return s * (2 * b * t * r + 4 * _run_count(walk) * bs * r)


COST_MODEL = {
    "nm_prune": {
        "formula": "s*(2*T*D + I*D)",
        "bytes": _nm_prune_bytes},
    "nm_prune_matmul": {
        "formula": "s*(T*D + I*D*N + I*D + I*N + T*N)",
        "bytes": _nm_prune_matmul_bytes},
    "nm_spmm": {
        "formula": "s*(J*T*D + I*D*N + I*J*D + T*N)",
        "bytes": _nm_spmm_bytes},
    "osparse_matmul": {
        "formula": "s*(2*J*T*D + 4*I*J*D + 2*I*N + T*N) + 2*I*D*N",
        "bytes": _osparse_matmul_bytes},
    "osparse_w8a8_decode": {
        "formula": "s*(J*T*D + 2*I*J*D + 2*I*N + T*N + 1) + I*D*N",
        "bytes": _osparse_w8a8_decode_bytes},
    "w8a8_matmul": {
        "formula": "J*T*D + I*D*N + s*(I*N + 1 + T*N)",
        "bytes": _w8a8_matmul_bytes},
    "flash_attention": {
        "formula": "s*(2*B*H*T*hd + 2*B*H*(T/bq)*S*hd)",
        "bytes": _flash_attention_bytes},
    "paged_attention": {
        "formula": "s*(2*B*H*T*hd + 2*P*bs*hd*tiles(visible walk))",
        "bytes": _paged_attention_bytes},
    "paged_kv_scatter": {
        "formula": "s*(2*B*T*r + runs(pool walk)*4*bs*r)",
        "bytes": _paged_kv_scatter_bytes},
    "nm_grouped_matmul": {
        "formula": "s*(bt*bk*runs(x) + bk*bo*runs(w) + bt*bo*runs(out))",
        "bytes": _nm_grouped_matmul_bytes},
}


def cost_model_doc() -> str:
    """The generated docstring table, rendered from :data:`COST_MODEL` —
    ``repro.analysis.hbm`` fails when the module docstring's marker
    section drifts from this (regenerate via
    ``python -m repro.analysis --hbm-table``)."""
    lines = ["--- HBM bytes per call (generated from COST_MODEL; "
             "do not edit) ---"]
    for name in sorted(COST_MODEL):
        lines.append(f"  {name:<20} {COST_MODEL[name]['formula']}")
    lines.append("--- end generated table ---")
    return "\n".join(lines)
