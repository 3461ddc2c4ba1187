"""Kernel microbenchmarks + analytic roofline for the Pallas hot paths.

Wall-times here are CPU interpret-mode (NOT TPU-representative); the
derived column carries the analytic TPU roofline estimate per call:
  nm_prune         — bandwidth-bound: 2·T·D·dtype_bytes / 819 GB/s
  nm_prune_matmul  — fused per-token projection: the GEMM's block
                     streaming is the same as dense; the fusion removes
                     the prune stage's masked-copy write + re-read
                     (2 full X passes) that the jnp chain pays on top
  nm_spmm          — compute-bound:   2·T·(D·n/m)·N_out / 197 TFLOP/s
  osparse_matmul   — int8 GEMM at 2× PEAK; fusion removes the jnp
                     chain's smoothed/masked/quantized copies
                     (~3 writes + 3 extra reads of X)
  w8a8_matmul      — compute-bound:   2·T·D·N_out / (2×197) TFLOP/s
  paged_attention  — bandwidth-bound: the gather oracle writes + re-reads
                     the (B, max_blocks·block_size) logical KV view per
                     call on top of the attention's own streaming; the
                     in-kernel block-table walk streams only the allocated
                     ≤ceil(kv_len/bs) blocks once (decode: O(pos) rows)
vs the dense bf16 GEMM baseline 2·T·D·N_out / 197 TFLOP/s.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, timeit_us
from repro.kernels import ops, ref

HBM = 819e9
PEAK = 197e12

# interpret-mode is slow on CPU — keep shapes modest; the derived column
# carries the analytic TPU estimate which is what §Roofline consumes
SHAPES = [(256, 2048, 2048)]


def run() -> list[str]:
    rows = []
    key = jax.random.PRNGKey(0)
    for t, d, no in SHAPES:
        k1, k2, k3 = jax.random.split(key, 3)
        x = jax.random.normal(k1, (t, d), dtype=jnp.bfloat16)
        w = jax.random.normal(k2, (d, no), dtype=jnp.bfloat16)
        scale = jax.random.uniform(k3, (d,)) + 0.5
        dense_s = 2 * t * d * no / PEAK

        us = timeit_us(lambda: ops.nm_prune(x, scale, 8, 16), iters=3)
        est = 2 * t * d * 2 / HBM
        rows.append(csv_row(f"kernel/nm_prune/{t}x{d}", us,
                            f"tpu_est_s={est:.3e};dense_gemm_s={dense_s:.3e};"
                            f"overhead_frac={est/dense_s:.3f}"))

        # fused per-token prune+GEMM: GEMM streaming is identical to the
        # dense tiled matmul; fusion saves the masked-copy write + re-read
        us = timeit_us(lambda: ops.nm_prune_matmul(x, w, scale, 8, 16),
                       iters=3)
        bytes_gemm = (t * d + d * no + t * no) * 2
        bytes_prune_pass = 2 * t * d * 2           # write Xp, re-read Xp
        est = max(dense_s, bytes_gemm / HBM)
        est_unfused = est + bytes_prune_pass / HBM
        saved = bytes_prune_pass / (bytes_gemm + bytes_prune_pass)
        rows.append(csv_row(
            f"kernel/nm_prune_matmul/{t}x{d}x{no}", us,
            f"tpu_est_s={est:.3e};unfused_est_s={est_unfused:.3e};"
            f"hbm_saved_frac={saved:.3f}"))

        us = timeit_us(lambda: ops.nm_spmm(x, w, scale, 8, 16), iters=3)
        est = 2 * t * (d // 2) * no / PEAK
        rows.append(csv_row(f"kernel/nm_spmm/{t}x{d}x{no}", us,
                            f"tpu_est_s={est:.3e};speedup_vs_dense="
                            f"{dense_s/est:.2f}x"))

        xq = jax.random.randint(k1, (t, d), -127, 128).astype(jnp.int8)
        wq = jax.random.randint(k2, (d, no), -127, 128).astype(jnp.int8)
        ws = jax.random.uniform(k3, (no,)) * 0.01
        us = timeit_us(
            lambda: ops.w8a8_matmul(xq, wq, jnp.float32(0.01), ws), iters=3)
        est = 2 * t * d * no / (2 * PEAK)
        rows.append(csv_row(f"kernel/w8a8/{t}x{d}x{no}", us,
                            f"tpu_est_s={est:.3e};speedup_vs_bf16="
                            f"{dense_s/est:.2f}x"))

        # fused Outstanding-sparse chain: smooth→prune→int8→GEMM→dequant
        smooth = jax.random.uniform(k3, (d,)) + 0.5
        us = timeit_us(
            lambda: ops.osparse_matmul(x.astype(jnp.float32), wq, smooth,
                                       scale, ws, 8, 16,
                                       act_scale=jnp.float32(0.01)),
            iters=3)
        bytes_fused = t * d * 2 + d * no + t * no * 4   # bf16 X, int8 W
        # jnp chain adds the smoothed (f32 write+read), masked (f32
        # write+read) and quantized (int8 write+read) copies of X
        bytes_chain = (t * d * (2 + 4 + 4 + 4 + 4 + 1 + 1)
                       + d * no + t * no * 4)
        est = max(2 * t * d * no / (2 * PEAK),      # int8 MXU at 2× PEAK
                  bytes_fused / HBM)
        est_chain = 2 * t * d * no / (2 * PEAK) + bytes_chain / HBM
        rows.append(csv_row(
            f"kernel/osparse_matmul/{t}x{d}x{no}", us,
            f"tpu_est_s={est:.3e};unfused_est_s={est_chain:.3e};"
            f"speedup_vs_bf16={dense_s/est:.2f}x"))

    # paged attention (shape-independent of the GEMM sweep above):
    # in-kernel block-table walk (interpret mode) vs the jnp gather oracle
    # that materializes a (B, mb·bs, Hkv, hd) logical view in HBM on every
    # chunked-prefill / decode call
    from repro.models.attention import paged_attention
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    nb_, bs_, mb_ = 24, 16, 16
    bp, tq, hq, hkv, hd = 2, 16, 4, 2, 64
    kpool = jax.random.normal(kk, (nb_, hkv, bs_, hd), dtype=jnp.bfloat16)
    vpool = jax.random.normal(kv, (nb_, hkv, bs_, hd), dtype=jnp.bfloat16)
    tab = np.full((bp, mb_), -1, np.int32)
    tab[0, :8] = np.arange(1, 9)           # 128 valid rows
    tab[1, :6] = np.arange(9, 15)          # 96 valid rows
    tabj = jnp.asarray(tab)
    qp = jax.random.normal(kq, (bp, tq, hq, hd), dtype=jnp.bfloat16)
    kvl = jnp.asarray([8 * bs_, 6 * bs_], jnp.int32)
    kw = dict(causal=True, q_offset=jnp.asarray(4 * bs_, jnp.int32),
              kv_len=kvl, chunk=128)
    # jit BOTH sides so the row compares lowered programs, not the
    # oracle's eager per-op Python dispatch against a cached pallas_call
    run_k = jax.jit(lambda q_, kp_, vp_, t_: paged_attention(
        q_, kp_, vp_, t_, use_kernel=True, **kw))
    run_o = jax.jit(lambda q_, kp_, vp_, t_: paged_attention(
        q_, kp_, vp_, t_, use_kernel=False, **kw))
    us_k = timeit_us(lambda: run_k(qp, kpool, vpool, tabj), iters=3)
    us_o = timeit_us(lambda: run_o(qp, kpool, vpool, tabj), iters=3)
    row_head = hd * 2                             # one head's KV row, bf16
    walked = int(jnp.sum(jnp.ceil(kvl / bs_))) * bs_
    view_rows = bp * mb_ * bs_
    # oracle: full-Hkv logical view written then re-read (2 passes), K and V
    est_o = (2 * 2 * view_rows * hkv * row_head) / HBM
    # kernel: each allocated block streams once per KV head (its group's
    # query heads share the q tile; one q tile here) — the cost model in
    # kernels/__init__.py
    est_k = (2 * hkv * walked * row_head) / HBM
    rows.append(csv_row(
        f"kernel/paged_attention/{bp}x{tq}x{mb_ * bs_}", us_k,
        f"oracle_us={us_o:.1f};tpu_est_s={est_k:.3e};"
        f"gather_est_s={est_o:.3e};"
        f"hbm_traffic_ratio={est_o / est_k:.2f}x"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
