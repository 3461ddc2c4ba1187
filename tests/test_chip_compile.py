"""Compile rehearsal: every kernel of the served path, at qwen2_7b widths
and at qwen3_30b_a3b's where it differs (routed experts, q width, GQA
group 8), lowered by Mosaic for a described TPU v5e (no chip attached).

Interpret mode passes kernels that Mosaic refuses for tiling, layout or
VMEM reasons; this file is where such a refusal shows up without a chip.
The topology is described inside a module fixture (never at import), and
the persistent compilation cache is off around these compiles: an entry
written for a described device cannot be read back without one.
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import (paged_attention_pallas,
                                           paged_kv_scatter_pallas)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

# qwen2_7b: prefill chunk T, d_model D, d_ff N, heads, head_dim, KV block
T, D, N, HQ, HKV, HD, BS = 2048, 3584, 18944, 28, 4, 128, 16
SLOTS, MAX_SEQ = 8, 8192
NB, MB = SLOTS * MAX_SEQ // BS + 1, MAX_SEQ // BS
CELL_SLOTS = 4
CELL_NB = CELL_SLOTS * MB + 1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)`` → a ShapeDtypeStruct on one described chip;
    the persistent compilation cache stays off while these compile."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


bf, f32, i8, i32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32

# qwen3_30b_a3b: a 512-token chunk's routed rows (x top-8), d_model,
# expert width, routed experts, q width (32 heads x 128), GQA group 8
Q3_ROWS, Q3_D, Q3_F, Q3_E, Q3_Q, Q3_HQ = 4096, 2048, 768, 128, 4096, 32

# name → (fn, operand shapes, is a projection kernel)
_CASES = {
    "nm_prune": (
        lambda x, s: ops.nm_prune(x, s, 8, 16, interpret=False),
        [((T, D), bf), ((D,), bf)], False),
    "nm_prune_matmul": (
        lambda x, w, s, b: ops.nm_prune_matmul(x, w, s, 8, 16, bias=b,
                                               interpret=False),
        [((T, D), bf), ((D, N), bf), ((D,), bf), ((N,), bf)], True),
    "nm_prune_matmul_down": (
        lambda x, w, s: ops.nm_prune_matmul(x, w, s, 8, 16, interpret=False),
        [((T, N), bf), ((N, D), bf), ((N,), bf)], True),
    # the benchmark cell's 512-token chunk through q_proj (with its bias),
    # gate_proj and down_proj: the blocks the wrapper picks for them
    "nm_prune_matmul_q_proj_512": (
        lambda x, w, s, b: ops.nm_prune_matmul(x, w, s, 8, 16, bias=b,
                                               interpret=False),
        [((512, D), bf), ((D, D), bf), ((D,), bf), ((D,), bf)], True),
    "nm_prune_matmul_gate_512": (
        lambda x, w, s: ops.nm_prune_matmul(x, w, s, 8, 16, interpret=False),
        [((512, D), bf), ((D, N), bf), ((D,), bf)], True),
    "nm_prune_matmul_down_512": (
        lambda x, w, s: ops.nm_prune_matmul(x, w, s, 8, 16, interpret=False),
        [((512, N), bf), ((N, D), bf), ((N,), bf)], True),
    # a prompt's last chunk of 20 tokens: one unpadded token block
    "nm_prune_matmul_tail_20": (
        lambda x, w, s: ops.nm_prune_matmul(x, w, s, 8, 16, interpret=False),
        [((20, D), bf), ((D, D), bf), ((D,), bf)], True),
    # the policy's other patterns at q_proj's width
    "nm_prune_matmul_2_4": (
        lambda x, w, s: ops.nm_prune_matmul(x, w, s, 2, 4, interpret=False),
        [((512, D), bf), ((D, D), bf), ((D,), bf)], True),
    "nm_prune_matmul_4_8": (
        lambda x, w, s: ops.nm_prune_matmul(x, w, s, 4, 8, interpret=False),
        [((512, D), bf), ((D, D), bf), ((D,), bf)], True),
    "nm_spmm": (
        lambda x, w, s: ops.nm_spmm(x, w, s, 8, 16, interpret=False),
        [((T, D), bf), ((D, N), bf), ((D,), bf)], True),
    "osparse_prefill": (
        lambda x, wq, sm, am, ws, a: ops.osparse_matmul(
            x, wq, sm, am, ws, 8, 16, act_scale=a, interpret=False),
        [((T, D), bf), ((D, N), i8), ((D,), f32), ((D,), f32), ((N,), f32),
         ((), f32)], True),
    "osparse_prefill_per_token": (
        lambda x, wq, sm, am, ws: ops.osparse_matmul(
            x, wq, sm, am, ws, 8, 16, per_token=True, interpret=False),
        [((T, D), bf), ((D, N), i8), ((D,), f32), ((D,), f32), ((N,), f32)],
        True),
    "osparse_decode": (
        lambda x, wq, sm, ws: ops.osparse_matmul(
            x, wq, sm, None, ws, 8, 16, prune=False, per_token=True,
            interpret=False),
        [((SLOTS, D), bf), ((D, N), i8), ((D,), f32), ((N,), f32)], True),
    "w8a8_matmul": (
        lambda x, wq, xs, ws: ops.w8a8_matmul(x, wq, xs, ws,
                                              interpret=False),
        [((T, D), i8), ((D, N), i8), ((), f32), ((N,), f32)], True),
    "flash_attention": (
        lambda q, k, v: flash_attention_pallas(q, k, v, interpret=False),
        [((1, HQ, T, HD), bf), ((1, HKV, T, HD), bf), ((1, HKV, T, HD), bf)],
        False),
    "paged_attention_prefill": (
        lambda q, kp, vp, tab, qo, kl: paged_attention_pallas(
            q, kp, vp, tab, qo, kl, causal=True, interpret=False),
        [((1, 512, HQ, HD), bf), ((NB, HKV, BS, HD), bf),
         ((NB, HKV, BS, HD), bf), ((1, MB), i32), ((1,), i32), ((1,), i32)],
        False),
    "paged_attention_decode": (
        lambda q, kp, vp, tab, qo, kl: paged_attention_pallas(
            q, kp, vp, tab, qo, kl, causal=False, interpret=False),
        [((SLOTS, 1, HQ, HD), bf), ((NB, HKV, BS, HD), bf),
         ((NB, HKV, BS, HD), bf), ((SLOTS, MB), i32), ((SLOTS,), i32),
         ((SLOTS,), i32)], False),
    # the benchmark cell's engine: 4 slots of 8192 positions (2049 pool
    # rows), a 512-token chunk and the 4-row decode
    "paged_attention_prefill_cell": (
        lambda q, kp, vp, tab, qo, kl: paged_attention_pallas(
            q, kp, vp, tab, qo, kl, causal=True, interpret=False),
        [((1, 512, HQ, HD), bf), ((CELL_NB, HKV, BS, HD), bf),
         ((CELL_NB, HKV, BS, HD), bf), ((1, MB), i32), ((1,), i32),
         ((1,), i32)], False),
    "paged_attention_decode_cell": (
        lambda q, kp, vp, tab, qo, kl: paged_attention_pallas(
            q, kp, vp, tab, qo, kl, causal=False, interpret=False),
        [((CELL_SLOTS, 1, HQ, HD), bf), ((CELL_NB, HKV, BS, HD), bf),
         ((CELL_NB, HKV, BS, HD), bf), ((CELL_SLOTS, MB), i32),
         ((CELL_SLOTS,), i32), ((CELL_SLOTS,), i32)], False),
    # multi-query attention, one KV head for 48 query heads (granite_34b):
    # the group shrinks the q tile's positions, not its rows
    "paged_attention_prefill_g48": (
        lambda q, kp, vp, tab, qo, kl: paged_attention_pallas(
            q, kp, vp, tab, qo, kl, causal=True, interpret=False),
        [((1, 512, 48, HD), bf), ((CELL_NB, 1, BS, HD), bf),
         ((CELL_NB, 1, BS, HD), bf), ((1, MB), i32), ((1,), i32),
         ((1,), i32)], False),
    "paged_attention_decode_g48": (
        lambda q, kp, vp, tab, qo, kl: paged_attention_pallas(
            q, kp, vp, tab, qo, kl, causal=False, interpret=False),
        [((CELL_SLOTS, 1, 48, HD), bf), ((CELL_NB, 1, BS, HD), bf),
         ((CELL_NB, 1, BS, HD), bf), ((CELL_SLOTS, MB), i32),
         ((CELL_SLOTS,), i32), ((CELL_SLOTS,), i32)], False),
    "paged_kv_scatter_prefill": (
        lambda kn, vn, kp, vp, tab, p, c: paged_kv_scatter_pallas(
            kn, vn, kp, vp, tab, p, c, interpret=False),
        [((1, 512, HKV, HD), bf), ((1, 512, HKV, HD), bf),
         ((NB, HKV, BS, HD), bf), ((NB, HKV, BS, HD), bf), ((1, MB), i32),
         ((1,), i32), ((1,), i32)], False),
    # float32 operands contract at full precision (chip_smoke's comparison)
    "nm_prune_matmul_f32": (
        lambda x, w, s: ops.nm_prune_matmul(x, w, s, 8, 16, interpret=False),
        [((512, D), f32), ((D, D), f32), ((D,), f32)], True),
    "paged_attention_prefill_f32": (
        lambda q, kp, vp, tab, qo, kl: paged_attention_pallas(
            q, kp, vp, tab, qo, kl, causal=True, interpret=False),
        [((1, 512, HQ, HD), f32), ((NB, HKV, BS, HD), f32),
         ((NB, HKV, BS, HD), f32), ((1, MB), i32), ((1,), i32), ((1,), i32)],
        False),
    "paged_kv_scatter_prefill_f32": (
        lambda kn, vn, kp, vp, tab, p, c: paged_kv_scatter_pallas(
            kn, vn, kp, vp, tab, p, c, interpret=False),
        [((1, 512, HKV, HD), f32), ((1, 512, HKV, HD), f32),
         ((NB, HKV, BS, HD), f32), ((NB, HKV, BS, HD), f32), ((1, MB), i32),
         ((1,), i32), ((1,), i32)], False),
    # routed experts at Qwen3-30B-A3B widths: gate (pruned), up (dense)
    # and down (pruned) over one chunk's expert-sorted rows
    "nm_grouped_matmul_gate_q3": (
        lambda x, w, g: ops.nm_grouped_matmul(x, w, g, 8, 16, prune=True,
                                              interpret=False),
        [((Q3_ROWS, Q3_D), bf), ((Q3_E, Q3_D, Q3_F), bf), ((Q3_E,), i32)],
        False),
    "nm_grouped_matmul_up_q3": (
        lambda x, w, g: ops.nm_grouped_matmul(x, w, g, 8, 16, prune=False,
                                              interpret=False),
        [((Q3_ROWS, Q3_D), bf), ((Q3_E, Q3_D, Q3_F), bf), ((Q3_E,), i32)],
        False),
    "nm_grouped_matmul_down_q3": (
        lambda x, w, g: ops.nm_grouped_matmul(x, w, g, 8, 16, prune=True,
                                              interpret=False),
        [((Q3_ROWS, Q3_F), bf), ((Q3_E, Q3_F, Q3_D), bf), ((Q3_E,), i32)],
        False),
    # as the serving layer scan calls it: the whole stack of 6 layers'
    # experts, read in place at the layer's index
    "nm_grouped_matmul_layer_stack_q3": (
        lambda x, w, g, i: ops.nm_grouped_matmul(x, w, g, 8, 16, prune=True,
                                                 interpret=False, layer=i),
        [((Q3_ROWS, Q3_D), bf), ((6, Q3_E, Q3_D, Q3_F), bf), ((Q3_E,), i32),
         ((), i32)], False),
    # decode: the active slots' routed rows, dense
    "nm_grouped_matmul_decode_q3": (
        lambda x, w, g: ops.nm_grouped_matmul(x, w, g, 8, 16, prune=False,
                                              interpret=False),
        [((SLOTS * 8, Q3_D), bf), ((Q3_E, Q3_D, Q3_F), bf), ((Q3_E,), i32)],
        False),
    "nm_prune_matmul_q_proj_q3": (
        lambda x, w, s: ops.nm_prune_matmul(x, w, s, 8, 16, interpret=False),
        [((512, Q3_D), bf), ((Q3_D, Q3_Q), bf), ((Q3_D,), bf)], True),
    "paged_attention_prefill_q3": (
        lambda q, kp, vp, tab, qo, kl: paged_attention_pallas(
            q, kp, vp, tab, qo, kl, causal=True, interpret=False),
        [((1, 512, Q3_HQ, HD), bf), ((CELL_NB, HKV, BS, HD), bf),
         ((CELL_NB, HKV, BS, HD), bf), ((1, MB), i32), ((1,), i32),
         ((1,), i32)], False),
    "paged_kv_scatter_decode": (
        lambda kn, vn, kp, vp, tab, p, c: paged_kv_scatter_pallas(
            kn, vn, kp, vp, tab, p, c, interpret=False),
        [((SLOTS, 1, HKV, HD), bf), ((SLOTS, 1, HKV, HD), bf),
         ((NB, HKV, BS, HD), bf), ((NB, HKV, BS, HD), bf),
         ((SLOTS, MB), i32), ((SLOTS,), i32), ((SLOTS,), i32)], False),
}


@pytest.mark.parametrize("name", sorted(_CASES))
def test_kernel_compiles_for_v5e(spec, name):
    fn, shapes, projection = _CASES[name]
    args = [spec(s, dt) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    # chip_smoke counts the projection kernels by their custom calls
    assert chip_smoke.projection_kernel_count(text) == int(projection)
