"""Compile rehearsal: every kernel of the served path, at qwen2_7b widths,
lowered by Mosaic for a described TPU v5e (no chip attached).

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
            q, kp, vp, tab, qo, kl, causal=True, block_q=128,
            interpret=False),
        [((1, 512, HQ, HD), bf), ((NB, HKV, BS, HD), bf),
         ((NB, HKV, BS, HD), bf), ((1, MB), i32), ((1,), i32), ((1,), i32)],
        False),
    "paged_attention_decode": (
        lambda q, kp, vp, tab, qo, kl: paged_attention_pallas(
            q, kp, vp, tab, qo, kl, causal=False, block_q=1,
            interpret=False),
        [((SLOTS, 1, HQ, HD), bf), ((NB, HKV, BS, HD), bf),
         ((NB, HKV, BS, HD), bf), ((SLOTS, MB), i32), ((SLOTS,), i32),
         ((SLOTS,), i32)], False),
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
            q, kp, vp, tab, qo, kl, causal=True, block_q=128,
            interpret=False),
        [((1, 512, HQ, HD), f32), ((NB, HKV, BS, HD), f32),
         ((NB, HKV, BS, HD), f32), ((1, MB), i32), ((1,), i32), ((1,), i32)],
        False),
    "paged_kv_scatter_prefill_f32": (
        lambda kn, vn, kp, vp, tab, p, c: paged_kv_scatter_pallas(
            kn, vn, kp, vp, tab, p, c, interpret=False),
        [((1, 512, HKV, HD), f32), ((1, 512, HKV, HD), f32),
         ((NB, HKV, BS, HD), f32), ((NB, HKV, BS, HD), f32), ((1, MB), i32),
         ((1,), i32), ((1,), i32)], False),
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
