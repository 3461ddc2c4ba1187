"""SparseLinear — every projection in the model zoo goes through here.

Dispatch order per call (all static except the per-layer skip flag):

  1. quantized?   (``wq`` present → W8A8 path; Outstanding-sparse prunes the
                   *smoothed* activations, paper §Outstanding-sparse)
  2. prunable?    (policy says this module is pruned in this phase)
  3. mode:        per-token N:M mask (paper-faithful) or tile-consensus
                   compacted matmul (TPU-native)
  4. backend:     ``policy.use_pallas_kernels`` lowers the pruned matmul /
                   Outstanding-sparse chain to one fused pallas_call
                   (``repro.kernels.ops``); the jnp forms below remain the
                   bit-exact oracle

``layer_flag`` supports ``lax.scan``-stacked layers: the per-layer q/gate
skip list becomes a boolean vector scanned alongside the weights.  With
kernels on, a flagged projection is a ``lax.cond`` between the pruned
kernel and the dense projection, so only the chosen branch runs; the jnp
oracle selects pruned vs dense *input* with a ``jnp.where`` (element-wise;
leaves matmul FLOPs untouched in per-token mode).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import pruner, quant
from repro.core.policy import SparsityPolicy
from repro.core.pruner import SCALE_KEY

__all__ = ["init_linear", "dense_linear", "sparse_linear"]


def init_linear(
    rng: jax.Array,
    d_in: int,
    d_out: int,
    bias: bool = False,
    dtype: Any = jnp.float32,
    scale: Optional[float] = None,
) -> Dict[str, jax.Array]:
    """He/Glorot-ish init: normal with std 1/sqrt(d_in) (or ``scale``)."""
    std = scale if scale is not None else d_in**-0.5
    p = {"w": (jax.random.normal(rng, (d_in, d_out)) * std).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense_linear(x: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def _quantized(x: jax.Array, p: Dict[str, jax.Array], prune: bool,
               policy: SparsityPolicy, layer_flag) -> jax.Array:
    """Outstanding-sparse path: smooth → (prune) → int8 matmul.

    With ``policy.use_pallas_kernels`` the whole chain collapses into one
    fused ``osparse_matmul`` pallas_call (no smoothed/masked/quantized
    copies in HBM) — for BOTH phases: the decode-phase call sets
    ``prune=False`` statically, which skips the N:M selection in-kernel and
    runs the plain smoothed W8A8 GEMM, and the bias-add rides the dequant
    epilogue.  (Flagged layers reach here with ``layer_flag=None``: the
    kernel path resolves the flag with ``lax.cond`` in ``sparse_linear``.)
    """
    per_token = bool(p.get("per_token", False))
    if policy.use_pallas_kernels:
        from repro.kernels import ops

        y = ops.osparse_matmul(
            x, p["wq"], p["smooth"], p.get(SCALE_KEY), p["w_scale"],
            policy.n, policy.m,
            act_scale=None if per_token else p["act_scale"],
            bias=p.get("b"), prune=prune, per_token=per_token)
        return y.astype(x.dtype)

    xs = x.astype(jnp.float32) / p["smooth"]
    if prune:
        xp = pruner.prune_input(xs, p.get(SCALE_KEY), policy)
        if layer_flag is not None:
            xp = jnp.where(layer_flag, xp, xs)
        xs = xp
    if per_token:
        xq, ts = quant.quantize_act_per_token(xs)
        y = quant.quantized_matmul(xq, p["wq"], ts, p["w_scale"])
    else:
        xq = quant.quantize_act_per_tensor(xs, p["act_scale"])
        y = quant.quantized_matmul(xq, p["wq"], p["act_scale"], p["w_scale"])
    y = y.astype(x.dtype)
    if "b" in p:
        y = y + p["b"]
    return y


def sparse_linear(
    x: jax.Array,
    p: Dict[str, jax.Array],
    module: str,
    policy: SparsityPolicy,
    phase: str,
    layer_idx: Optional[int] = None,
    layer_flag: Optional[jax.Array] = None,
) -> jax.Array:
    """Linear projection honoring the Amber Pruner policy.

    Args:
      module:     canonical projection name ('q_proj', 'down_proj', ...).
      layer_idx:  static layer index (unrolled models) — consults the
                  policy's skip list directly.
      layer_flag: traced bool (scan-stacked models) — True ⇒ prune here.
    """
    prune = policy.active(phase) and policy.should_prune(module, layer_idx)
    if prune and layer_flag is not None and policy.use_pallas_kernels:
        # scan-stacked skip list with kernels on: the flag picks the pruned
        # kernel or the dense projection at run time — both branches are
        # compiled, only the chosen one runs
        return jax.lax.cond(layer_flag,
                            lambda x_: _project(x_, p, True, policy),
                            lambda x_: _project(x_, p, False, policy), x)
    return _project(x, p, prune, policy, layer_flag if prune else None)


def _project(x: jax.Array, p: Dict[str, jax.Array], prune: bool,
             policy: SparsityPolicy, layer_flag=None) -> jax.Array:
    if "wq" in p:  # Outstanding-sparse / plain W8A8
        return _quantized(x, p, prune, policy, layer_flag)

    if not prune:
        return dense_linear(x, p)

    scale = p.get(SCALE_KEY)
    if policy.tile_consensus or policy.use_pallas_kernels:
        # with kernels on: one fused pallas_call, bias-add folded into the
        # kernel epilogue
        y = pruner.sparse_matmul(x, p["w"], scale, policy, bias=p.get("b"))
        if layer_flag is not None:
            # jnp tile-consensus: compacted inputs can't be element-wise
            # selected against the dense ones, so pick between the outputs
            y = jnp.where(layer_flag, y, dense_linear(x, p))
    else:
        # jnp oracle, mask-select form: scan-stacked models pick pruned vs
        # dense input with the traced per-layer flag
        xp = pruner.prune_input(x, scale, policy)
        if layer_flag is not None:
            xp = jnp.where(layer_flag, xp, x)
        y = xp @ p["w"]
        if "b" in p:
            y = y + p["b"]
    return y
