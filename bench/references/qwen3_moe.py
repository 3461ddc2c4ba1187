"""Plain reference of a Qwen3 mixture-of-experts decoder under Amber-P, in
float32.

Imports nothing of the program.  Its weights come from ``bench.weights``
by path (the same seeded values the program is served with, regenerated
here), and its q_proj Amber scales are computed here from those weights.
It shares the Qwen2 reference's pieces (``bench/references/qwen2.py``):
RMSNorm, RoPE, causal grouped-query attention in row blocks, the N:M
selection, the Robust-Norm scale, the int8 control and ``gaps``.

The model (Qwen3-30B-A3B, as published): token embedding; per layer
RMSNorm -> q/k/v projections without bias -> RMSNorm over each head's q
and k (its own weight of ``head_dim``, eps ``rms_norm_eps``) -> RoPE
(rotate-half, ``rope_theta``) -> causal grouped-query attention -> o
projection -> residual; RMSNorm -> routed experts -> residual; final
RMSNorm -> LM head.  Routed experts: a float32 router over all
``num_experts`` experts, the ``num_experts_per_tok`` largest logits, gates
a softmax over those chosen logits (``norm_topk_prob``), each chosen
expert a SwiGLU MLP (``down(silu(gate(x)) * up(x))``) of width
``moe_intermediate_size``, the outputs summed with their gates.  No
shared expert.

Amber-P (paper's deployment): in prefill, the input of ``q_proj`` keeps
the 8 of every 16 consecutive channels with the largest ``|x| * s`` (``s``
its Robust-Norm scale), and the inputs of each routed expert's
``gate_proj`` and ``down_proj`` those with the largest plain ``|x|``;
q_proj and the experts' gate_proj are skipped in the configuration's
skip layers.  The router and the k, v, o and up projections run dense, as
do decode rows (rows at or past the prompt's end).

The experts are computed over the rows routed to each, in tiles of
``TILE`` rows of one expert (each expert's rows padded to whole tiles),
never as a dense all-expert product.  A sequence is padded to a power of
two of whole row blocks, so a sample compiles a few shapes.

Precision: every operation computes in float32 (matrix products at
``highest``) and its result is rounded to ``torch_dtype`` (a
reduce-precision, never folded away) where the served model stores one: the normed inputs, each projection's output, the normed
and the rotated q and k, the attention output, the residual stream, the
gates, each expert's gate, up and down outputs and its hidden product,
and the summed expert output.  The router's weight is float32, as the
program holds it.  The logits stay float32.

``control=True`` computes the same model with every projection, the
router and the LM head in int8 (per-output-channel weights, per-token
activations): the precision step below the configuration's bfloat16.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from bench import sizes, weights
from bench.references.qwen2 import (ROW_BLOCK, _attention, _matmul, _nm,
                                    _rms, _rope, gaps, robust_scale)

__all__ = ["Reference", "gaps", "layout"]

TILE = 128                  # rows of one expert computed together
P = "periods/b0/"
E = P + "moe/experts/"


def _storer(conf: dict):
    """Round a float32 value to the configuration's dtype, kept in float32:
    a reduce-precision, which XLA keeps where it may fold a pair of casts
    away."""
    info = jnp.finfo(jnp.dtype(conf["torch_dtype"]))
    return lambda a: jax.lax.reduce_precision(
        a, exponent_bits=info.nexp, mantissa_bits=info.nmant)


def _head_fn(conf: dict, control: bool):
    eps = conf["rms_norm_eps"]
    store = _storer(conf)

    @jax.jit
    def head(h, norm, w):
        return _matmul(store(_rms(h, eps) * norm.astype(jnp.float32)), w,
                       control)

    return head


def layout(conf: dict) -> dict:
    """Path -> (shape, dtype) of every weight the reference reads, in the
    served program's parameter layout (stacked over layers)."""
    d, v = conf["hidden_size"], conf["vocab_size"]
    qd, kvd, hd = sizes.q_dim(conf), sizes.kv_dim(conf), sizes.head_dim(conf)
    e = sizes.experts(conf)
    n = conf["num_hidden_layers"]
    dt = jnp.dtype(conf["torch_dtype"])
    shapes = {"embed/w": (v, d), "final_norm/w": (d,), "lm_head/w": (d, v),
              P + "ln1/w": (n, d), P + "ln2/w": (n, d),
              P + "q_norm/w": (n, hd), P + "k_norm/w": (n, hd),
              P + "q_proj/w": (n, d, qd), P + "k_proj/w": (n, d, kvd),
              P + "v_proj/w": (n, d, kvd), P + "o_proj/w": (n, qd, d),
              E + "gate_proj/w": (n, e.count, d, e.width),
              E + "up_proj/w": (n, e.count, d, e.width),
              E + "down_proj/w": (n, e.count, e.width, d)}
    out = {p: (s, dt) for p, s in shapes.items()}
    out[P + "moe/router/w"] = ((n, d, e.count), jnp.dtype(jnp.float32))
    return out


def load(conf: dict, seed: int) -> dict:
    """The weights, as served, each leaf made by its own call."""
    key = weights.base_key(seed)
    return {path: jax.jit(lambda k, p=path, s=shape, t=dt: weights.leaf(
        k, p, s, t))(key) for path, (shape, dt) in layout(conf).items()}


def _attn_fn(conf: dict, control: bool):
    """Norm, attention with q/k norms, residual, and the experts' normed
    input: (h, x2)."""
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = sizes.head_dim(conf)
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    n, m = conf["amber"]["n"], conf["amber"]["m"]
    store = _storer(conf)

    @jax.jit
    def attn(h, lw, q_scale, q_flag, n_prompt):
        t = h.shape[0]
        pos = jnp.arange(t)
        rows = (pos < n_prompt)[:, None]
        x = store(_rms(h, eps) * lw["ln1"].astype(jnp.float32))
        xq = jnp.where(rows & q_flag, _nm(x, q_scale, n, m), x)
        q = store(_matmul(xq, lw["q_w"], control))
        k = store(_matmul(x, lw["k_w"], control))
        v = store(_matmul(x, lw["v_w"], control))
        q = store(_rms(q.reshape(t, hq, hd), eps)
                  * lw["q_norm"].astype(jnp.float32))
        k = store(_rms(k.reshape(t, hkv, hd), eps)
                  * lw["k_norm"].astype(jnp.float32))
        q = _rope(q, pos, theta, store)
        k = _rope(k, pos, theta, store)
        o = store(_attention(q, k, v.reshape(t, hkv, hd)))
        h = store(h + store(_matmul(o, lw["o_w"], control)))
        return h, store(_rms(h, eps) * lw["ln2"].astype(jnp.float32))

    return attn


def _route_fn(conf: dict, control: bool):
    k = sizes.experts(conf).top_k
    store = _storer(conf)

    @jax.jit
    def route(x2, router):
        logits = _matmul(x2, router, control)
        vals, ids = jax.lax.top_k(logits, k)
        return ids, store(jax.nn.softmax(vals, axis=-1))

    return route


def _experts_fn(conf: dict, control: bool):
    """h + the routed experts' output.  Rows sorted by expert are laid
    out in whole tiles of ``TILE`` rows, each expert's rows padded to its
    own tiles (at most ``t * top_k / TILE + experts`` tiles), and each
    tile is computed with its expert's weights."""
    n, m = conf["amber"]["n"], conf["amber"]["m"]
    store = _storer(conf)

    @jax.jit
    def experts(h, x2, ids, gates, wg, wu, wd, n_prompt, g_flag):
        t, d = x2.shape
        count, _, width = wg.shape
        rows = ids.size
        flat_e = ids.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        e_sorted = flat_e[order]
        counts = jnp.bincount(flat_e, length=count)
        padded = -(-counts // TILE) * TILE
        ends = jnp.cumsum(padded)
        # each sorted row's place in its expert's padded tiles
        slot = (ends - padded)[e_sorted] + jnp.arange(rows) - (
            jnp.cumsum(counts) - counts)[e_sorted]
        tiles = rows // TILE + count
        src = jnp.full((tiles * TILE,), t).at[slot].set(order // ids.shape[1])
        gate = jnp.zeros((tiles * TILE,)).at[slot].set(
            gates.reshape(-1)[order])
        tile_e = jnp.minimum(jnp.searchsorted(
            ends, jnp.arange(tiles) * TILE, side="right"), count - 1)
        xpad = jnp.concatenate([x2, jnp.zeros((1, d))])     # row t: none
        ones_d, ones_f = jnp.ones((d,)), jnp.ones((width,))

        def one(y, xs):
            e, idx, g_t = xs
            pre = (idx < n_prompt)[:, None]
            xe = xpad[idx]
            xg = jnp.where(pre & g_flag, _nm(xe, ones_d, n, m), xe)
            g = store(_matmul(xg, wg[e], control))
            u = store(_matmul(xe, wu[e], control))
            a = store(jax.nn.silu(g) * u)
            a = jnp.where(pre, _nm(a, ones_f, n, m), a)
            out = store(_matmul(a, wd[e], control))
            return y.at[idx].add(out * g_t[:, None]), None

        y, _ = jax.lax.scan(one, jnp.zeros((t + 1, d)),
                            (tile_e, src.reshape(tiles, TILE),
                             gate.reshape(tiles, TILE)))
        return store(h + store(y[:t]))

    return experts


class Reference:
    """The reference model of one seed, its weights on the device."""

    def __init__(self, conf: dict, seed: int):
        self.conf = conf
        self.w = load(conf, seed)
        skip = conf["amber"]["qgate_skip_layers"]
        self.scales = {i: robust_scale(self.w[P + "q_proj/w"][i])
                       for i in range(conf["num_hidden_layers"])
                       if i not in skip}
        self._attn = {c: _attn_fn(conf, c) for c in (False, True)}
        self._route = {c: _route_fn(conf, c) for c in (False, True)}
        self._experts = {c: _experts_fn(conf, c) for c in (False, True)}
        self._head = {c: _head_fn(conf, c) for c in (False, True)}

    def _layer_args(self, i: int) -> dict:
        w = self.w
        return {"ln1": w[P + "ln1/w"][i], "ln2": w[P + "ln2/w"][i],
                "q_norm": w[P + "q_norm/w"][i],
                "k_norm": w[P + "k_norm/w"][i],
                "q_w": w[P + "q_proj/w"][i], "k_w": w[P + "k_proj/w"][i],
                "v_w": w[P + "v_proj/w"][i], "o_w": w[P + "o_proj/w"][i]}

    def logits(self, tokens, n_prompt: int, positions, control=False):
        """Logits (len(positions), vocab) of the sequence ``tokens`` at
        ``positions``; rows before ``n_prompt`` are prefill (pruned)."""
        t = len(tokens)
        # a power of two of whole row blocks: few shapes to compile
        tb = ROW_BLOCK * 2 ** int(np.ceil(np.log2(-(-t // ROW_BLOCK))))
        ids = jnp.asarray(np.pad(np.asarray(tokens, np.int32), (0, tb - t)))
        w, d = self.w, self.conf["hidden_size"]
        n_p = jnp.int32(n_prompt)
        with jax.default_matmul_precision("highest"):
            h = w["embed/w"][ids].astype(jnp.float32)
            for i in range(self.conf["num_hidden_layers"]):
                s = self.scales.get(i)
                scale = jnp.ones((d,), jnp.float32) if s is None else s
                flag = jnp.asarray(s is not None)
                h, x2 = self._attn[control](h, self._layer_args(i), scale,
                                            flag, n_p)
                eids, gates = self._route[control](
                    x2, w[P + "moe/router/w"][i])
                h = self._experts[control](
                    h, x2, eids, gates, w[E + "gate_proj/w"][i],
                    w[E + "up_proj/w"][i], w[E + "down_proj/w"][i], n_p,
                    flag)
            return self._head[control](h[jnp.asarray(positions)],
                                       w["final_norm/w"], w["lm_head/w"])
