"""Plain reference of a Qwen2 decoder under Amber-P, in float32.

Imports nothing of the program.  Its weights come from ``bench.weights`` by
path (the same seeded values the program is served with, regenerated here),
and its Amber scales are computed here from those weights.

The model (Qwen2, as published): token embedding; per layer RMSNorm ->
q/k/v projections with bias -> RoPE (rotate-half, ``rope_theta``) -> causal
grouped-query attention -> o projection -> residual; RMSNorm -> SwiGLU MLP
(``down(silu(gate(x)) * up(x))``) -> residual; final RMSNorm -> LM head.

Amber-P (paper's deployment): in prefill, the inputs of ``q_proj`` and
``gate_proj`` (except in the configuration's skip layers) and of
``down_proj`` (every layer) keep the 8 of every 16 consecutive channels with
the largest ``|x| * s``, ties to the lower channel.  ``s`` is the
Robust-Norm score of the weight: clamp to its [0.5%, 99.5%] quantiles
(linear interpolation), standardize by the clamped tensor's mean and
deviation, L2 norm of each input channel's row, divided by the smallest
such norm.  Decode runs dense: a served token fed back into the model is
not pruned, so rows at or past the prompt's end are dense here too.

Precision: every operation computes in float32 (matrix products at
``highest``), and its result is stored in the configuration's
``torch_dtype`` (bfloat16) where the served model stores one: the normed
inputs of the projections, each projection's output, the rotated q and k,
the attention output, the residual stream and the MLP's hidden product.
The logits stay float32.  The N:M selection is discontinuous: compared
with activations kept in float32, a one-ulp difference flips near-tied
selections and the flips cascade through the layers (PERF.md,
Findings), so the reference holds the values the configuration serves.

``control=True`` computes the same model with every projection and the LM
head in int8 (per-output-channel weights, per-token activations): the
precision step below the configuration's bfloat16.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from bench import sizes, weights

ROW_BLOCK = 1024            # rows per block of the MLP and of attention


def layout(conf: dict) -> dict:
    """Path -> shape of every weight the reference reads, in the served
    program's parameter layout (stacked over layers)."""
    d, f, v = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"]
    qd, kvd = sizes.q_dim(conf), sizes.kv_dim(conf)
    n = conf["num_hidden_layers"]
    p = "periods/b0/"
    out = {"embed/w": (v, d), "final_norm/w": (d,), "lm_head/w": (d, v),
           p + "ln1/w": (n, d), p + "ln2/w": (n, d),
           p + "o_proj/w": (n, qd, d),
           p + "mlp/gate_proj/w": (n, d, f), p + "mlp/up_proj/w": (n, d, f),
           p + "mlp/down_proj/w": (n, f, d)}
    for name, width in (("q_proj", qd), ("k_proj", kvd), ("v_proj", kvd)):
        out[p + name + "/w"] = (n, d, width)
        out[p + name + "/b"] = (n, width)
    return out


def load(conf: dict, seed: int) -> dict:
    """The weights, bfloat16 as served, each leaf made by its own call."""
    key = weights.base_key(seed)
    dt = jnp.dtype(conf["torch_dtype"])
    out = {}
    for path, shape in layout(conf).items():
        out[path] = jax.jit(lambda k, p=path, s=shape: weights.leaf(
            k, p, s, dt))(key)
    return out


# ------------------------------------------------------------ Amber scales

def _order_key(x):
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


@jax.jit
def _kth(x, k):
    """The k-th smallest (0-based) of a float32 array, by bisection over
    its 32-bit order keys: one counting pass per bit, no sort."""
    key = _order_key(x.reshape(-1))

    def step(i, lo):
        cand = lo | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(jnp.sum(key < cand) <= k, cand, lo)

    ans = jax.lax.fori_loop(0, 32, step, jnp.uint32(0))
    u = jnp.where(ans >> 31 == 1, ans & jnp.uint32(0x7FFFFFFF), ~ans)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _quantile(x, q: float):
    pos = q * (x.size - 1)
    lo, hi = int(np.floor(pos)), int(np.ceil(pos))
    a, b = _kth(x, lo), _kth(x, hi)
    return a + (b - a) * (pos - lo)


@jax.jit
def _robust(w, lo, hi):
    wc = jnp.clip(w.astype(jnp.float32), lo, hi)
    wn = (wc - wc.mean()) / jnp.sqrt(wc.var() + 1e-12)
    norms = jnp.sqrt(jnp.sum(wn * wn, axis=-1))
    return norms / (norms.min() + 1e-12)


def robust_scale(w) -> jax.Array:
    wf = w.astype(jnp.float32)
    return _robust(w, _quantile(wf, 0.005), _quantile(wf, 0.995))


def pruned(conf: dict):
    """(layer, module) pairs Amber-P prunes in prefill."""
    return [(i, p.name) for i in range(conf["num_hidden_layers"])
            for p in sizes.projections(conf, i) if p.pruned]


def scales(conf: dict, w: dict) -> dict:
    return {(i, m): robust_scale(w[f"periods/b0/{m}/w"][i])
            for i, m in pruned(conf)}


# ------------------------------------------------------------------ model

def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _nm(x, s, n, m):
    """Keep the n largest |x| * s of every m consecutive channels: an
    entry's rank is how many of its group score higher, or score the same
    at a lower channel; ranks below n stay."""
    t, d = x.shape
    g = (jnp.abs(x) * s).reshape(t, d // m, m)
    a, b = g[..., :, None], g[..., None, :]
    lower = jnp.arange(m)[None, :] < jnp.arange(m)[:, None]   # [i, j]: j < i
    rank = jnp.sum((b > a) | ((b == a) & lower), axis=-1)
    return jnp.where((rank < n).reshape(t, d), x, 0.0)


def _int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale) * scale


def _matmul(x, w, control):
    w = w.astype(jnp.float32)
    if control:
        x, w = _int8(x, -1), _int8(w, 0)
    return x @ w


def _rope(x, pos, theta, store):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    c, s = store(jnp.cos(ang)), store(jnp.sin(ang))
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return store(jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1))


def _storer(conf: dict):
    """Round a float32 value to the dtype the configuration serves in."""
    dt = jnp.dtype(conf["torch_dtype"])
    return lambda a: a.astype(dt).astype(jnp.float32)


def _attention(q, k, v):
    """Causal GQA, one block of queries at a time.  q: (T, Hq, hd)."""
    t, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    kpos = jnp.arange(t)
    outs = []
    for lo in range(0, t, ROW_BLOCK):
        qb = q[lo:lo + ROW_BLOCK].reshape(-1, hkv, g, hd) * hd ** -0.5
        s = jnp.einsum("qkgd,skd->kgqs", qb, k)
        qpos = lo + jnp.arange(qb.shape[0])
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", p, v).reshape(-1, hq * hd))
    return jnp.concatenate(outs)


def _layer_fn(conf: dict, control: bool):
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = sizes.head_dim(conf)
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    n, m = conf["amber"]["n"], conf["amber"]["m"]
    store = _storer(conf)

    def lin(x, w, rows, scale, flag, b=None):
        if scale is not None:
            x = jnp.where(rows[:, None] & flag, _nm(x, scale, n, m), x)
        y = _matmul(x, w, control)
        return store(y if b is None else y + b.astype(jnp.float32))

    @jax.jit
    def layer(h, lw, sc, flags, n_prompt):
        t = h.shape[0]
        pos = jnp.arange(t)
        rows = pos < n_prompt
        x = store(_rms(h, eps) * lw["ln1"].astype(jnp.float32))
        q = lin(x, lw["q_w"], rows, sc["q"], flags[0], lw["q_b"])
        k = lin(x, lw["k_w"], rows, None, False, lw["k_b"])
        v = lin(x, lw["v_w"], rows, None, False, lw["v_b"])
        q = _rope(q.reshape(t, hq, hd), pos, theta, store)
        k = _rope(k.reshape(t, hkv, hd), pos, theta, store)
        o = store(_attention(q, k, v.reshape(t, hkv, hd)))

        def mlp(args):
            hb, ob, rb = args
            hb = store(hb + lin(ob, lw["o_w"], rb, None, False))
            x2 = store(_rms(hb, eps) * lw["ln2"].astype(jnp.float32))
            g = lin(x2, lw["g_w"], rb, sc["g"], flags[1])
            u = lin(x2, lw["u_w"], rb, None, False)
            a = store(jax.nn.silu(g) * u)
            return store(hb + lin(a, lw["d_w"], rb, sc["d"], True))

        blocks = t // ROW_BLOCK
        split = lambda a: a.reshape(blocks, ROW_BLOCK, *a.shape[1:])
        out = jax.lax.map(mlp, (split(h), split(o), split(rows)))
        return out.reshape(t, -1)

    return layer


def _head_fn(conf: dict, control: bool):
    eps = conf["rms_norm_eps"]
    store = _storer(conf)

    @jax.jit
    def head(h, norm, w):
        x = store(_rms(h, eps) * norm.astype(jnp.float32))
        return _matmul(x, w, control)

    return head


class Reference:
    """The reference model of one seed, its weights on the device."""

    def __init__(self, conf: dict, seed: int):
        self.conf = conf
        self.w = load(conf, seed)
        self.scales = scales(conf, self.w)
        self._layer = {c: _layer_fn(conf, c) for c in (False, True)}
        self._head = {c: _head_fn(conf, c) for c in (False, True)}

    def _layer_args(self, i: int):
        w, p = self.w, "periods/b0/"
        lw = {"ln1": w[p + "ln1/w"][i], "ln2": w[p + "ln2/w"][i],
              "o_w": w[p + "o_proj/w"][i]}
        for short, name in (("q", "q_proj"), ("k", "k_proj"),
                            ("v", "v_proj")):
            lw[short + "_w"] = w[p + name + "/w"][i]
            lw[short + "_b"] = w[p + name + "/b"][i]
        for short, name in (("g", "gate_proj"), ("u", "up_proj"),
                            ("d", "down_proj")):
            lw[short + "_w"] = w[p + "mlp/" + name + "/w"][i]
        d, f = self.conf["hidden_size"], self.conf["intermediate_size"]
        sc, flags = {}, []
        for short, name, width in (("q", "q_proj", d),
                                   ("g", "mlp/gate_proj", d),
                                   ("d", "mlp/down_proj", f)):
            s = self.scales.get((i, name))
            sc[short] = jnp.ones((width,), jnp.float32) if s is None else s
            flags.append(s is not None)
        return lw, sc, jnp.asarray(flags[:2])

    def logits(self, tokens, n_prompt: int, positions, control=False):
        """Logits (len(positions), vocab) of the sequence ``tokens`` at
        ``positions``; rows before ``n_prompt`` are prefill (pruned)."""
        t = len(tokens)
        tb = -(-t // ROW_BLOCK) * ROW_BLOCK     # whole row blocks
        ids = jnp.asarray(np.pad(np.asarray(tokens, np.int32), (0, tb - t)))
        with jax.default_matmul_precision("highest"):
            h = self.w["embed/w"][ids].astype(jnp.float32)
            for i in range(self.conf["num_hidden_layers"]):
                lw, sc, flags = self._layer_args(i)
                h = self._layer[control](h, lw, sc, flags,
                                         jnp.int32(n_prompt))
            return self._head[control](h[jnp.asarray(positions)],
                                       self.w["final_norm/w"],
                                       self.w["lm_head/w"])


def gaps(ref: Reference, prompt, out, control: bool = False) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best.  ``control``: the token is instead the one the int8
    model puts first at that position (same prompt and served tokens)."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(out[:-1], np.int32)])
    p = len(prompt)
    pos = np.arange(p - 1, p - 1 + len(out))
    want = np.asarray(ref.logits(seq, p, pos))
    chosen = np.asarray(out)
    if control:
        chosen = np.asarray(ref.logits(seq, p, pos, control=True)).argmax(-1)
    return want.max(-1) - want[np.arange(len(out)), chosen]
