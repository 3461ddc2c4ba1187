"""The Amber projection kernel alone on the chip: device time of one
``nm_prune_matmul`` call (8:16, Robust-Norm-style channel scale, bf16) at
the prefill projections of the benchmark's models, beside XLA's dense
projection of the same shape.

    PYTHONPATH=src python benchmarks/nm_prune_matmul_chip.py \
        [--other path/to/nm_prune_matmul.py] [--blocks BT,BO,BK ...]

Cases: a 512-token chunk through ``qwen2_7b``'s q_proj (3584 -> 3584),
gate_proj (3584 -> 18944) and down_proj (18944 -> 3584), and
``qwen3_30b_a3b``'s q_proj (2048 -> 4096); plus a 20-token final chunk
through q_proj.  Each call runs ``reps`` times inside one jitted loop, each
iteration depending on the last through a scalar, so the time per call is
device time, not dispatch.  The kernel, XLA's dense bf16 projection with a
float32 accumulator and, with ``--other``, another tree's kernel module
(its ``nm_prune_matmul_pallas`` at its default blocks, which an older
wrapper picks at these shapes) are timed in turn, round after round in one
process, and each keeps its best round.  Per case it prints one JSON
object, naming the device: those times, the blocks the wrapper chooses
(and the time at every ``--blocks`` triple that divides the shape), the
kernel's share of the least time the work needs
(``bench/work/nm_prune_matmul.py`` on ``bench/peaks.json``'s peaks), and
the largest gap to the float32 oracle on the same bf16 operands.
Refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, M = 8, 16

# name -> (tokens, d_in, d_out)
CASES = {
    "qwen2_7b.q_proj": (512, 3584, 3584),
    "qwen2_7b.gate_proj": (512, 3584, 18944),
    "qwen2_7b.down_proj": (512, 18944, 3584),
    "qwen3_30b_a3b.q_proj": (512, 2048, 4096),
    "qwen2_7b.q_proj_tail20": (20, 3584, 3584),
}


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def least_s_fn(device_kind):
    """The least seconds of a (t, d) @ (d, n_out) N:M projection on this
    device: the benchmark's own work formula over its peaks."""
    work = _load("nm_prune_matmul_work",
                 os.path.join(ROOT, "bench", "work", "nm_prune_matmul.py"))
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        peaks = json.load(f)["devices"][device_kind]

    def least_s(t, d, n_out):
        flops, nbytes = work.work(t, d, n_out, N, M)
        return max(flops / peaks["flops_per_s"]["bfloat16"],
                   nbytes / peaks["hbm_bytes_per_s"])
    return least_s


def chained(fn, reps):
    """``reps`` calls of ``fn(x, w, s)`` in one jitted loop, each fed a
    scale offset by the last output's first element times zero (XLA keeps
    a float multiply by zero)."""
    @jax.jit
    def chain(x, w, s):
        def body(i, c):
            y = fn(x, w, s + c)
            return c + y[0, 0].astype(s.dtype) * 0
        return jax.lax.fori_loop(0, reps, body, jnp.zeros((), s.dtype))
    return chain


def device_s(fns, args, reps, rounds=5):
    """Seconds per call of each of ``fns``: all compiled and warmed first,
    then timed in turn for ``rounds`` rounds; each keeps its best."""
    chains = [chained(fn, reps) for fn in fns]
    for chain in chains:
        chain(*args).block_until_ready()
    best = [float("inf")] * len(chains)
    for _ in range(rounds):
        for i, chain in enumerate(chains):
            t0 = time.perf_counter()
            chain(*args).block_until_ready()
            best[i] = min(best[i], time.perf_counter() - t0)
    return [b / reps for b in best]


def dense(x, w, s):
    # the model's dense projection: float32 accumulation, stored once;
    # the scale term keeps the loop's dependency (a (D,) vector op)
    xs = x * (1 + s[0] * 0).astype(x.dtype)
    return jnp.dot(xs, w, preferred_element_type=jnp.float32).astype(x.dtype)


def run_case(name, blocks, reps, least_s, other=None):
    t, d, n_out = CASES[name]
    kx, kw, ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (t, d), jnp.bfloat16)
    w = (jax.random.normal(kw, (d, n_out), jnp.float32) * d**-0.5).astype(
        jnp.bfloat16)
    s = (jax.random.uniform(ks, (d,)) + 0.5).astype(jnp.bfloat16)
    (bt, _), (bo, _), (bk, _) = ops._nm_prune_matmul_blocks(
        t, d, n_out, M, 2, None, None, None)
    got = ops.nm_prune_matmul(x, w, s, N, M)
    with jax.default_matmul_precision("highest"):
        want = ref.nm_prune_matmul_ref(x.astype(jnp.float32),
                                       w.astype(jnp.float32),
                                       s.astype(jnp.float32), N, M)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    fns = [lambda x_, w_, s_: ops.nm_prune_matmul(x_, w_, s_, N, M), dense]
    if other is not None:
        fns.append(lambda x_, w_, s_: other.nm_prune_matmul_pallas(
            x_, w_, s_, N, M))
    times = device_s(fns, (x, w, s), reps)
    kern, xla = times[:2]
    out = {"case": name, "shape": [t, d, n_out], "blocks": [bt, bo, bk],
           "kernel_us": round(kern * 1e6, 2),
           "dense_xla_us": round(xla * 1e6, 2),
           "kernel_over_dense": round(kern / xla, 3),
           "roofline_pct": round(100 * least_s(t, d, n_out) / kern, 2),
           "max_err": err}
    if other is not None:
        out["other_kernel_us"] = round(times[2] * 1e6, 2)
    tried = {}
    for bt, bo, bk in blocks:
        if t % bt or n_out % bo or d % bk or bk % M:
            continue
        tried[f"{bt},{bo},{bk}"] = round(1e6 * device_s([
            lambda x_, w_, s_: ops.nm_prune_matmul(
                x_, w_, s_, N, M, block_t=bt, block_o=bo, block_k=bk)],
            (x, w, s), reps)[0], 2)
    if tried:
        out["kernel_us_at_blocks"] = tried
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--other", default=None,
                    help="another tree's kernels/nm_prune_matmul.py to time "
                         "beside this one")
    ap.add_argument("--blocks", nargs="*", default=[],
                    help="extra block triples BT,BO,BK to time per case")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--cases", nargs="*", default=list(CASES))
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}")
    least_s = least_s_fn(dev.device_kind)
    other = (_load("other_nm_prune_matmul", args.other) if args.other
             else None)
    blocks = [tuple(int(v) for v in b.split(",")) for b in args.blocks]
    for name in args.cases:
        out = run_case(name, blocks, args.reps, least_s, other)
        out["device_kind"] = dev.device_kind
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
