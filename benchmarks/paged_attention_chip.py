"""Paged attention alone on the chip: device time of one
``paged_attention_pallas`` call at the serving shapes of ``qwen2_7b``
(28 query / 4 KV heads of 128, bf16 pool, block 16).

    PYTHONPATH=src python benchmarks/paged_attention_chip.py

Cases: a 512-token prefill chunk at cache offsets 0, 2048 and 7168 of an
8192-position slot (512 table entries), decode with 4 slots near 2,600
positions, and decode with 16 slots of a 2048-position table.  Each call is
chained ``reps`` times inside one jitted loop (the output is the next
query), so the time per call is device time, not dispatch.  Each case
also checks one call against the gather oracle (``max_err``, float32
reference on the same bf16 operands).  Prints one JSON object per case,
naming the device.  Refuses to run without a TPU.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import paged_attention_pallas
from repro.models.attention import paged_attention

HQ, HKV, HD, BS = 28, 4, 128, 16

# name -> (slots, query tokens, table entries per slot, q offsets, kv lens)
CASES = {
    "prefill_512_at_0": (1, 512, 512, [0], [512]),
    "prefill_512_at_2048": (1, 512, 512, [2048], [2560]),
    "prefill_512_at_7168": (1, 512, 512, [7168], [7680]),
    "decode_4_slots_2600": (4, 1, 512, [2590, 2601, 2613, 2624],
                            [2591, 2602, 2614, 2625]),
    "decode_16_slots_2048": (16, 1, 128, list(range(400, 2000, 100)),
                             list(range(401, 2001, 100))),
}


def inputs(slots, t, mb, qoff, kvl, seed=0):
    rng = np.random.default_rng(seed)
    nb = slots * mb + 1
    perm = rng.permutation(nb - 1)
    tab = np.full((slots, mb), -1, np.int32)
    for s in range(slots):
        need = -(-kvl[s] // BS)
        tab[s, :need] = perm[s * mb:s * mb + need]
    key = jax.random.PRNGKey(seed)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (slots, t, HQ, HD), jnp.bfloat16)
    kp = jax.random.normal(kk, (nb, HKV, BS, HD), jnp.bfloat16)
    vp = jax.random.normal(kv, (nb, HKV, BS, HD), jnp.bfloat16)
    return (q, kp, vp, jnp.asarray(tab), jnp.asarray(qoff, jnp.int32),
            jnp.asarray(kvl, jnp.int32))


def time_case(name, reps):
    slots, t, mb, qoff, kvl = CASES[name]
    q, kp, vp, tab, qo, kl = inputs(slots, t, mb, qoff, kvl)
    causal = t > 1

    @jax.jit
    def chain(q):
        return jax.lax.fori_loop(
            0, reps, lambda i, x: paged_attention_pallas(
                x, kp, vp, tab, qo, kl, causal=causal), q)

    chain(q).block_until_ready()                 # compile + warm up
    got = paged_attention_pallas(q, kp, vp, tab, qo, kl, causal=causal)
    f32 = lambda a: a.astype(jnp.float32)
    want = paged_attention(f32(q), f32(kp), f32(vp), tab, causal=causal,
                           q_offset=qo, kv_len=kl)
    err = float(jnp.max(jnp.abs(f32(got) - want)))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        chain(q).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / reps, err


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}")
    for name in CASES:
        reps = 20 if CASES[name][1] > 1 else 200
        sec, err = time_case(name, reps)
        print(json.dumps({"case": name, "us_per_call": round(sec * 1e6, 2),
                          "max_err": err, "device_kind": dev.device_kind}),
              flush=True)


if __name__ == "__main__":
    main()
