"""Continuous-batching scheduler vs the legacy one-shot engine.

The contract (ISSUE 2): for greedy decode, the continuous path — staggered
arrivals, chunked sparse prefill at cache offsets, slot reuse — must
produce token-identical output to ``ServingEngine.generate`` for every
request, and a stream of varied prompt lengths inside one shape bucket
must compile each phase exactly once.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.core.policy import DENSE, paper_policy
from repro.core.pruner import precompute_scales
from repro.models import build_model
from repro.serve import (ContinuousConfig, ContinuousServingEngine,
                         ServeConfig, ServingEngine)

MAX_SEQ = 64


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(get_smoke_config("llama31_8b"),
                              dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, lens, seed0=10):
    return [np.asarray(jax.random.randint(jax.random.PRNGKey(seed0 + i),
                                          (l,), 0, cfg.vocab_size))
            for i, l in enumerate(lens)]


def _oracle(model, params, policy, prompt, max_new, eos=-1):
    """Per-request one-shot generation, truncated at eos (inclusive)."""
    eng = ServingEngine(model, policy,
                        ServeConfig(max_seq=MAX_SEQ, eos_token=eos))
    out = eng.generate(params, {"tokens": jnp.asarray(prompt)[None, :]},
                       max_new_tokens=max_new)
    seq = np.asarray(out["tokens"])[0].tolist()
    if eos in seq:
        seq = seq[:seq.index(eos) + 1]
    return seq


def _serve(model, params, policy, prompts, arrivals, max_new, *,
           slots=2, chunk=8, eos=-1):
    eng = ContinuousServingEngine(model, policy, ContinuousConfig(
        max_seq=MAX_SEQ, num_slots=slots, chunk_size=chunk, eos_token=eos))
    for p, a, mn in zip(prompts, arrivals, max_new):
        eng.submit(p, max_new_tokens=mn, arrival=a)
    return eng, eng.run(params)


def test_staggered_arrivals_token_identical(tiny):
    """4 mixed-length requests over 2 slots: queueing + slot reuse + padded
    final chunks, all token-identical to the one-shot engine."""
    cfg, model, params = tiny
    lens, arrivals, max_new = [5, 13, 21, 9], [0, 1, 3, 6], [8, 6, 10, 7]
    prompts = _prompts(cfg, lens)
    _, res = _serve(model, params, DENSE, prompts, arrivals, max_new)
    for i, p in enumerate(prompts):
        assert res["outputs"][i] == _oracle(model, params, DENSE, p,
                                            max_new[i]), f"request {i}"
    # staggered requests actually overlapped in the scheduler
    reqs = res["metrics"]["requests"]
    assert max(r["arrival"] for r in reqs) > 0
    assert all(r["first_token_iter"] >= 0 for r in reqs)


def test_sparse_prefill_token_identical(tiny):
    """Chunked Amber-sparse prefill (per-token masks are chunking-invariant)
    matches one-shot sparse prefill."""
    cfg, model, params = tiny
    policy = paper_policy(2, 4, cfg.qgate_skip_layers)
    params = precompute_scales(params, policy)
    lens, arrivals, max_new = [7, 17, 12], [0, 0, 2], [6, 8, 6]
    prompts = _prompts(cfg, lens, seed0=30)
    _, res = _serve(model, params, policy, prompts, arrivals, max_new)
    for i, p in enumerate(prompts):
        assert res["outputs"][i] == _oracle(model, params, policy, p,
                                            max_new[i]), f"request {i}"


def test_eos_mid_batch_frees_slot(tiny):
    """A request hitting eos mid-stream truncates identically to the
    one-shot engine and releases its slot to a queued request."""
    cfg, model, params = tiny
    lens, max_new = [11, 6, 15], [8, 8, 8]
    prompts = _prompts(cfg, lens, seed0=50)
    # pick an eos that genuinely fires mid-generation for request 0: the
    # first token whose first occurrence is past the first decode step
    probe = _oracle(model, params, DENSE, prompts[0], max_new[0])
    j = next(j for j in range(1, len(probe)) if probe[j] not in probe[:j])
    eos = probe[j]
    eng, res = _serve(model, params, DENSE, prompts, [0, 0, 1], max_new,
                      slots=2, eos=eos)
    for i, p in enumerate(prompts):
        want = _oracle(model, params, DENSE, p, max_new[i], eos=eos)
        assert res["outputs"][i] == want, f"request {i}"
    assert res["outputs"][0][-1] == eos
    assert len(res["outputs"][0]) == j + 1 < max_new[0]
    reqs = {r["rid"]: r for r in res["metrics"]["requests"]}
    # request 2 was queued behind a full slot pool and entered after the
    # eos'd request released its slot
    assert reqs[2]["admitted_iter"] >= reqs[0]["done_iter"]


def test_single_trace_per_bucket(tiny):
    """Varied prompt lengths within one chunk bucket: exactly one compile
    per phase (the 'jitted once per shape bucket' claim, now enforced)."""
    cfg, model, params = tiny
    lens = [3, 9, 14, 23, 31, 6]
    prompts = _prompts(cfg, lens, seed0=70)
    eng, res = _serve(model, params, DENSE, prompts,
                      [0, 0, 1, 2, 5, 9], [5] * len(lens),
                      slots=3, chunk=16)
    # fused one-dispatch default: ONE compiled step program per
    # (prefill?, decode?) phase-presence bucket, each traced exactly once
    assert eng.trace_counts == {"step_prefill": 1, "step_decode": 1,
                                "step_prefill_decode": 1}, eng.trace_counts
    assert res["metrics"]["dispatches_per_iteration"] == 1.0
    assert all(len(res["outputs"][i]) == 5 for i in range(len(lens)))


def test_recurrent_arch_dyadic_chunks():
    """rwkv6: recurrent state carries across exact dyadic chunks; outputs
    stay token-identical and the trace count is bounded by the ladder."""
    cfg = dataclasses.replace(get_smoke_config("rwkv6_7b"), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    lens, arrivals, max_new = [13, 7], [0, 1], [6, 6]
    prompts = _prompts(cfg, lens, seed0=90)
    eng, res = _serve(model, params, DENSE, prompts, arrivals, max_new,
                      slots=2, chunk=8)
    for i, p in enumerate(prompts):
        assert res["outputs"][i] == _oracle(model, params, DENSE, p,
                                            max_new[i]), f"request {i}"
    # dyadic ladder: at most log2(chunk)+1 prefill shapes per step bucket,
    # one decode-only shape
    pf = sum(v for k, v in eng.trace_counts.items() if "prefill" in k)
    assert pf <= 8, eng.trace_counts
    assert eng.trace_counts.get("step_decode", 0) <= 1, eng.trace_counts
