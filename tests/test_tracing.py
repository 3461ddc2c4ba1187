"""Spans and the request timeline of the serving loop (serve/tracing.py).

* A ``run()`` under a ``jax.profiler`` session records every span of the
  loop, properly nested, with ``serve.iteration`` carrying its step number
  and the prefill request's id.
* Every finished request's timeline is ordered: visible, admitted, first
  chunk, first token, done, through preemption and prefix-cache hits.
* A profiler session changes no compiled program and no dispatch count.
* The step programs carry their bucket's name and the named scopes of
  ``tracing.DEVICE_SCOPES`` into the lowered program's op metadata.
"""
import dataclasses
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.core.policy import DENSE, paper_policy
from repro.core.pruner import precompute_scales
from repro.models import build_model
from repro.serve import (ContinuousConfig, ContinuousServingEngine,
                         EngineConfig, tracing)
from repro.serve.api import Engine
from repro.serve.faults import FaultInjector, FaultSpec

MAX_SEQ = 64


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(get_smoke_config("llama31_8b"),
                              dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _tokens(cfg, n, seed):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         cfg.vocab_size), np.int32)


def _serve_spans(trace_dir):
    """(name, start, end, stats) of every ``serve.*`` host event."""
    from jax.profiler import ProfileData
    out = []
    for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)) for e in line.events
                           if e.name.startswith("serve."))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _parent(spans, i):
    """Name of the smallest span enclosing span ``i`` (None at the top)."""
    _, s, e, _ = spans[i]
    best = None
    for j, (n, a, b, _) in enumerate(spans):
        if j != i and a <= s and e <= b and (
                best is None or b - a < best[1] - best[0]):
            best = (a, b, n)
    return None if best is None else best[2]


def test_run_records_every_span_properly_nested(tiny, tmp_path):
    cfg, model, params = tiny
    # one injected non-finite prefill degrades an iteration: the oracle
    # re-run is the one span a clean run never opens
    inj = FaultInjector(seed=0, schedule=[
        FaultSpec(site="prefill", kind="nonfinite", calls=[1])])
    eng = Engine.from_config(model, EngineConfig(serving=ContinuousConfig(
        max_seq=MAX_SEQ, num_slots=2, chunk_size=8, block_size=4)),
        faults=inj)
    for i, n in enumerate((12, 5)):
        eng.submit(_tokens(cfg, n, 900 + i), max_new_tokens=3, arrival=i)
    eng.replicas[0].iteration_hook = lambda e, it: None
    with jax.profiler.trace(str(tmp_path)):
        eng.run(params)
    spans = _serve_spans(str(tmp_path))
    assert {s[0] for s in spans} == set(tracing.SPANS)
    for i, (_, s, e, _) in enumerate(spans):        # nest or stay apart
        for _, a, b, _ in spans[i + 1:]:
            assert b <= e or a >= e, "spans overlap without nesting"
    inner = {tracing.SCHEDULE, tracing.APPLY_EFFECTS, tracing.DISPATCH,
             tracing.WAIT, tracing.ORACLE, tracing.COMMIT}
    for i, (name, *_rest) in enumerate(spans):
        parent = _parent(spans, i)
        if name in inner:
            assert parent == tracing.ITERATION, (name, parent)
        elif name in (tracing.HOOK, tracing.ITERATION):
            assert parent == tracing.RUN, (name, parent)
        elif name == tracing.METRICS:       # the engine's, the router's
            assert parent in (tracing.RUN, None), (name, parent)
        else:
            assert name == tracing.RUN and parent is None
    steps = [st for n, _, _, st in spans if n == tracing.ITERATION]
    assert [st["step_num"] for st in steps] == list(range(len(steps)))
    rids = {st["rid"] for st in steps if "rid" in st}
    assert rids == {0, 1}
    assert all(st["tokens"] > 0 and "offset" in st
               for st in steps if "rid" in st)
    assert any(st.get("decode_rows", 0) > 0 for st in steps)
    m = eng.metrics
    assert m.degraded_iterations == 1
    assert all(r.state == "done" for r in m.requests)


def test_request_timeline_is_ordered(tiny):
    """req1 is preempted mid-prefill and its replay re-matches its own
    blocks (a prefix-cache hit); a later request hits req0's prompt."""
    cfg, model, params = tiny
    prompts = [_tokens(cfg, 8, 95), _tokens(cfg, 40, 96)]
    eng = ContinuousServingEngine(model, DENSE, ContinuousConfig(
        max_seq=MAX_SEQ, num_slots=2, chunk_size=8, block_size=4,
        num_blocks=13, validate_pool=True))
    for p, a, mn in zip(prompts, (0, 2), (24, 8)):
        eng.submit(p, max_new_tokens=mn, arrival=a)
    tops = {}
    eng.iteration_hook = lambda e, it: tops.setdefault(it,
                                                       time.perf_counter())
    eng.run(params)
    eng.submit(np.concatenate([prompts[0], prompts[0][:5]]),
               max_new_tokens=4, arrival=eng._it)
    reqs = eng.run(params)["metrics"]["requests"]
    assert reqs[1]["preemptions"] >= 1 and reqs[1]["cached_tokens"] > 0
    assert reqs[2]["cached_tokens"] > 0
    for r in reqs:
        assert r["state"] == "done"
        stamps = [r["visible_t"], r["admitted_t"], r["first_chunk_t"],
                  r["first_token_t"], r["done_t"]]
        assert 0 <= stamps[0] and stamps == sorted(stamps), r
        assert r["latency_s"] == pytest.approx(r["done_t"] - r["visible_t"])
    # the replayed request keeps the stamps of its first admission: both
    # precede the iteration that re-admitted it
    assert reqs[1]["first_chunk_t"] < tops[reqs[1]["admitted_iter"]]


def test_profiler_session_changes_no_program(tiny, tmp_path):
    cfg, model, params = tiny
    policy = paper_policy(2, 4, cfg.qgate_skip_layers,
                          use_pallas_kernels=True)
    sparams = precompute_scales(params, policy)

    def serve(traced):
        eng = ContinuousServingEngine(model, policy, ContinuousConfig(
            max_seq=MAX_SEQ, num_slots=2, chunk_size=8, block_size=4))
        for i, n in enumerate((11, 6, 17)):
            eng.submit(_tokens(cfg, n, 930 + i), max_new_tokens=3,
                       arrival=i)
        if traced:
            with jax.profiler.trace(str(tmp_path)):
                res = eng.run(sparams)
        else:
            res = eng.run(sparams)
        return res["outputs"], dict(eng.trace_counts), eng.dispatches

    assert serve(False) == serve(True)


def test_step_programs_carry_their_names(tiny):
    cfg, model, params = tiny
    policy = paper_policy(2, 4, cfg.qgate_skip_layers,
                          use_pallas_kernels=True)
    sparams = precompute_scales(params, policy)
    eng = ContinuousServingEngine(model, policy, ContinuousConfig(
        max_seq=MAX_SEQ, num_slots=2, chunk_size=8, block_size=4))
    ex = eng.exec
    ex.init_cache(eng.pool.num_blocks)
    zero = np.zeros((2,), np.uint32)
    args = (sparams, ex.cache, np.int32(0), np.zeros((1, 8), np.int32),
            np.int32(8), {}, np.zeros((2,), np.int32), np.ones((2,), bool),
            zero, zero, np.float32(0))
    seen = set()
    for bucket, name, fn in ex.step_programs():
        if bucket[0]:           # replay buckets run the same scopes dense
            continue
        text = jax.jit(fn).lower(*args).as_text(debug_info=True)
        assert f"jit_{name}" in text or f"jit({name})" in text
        seen |= {s for s in tracing.DEVICE_SCOPES if s in text}
    # each scope is set by at least one bucket
    assert seen == set(tracing.DEVICE_SCOPES)
