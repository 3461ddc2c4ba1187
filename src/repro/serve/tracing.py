"""Host spans of the serving loop, on JAX's profiler clock.

One mechanism: ``jax.profiler.TraceAnnotation`` (``StepTraceAnnotation``
for the iteration).  A span records only while a ``jax.profiler`` session
is active (``jax.profiler.start_trace``/``trace``); without one, entering
a span costs its construction and nothing else.  The spans land in the
same trace as the device's operations, on the same clock.

Nesting, one span per layer boundary::

    serve.run                      ContinuousServingEngine.run
      serve.hook                   iteration_hook(engine, it)
      serve.iteration  step_num=it; rid, offset, tokens, decode_rows
        serve.schedule             stamp, reap, admit, ensure blocks, plan
        serve.apply_effects        slot resets, block-table upload
        serve.dispatch             the jitted step call (and any compile)
        serve.wait                 host blocked on the device result
        serve.oracle               degradation re-run (degraded only)
        serve.commit               commit_chunk, emit_* tokens
      serve.metrics                MetricsSnapshot build (Router: merge)

Device names are ``jax.named_scope``s set inside the traced programs
(``models/``, ``layers/linear.py``, ``serve/executor.py``).  Each is
``<area>.<name>``: the dot sets them apart from the components JAX adds
to an operation's ``op_name`` (``jit(...)``, ``while``, ``body``, ``cond``).
"""
from __future__ import annotations

import jax

__all__ = ["RUN", "HOOK", "ITERATION", "SCHEDULE", "APPLY_EFFECTS",
           "DISPATCH", "WAIT", "ORACLE", "COMMIT", "METRICS", "SPANS",
           "DEVICE_SCOPES", "span", "iteration", "describe"]

RUN = "serve.run"
HOOK = "serve.hook"
ITERATION = "serve.iteration"
SCHEDULE = "serve.schedule"
APPLY_EFFECTS = "serve.apply_effects"
DISPATCH = "serve.dispatch"
WAIT = "serve.wait"
ORACLE = "serve.oracle"
COMMIT = "serve.commit"
METRICS = "serve.metrics"
SPANS = (RUN, HOOK, ITERATION, SCHEDULE, APPLY_EFFECTS, DISPATCH, WAIT,
         ORACLE, COMMIT, METRICS)

# the named scopes of the step programs, as they appear in op metadata
DEVICE_SCOPES = (
    "model.embed",        # token embedding
    "model.layers",       # the layer loop: per-layer weight and cache slices
    "model.attention",    # norm, q/k/v/o projections, rope, attention
    "model.kv_update",    # KV written into the (paged) cache
    "model.mlp",          # norm, gate/up/down projections, activation
    "model.amber_proj",   # a pruned (N:M) projection
    "model.dense_proj",   # a dense projection (skip-listed or decode)
    "model.lm_head",      # final norm and vocabulary projection
    "step.slot_io",       # a prefill slot's cache sliced out and written back
    "step.cache_select",  # decode's per-slot keep-or-advance select
    "step.sample",        # token sampling
)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(name, **args)


def iteration(it: int) -> jax.profiler.StepTraceAnnotation:
    return jax.profiler.StepTraceAnnotation(ITERATION, step_num=it)


def describe(step_span, plan) -> None:
    """Attach the iteration's work to its span: the prefill request's id,
    chunk offset and valid tokens, and the number of decode rows."""
    if not step_span.is_enabled():
        return
    args = {}
    if plan.prefill is not None:
        pw = plan.prefill
        args.update(rid=pw.req.rid, offset=pw.req.filled,
                    tokens=pw.chunk_len)
    if plan.decode is not None:
        args["decode_rows"] = len(plan.decode.requests)
    if args:
        step_span.set_metadata(**args)
