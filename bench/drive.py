"""Drives the served engine through its public iteration hook and takes
every timestamp on the host clock.

The window drives ``repro.serve.api.Engine``: ``submit(..., arrival=it)``
and ``run()``.  ``ContinuousServingEngine.iteration_hook(engine, it)`` is
called at the top of every scheduler iteration; each step ends with its
sampled tokens on the host, so the hook's time is also the end of the
previous iteration.  At each hook the harness

* closes the previous iteration: the tokens each request gained there are
  stamped with this time, and the prefill chunk and decode rows it ran are
  recorded (the work functions count from them);
* submits what is due: a request at its due wall time, or at the next
  iteration boundary when the engine is mid-step.

When the engine drains before the next request is due, the harness sleeps
until then and calls ``run()`` again; the cache and prefix index persist
across calls.  A mix's generator module may bring a driver class of its
own (``Loop``); the default is :class:`OpenLoop`.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Dict, List, Optional

import jax

TERMINAL = ("done", "rejected", "timed_out", "cancelled")


@dataclasses.dataclass
class Track:
    """One request as the harness saw it."""
    rid: int
    gen: object                 # generator.Req
    due_t: float                # wall time it was due
    filled: int = 0
    cached: int = 0             # prompt rows served from the prefix cache
    n_out: int = 0
    times: List[float] = dataclasses.field(default_factory=list)
    state: str = "waiting"
    end_t: float = 0.0
    admitted_iter: int = -1


@dataclasses.dataclass
class Iteration:
    it: int
    t0: float
    t1: float
    # ("prefill", tokens, offset, completes the prompt) or ("decode", 1,
    # position of the row fed in): see bench/work/model_step.py
    items: List[tuple]


class CompileCounter:
    """Counts XLA compilations while ``armed`` (JAX's own monitoring
    events, which a persistent-cache hit does not raise)."""

    def __init__(self):
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


class Feeder:
    def __init__(self, eng, mix: dict, seconds: float,
                 annotate: bool = False):
        self.eng = eng
        self.rep = eng.replicas[0]
        assert len(eng.replicas) == 1, "one replica only"
        self.mix = mix
        self.seconds = seconds
        self.annotate = annotate
        self.tracks: Dict[int, Track] = {}
        self.live: Dict[int, Track] = {}
        self.iters: List[Iteration] = []
        self._open: Optional[tuple] = None
        self.window = (0.0, 0.0)
        self.late: List[float] = []          # submit time - due time
        self.sleep_late: List[float] = []    # wake-up time - due time
        self.compiles = CompileCounter()
        self._span = None

    # -------------------------------------------------------------- engine
    def _program_request(self, rid: int):
        return self.rep.requests[rid]

    def submit(self, gen, due_t: float) -> Track:
        now = time.perf_counter()
        rid = self.eng.submit(gen.prompt, max_new_tokens=gen.max_new,
                              arrival=self.rep.sched.it)
        tr = Track(rid, gen, due_t)
        self.tracks[rid] = tr
        self.live[rid] = tr
        self.late.append(now - due_t)
        return tr

    def _close(self, now: float) -> None:
        """Attribute what changed since the last hook to the iteration
        that just ran."""
        if self._open is None:
            return
        it, t0 = self._open
        self._open = None
        items = []
        for rid, tr in list(self.live.items()):
            r = self._program_request(rid)
            if r.admitted_iter >= 0 and tr.admitted_iter < 0:
                tr.admitted_iter = r.admitted_iter
            hit = r.cached_tokens - tr.cached
            grew = r.filled - tr.filled - hit
            if grew > 0:
                items.append(("prefill", grew, tr.filled + hit,
                              r.filled >= len(r.tokens)))
            n = len(r.out)
            if n > tr.n_out:
                tr.times.extend([now] * (n - tr.n_out))
                if grew <= 0:
                    items.append(("decode", 1, len(r.tokens) + tr.n_out - 1))
            tr.filled, tr.cached, tr.n_out = r.filled, r.cached_tokens, n
            if r.state in TERMINAL:
                tr.state, tr.end_t = r.state, now
                del self.live[rid]
        self.iters.append(Iteration(it, t0, now, items))

    def _annotate(self, name: Optional[str]) -> None:
        """Host span on the profiler's clock (traced runs only)."""
        if not self.annotate:
            return
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        if name is not None:
            self._span = jax.profiler.TraceAnnotation(name)
            self._span.__enter__()

    def _run(self, params) -> None:
        self._annotate("bench.iteration")
        self.eng.run(params)
        self._close(time.perf_counter())
        self._annotate(None)

    # ---------------------------------------------------------- warm-up
    def warm_up(self, params, gens) -> None:
        """Serve ``gens`` before the window: compiles (or loads) every
        step program the mix uses, and runs each once."""
        for g in gens:
            self.eng.submit(g.prompt, max_new_tokens=g.max_new,
                            arrival=self.rep.sched.it)
        self.eng.run(params)
        self.rep.iteration_hook = self.hook

    def hook(self, engine, it: int) -> None:
        now = time.perf_counter()
        self._close(now)
        self._open = (it, now)
        self._annotate("bench.iteration")
        self.on_iteration(now)

    def on_iteration(self, now: float) -> None:
        raise NotImplementedError


class OpenLoop(Feeder):
    """Requests due at fixed wall times from the window's start; after the
    window no more are due and the engine drains what is left, up to
    ``drain_s`` past the close (the rest never finished: failed)."""

    def __init__(self, eng, mix, seconds, reqs, drain_s: float = 60.0,
                 annotate: bool = False):
        super().__init__(eng, mix, seconds, annotate)
        self.pending = collections.deque(sorted(reqs, key=lambda g: g.due_s))
        self.drain_s = drain_s
        self.t0 = 0.0

    def _submit_due(self, now: float) -> None:
        while self.pending and self.t0 + self.pending[0].due_s <= now:
            g = self.pending.popleft()
            self.submit(g, self.t0 + g.due_s)

    def on_iteration(self, now: float) -> None:
        self._submit_due(now)
        if now > self.t0 + self.seconds + self.drain_s:
            for rid in list(self.live):
                self.eng.cancel(rid)

    def serve(self, params) -> None:
        self.t0 = time.perf_counter()
        self.window = (self.t0, self.t0 + self.seconds)
        self.compiles.armed = True
        while True:
            now = time.perf_counter()
            self._submit_due(now)
            if self.live:
                self._run(params)
            elif self.pending:
                due = self.t0 + self.pending[0].due_s
                self._annotate("bench.wait_arrival")
                time.sleep(max(0.0, due - time.perf_counter()))
                self.sleep_late.append(time.perf_counter() - due)
                self._annotate(None)
            else:
                break
        self.compiles.armed = False
