"""Continuous-batching serving engine: chunked Amber-sparse prefill
interleaved with slot-batched dense decode over a **paged** KV cache.

Requests arrive asynchronously (:meth:`ContinuousServingEngine.submit`) and
are scheduled over a fixed pool of decode **slots** whose KV rows live in a
global **block pool** (:mod:`repro.serve.paged`).  Each scheduler
iteration plans the steps below on the host and runs its device work —
the prefill chunk and the slot-batched decode — as ONE compiled step
program:

  1. **admit** — waiting requests whose arrival time has passed claim free
     slots FCFS, gated by a block-budget check (the pool must cover the
     prompt); the slot's recurrent state is zeroed and its block table row
     populated.  With prefix caching on, the longest indexed block-prefix
     of the prompt (shared system prompt, few-shot template, or this
     request's own preemption replay) is acquired instead of recomputed:
     the shared block ids go straight into the table, the slot's ``pos``
     starts at the first non-cached token, and prefill begins mid-sequence;
  2. **ensure/preempt** — decoding slots crossing a block boundary grab a
     fresh block; when the pool is dry the **youngest** active request is
     preempted (blocks released, request requeued; its emitted tokens are
     replayed through prefill on re-admission, so greedy output is
     unchanged);
  3. **prefill** — the oldest admitted-but-unprefilled request advances by
     one fixed-size token chunk through the Amber-sparse projection path
     (``model.prefill_chunk``), scattering KV through its block table;
  4. **decode** — all slots that were decoding when the iteration was
     planned take one dense decode step as a single padded batch (inactive
     slots are masked out of the cache update); it reads the KV the
     prefill chunk just wrote.

This class is a thin layer over two others (serve/README.md
"Architecture"):

* :class:`~repro.serve.scheduler.Scheduler` — every piece of host state
  (request lifecycles, slots, the block pool, prefix index, watchdog,
  counters); emits :class:`~repro.serve.scheduler.StepPlan`s and commits
  their results.  Never touches device arrays.
* :class:`~repro.serve.executor.Executor` — the cache pytree, the jit'd
  step programs and their oracle twins, the fault/degradation ladder, and
  (optionally) a TP mesh that shards the kernels.  Never touches request
  state.

The driver owns only the run loop, the sampling PRNG, snapshot/restore
composition, and metrics assembly.  One instance is one serving replica:
:class:`repro.serve.api.Engine` wraps it (through the
:class:`~repro.serve.router.Router`), and tools may drive it directly.

Shape buckets: a step program compiles once per phase-presence bucket
(``executor.STEP_BUCKETS``) and prefill chunk shape (a single
``chunk_size`` for attention archs; a dyadic ladder of at most
log2(chunk_size)+1 sizes for archs with recurrent blocks, whose scans
cannot mask padded tokens); decode always runs the padded ``num_slots``
batch — arbitrary traffic never retraces, and block allocation/preemption
only rewrites the small int32 block-table array, so paging does not add
shape buckets.  The ``trace_counts`` attribute counts actual retraces per
bucket and is asserted in the test suite.

Equivalence: with greedy decoding and **per-token** sparsity modes the
per-request output stream is token-identical to the one-shot
:class:`~repro.serve.engine.ServingEngine` — a token's N:M mask doesn't
depend on which chunk carries it, chunked prefill attends over the cached
prefix so logits match, decode rows are independent of batch composition,
and preemption replays the exact emitted prefix.  ``tile_consensus``
policies remain valid N:M serving but are NOT bit-identical to one-shot
prefill: their masks are pooled over token tiles, and chunking changes
tile membership (see serve/README.md).
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import DENSE, SparsityPolicy
from repro.kernels.paged_attention import paged_tile, paged_tile_walk
from repro.serve import faults as fault_mod
from repro.serve import tracing
from repro.serve.executor import Executor
from repro.serve.faults import EngineCrash, FaultInjector
from repro.serve.metrics import (LifecycleMetrics, MetricsSnapshot,
                                 MoEMetrics, PagedMetrics,
                                 RequestMetrics)
from repro.serve.paged import max_blocks_per_slot
from repro.serve.scheduler import TERMINAL, Request, Scheduler, StepPlan

__all__ = ["ContinuousConfig", "Request", "ContinuousServingEngine"]


@dataclasses.dataclass(frozen=True)
class ContinuousConfig:
    max_seq: int = 512        # per-slot KV capacity (prompt + new tokens)
    num_slots: int = 4        # decode batch width (the padded batch bucket)
    chunk_size: int = 64      # prefill chunk bucket (tokens per chunk)
    temperature: float = 0.0  # 0 → greedy
    eos_token: int = -1       # -1 → never stop early
    seed: int = 0
    max_iters: int = 100_000  # scheduler-loop safety valve
    # --- paged KV allocation (serve/paged.py) ---
    paged: bool = True        # auto-disabled where no full-attn KV exists
    block_size: int = 16      # KV rows per block
    num_blocks: Optional[int] = None
    # None → num_slots * ceil(max_seq / block_size): same capacity as the
    # dense slab, paged mechanics.  The memory win is sizing it LOWER and
    # letting admission gating + preemption absorb the pressure.
    prefix_cache: bool = True
    # block-level prefix caching across requests: full blocks are chain-
    # hashed and refcounted so a request whose prompt repeats a cached
    # prefix (shared system prompt, preemption replay) skips its prefill.
    # Auto-disabled alongside paging, and for archs with recurrent blocks
    # (their scan state cannot be restored from cached KV).
    validate_pool: bool = False
    # audit block-pool/refcount/ownership invariants after every scheduler
    # iteration (O(num_blocks) host work) — test/debug instrumentation.
    # Also forced on by REPRO_VALIDATE_POOL=1 (set by tests/conftest.py so
    # the whole serving suite runs audited).
    # --- request-lifecycle hardening (ISSUE 6) ---
    ttl_default: Optional[int] = None
    # default per-request deadline: arrival + ttl_default scheduler
    # iterations (None = no deadline); submit(ttl=...) overrides per
    # request.  Past it the request moves to terminal TIMED_OUT from any
    # phase, its slot/blocks/prefix refs unwound.
    admission_retries: int = 8
    # transient admission failures (injected pool/admit faults, or a real
    # allocation error) absorbed per request before the REJECTED backstop
    retry_backoff: int = 2
    # exponential-backoff base: after the k-th transient failure the
    # request retries no earlier than it + min(retry_backoff**k, 64)
    watchdog_iters: int = 64
    # no-progress window: if admission-eligible requests exist but nothing
    # advanced for this many iterations (possible only under persistent
    # faults — clean scheduling always progresses), the watchdog force-
    # rejects the oldest stuck request instead of livelocking to max_iters
    snapshot_every: int = 0
    # >0: keep ``last_snapshot`` refreshed every k iterations (taken at
    # the top-of-iteration boundary) so a crashed engine can be rebuilt
    # with restore() and resume token-identically.  0 = manual snapshots.


class ContinuousServingEngine:
    """One serving replica: runs a Scheduler and an Executor over a paged
    slot cache.  :class:`repro.serve.api.Engine` builds one per dp
    replica (the benchmark reads it through ``Engine.replicas``); tools
    such as ``repro.analysis`` build and drive it directly."""

    def __init__(self, model, policy: SparsityPolicy = DENSE,
                 cfg: ContinuousConfig = ContinuousConfig(),
                 faults: Optional[FaultInjector] = None,
                 mesh=None):
        self.model = model
        self.policy = policy
        self.cfg = cfg
        # deterministic fault injection (serve/faults.py): consulted at the
        # engine's own sites (admit/prefill/decode) and globally activated
        # around run() for the pool + kernel-dispatch sites
        self.faults = faults
        # optional host-side hook called at the top of every scheduler
        # iteration as hook(engine, it) — external control plane (the chaos
        # harness drives cancel() through it; a server could drive
        # monitoring or load shedding)
        self.iteration_hook: Optional[Callable] = None
        self._validate = (cfg.validate_pool
                          or os.environ.get("REPRO_VALIDATE_POOL") == "1")
        self.exec = Executor(model, policy, cfg, mesh=mesh)
        self.sched = Scheduler(
            cfg, paged=self.exec.paged,
            exact_chunks=self.exec.exact_chunks,
            policy_enabled=policy.enabled, prefix_cache=cfg.prefix_cache,
            faults=faults, validate=self._validate)
        self.work_iterations = 0  # iterations that dispatched any program
        self.restores = 0         # times restore() rebuilt this engine
        # paged-attention kernel tiles (walked, visible), per attention layer
        self.attention_tiles = [0, 0]
        # routed-expert rows (valid tokens x top_k) per phase
        self.routed_rows = {"prefill": 0, "decode": 0}
        self.metrics: Dict[str, Any] = {}
        self.metrics_snapshot: Optional[MetricsSnapshot] = None
        self._key = None                       # sampling PRNG (run-owned)
        self.last_snapshot: Optional[Dict] = None

    # -------------------------------------------------- layer delegation
    # layer-owned state read through the replica by the benchmark, tools
    # and tests
    @property
    def requests(self):
        return self.sched.requests

    @property
    def pool(self):
        return self.sched.pool

    @property
    def paged(self) -> bool:
        return self.exec.paged

    @property
    def prefix_cache(self) -> bool:
        return self.sched.prefix_cache

    @property
    def preempt_log(self):
        return self.sched.preempt_log

    @property
    def trace_counts(self):
        return self.exec.trace_counts

    @property
    def dispatches(self) -> int:
        return self.exec.dispatches

    @property
    def _spec(self):
        return self.exec._spec

    @property
    def _step_raw(self):
        return self.exec._step_raw

    @property
    def _it(self) -> int:
        return self.sched.it

    # ------------------------------------------------------------ admission
    def submit(self, tokens, max_new_tokens: int = 32, arrival: int = 0,
               ttl: Optional[int] = None) -> int:
        """Queue a request; returns its request id.

        ``arrival`` is the scheduler iteration at which the request becomes
        visible (simulated asynchronous traffic).  ``ttl`` bounds its
        lifetime: past ``arrival + ttl`` scheduler iterations the request
        is moved to terminal ``TIMED_OUT`` from whatever phase it is in
        (None → ``cfg.ttl_default``; both None → no deadline)."""
        return self.sched.submit(tokens, max_new_tokens, arrival, ttl)

    def cancel(self, rid: int) -> bool:
        """Withdraw a request from any lifecycle phase.  Processed at the
        next iteration boundary (so a jitted phase never observes a
        half-unwound slot): the request moves to terminal ``CANCELLED``
        and its slot/blocks/prefix refs are released.  Returns False if
        the request is unknown or already terminal."""
        return self.sched.cancel(rid)

    def clear(self) -> None:
        """Drop completed requests (e.g. after a warmup pass) so a fresh
        stream can be submitted and measured on the already-compiled
        engine.  The prefix index deliberately survives: a warm cache
        across streams is the production behavior being measured."""
        self.sched.clear()
        self._key = None

    # ------------------------------------------------------------- phases
    def _crash_fire(self, site: str, it: int) -> float:
        """Fire a fault site; raise on "crash", return the logits-fault
        addend ("nonfinite" → NaN, clean → 0)."""
        kind = self.sched._fire(site)
        if kind == "crash":
            raise EngineCrash(f"injected crash in {site} (it={it})")
        return float("nan") if kind == "nonfinite" else 0.0

    def _attention_tile(self, t: int) -> Tuple[int, int]:
        """The paged-attention kernel's ``(bq, pages)`` for a ``t``-query
        call of this engine's model and pool."""
        mcfg, bs = self.model.cfg, self.cfg.block_size
        return paged_tile(mcfg.n_heads // mcfg.n_kv_heads, t, bs,
                          max_blocks_per_slot(self.cfg.max_seq, bs),
                          mcfg.dtype)

    def _chunk_tile(self) -> Tuple[int, int]:
        """``paged.attention_tile``: (q rows, pages) of the kernel's tile
        for a full prefill chunk; (0, 0) with the kernel off."""
        if not self.exec.paged_kernel:
            return (0, 0)
        mcfg = self.model.cfg
        bq, pages = self._attention_tile(self.cfg.chunk_size)
        return (mcfg.n_heads // mcfg.n_kv_heads * bq, pages)

    def _count_attention_tiles(self, plan: StepPlan) -> None:
        """Add one dispatch of ``plan`` to ``paged.attention_tiles_*``: the
        kernel's KV-tile walk replayed from the plan's positions and block
        counts (host integers, no device read).  Decode rows see a
        prefill chunk of the same dispatch as already written."""
        if not self.exec.paged_kernel:
            return
        bs = self.cfg.block_size
        mb = max_blocks_per_slot(self.cfg.max_seq, bs)

        def walk(t, causal, q_off, kv_len, n_blocks):
            bq, pages = self._attention_tile(t)
            return paged_tile_walk(min(kv_len, n_blocks * bs), q_off, t=t,
                                   bq=bq, pages=pages, block_size=bs,
                                   max_blocks=mb, causal=causal)

        pw, rows = plan.prefill, []
        if pw is not None:
            r = pw.req
            rows.append(walk(pw.tokens.shape[1], True, r.kv_len,
                             r.kv_len + pw.chunk_len, len(r.blocks)))
        if plan.decode is not None:
            for r in self.sched.slot_requests:
                if r is None:      # free slot: an all -1 table row
                    rows.append(walk(1, False, 0, 0, 0))
                    continue
                pos = r.kv_len + (pw.chunk_len if pw is not None
                                  and pw.req is r else 0)
                rows.append(walk(1, False, pos, pos + 1, len(r.blocks)))
        hkv = self.model.cfg.n_kv_heads
        for walked, visible in rows:
            self.attention_tiles[0] += walked * hkv
            self.attention_tiles[1] += visible * hkv

    def _count_routed_rows(self, plan: StepPlan) -> None:
        """Add one dispatch of ``plan`` to ``moe.routed_rows.*``: the
        prefill chunk's valid tokens and the active decode rows, each
        times top_k (host integers, no device read)."""
        k = self.model.cfg.top_k if self.model.cfg.n_experts else 0
        if plan.prefill is not None:
            self.routed_rows["prefill"] += plan.prefill.chunk_len * k
        if plan.decode is not None:
            self.routed_rows["decode"] += int(plan.decode.active.sum()) * k

    def _step(self, params, extras: Dict[int, Dict], it: int,
              plan: StepPlan) -> bool:
        """One iteration: the scheduler's plan runs as a SINGLE compiled
        step program (executor-side bucketing by static phase presence).
        Returns whether any model work ran.

        The decode roster is frozen before dispatch, so a request whose
        final prefill chunk lands this iteration samples its first token
        here and starts decoding NEXT iteration."""
        if not plan.has_work:
            return False
        self.exec.apply_effects(plan)
        # both fault sites fire by name (chaos schedules target them);
        # either hit folds into the step's shared fault operand, so a
        # single fault degrades the WHOLE step to the oracle — exactly the
        # blast radius of one compiled program
        fault_val = 0.0
        if plan.prefill is not None:
            fault_val += self._crash_fire("prefill", it)
        if plan.decode is not None:
            fault_val += self._crash_fire("decode", it)
        fault = jnp.float32(fault_val)
        # sampling keys split prefill first, then decode
        pkey = dkey = jnp.zeros((2,), jnp.uint32)   # placeholder operands
        if plan.prefill is not None:
            self._key, pkey = jax.random.split(self._key)
        if plan.decode is not None:
            self._key, dkey = jax.random.split(self._key)
        pw = plan.prefill
        ex = extras.get(pw.req.rid, {}) if pw is not None and pw.first else {}
        res = self.exec.step(params, plan, ex, pkey, dkey, fault)
        self._count_attention_tiles(plan)
        self._count_routed_rows(plan)
        with tracing.span(tracing.COMMIT):
            if pw is not None:
                self.sched.commit_chunk(pw.req, pw.chunk_len)
                if self.sched.seq_complete(pw.req):   # seq ingested: sample
                    self.sched.emit_prefill_token(pw.req, res.prefill_token,
                                                  it)
            if plan.decode is not None:
                self.sched.emit_decode_tokens(plan.decode,
                                              res.decode_tokens, it)
        return True

    # ------------------------------------------------------------ main loop
    def run(self, params, extras: Optional[Dict[int, Dict]] = None) -> Dict:
        """Drive the scheduler until every submitted request completes.

        ``extras`` maps request id → modality arrays sent with the first
        prefill chunk (``frame_embeds`` for encdec, ``pixel_embeds`` for
        VLM stubs).  Returns per-request outputs and aggregate metrics.
        Under a ``jax.profiler`` session the loop records the spans of
        :mod:`repro.serve.tracing`.
        """
        with tracing.span(tracing.RUN):
            return self._run(params, extras or {})

    def _run(self, params, extras: Dict[int, Dict]) -> Dict:
        sched, ex = self.sched, self.exec
        ex.init_cache(sched.pool.num_blocks if self.paged else None)
        sched.mark_extras(extras)
        if self._key is None:   # survives across run() calls and restore()
            self._key = jax.random.PRNGKey(self.cfg.seed)
        t0 = time.perf_counter()
        it0 = sched.it
        preempt0, reject0 = sched.preemptions, sched.rejections
        hits0, reused0 = sched.prefix_hits, sched.blocks_reused
        skipped0, demand0 = sched.tokens_skipped, sched.prefill_demand
        degraded0, retries0 = ex.degraded_iterations, sched.admission_retries
        wdog0, timeout0 = sched.watchdog_trips, sched.timeouts
        cancel0 = sched.cancellations
        disp0, work0 = ex.dispatches, self.work_iterations
        tiles0 = list(self.attention_tiles)
        routed0 = dict(self.routed_rows)
        if self.paged:
            sched.pool.peak_in_use = sched.pool.in_use   # per-run peak
            evict0 = sched.pool.evictions
        # the kernel-dispatch fault sites (core/pruner, models/attention)
        # cannot see this engine — activate the injector globally for the
        # duration of the loop (EngineCrash still deactivates cleanly)
        fault_mod.activate(self.faults)
        try:
            while sched.live():
                it = sched.it
                assert it - it0 < self.cfg.max_iters, "scheduler stuck"
                if self.faults is not None:
                    self.faults.tick(it)
                if self.iteration_hook is not None:
                    with tracing.span(tracing.HOOK):
                        self.iteration_hook(self, it)
                with tracing.iteration(it) as step_span:
                    if (self.cfg.snapshot_every
                            and it % self.cfg.snapshot_every == 0):
                        # iteration boundary = consistent state: a crash
                        # later this iteration rewinds here via restore()
                        self.last_snapshot = self.snapshot()
                    with tracing.span(tracing.SCHEDULE):
                        sched.stamp_arrivals(it, time.perf_counter())
                        reaped = sched.reap(it)
                        admitted = sched.admit(it)
                        # block grab BEFORE the dispatch: the step program
                        # reads the final roster/table, and a dry-pool
                        # preemption can still unwind the prefilling
                        # request ahead of its chunk
                        if self.paged:
                            sched.ensure_decode_blocks()
                        plan = sched.plan_step()
                    tracing.describe(step_span, plan)
                    worked = self._step(params, extras, it, plan)
                    if worked:
                        self.work_iterations += 1
                    if self.paged and self._validate:
                        sched.audit_pool()
                    sched.observe_progress(it, bool(reaped or admitted
                                                    or worked))
                    sched.it += 1
        finally:
            fault_mod.deactivate()
        with tracing.span(tracing.METRICS):
            wall = time.perf_counter() - t0
            gen = sum(len(r.out) for r in sched.requests)
            snap = MetricsSnapshot(
                iterations=sched.it - it0,
                wall_s=wall,
                generated_tokens=gen,
                tokens_per_s=gen / max(wall, 1e-9),
                trace_counts=dict(ex.trace_counts),
                # compiled-program launches per iteration that ran model
                # work (oracle re-runs included) — 1.0 on a clean run
                dispatches=ex.dispatches - disp0,
                dispatches_per_iteration=(
                    (ex.dispatches - disp0)
                    / max(self.work_iterations - work0, 1)),
                degraded_iterations=ex.degraded_iterations - degraded0,
                lifecycle=LifecycleMetrics(
                    terminal_states={
                        s: sum(1 for r in sched.requests if r.state == s)
                        for s in TERMINAL},
                    admission_retries=sched.admission_retries - retries0,
                    watchdog_trips=sched.watchdog_trips - wdog0,
                    timeouts=sched.timeouts - timeout0,
                    cancellations=sched.cancellations - cancel0,
                    restores=self.restores,
                    faults_fired=(self.faults.total_fired
                                  if self.faults is not None else 0),
                ),
                paged=(PagedMetrics(
                    enabled=True,
                    block_size=sched.pool.block_size,
                    num_blocks=sched.pool.num_blocks,
                    peak_blocks_in_use=sched.pool.peak_in_use,
                    preemptions=sched.preemptions - preempt0,
                    rejections=sched.rejections - reject0,
                    attention_kernel=ex.paged_kernel,
                    prefix_cache=sched.prefix_cache,
                    prefix_hits=sched.prefix_hits - hits0,
                    blocks_reused=sched.blocks_reused - reused0,
                    tokens_skipped=sched.tokens_skipped - skipped0,
                    prefill_tokens=sched.prefill_demand - demand0,
                    cached_blocks=sched.pool.cached_blocks,
                    evictions=sched.pool.evictions - evict0,
                    attention_tiles_walked=(self.attention_tiles[0]
                                            - tiles0[0]),
                    attention_tiles_visible=(self.attention_tiles[1]
                                             - tiles0[1]),
                    attention_tile=self._chunk_tile(),
                ) if self.paged else PagedMetrics(enabled=False)),
                moe=MoEMetrics(routed_rows={
                    ph: n - routed0[ph]
                    for ph, n in self.routed_rows.items()}),
                requests=[RequestMetrics(
                    rid=r.rid,
                    prompt_len=int(len(r.tokens)),
                    arrival=r.arrival,
                    state=r.state,
                    admitted_iter=r.admitted_iter,
                    first_token_iter=r.first_token_iter,
                    done_iter=r.done_iter,
                    latency_iters=r.done_iter - r.arrival,
                    latency_s=(r.done_t - r.visible_t if r.done_t >= 0
                               and r.visible_t >= 0 else 0.0),
                    n_out=len(r.out),
                    preemptions=r.preempted,
                    cached_tokens=r.cached_tokens,
                    retries=r.retries,
                    visible_t=r.visible_t,
                    admitted_t=r.admitted_t,
                    first_chunk_t=r.first_chunk_t,
                    first_token_t=r.first_token_t,
                    done_t=r.done_t,
                ) for r in sched.requests],
            )
            self.metrics_snapshot = snap
            self.metrics = snap.to_dict()
        return {
            "outputs": {r.rid: list(r.out) for r in sched.requests},
            "metrics": self.metrics,
        }

    # ------------------------------------------------------ crash recovery
    def snapshot(self) -> Dict[str, Any]:
        """Copy of all host-side engine state at an iteration boundary:
        request lifecycles (including emitted tokens and memoized hash
        chains), slot assignment, the block pool (tables, refcounts,
        prefix index, LRU order), the iteration clock, and the sampling
        PRNG.  Process-local — chain hashes use Python's per-process
        salted ``hash()``, so a snapshot only restores into the same
        process (matching its purpose: surviving an ENGINE crash, not a
        process crash)."""
        snap = self.sched.host_snapshot()
        snap["key"] = None if self._key is None else np.asarray(self._key)
        # executor/driver counters ride along in the scheduler's counter
        # dict so the snapshot schema matches the pre-split engine's
        snap["counters"]["degraded_iterations"] = \
            self.exec.degraded_iterations
        snap["counters"]["restores"] = self.restores
        return snap

    def restore(self, snap: Dict[str, Any]) -> None:
        """Rebuild host-side state from a :meth:`snapshot` taken in this
        process (possibly by a different, now-dead engine instance over
        the same model/config).  Device KV is treated as LOST — the crash
        that motivated the restore invalidates it — so in-flight requests
        are demoted to ``WAITING`` with a fresh block pool and empty
        prefix index, and replay through prefill on re-admission: the
        same recompute path preemption uses, so resumed greedy outputs
        are token-identical to an undisturbed run."""
        counters = dict(snap["counters"])
        self.exec.degraded_iterations = counters.pop("degraded_iterations")
        self.restores = counters.pop("restores") + 1
        self.sched.host_restore({**snap, "counters": counters})
        self._key = (None if snap["key"] is None
                     else jnp.asarray(snap["key"]))
        self.exec.drop_cache()             # rebuilt lazily by run()
