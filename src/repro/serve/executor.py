"""Device-program layer of the serving engine (the API split's second
layer — see serve/README.md "Architecture").

The :class:`Executor` owns everything that touches jax: the cache
pytree, the jit'd step programs (one per :data:`STEP_BUCKETS` row, each
running an iteration's prefill chunk and slot-batched decode in one
dispatch), their bit-exact jnp oracle twins, and the fault/degradation
ladder.  It consumes :class:`~repro.serve.scheduler.StepPlan`s — plain
host data — and returns sampled tokens; it never reads or mutates
request state.

Every step program is a **pure function** of ``(params, cache, plan
operands)``: the only Python-side reads inside a traced body are
static configuration (model, policy, slot spec, temperature) and the
trace-counter side effect, which runs at trace time only.  That is what
makes the program ``shard_map``-able: when the executor is built with a
``mesh``, each dispatch runs under a :func:`repro.distributed.tp.scope`
and the projection kernels / paged attention shard themselves across
the mesh's model axis (column-parallel N_out, KV-head split) with
bit-identical results — see ``distributed/tp.py``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import DENSE, SparsityPolicy
from repro.distributed import tp as tp_mod
from repro.serve import slots as slot_ops
from repro.serve import tracing
from repro.serve.faults import KernelFault
from repro.serve.paged import init_paged_cache
from repro.serve.scheduler import StepPlan

__all__ = ["Executor", "StepResult", "STEP_BUCKETS", "declared_trace_keys"]

# The one-dispatch step buckets, keyed (replay, has_prefill,
# has_decode) — static phase presence (PR 7).  This table is THE
# enumeration: ``Executor.__init__`` builds one program (plus its jnp
# oracle twin) per row, and ``repro.analysis`` sweeps its jaxpr/trace
# rules over exactly these buckets, so adding a bucket here is
# automatically adding it to the checked contract.
STEP_BUCKETS: Dict[Tuple[bool, bool, bool], str] = {
    (False, True, False): "step_prefill",
    (False, True, True): "step_prefill_decode",
    (False, False, True): "step_decode",
    (True, True, False): "step_replay",
    (True, True, True): "step_replay_decode",
}

# XLA options of every serving program: a narrow (bfloat16) value is
# rounded wherever the program stores one, never carried wider
EXACT_STORES = {"xla_allow_excess_precision": False}


def declared_trace_keys() -> Tuple[str, ...]:
    """Every ``trace_counts`` key an :class:`Executor` may legitimately
    record: the step buckets and their ``_oracle`` degradation twins.
    The retrace rule treats any key outside this set as an undeclared
    (hence unbounded) trace bucket."""
    base = tuple(STEP_BUCKETS.values())
    return base + tuple(k + "_oracle" for k in base)


@dataclasses.dataclass
class StepResult:
    """Host-side result of one executed plan."""
    prefill_token: Optional[int] = None   # sampled iff the plan had prefill
    decode_tokens: Optional[np.ndarray] = None  # (num_slots,) iff decode
    degraded: bool = False                # re-ran on the jnp oracle twin


class Executor:
    """Owns the cache pytree + jit'd step programs; executes plans.

    May mutate: ``self.cache``, its own dispatch/degradation counters,
    ``trace_counts``.  May NOT touch: requests, slots bookkeeping, the
    block pool (scheduler territory).  ``mesh`` (a 1-axis TP mesh, see
    ``distributed/tp.replica_meshes``) holds this executor's params and
    cache (replicated) and shards the kernels over its devices — a
    one-device mesh pins a dp replica to its own chip; ``mesh=None`` is
    the single-device executor on the default device."""

    def __init__(self, model, policy: SparsityPolicy, cfg,
                 mesh=None, tp_axis: str = "model"):
        self.model = model
        self.policy = policy
        self.cfg = cfg
        self.mesh = mesh
        self.tp_axis = tp_axis
        mcfg = model.cfg
        if getattr(mcfg, "vision_stub", False):
            assert cfg.chunk_size >= mcfg.n_patches, (
                "chunk_size must cover the VLM patch stub "
                f"({cfg.chunk_size} < {mcfg.n_patches})")
        # recurrent scans cannot mask padded tokens out of their state, so
        # hybrid/SSM archs get exact dyadic chunks instead of a padded tail
        if mcfg.is_encdec:
            self.exact_chunks = False
        else:
            from repro.models.transformer import layer_kinds
            self.exact_chunks = any(k != "attn" for k in layer_kinds(mcfg))
        if mcfg.attn_type in ("swa", "local"):
            assert cfg.chunk_size <= min(mcfg.window, cfg.max_seq), (
                "chunk_size must fit the sliding-window ring buffer")
        # paged KV: only archs with full-attention KV leaves benefit;
        # encdec (request-shaped caches), SWA rings, and pure-recurrent
        # archs fall back to the dense per-slot slab automatically
        spec = model.paged_kv_spec() if cfg.paged else None
        if spec is not None and not any(jax.tree_util.tree_leaves(spec)):
            spec = None
        self._spec = spec
        self.paged = spec is not None
        # the projections' policy flag also routes paged attention through
        # the in-kernel block-table walk (models/attention.paged_attention
        # ladder); decode runs DENSE projections but must carry the flag so
        # its attention takes the same path as prefill's
        self.paged_kernel = self.paged and bool(policy.use_pallas_kernels)
        if self.paged_kernel and not self.exact_chunks:
            # a padded prefill bucket the kernel cannot tile would silently
            # fall back to the gather oracle while metrics/--trace claimed
            # the kernel ran — reject it here instead (exact-chunk archs
            # emit power-of-two chunks, always covered; decode is T = 1)
            from repro.kernels.paged_attention import paged_kernel_covers
            assert paged_kernel_covers(cfg.chunk_size), (
                "paged-attention kernel cannot tile chunk_size="
                f"{cfg.chunk_size} (see kernels.paged_attention"
                ".paged_kernel_covers); use a power-of-two chunk_size or "
                "drop use_pallas_kernels")
        # every value the program stores in a narrow dtype is rounded
        # where the program stores it: XLA otherwise keeps a fused chain
        # in float32 past a store (a residual add read by the next norm),
        # so a routed layer's top-k would hang on fusion choices
        jit = functools.partial(jax.jit, compiler_options=EXACT_STORES)
        # a TP mesh returns every output replicated over it — the layout
        # the cache and params go in with, so a step never retraces on a
        # changed input sharding
        if mesh is not None and mesh.size > 1:
            from jax.sharding import NamedSharding, PartitionSpec
            jit = functools.partial(jit, out_shardings=NamedSharding(
                mesh, PartitionSpec()))
        self.cache = None               # built lazily per params
        self._placed = None             # (params, params on self.mesh)
        self.trace_counts: Dict[str, int] = {}
        self.dispatches = 0       # compiled-program launches (incl. oracle)
        self.degraded_iterations = 0  # iterations re-run on the jnp oracle

        # one-dispatch iterations: a single hybrid step program per shape
        # bucket runs the active request's prefill chunk AND the
        # slot-batched decode in one compiled dispatch.  Buckets are keyed
        # (replay, has_prefill, has_decode) — static phase presence, so an
        # idle phase costs nothing in the lowered program.  The prefill
        # half writes its chunk KV first; the decode half then reads the
        # already-updated cache.  Every program takes a runtime ``fault``
        # operand added onto its logits (0.0 on clean runs, NaN when the
        # injector fires a "nonfinite" fault — a runtime value, so
        # injection never bakes into or retraces the compiled program).
        # Both halves share it and fold into one all-finite ``ok`` verdict
        # (inactive decode rows masked; it also trips on GENUINE
        # non-finite logits from a kernel bug), so the degradation ladder
        # re-runs the WHOLE step on the oracle twin.
        def make_step_fn(pf_policy, dec_policy, count_key,
                         has_prefill, has_decode):
            def step_fn(params, cache, slot, tokens, chunk_len, extras,
                        toks, active, pkey, dkey, fault):
                # runs at trace time only
                self.trace_counts[count_key] = \
                    self.trace_counts.get(count_key, 0) + 1
                ok = jnp.asarray(True)
                ptok = jnp.asarray(0, jnp.int32)
                if has_prefill:
                    with jax.named_scope("step.slot_io"):
                        sub = slot_ops.slice_slot(cache, slot, self._spec)
                    batch = {"tokens": tokens, "chunk_len": chunk_len,
                             **extras}
                    p_logits, sub = self.model.prefill_chunk(
                        params, batch, sub, policy=pf_policy)
                    p_logits = p_logits[0] + fault
                    ok = ok & jnp.all(jnp.isfinite(p_logits))
                    with jax.named_scope("step.slot_io"):
                        cache = slot_ops.write_slot(cache, slot, sub,
                                                    self._spec)
                    ptok = self._sample(p_logits, pkey)
                nxt = toks
                if has_decode:
                    d_logits, new_cache = self.model.decode_step(
                        params, toks[:, None], cache, policy=dec_policy)
                    d_logits = d_logits + fault
                    with jax.named_scope("step.cache_select"):
                        cache = slot_ops.where_active(active, new_cache,
                                                      cache, self._spec)
                    # inactive slots may legitimately hold junk logits —
                    # only active rows gate the degradation ladder
                    ok = ok & jnp.all(
                        jnp.isfinite(d_logits)
                        | ~active.reshape(active.shape[0],
                                          *([1] * (d_logits.ndim - 1))))
                    nxt = jnp.where(active, self._sample(d_logits, dkey),
                                    toks)
                return ptok, nxt, cache, ok
            # the bucket's name names the jitted program: XLA's module
            # (jit_<bucket>) and JAX's compile events carry it
            step_fn.__name__ = step_fn.__qualname__ = count_key
            return step_fn

        # decode runs dense projections, and so does a preemption replay:
        # it re-ingests tokens the request already EMITTED, whose KV the
        # dense decode originally wrote, so a sparse replay would drift
        # from the one-shot oracle.  Chunks never span the prompt/emitted
        # boundary (see Scheduler.next_chunk); the replay buckets only
        # trace if a preemption happens under a non-dense policy.
        dense = DENSE.with_(use_pallas_kernels=policy.use_pallas_kernels)
        # graceful-degradation ladder: bit-exact jnp oracle twins of every
        # step program (kernel dispatch forced off).  jax.jit is lazy, so
        # none of these trace — and no "*_oracle" trace-count key appears —
        # unless an iteration actually degrades.
        opolicy = policy.with_(use_pallas_kernels=False) \
            if policy.use_pallas_kernels else policy
        # raw (unjitted) step fns are kept for the jaxpr pins in tests and
        # repro.analysis — ``step_program(bucket)`` is the public accessor
        self._step_raw: Dict[tuple, Callable] = {}
        self._step_oracle_raw: Dict[tuple, Callable] = {}
        self._step_jits: Dict[tuple, Callable] = {}
        self._step_oracle_jits: Dict[tuple, Callable] = {}
        for key, name in STEP_BUCKETS.items():
            replay, hp, hd = key
            pf = dense if replay else policy
            opf = DENSE if replay else opolicy
            self._step_raw[key] = make_step_fn(pf, dense, name, hp, hd)
            self._step_oracle_raw[key] = make_step_fn(
                opf, DENSE, name + "_oracle", hp, hd)
            self._step_jits[key] = jit(self._step_raw[key])
            self._step_oracle_jits[key] = jit(self._step_oracle_raw[key])

    # ------------------------------------------------------------- sampling
    def _sample(self, logits, key):
        with jax.named_scope("step.sample"):
            if self.cfg.temperature <= 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jax.random.categorical(
                key, logits / self.cfg.temperature, axis=-1).astype(jnp.int32)

    # ------------------------------------------------------------ the cache
    def _on_mesh(self, tree):
        """``tree`` replicated over this executor's mesh — the one device
        of a dp replica, or its TP submesh.  Without a mesh, as given."""
        if self.mesh is None:
            return tree
        if self.mesh.size == 1:   # the sharding a one-device jit returns
            return jax.device_put(tree, self.mesh.devices.flat[0])
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(tree, NamedSharding(self.mesh, PartitionSpec()))

    def place(self, params):
        """``params`` on this executor's devices, copied once per params
        object (a dp replica keeps its own copy on its own device)."""
        if self._placed is None or self._placed[0] is not params:
            self._placed = (params, self._on_mesh(params))
        return self._placed[1]

    def init_cache(self, num_blocks: Optional[int] = None) -> None:
        if self.cache is not None:
            return
        if self.paged:
            cache = init_paged_cache(
                self.model, self.cfg.num_slots, self.cfg.max_seq,
                self.cfg.block_size, num_blocks, self._spec)
        else:
            cache = slot_ops.init_slot_cache(
                self.model, self.cfg.num_slots, self.cfg.max_seq)
        self.cache = self._on_mesh(cache)

    def drop_cache(self) -> None:
        """Forget device state (restore path: the crash that motivated a
        restore invalidates the KV anyway)."""
        self.cache = None

    def apply_effects(self, plan: StepPlan) -> None:
        """Apply the plan's idempotent cache-side effects BEFORE the step:
        slot resets decided at admission and the host block table when the
        scheduler rewrote it."""
        with tracing.span(tracing.APPLY_EFFECTS):
            for slot, pos in plan.resets:
                self.cache = slot_ops.reset_slot(self.cache, slot,
                                                 self._spec, pos=pos)
            if plan.table is not None:
                self.cache["block_table"] = self._on_mesh(
                    jnp.asarray(plan.table))

    # ----------------------------------------------------------- dispatch
    def _tp_scope(self):
        return tp_mod.scope(self.mesh, self.tp_axis)

    def _run_ladder(self, fn, ofn, args, fault):
        """One dispatch + the degradation ladder: on a KernelFault (trace-
        time kernel failure — the failed trace aborted before any output
        existed) or a non-finite ``ok`` verdict, discard the faulted
        outputs (functional jit — ``self.cache`` is untouched) and re-run
        the SAME operands on the bit-exact jnp oracle program."""
        self.dispatches += 1
        try:
            with tracing.span(tracing.DISPATCH), self._tp_scope():
                out = fn(*args, fault)
            with tracing.span(tracing.WAIT):
                ok = bool(out[-1])
        except KernelFault:
            ok = False
        if not ok:
            self.degraded_iterations += 1
            self.dispatches += 1
            with tracing.span(tracing.ORACLE):
                with self._tp_scope():
                    out = ofn(*args, jnp.float32(0.0))
                assert bool(out[-1]), "oracle produced non-finite logits"
        return out

    def step(self, params, plan: StepPlan, extras: Dict, pkey, dkey,
             fault) -> StepResult:
        """Execute a one-dispatch plan.  ``extras`` are the modality
        arrays for the chunk (already resolved to {} by the driver when
        this is not the request's first chunk)."""
        degraded0 = self.degraded_iterations
        pw, dw = plan.prefill, plan.decode
        if pw is not None:
            slot = jnp.asarray(pw.req.slot, jnp.int32)
            ptoks = jnp.asarray(pw.tokens)
            pclen = jnp.asarray(pw.chunk_len, jnp.int32)
            ex = extras
        else:
            ex = {}
            slot = jnp.asarray(0, jnp.int32)
            ptoks = jnp.zeros((1, 1), jnp.int32)
            pclen = jnp.asarray(0, jnp.int32)
        if dw is not None:
            toks, act = jnp.asarray(dw.toks), jnp.asarray(dw.active)
        else:
            toks = jnp.zeros((self.cfg.num_slots,), jnp.int32)
            act = jnp.zeros((self.cfg.num_slots,), bool)
        bucket = plan.bucket
        args = (self.place(params), self.cache, slot, ptoks, pclen, ex, toks, act,
                pkey, dkey)
        ptok, nxt, new_cache, _ = self._run_ladder(
            self._step_jits[bucket], self._step_oracle_jits[bucket],
            args, fault)
        self.cache = new_cache
        with tracing.span(tracing.WAIT):
            return StepResult(
                prefill_token=int(ptok) if pw is not None else None,
                decode_tokens=np.asarray(nxt) if dw is not None else None,
                degraded=self.degraded_iterations > degraded0)

    # ----------------------------------------------------------- test hooks
    def step_program(self, bucket: Tuple[bool, bool, bool],
                     oracle: bool = False):
        """The raw (unjitted) step program for a phase-presence bucket —
        a pure function of its operands, used by the jaxpr purity pins
        (buckets enumerated by :data:`STEP_BUCKETS`).  ``oracle=True``
        returns the bit-exact jnp degradation twin, so the analyzer can
        check the kernels-off program as well (and prove the kernels-on
        pins aren't vacuously true)."""
        return (self._step_oracle_raw if oracle else self._step_raw)[bucket]

    def step_programs(self, oracle: bool = False):
        """Sweep hook: yield ``(bucket, name, program)`` for EVERY
        :data:`STEP_BUCKETS` row — the analyzer iterates this (rather
        than hand-listing buckets) so a new bucket is in the checked
        contract the moment it exists."""
        for bucket, name in STEP_BUCKETS.items():
            yield bucket, name, self.step_program(bucket, oracle=oracle)
