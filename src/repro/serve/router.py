"""Data-parallel serving router: dp-replicated engines behind one
submit/run surface (the API redesign's third layer — serve/README.md
"Architecture").

The :class:`Router` owns ``dp`` independent
:class:`~repro.serve.continuous.ContinuousServingEngine` replicas — each
a full Scheduler+Executor pair with its own slot set, block pool, and
prefix index — and load-balances admissions across them.  dp replication
is **host-level**: no collective spans the data axis in serving (replicas
never exchange activations).  Without a mesh the replicas share the
default device; a ``(dp, tp)`` mesh (``launch.mesh.make_serving_mesh``)
gives each replica its own row (:func:`repro.distributed.tp
.replica_meshes`), which holds that replica's params and cache and, for
``tp > 1``, shards its kernels.

Routing is least-loaded with **prefix affinity**: requests opening with
the same leading KV block are pinned to the same replica, so the
block-level prefix index — which is replica-local device state and
cannot be shared across pools — still converges to one copy of each hot
prefix family per replica instead of dp cold misses.

Token identity: greedy outputs are batch-composition- and chunking-
invariant (the continuous engine's core equivalence), so WHERE a request
lands never changes WHAT it generates — ``dp=N`` outputs are token-
identical per request to a single-replica run.

Failover: a replica that dies mid-run (:class:`EngineCrash`) is drained
— its terminal requests keep their outputs, its in-flight/waiting
requests transplant to a surviving replica demoted to ``WAITING`` with
their emitted tokens kept for dense replay (the same recompute path
preemption uses), so resumed greedy outputs stay token-identical.  With
``dp=1`` there is no survivor and the crash propagates to the caller
(the single-engine snapshot/restore contract).
"""
from __future__ import annotations

import copy
import time
from typing import Any, Dict, List, Optional, Tuple

from repro.core.policy import DENSE, SparsityPolicy
from repro.distributed import tp as tp_mod
from repro.serve import tracing
from repro.serve.continuous import ContinuousConfig, ContinuousServingEngine
from repro.serve.faults import EngineCrash, FaultInjector
from repro.serve.metrics import MetricsSnapshot
from repro.serve.scheduler import TERMINAL, WAITING

__all__ = ["Router"]


class Router:
    """dp-replicated continuous serving behind one request surface."""

    def __init__(self, model, policy: SparsityPolicy = DENSE,
                 cfg: ContinuousConfig = ContinuousConfig(), *,
                 dp: int = 1, mesh=None,
                 faults: Optional[FaultInjector] = None):
        assert dp >= 1, "need at least one replica"
        self.cfg = cfg
        self.dp = dp
        # one TP submesh per replica when a (data, model) mesh is given;
        # the mesh's data axis must cover the replica count
        if mesh is not None:
            subs = tp_mod.replica_meshes(mesh)
            assert len(subs) >= dp, \
                f"mesh data axis {len(subs)} < dp={dp}"
        else:
            subs = [None] * dp
        # the injector is shared: site schedules (and their limits) apply
        # across the whole fleet, wherever the site happens to fire
        self.replicas: List[ContinuousServingEngine] = [
            ContinuousServingEngine(model, policy, cfg, faults=faults,
                                    mesh=subs[i])
            for i in range(dp)]
        self.alive = [True] * dp
        self.crashes = 0                  # replicas lost to EngineCrash
        self.transplants = 0              # requests re-admitted to survivors
        self._rid_map: Dict[int, Tuple[int, int]] = {}  # grid → (rep, lrid)
        self._affinity: Dict[bytes, int] = {}           # first-block → rep
        self._outputs: Dict[int, List[int]] = {}   # harvested from the dead
        self.metrics_snapshot: Optional[MetricsSnapshot] = None
        self.metrics: Dict[str, Any] = {}

    # ------------------------------------------------------------- routing
    def _load(self, i: int) -> int:
        """Outstanding KV demand of a replica (tokens it still owes)."""
        return sum(len(r.tokens) + r.max_new_tokens
                   for r in self.replicas[i].requests
                   if r.state not in TERMINAL)

    def _route(self, tokens) -> int:
        """Least-loaded admission with prefix affinity: a prompt whose
        leading block matches an earlier request lands on the same replica
        (the prefix index is replica-local — affinity is what keeps reuse
        alive across the split pools)."""
        alive = [i for i in range(self.dp) if self.alive[i]]
        assert alive, "no live replicas"
        key = None
        if self.cfg.prefix_cache and len(tokens) >= self.cfg.block_size:
            key = tokens[:self.cfg.block_size].tobytes()
            hit = self._affinity.get(key)
            if hit is not None and self.alive[hit]:
                return hit
        best = min(alive, key=lambda i: (self._load(i), i))
        if key is not None:
            self._affinity[key] = best
        return best

    def submit(self, tokens, max_new_tokens: int = 32, arrival: int = 0,
               ttl: Optional[int] = None) -> int:
        """Queue a request on the best replica; returns a GLOBAL request
        id (stable across failover transplants)."""
        import numpy as np
        tokens = np.asarray(tokens).reshape(-1).astype(np.int32)
        rep = self._route(tokens)
        lrid = self.replicas[rep].submit(tokens, max_new_tokens, arrival,
                                         ttl)
        grid = len(self._rid_map)
        self._rid_map[grid] = (rep, lrid)
        return grid

    def cancel(self, grid: int) -> bool:
        if grid not in self._rid_map:
            return False
        rep, lrid = self._rid_map[grid]
        return self.replicas[rep].cancel(lrid)

    def request_state(self, grid: int) -> str:
        rep, lrid = self._rid_map[grid]
        return self.replicas[rep].requests[lrid].state

    # ------------------------------------------------------------ failover
    def _transplant(self, dead: int, dst: int) -> None:
        """Drain a dead replica: keep terminal outputs, re-admit everything
        else to ``dst`` demoted to WAITING.  Emitted tokens ride along and
        replay through dense prefill on re-admission — the preemption
        recompute path — so resumed greedy outputs are token-identical."""
        src = self.replicas[dead]
        dst_eng = self.replicas[dst]
        for grid, (rep, lrid) in list(self._rid_map.items()):
            if rep != dead:
                continue
            req = src.requests[lrid]
            if req.state in TERMINAL:
                # finished before the crash: the tokens are safe host state
                self._outputs[grid] = list(req.out)
                continue
            moved = copy.deepcopy(req)
            moved.rid = len(dst_eng.requests)
            moved.state = WAITING
            moved.slot = -1
            moved.blocks = []
            moved.shared = moved.registered = 0
            moved.filled = 0
            moved.kv_len = 0
            # hash_chain survives: chain hashes are content-addressed, so
            # they are valid against the survivor's index too (exactly the
            # host_restore demotion, which also keeps them)
            dst_eng.sched.requests.append(moved)
            self._rid_map[grid] = (dst, moved.rid)
            self.transplants += 1
        self.alive[dead] = False
        self.crashes += 1

    def _survivor(self, dead: int) -> Optional[int]:
        alive = [i for i in range(self.dp) if self.alive[i] and i != dead]
        if not alive:
            return None
        return min(alive, key=lambda i: (self._load(i), i))

    # ------------------------------------------------------------ main loop
    def run(self, params,
            extras: Optional[Dict[int, Dict]] = None) -> Dict:
        """Drive every replica to completion; returns outputs keyed by
        GLOBAL rid plus the merged :class:`MetricsSnapshot` (as the same
        legacy dict shape single engines return).

        Replicas are independent (host-level dp), so they are driven
        sequentially on this host; on hardware each replica's step stream
        is its own device program queue and the wall-clock merge reflects
        the slowest replica.  A replica that crashes is drained to a
        survivor (see class docstring), which is then re-driven."""
        extras = extras or {}
        t0 = time.perf_counter()
        # local-extras view per replica, rebuilt after any transplant
        parts: Dict[int, MetricsSnapshot] = {}
        work = [i for i in range(self.dp) if self.alive[i]]
        while work:
            i = work.pop(0)
            if not self.alive[i]:
                continue
            eng = self.replicas[i]
            local_extras = {lrid: extras[g]
                            for g, (rep, lrid) in self._rid_map.items()
                            if rep == i and g in extras}
            try:
                eng.run(params, extras=local_extras)
                parts[i] = eng.metrics_snapshot
            except EngineCrash:
                dst = self._survivor(i)
                if dst is None:
                    raise              # dp=1: the caller owns recovery
                self._transplant(i, dst)
                parts.pop(i, None)
                if dst not in work:
                    work.append(dst)
        wall = time.perf_counter() - t0
        # merged metrics: one part per live replica (its last run), request
        # records relabeled to global rids.  Requests drained off a dead
        # replica are counted where they finished; a dead replica's own
        # partial run contributes no counters (its work was re-done).
        with tracing.span(tracing.METRICS):
            back = {(rep, lrid): g
                    for g, (rep, lrid) in self._rid_map.items()}
            merged_parts = []
            for i, p in sorted(parts.items()):
                p = MetricsSnapshot.from_dict(p.to_dict())  # private copy
                for r in p.requests:
                    r.rid = back.get((i, r.rid), r.rid)
                merged_parts.append(p)
            self.metrics_snapshot = MetricsSnapshot.merge(merged_parts,
                                                          wall_s=wall)
            self.metrics = self.metrics_snapshot.to_dict()
        outputs = dict(self._outputs)
        for g, (rep, lrid) in self._rid_map.items():
            if g not in outputs:
                outputs[g] = list(self.replicas[rep].requests[lrid].out)
        return {"outputs": outputs, "metrics": self.metrics}

    # ------------------------------------------------------ crash recovery
    def snapshot(self) -> Dict[str, Any]:
        """Host-state snapshot of the whole fleet (iteration-boundary per
        replica).  Only valid while every replica is alive — after a
        failover the fleet shape changed and the next run re-snapshots."""
        assert all(self.alive), "cannot snapshot a degraded fleet"
        return {
            "replicas": [e.snapshot() for e in self.replicas],
            "rid_map": dict(self._rid_map),
            "affinity": dict(self._affinity),
            "outputs": {g: list(o) for g, o in self._outputs.items()},
        }

    def restore(self, snap: Dict[str, Any]) -> None:
        assert len(snap["replicas"]) == self.dp, \
            "snapshot replica count does not match this router"
        for eng, s in zip(self.replicas, snap["replicas"]):
            eng.restore(s)
        self.alive = [True] * self.dp
        self._rid_map = dict(snap["rid_map"])
        self._affinity = dict(snap["affinity"])
        self._outputs = {g: list(o) for g, o in snap["outputs"].items()}

    def clear(self) -> None:
        for e in self.replicas:
            if self.alive[self.replicas.index(e)]:
                e.clear()
        self._rid_map = {}
        self._outputs = {}
