"""Public serving facade — the one supported entry point.

Everything under ``repro.serve`` below this module is implementation
detail with a stability contract only through here::

    from repro.serve.api import Engine, EngineConfig

    eng = Engine.from_config(model, EngineConfig(dp=2, tp=1))
    rid = eng.submit(prompt_tokens, max_new_tokens=32)
    res = eng.run(params)                # res["outputs"][rid]
    print(eng.metrics.to_json(indent=2))

``EngineConfig`` wraps the per-replica :class:`ContinuousConfig` plus the
parallelism layout: ``dp`` replicas (host-level — each an independent
Scheduler+Executor with its own slot/block pool, load-balanced by the
:class:`~repro.serve.router.Router`) by ``tp`` tensor-parallel shards per
replica (device-level — column-parallel projections and head-sharded
paged attention, see ``distributed/tp.py``).  ``tp > 1``, or ``dp > 1``
on a backend with a device per replica, builds a ``(data, model)`` mesh
via ``launch.mesh.make_serving_mesh``: replica *i* holds its params and
cache on mesh row *i*.

The legacy entry points survive as thin adapters over this stack:
``ContinuousServingEngine`` is exactly a dp=1 router replica and
``ServingEngine.generate`` (one-shot) is "submit the whole batch, close
admission, run" — both now raise ``DeprecationWarning`` on direct
construction.  serve/README.md has the migration table.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

from repro.core.policy import DENSE, SparsityPolicy
from repro.serve.continuous import ContinuousConfig
from repro.serve.faults import FaultInjector
from repro.serve.metrics import MetricsSnapshot
from repro.serve.router import Router

__all__ = ["EngineConfig", "Engine"]


def _device_count() -> int:
    import jax
    return len(jax.devices())


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Serving-wide configuration: parallel layout + per-replica knobs."""
    dp: int = 1                    # data-parallel engine replicas
    tp: int = 1                    # tensor-parallel shards per replica
    serving: ContinuousConfig = ContinuousConfig()

    def __post_init__(self):
        assert self.dp >= 1 and self.tp >= 1, "dp/tp must be positive"


class Engine:
    """User-facing serving engine: a Router with a typed config and a
    :class:`MetricsSnapshot`-returning metrics surface."""

    def __init__(self, router: Router, cfg: EngineConfig):
        self._router = router
        self.cfg = cfg

    @classmethod
    def from_config(cls, model, cfg: EngineConfig = EngineConfig(), *,
                    policy: SparsityPolicy = DENSE,
                    faults: Optional[FaultInjector] = None,
                    mesh=None) -> "Engine":
        """Build the full serving stack for ``cfg``'s layout.

        ``mesh`` overrides the auto-built one (useful in tests that fake
        host devices); otherwise a ``(dp, tp)`` mesh is built when
        ``tp > 1`` — raising a clear ValueError when the backend lacks the
        devices — or when ``dp > 1`` and the backend has a device per
        replica, so each replica's params and cache sit on its own chip.
        With fewer devices than ``dp`` (and ``tp == 1``) the replicas
        share the default device.
        """
        if mesh is None and (cfg.tp > 1 or 1 < cfg.dp <= _device_count()):
            from repro.launch.mesh import make_serving_mesh
            mesh = make_serving_mesh(cfg.dp, cfg.tp)
        router = Router(model, policy, cfg.serving, dp=cfg.dp, mesh=mesh,
                        faults=faults)
        return cls(router, cfg)

    # ------------------------------------------------------------ requests
    def submit(self, tokens, max_new_tokens: int = 32, arrival: int = 0,
               ttl: Optional[int] = None) -> int:
        return self._router.submit(tokens, max_new_tokens, arrival, ttl)

    def cancel(self, rid: int) -> bool:
        return self._router.cancel(rid)

    def run(self, params, extras: Optional[Dict[int, Dict]] = None) -> Dict:
        return self._router.run(params, extras=extras)

    def generate(self, params, prompts: Sequence, max_new_tokens: int = 32
                 ) -> List[List[int]]:
        """One-shot convenience (the old ``ServingEngine.generate`` shape):
        submit the whole batch at arrival 0, run to completion with
        admission closed, return outputs in submission order."""
        rids = [self.submit(p, max_new_tokens) for p in prompts]
        res = self.run(params)
        return [res["outputs"][r] for r in rids]

    # ------------------------------------------------------------- observe
    @property
    def metrics(self) -> Optional[MetricsSnapshot]:
        """Merged fleet metrics from the last ``run()`` (None before)."""
        return self._router.metrics_snapshot

    def request_state(self, rid: int) -> str:
        return self._router.request_state(rid)

    @property
    def replicas(self):
        """The underlying per-replica engines (read-only introspection)."""
        return tuple(self._router.replicas)

    # ---------------------------------------------------- state management
    def snapshot(self) -> Dict[str, Any]:
        return self._router.snapshot()

    def restore(self, snap: Dict[str, Any]) -> None:
        self._router.restore(snap)

    def clear(self) -> None:
        self._router.clear()
