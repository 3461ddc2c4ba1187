"""Sparsity policy: which linear projections get N:M-pruned, and how.

The paper's deployment policy (Experiments §Setup):

  * sparsity is confined to the **prefill** phase;
  * ``k_proj`` / ``v_proj`` are never pruned (GQA ⇒ negligible FLOP share);
  * ``o_proj`` / ``up_proj`` are never pruned (highest sensitivity, App. D);
  * ``down_proj`` is pruned in **all** layers (lowest sensitivity);
  * ``q_proj`` / ``gate_proj`` are pruned except in a small per-model skip
    list chosen by sensitivity analysis (e.g. layers 19/21/28/30/31 for
    LLaMA3.1-8B).

A :class:`SparsityPolicy` is a hashable static dataclass so it can be closed
over by jitted step functions without retracing churn.
"""
from __future__ import annotations

import dataclasses
from typing import FrozenSet, Mapping, Tuple

__all__ = [
    "SparsityPolicy",
    "DENSE",
    "paper_policy",
    "naive_policy",
]

# canonical projection names used across the model zoo
ATTN_PROJS = ("q_proj", "k_proj", "v_proj", "o_proj")
MLP_PROJS = ("gate_proj", "up_proj", "down_proj")
ALL_PROJS = ATTN_PROJS + MLP_PROJS


@dataclasses.dataclass(frozen=True)
class SparsityPolicy:
    """Static description of the Amber Pruner deployment.

    Attributes:
      enabled:       master switch.
      n, m:          the N:M pattern (2:4, 4:8, 8:16).
      score_mode:    'naive' | 'wanda' | 'robust'.
      skip_modules:  projection names never pruned (any layer).
      skip_layers:   mapping module -> layer indices additionally skipped.
      phases:        phases in which sparsity is active ('prefill' only per
                     the paper; 'train'/'decode' may be added for ablations).
      moe_plain_score: Robust-Norm scoring is N/A inside routed experts
                     (tokens routed dynamically → per-expert statistics are
                     not stable); fall back to |X| there when True.
      tile_consensus: TPU-native mode — one shared N:M pattern per token
                     tile (see DESIGN.md §2); tile size in tokens.
      use_pallas_kernels: route prunable projections through the fused
                     Pallas kernels (``repro.kernels.ops``): per-token mode
                     lowers to one ``nm_prune_matmul`` call, tile-consensus
                     to the k-blocked ``nm_spmm``, and the Outstanding-
                     sparse W8A8 chain to ``osparse_matmul``; scan-stacked
                     ``layer_flag`` models choose kernel or dense per layer
                     with ``lax.cond``.  The pure-jnp path stays the
                     bit-exact oracle.  The platform decides whether the
                     kernels compile (TPU) or run interpreted (CPU).
    """

    enabled: bool = True
    n: int = 8
    m: int = 16
    score_mode: str = "robust"
    skip_modules: Tuple[str, ...] = ("k_proj", "v_proj", "o_proj", "up_proj")
    skip_layers: Mapping[str, FrozenSet[int]] = dataclasses.field(
        default_factory=dict
    )
    phases: Tuple[str, ...] = ("prefill",)
    moe_plain_score: bool = True
    tile_consensus: bool = False
    tile_size: int = 256
    use_pallas_kernels: bool = False

    def __post_init__(self):
        # N with N not dividing M is legal (e.g. 3:8) — the only structural
        # requirements are integer 0 < N ≤ M, checked even when disabled so
        # a bad policy cannot lie dormant behind ``enabled=False``
        import numbers
        if not (isinstance(self.n, numbers.Integral)
                and isinstance(self.m, numbers.Integral)
                and 0 < self.n <= self.m):
            raise ValueError(f"bad N:M {self.n}:{self.m}")
        from repro.core.scoring import SCORE_MODES
        if self.score_mode not in SCORE_MODES:
            raise ValueError(f"unknown score_mode {self.score_mode!r}; "
                             f"expected one of {SCORE_MODES}")
        if self.tile_size < 1:
            raise ValueError(f"tile_size must be >= 1, got {self.tile_size}")
        # freeze the mapping for hashability
        object.__setattr__(
            self,
            "skip_layers",
            tuple(sorted((k, tuple(sorted(v))) for k, v in dict(self.skip_layers).items())),
        )

    # skip_layers is stored as a tuple of (name, (idx...)) pairs post-init
    def _skips_for(self, module: str) -> Tuple[int, ...]:
        for name, idxs in self.skip_layers:  # type: ignore[attr-defined]
            if name == module:
                return idxs
        return ()

    def active(self, phase: str) -> bool:
        return self.enabled and phase in self.phases

    def should_prune(self, module: str, layer_idx: int | None = None) -> bool:
        """Static decision: is this projection pruned at this layer?"""
        if not self.enabled:
            return False
        if module in self.skip_modules:
            return False
        if layer_idx is not None and layer_idx in self._skips_for(module):
            return False
        return True

    def with_(self, **kw) -> "SparsityPolicy":
        cur = dataclasses.asdict(self)
        cur["skip_layers"] = dict(self.skip_layers)  # type: ignore[arg-type]
        cur.update(kw)
        return SparsityPolicy(**cur)


DENSE = SparsityPolicy(enabled=False)


def paper_policy(
    n: int = 8,
    m: int = 16,
    qgate_skip_layers: Tuple[int, ...] = (),
    score_mode: str = "robust",
    tile_consensus: bool = False,
    use_pallas_kernels: bool = False,
) -> SparsityPolicy:
    """The paper's deployment: Amber-P with layer skipping.

    ``qgate_skip_layers`` is the per-model list of layers in which q_proj and
    gate_proj are additionally skipped (sensitivity-selected).
    """
    return SparsityPolicy(
        n=n,
        m=m,
        score_mode=score_mode,
        skip_modules=("k_proj", "v_proj", "o_proj", "up_proj"),
        skip_layers={
            "q_proj": frozenset(qgate_skip_layers),
            "gate_proj": frozenset(qgate_skip_layers),
        },
        tile_consensus=tile_consensus,
        use_pallas_kernels=use_pallas_kernels,
    )


def naive_policy(n: int, m: int) -> SparsityPolicy:
    """Naïve top-k baseline: |X| scores, prune everything, no skipping."""
    return SparsityPolicy(n=n, m=m, score_mode="naive", skip_modules=(), skip_layers={})
