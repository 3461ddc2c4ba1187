"""One-shot batched serving engine: Amber-sparse prefill + dense decode.

The paper's deployment story: N:M activation sparsity runs **only during
prefill** (compute-bound), decode stays dense (memory-bound — sparsity
buys nothing there and risks KV-cache drift).  The engine makes that split
explicit:

    engine = ServingEngine(model, policy)
    out = engine.generate(params, prompts, max_new_tokens=64)

``generate`` is the one-shot whole-batch reference that the chunked
serving path is tested against: every request in the batch arrives
together, prefill runs as one monolithic jit, and decode runs as a
``lax.scan`` over steps.  Served traffic — asynchronous arrivals, mixed
prompt lengths, slot reuse — goes through
:class:`repro.serve.continuous.ContinuousServingEngine`, which chunks the
sparse prefill and runs it with slot-batched decode as one step program
per shape bucket (see ``serve/README.md``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.policy import DENSE, SparsityPolicy

__all__ = ["ServeConfig", "ServingEngine"]


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_seq: int = 512
    temperature: float = 0.0       # 0 → greedy
    eos_token: int = -1            # -1 → never stop early
    seed: int = 0


class ServingEngine:
    """The one-shot whole-batch reference: monolithic prefill, then a
    scanned dense decode.  The chunked serving path is tested against
    it; serving itself goes through :class:`repro.serve.api.Engine`."""

    def __init__(self, model, policy: SparsityPolicy = DENSE,
                 cfg: ServeConfig = ServeConfig()):
        self.model = model
        self.policy = policy
        self.cfg = cfg
        self._prefill_jit = jax.jit(self._prefill)
        self._decode_loop_jit = jax.jit(self._decode_loop,
                                        static_argnames=("steps",))

    # --- jitted bodies -----------------------------------------------------
    def _prefill(self, params, batch, cache):
        return self.model.prefill(params, batch, cache, policy=self.policy)

    def _sample(self, logits, key):
        if self.cfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(key, logits / self.cfg.temperature,
                                      axis=-1)

    def _decode_loop(self, params, first_tokens, cache, key, *, steps: int):
        def body(carry, i):
            tokens, cache, key, done = carry
            key, sub = jax.random.split(key)
            logits, cache = self.model.decode_step(
                params, tokens[:, None], cache, policy=DENSE)
            nxt = self._sample(logits, sub)
            nxt = jnp.where(done, tokens, nxt)
            done = done | (nxt == self.cfg.eos_token)
            return (nxt, cache, key, done), nxt

        b = first_tokens.shape[0]
        done0 = jnp.zeros((b,), bool)
        (_, cache, _, _), toks = jax.lax.scan(
            body, (first_tokens, cache, key, done0), jnp.arange(steps))
        return toks.T, cache                      # (B, steps)

    # --- public API ----------------------------------------------------------
    def generate(
        self,
        params,
        batch: Dict[str, jax.Array],
        max_new_tokens: int = 32,
    ) -> Dict[str, Any]:
        """batch must hold "tokens" (B, T_prompt) (+ modality stubs)."""
        prompts = batch["tokens"]
        b, t = prompts.shape
        assert t + max_new_tokens <= self.cfg.max_seq, "max_seq too small"
        cache = self.model.init_cache(b, self.cfg.max_seq)
        logits, cache = self._prefill_jit(params, batch, cache)
        key = jax.random.PRNGKey(self.cfg.seed)
        key, sub = jax.random.split(key)
        first = self._sample(logits, sub)
        if max_new_tokens == 1:
            return {"tokens": first[:, None], "cache": cache}
        rest, cache = self._decode_loop_jit(
            params, first, cache, key, steps=max_new_tokens - 1)
        return {
            "tokens": jnp.concatenate([first[:, None], rest], axis=1),
            "cache": cache,
        }
