"""Known-bad step program for the jaxpr pool-containment pin: a
pool-shaped ``jnp.take`` — exactly the O(pool) logical-view gather the
paged-attention kernel exists to eliminate.  Loaded by
``python -m repro.analysis --jaxpr-extra`` in the analyzer's own tests,
which assert the rule fires."""
import jax
import jax.numpy as jnp

POOL_SHAPE = (64, 2, 16, 8)          # (num_blocks, Hkv, block_size, hd)


def gathering_step(pool, idx):
    return jnp.take(pool, idx, axis=0)


JAXPR_ENTRIES = [
    ("pool-gather-step", gathering_step,
     (jax.ShapeDtypeStruct(POOL_SHAPE, jnp.float32),
      jax.ShapeDtypeStruct((4,), jnp.int32)),
     {POOL_SHAPE}),
]
