"""Seeded weights, made by the benchmark (never by the program under test).

Every leaf is a pure function of ``(seed, path, shape)``: the served program
gets the whole tree from one jitted call on the device, in the type it is
served in, and the reference regenerates any single leaf by its path without
touching the program's arrays.

Rule by path (the program's parameter layout, joined with ``/``):

* ``.../ln1/w``, ``.../ln2/w`` and any ``.../<name>_norm/w`` (``final_norm``,
  a per-head ``q_norm``): ones;
* ``embed/w``: N(0, 0.02^2);
* ``.../b``: N(0, 0.02^2), so the fused bias epilogues carry real values;
* any other ``w``: N(0, 1/d_in), with ``d_in = shape[-2]``.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

NORMS = ("ln1", "ln2")


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64 bits of it kept."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf(key: jax.Array, path: str, shape, dtype) -> jax.Array:
    """The value of the leaf at ``path`` (traceable)."""
    parts = path.split("/")
    if parts[-1] == "w" and len(parts) >= 2 and (
            parts[-2] in NORMS or parts[-2].endswith("_norm")):
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    if path == "embed/w" or parts[-1] == "b":
        std = 0.02
    else:
        std = shape[-2] ** -0.5
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def build_like(tree, seed: int):
    """Fill a pytree of ``ShapeDtypeStruct`` leaves (the program's layout)
    with this module's values, in one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)

    def make(key):
        return [leaf(key, path_str(p), s.shape, s.dtype) for p, s in flat]

    leaves = jax.jit(make)(base_key(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)
