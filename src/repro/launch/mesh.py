"""Production mesh definitions (TPU v5e pods).

Built as FUNCTIONS so importing this module never touches jax device
state — the 512-device dry-run sets XLA_FLAGS before the first jax init
and only then calls these.
"""
from __future__ import annotations

import math

import jax

__all__ = ["make_production_mesh", "make_local_mesh", "make_mesh_auto",
           "make_serving_mesh"]


def make_mesh_auto(shape, axes):
    """``jax.make_mesh`` with Auto axis types.

    Raises ValueError up front when the mesh asks for more devices than
    the backend exposes — ``jax.make_mesh``'s own error talks about array
    reshapes, which buries the actual fix (fewer dp/tp replicas, or more
    fake host devices via XLA_FLAGS).
    """
    want = math.prod(shape)
    have = len(jax.devices())
    if want > have:
        raise ValueError(
            f"mesh {dict(zip(axes, shape))} needs {want} devices but the "
            f"backend has {have}; lower dp/tp or export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={want} "
            "BEFORE the first jax call to fake host devices")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) single pod = 256 chips; (2, 16, 16) = 2 pods / 512 chips.

    Axes: DP over ("pod", "data") — gradient/batch parallelism, hierarchical
    reduce (intra-pod reduce-scatter, inter-pod all-reduce chosen by XLA
    from the mesh nesting) — and TP over "model".
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_auto(shape, axes)


def make_serving_mesh(dp: int = 1, tp: int = 1):
    """``(dp, tp)`` serving mesh with the ("data", "model") axes the
    sharded serving stack expects: the Router slices it into per-replica
    TP submeshes (``distributed.tp.replica_meshes``) and runs one engine
    per ``data`` row.  Validated against the device count up front."""
    return make_mesh_auto((dp, tp), ("data", "model"))


def make_local_mesh():
    """1×1 mesh over whatever single device the host has (tests/examples)."""
    n = len(jax.devices())
    return make_mesh_auto((n, 1), ("data", "model"))
