"""Where the Pallas kernels run: the one place that decides it.

On a TPU backend every kernel compiles with Mosaic; on any other backend
(the CPU test hosts) the same BlockSpecs run through the Pallas
interpreter.  Kernel entry points take ``interpret=None`` to mean "chosen
by platform"; an explicit bool overrides it (the compile rehearsal in
``tests/test_chip_compile.py`` passes ``False`` to lower for a described
TPU from a CPU host).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["resolve_interpret", "contract_precision"]


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def contract_precision(*dtypes) -> Optional[jax.lax.Precision]:
    """Precision of an in-kernel float32 contraction whose operands arrive
    in ``dtypes``: full fp32 where one arrives as float32 (Mosaic's default
    contraction need not be), the default otherwise — bfloat16 values cast
    up to float32 multiply exactly at any precision."""
    if any(jnp.dtype(d) == jnp.float32 for d in dtypes):
        return jax.lax.Precision.HIGHEST
    return None
