"""Seeded-bad fixture: a kernel body DMAs from an HBM operand at a row
its scalar-prefetched table puts past the array.

The table reads ``[0, 2]`` and the operand has 2 rows, so step 1 copies
row 2.  No BlockSpec describes this copy — the ``races`` checker must
find it by walking the body and flag it with exactly one ``dma-oob``
finding.
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _body(idx_ref, x_hbm, o_ref, buf, sem):
    cp = pltpu.make_async_copy(x_hbm.at[idx_ref[pl.program_id(0)]], buf,
                               sem)
    cp.start()
    cp.wait()
    o_ref[0] = buf[...] + 1.0


def dma_oob(idx, x):
    return pl.pallas_call(
        _body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(2,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 8, 8), lambda i, idx: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((8, 8), jnp.float32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((2, 8, 8), jnp.float32),
        interpret=True,
    )(idx, x)


GRID_ENTRIES = [
    ("race_dma_oob", dma_oob,
     (jnp.asarray([0, 2], jnp.int32), jnp.zeros((2, 8, 8), jnp.float32))),
]
