"""Pallas TPU kernel: cohort row gather driven by scalar-prefetched ids.

`out[i] = table[ids[i]]` for a (N, D) table and (M,) int ids.  A dense
`jnp.take` is a fine gather on small tables, but it gives XLA no hint
that only M ≪ N rows are live; here the cohort ids are scalar-prefetched
into SMEM and consumed by the *input BlockSpec's index_map*, so the DMA
pipeline fetches exactly one (1, BLOCK_D) tile of the table per output
row — the kernel body is a pure VMEM copy and the table never leaves HBM
beyond the M selected rows.

Grid: (M, D // BLOCK_D).  Program (i, j) copies block j of row ids[i].
The index_map receives the prefetched ids ref as a trailing argument
(PrefetchScalarGridSpec contract, same as `prefix_avg`); block indices
are in block units.  The table is viewed (free reshape) as (N, 1, D) and
tiled by (squeezed, 1, BLOCK_D) blocks, so the leading block index IS the
row id.  Mosaic requires a block's last two dims to be (8k, 128k) or the
array's own; a (1, BLOCK_D) block of the 2-D (N, D) table is refused, a
(1, BLOCK_D) tail of the (N, 1, D) view matches the array's unit dim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_D = 2048  # lane-dim tile; multiple of 128 (MXU) and 8*128 (VREG)


def _gather_kernel(ids_ref, table_ref, out_ref):
    # ids: (M,) in SMEM; table_ref: the (1, BLOCK_D) tile of row ids[i]
    # (the index_map did the gather); out_ref: the matching output tile
    del ids_ref
    out_ref[...] = table_ref[...]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def cohort_gather_kernel(table: jax.Array, ids: jax.Array, *,
                         block_d: int = BLOCK_D,
                         interpret: bool = False) -> jax.Array:
    """table (N, D) x ids (M,) int -> (M, D) gathered rows.

    D % block_d == 0 (callers pad; see ops.py).  Ids must be in [0, N).
    """
    n, d = table.shape
    (m,) = ids.shape
    assert d % block_d == 0, (d, block_d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m, d // block_d),
        in_specs=[
            # data-dependent row fetch: leading block index = the cohort id
            pl.BlockSpec((pl.Squeezed(), 1, block_d),
                         lambda i, j, ids: (ids[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((pl.Squeezed(), 1, block_d),
                               lambda i, j, ids: (i, 0, j)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, 1, d), table.dtype),
        interpret=interpret,
    )(ids.astype(jnp.int32), table.reshape(n, 1, d))
    return out.reshape(m, d)
