"""jit'd public wrapper: pytree-aware batched subset averaging.

`weighted_avg(stacked_tree, weights)` flattens the stacked client pytree to
one (M, D_total) matrix view per leaf, runs the Pallas kernel per leaf
(compiled natively on TPU, interpreted elsewhere; leaves narrower than one
block, or `use_kernel=False`, take the jnp reference), and rebuilds R
averaged pytrees stacked on a leading subset axis.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax

from repro.kernels import default_interpret, pad_to
from repro.kernels.weighted_avg.kernel import weighted_avg_kernel
from repro.kernels.weighted_avg.ref import weighted_avg_ref

PyTree = Any


@partial(jax.jit, static_argnames=("use_kernel", "interpret", "block_d"))
def weighted_avg(stacked_tree: PyTree, weights: jax.Array, *,
                 use_kernel: bool = True, interpret: bool | None = None,
                 block_d: int = 2048) -> PyTree:
    """stacked_tree leaves (M, *s); weights (R, M) -> leaves (R, *s).

    `interpret=None` derives from the backend (compile natively on TPU,
    interpret elsewhere).
    """
    if interpret is None:
        interpret = default_interpret()

    def one(leaf: jax.Array) -> jax.Array:
        m = leaf.shape[0]
        flat = leaf.reshape(m, -1)
        d = flat.shape[1]
        if not use_kernel or d < block_d:
            out = weighted_avg_ref(flat, weights.astype(flat.dtype))
        else:
            padded = pad_to(flat, block_d)
            out = weighted_avg_kernel(padded, weights.astype(flat.dtype),
                                      block_d=block_d, interpret=interpret)
            out = out[:, :d]
        return out.reshape((weights.shape[0],) + leaf.shape[1:])

    return jax.tree.map(one, stacked_tree)
