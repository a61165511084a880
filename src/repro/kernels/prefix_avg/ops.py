"""jit'd public wrapper: pytree-aware streaming prefix averaging.

`prefix_avg(stacked_tree, perms, n_k)` flattens the stacked client pytree
to one (M, D_leaf) matrix view per leaf, runs the Pallas kernel per leaf
(compiled natively on TPU, interpreted elsewhere; leaves narrower than
one block, or `use_kernel=False`, take the jnp reference), and rebuilds
the R*M prefix-averaged models stacked on a leading flat walk-major axis
— the exact model order the batched utility evaluator consumes
(`core/shapley_batched.gtg_shapley_streaming`).
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax

from repro.kernels import default_interpret, pad_to
from repro.kernels.prefix_avg.kernel import prefix_avg_kernel
from repro.kernels.prefix_avg.ref import prefix_avg_ref

PyTree = Any


@partial(jax.jit, static_argnames=("use_kernel", "interpret", "block_d"))
def prefix_avg(stacked_tree: PyTree, perms: jax.Array, n_k: jax.Array, *,
               use_kernel: bool = True, interpret: bool | None = None,
               block_d: int = 2048) -> PyTree:
    """stacked_tree leaves (M, *s); perms (R, M) -> leaves (R*M, *s).

    `interpret=None` derives from the backend (compile natively on TPU,
    interpret elsewhere).
    """
    if interpret is None:
        interpret = default_interpret()
    r, m = perms.shape

    def one(leaf: jax.Array) -> jax.Array:
        flat = leaf.reshape(m, -1)
        d = flat.shape[1]
        if not use_kernel or d < block_d:
            out = prefix_avg_ref(flat, perms, n_k)
        else:
            padded = pad_to(flat, block_d)
            out = prefix_avg_kernel(padded, perms, n_k,
                                    block_d=block_d, interpret=interpret)
            out = out[:, :d]
        return out.reshape((r * m,) + leaf.shape[1:])

    return jax.tree.map(one, stacked_tree)
