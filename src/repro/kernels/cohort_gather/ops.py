"""Public wrapper: pytree-aware sparse cohort gather, dense or sharded.

Two regimes behind one call:

  * dense (`axis_name=None`) — the whole (N, ...) stack is local and the
    cohort is `jnp.take(arr, ids, axis=0)` on the stack's stored shape.
    XLA lowers it to one gather that reads and writes only the M
    selected rows: no reshape of the stack to (N, D), no pad to a tile,
    and under a replica `vmap` the replica axis folds into the gather's
    indices instead of a per-replica loop over the whole table
    (tests/test_tpu_compile.py checks the compiled v5e loop body).

  * client-sharded (`axis_name="clients"`) — `arr` is this shard's
    (N/devices, ...) block inside a `shard_map` body and `ids` is the
    global replicated (M,) cohort.  Each shard gathers its local hits
    (clamped take + validity mask) and the rows are combined with a
    `psum` over the client axis.  Exactly one shard contributes each
    row, so the sum is exact — and float leaves are bit-exact too,
    because they are summed as same-width unsigned ints (bitcast, mask,
    psum, bitcast back), sidestepping float-add edge cases (-0.0, NaN
    payloads) that could break the sharded==dense bitwise contract.

A gather copies bits — no arithmetic, no accumulation order — so both
regimes return bit-identical results to `jnp.take(arr, ids, 0)` on the
equivalent dense stack; the engines rely on that (DESIGN.md §16).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

PyTree = Any


def _cross_shard_take(arr: jax.Array, ids: jax.Array,
                      axis_name: str) -> jax.Array:
    """Gather global rows `ids` out of this shard's local block of a
    client-axis-sharded (N, ...) stack; call inside a shard_map body."""
    n_local = arr.shape[0]
    lo = jax.lax.axis_index(axis_name) * n_local
    loc = ids - lo
    valid = (loc >= 0) & (loc < n_local)
    rows = jnp.take(arr, jnp.clip(loc, 0, n_local - 1), axis=0)
    mask = valid.reshape((-1,) + (1,) * (arr.ndim - 1))
    if jnp.issubdtype(arr.dtype, jnp.floating):
        # sum the bits, not the floats: integer adds of one-hot nonzero
        # contributions are exact, so sharded == dense stays bitwise
        uint = jnp.dtype(f"uint{arr.dtype.itemsize * 8}")
        bits = jax.lax.bitcast_convert_type(rows, uint)
        bits = jnp.where(mask, bits, jnp.zeros_like(bits))
        summed = jax.lax.psum(bits, axis_name)
        return jax.lax.bitcast_convert_type(summed, arr.dtype)
    rows = jnp.where(mask, rows, jnp.zeros_like(rows))
    return jax.lax.psum(rows, axis_name)


def cohort_take(arr: jax.Array, ids: jax.Array, *,
                axis_name: Optional[str] = None) -> jax.Array:
    """Gather rows `ids` (M,) from `arr` (N, ...) -> (M, ...).

    With `axis_name` set, `arr` is the local (N/devices, ...) shard of a
    client-axis-sharded stack (see `_cross_shard_take`); otherwise the
    dense gather on the stored shape.
    """
    if axis_name is not None:
        return _cross_shard_take(arr, ids, axis_name)
    return jnp.take(arr, ids, axis=0)


def cohort_gather(tree: PyTree, ids: jax.Array, *,
                  axis_name: Optional[str] = None) -> PyTree:
    """Pytree version: every (N, ...) leaf gathered to (M, ...)."""
    return jax.tree.map(partial(cohort_take, ids=ids, axis_name=axis_name),
                        tree)
