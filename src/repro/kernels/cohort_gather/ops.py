"""Public wrapper: pytree-aware sparse cohort gather, dense or sharded.

Two regimes behind one call:

  * dense (`axis_name=None`) — the whole (N, ...) stack is local.  On
    TPU (`use_kernel=None`) leaves of at least one BLOCK_D tile run the
    natively compiled Pallas kernel; off-TPU the interpreter would add
    pure overhead to a copy, and `jnp.take` IS the bitwise reference, so
    the ref is used.  Small leaves always take the ref, mirroring
    `prefix_avg`.

  * client-sharded (`axis_name="clients"`) — `arr` is this shard's
    (N/devices, ...) block inside a `shard_map` body and `ids` is the
    global replicated (M,) cohort.  Each shard gathers its local hits
    (clamped take + validity mask) and the rows are combined with a
    `psum` over the client axis.  Exactly one shard contributes each
    row, so the sum is exact — and float leaves are bit-exact too,
    because they are summed as same-width unsigned ints (bitcast, mask,
    psum, bitcast back), sidestepping float-add edge cases (-0.0, NaN
    payloads) that could break the sharded==dense bitwise contract.

Both regimes return bit-identical results to `jnp.take(arr, ids, 0)` on
the equivalent dense stack; the engines rely on that (DESIGN.md §16).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret, pad_to
from repro.kernels.cohort_gather.kernel import BLOCK_D, cohort_gather_kernel
from repro.kernels.cohort_gather.ref import cohort_gather_ref

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
                axis_name: Optional[str] = None,
                use_kernel: Optional[bool] = None,
                interpret: Optional[bool] = None,
                block_d: int = BLOCK_D) -> jax.Array:
    """Gather rows `ids` (M,) from `arr` (N, ...) -> (M, ...).

    With `axis_name` set, `arr` is the local (N/devices, ...) shard of a
    client-axis-sharded stack (see `_cross_shard_take`); otherwise the
    dense single-device gather.  `use_kernel=None` resolves to
    TPU-only (a copy gains nothing from the Pallas interpreter);
    `interpret=None` derives from the backend like the other kernels, so
    on TPU the kernel always runs compiled.
    """
    if axis_name is not None:
        return _cross_shard_take(arr, ids, axis_name)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = default_interpret()
    m = ids.shape[0]
    flat = arr.reshape(arr.shape[0], -1)
    d = flat.shape[1]
    if not use_kernel or d < block_d:
        out = cohort_gather_ref(flat, ids)
    else:
        padded = pad_to(flat, block_d)
        out = cohort_gather_kernel(padded, ids, block_d=block_d,
                                   interpret=interpret)
        out = out[:, :d]
    return out.reshape((m,) + arr.shape[1:])


def cohort_gather(tree: PyTree, ids: jax.Array, *,
                  axis_name: Optional[str] = None,
                  use_kernel: Optional[bool] = None,
                  interpret: Optional[bool] = None,
                  block_d: int = BLOCK_D) -> PyTree:
    """Pytree version: every (N, ...) leaf gathered to (M, ...)."""
    take = partial(cohort_take, ids=ids, axis_name=axis_name,
                   use_kernel=use_kernel, interpret=interpret,
                   block_d=block_d)
    return jax.tree.map(lambda leaf: take(leaf), tree)
