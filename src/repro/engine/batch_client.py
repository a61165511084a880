"""Batched ClientUpdate — the cohort of M selected clients as ONE program.

The legacy server loop dispatches `client_update` M times per round from
Python; every dispatch pays host-side overhead and XLA sees M disjoint
programs it cannot fuse.  Client datasets are already padded and stacked as
`(N, cap, ...)` (see `server._pad_clients`), so the natural execution is:
gather the selected rows with one `take`, then `vmap` the shared local-SGD
step over the cohort axis.  XLA fuses the M local trainings into batched
matmuls; on a mesh the cohort axis shards over "data" (DESIGN.md §6).

Key-derivation parity: `cohort_update` splits the round key exactly like the
legacy loop (`split(round_key, M+1)`, client i takes key i, the Shapley pass
takes the last) so loop and batched engines are bit-compatible per client.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.federated.client import ClientConfig, client_update
from repro.kernels.cohort_gather import cohort_take
from repro.models.mlp_cnn import ClassifierModel
from repro.telemetry.trace import named_stage

PyTree = Any


def batched_client_update(
    model: ClassifierModel,
    ccfg: ClientConfig,
    params: PyTree,          # replicated server model w^t
    xs: jax.Array,           # (M, cap, ...) cohort padded data
    ys: jax.Array,           # (M, cap)
    n_valid: jax.Array,      # (M,)
    epochs_k: jax.Array,     # (M,) straggler/deadline-adjusted local epochs
    sigma_k: jax.Array,      # (M,) privacy noise levels
    keys: jax.Array,         # (M,) rng keys
) -> PyTree:
    """vmap of ClientUpdate over the cohort; leaves come back (M, *shape)."""
    return jax.vmap(
        lambda x, y, n, e, s, k: client_update(model, ccfg, params, x, y, n,
                                               e, s, k)
    )(xs, ys, n_valid, epochs_k, sigma_k, keys)


def cohort_update(
    model: ClassifierModel,
    ccfg: ClientConfig,
    params: PyTree,
    xs_all: jax.Array,       # (N, cap, ...) ALL clients' padded data
    ys_all: jax.Array,       # (N, cap)
    nv_all: jax.Array,       # (N,)
    sigma_all: jax.Array,    # (N,)
    sel: jax.Array,          # (M,) int selected client ids
    epochs_k: jax.Array,     # (M,)
    round_key: jax.Array,
    client_axis: str = None,
) -> tuple[PyTree, jax.Array, jax.Array]:
    """Gather the cohort out of the full stacks and train it in one vmap.

    Returns (stacked updates, n_k of the cohort, shapley key).  Designed to
    be traced inside the fused `round_step` (and vmapped over seeds), so the
    gather happens on-device — no host round-trip per client.

    With `client_axis` set the `*_all` stacks are this shard's local
    blocks of client-axis-sharded arrays (DESIGN.md §16) and the gather
    goes cross-shard through `cohort_take`; `sel` stays global.  Either
    way the gathered cohort is bitwise the dense `jnp.take` result.
    """
    m = sel.shape[0]
    ckeys = jax.random.split(round_key, m + 1)
    # its own scope (a sub-stage, inside the caller's `repro.train`), so a
    # device profile tells the gather's time from local SGD's
    with named_stage("gather"):
        xs = cohort_take(xs_all, sel, axis_name=client_axis)
        ys = cohort_take(ys_all, sel, axis_name=client_axis)
        nv = cohort_take(nv_all, sel, axis_name=client_axis)
        sg = cohort_take(sigma_all, sel, axis_name=client_axis)
    stacked = batched_client_update(model, ccfg, params, xs, ys, nv,
                                    epochs_k, sg, ckeys[:m])
    return stacked, nv.astype(jnp.float32), ckeys[m]


@partial(jax.jit, static_argnames=("model", "ccfg"))
def jit_batched_client_update(model, ccfg, params, xs, ys, n_valid, epochs_k,
                              sigma_k, keys):
    """Standalone jitted entry point (benchmarks / interactive use)."""
    return batched_client_update(model, ccfg, params, xs, ys, n_valid,
                                 epochs_k, sigma_k, keys)
