"""Sharded replicas + sharded clients: the grid on a 2-D run mesh.

One scan program holds the full (R, N, cap, ...) client stacks plus the
(R, T, M) outputs resident; replica batches multiply the PR-2 footprint,
so "millions of users" grids need memory that scales with
replicas / n_devices (ROADMAP "scan memory at paper scale").  Two mesh
axes split that footprint (DESIGN.md §12, §16):

  * `REPLICA_AXIS` — replicas are embarrassingly parallel: every operand
    of the vmapped segment step carries a leading replica axis and
    replicas never communicate, so a sharding-annotated jit over the
    replica axis partitions everything with no collectives — the
    executable is the same segment program placed `n_devices` times.
    Only `t0` (the shared global round offset) and `eval_any_seg` (the
    OR of the replicas' eval-mask rows, DESIGN.md §13) stay replicated,
    which also keeps the in-scan eval cond a real branch.

  * `CLIENT_AXIS` — the population axis: the (R, N, cap, ...) data
    stacks, per-client schedule tables, and the per-client selector-state
    vectors additionally shard their N axis (padded to a multiple of the
    shard count by `pad_batch_clients`), making per-device client memory
    O(N / clients_shards).  Clients DO communicate — selection is a
    global top-m and the cohort is gathered across shards — so this path
    is an explicit `shard_map`: the selector state is all-gathered to its
    exact (N,) form per round and the cohort rows combine via the
    bitcast-psum gather in `repro.kernels.cohort_gather`.  Sharded and
    dense runs are bit-identical by construction (gathers copy bits; the
    strategies run on the same (N,) state either way), pinned by
    tests/test_client_sharding.py.

CI validates both paths on the forced-host 8-device debug mesh
(tests/test_grid.py, tests/test_client_sharding.py, subprocess — the
main pytest process must keep seeing one CPU device).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.selection_jax import DeviceSelectorState
from repro.core.valuation import ValuationState
from repro.engine.round_engine import (
    ScanSpec, SegmentCarry, SegmentOutput, make_segment_step,
)
from repro.launch.mesh import (  # re-export
    CLIENT_AXIS, REPLICA_AXIS, make_replica_mesh, make_run_mesh,
)

__all__ = ["CLIENT_AXIS", "REPLICA_AXIS", "make_replica_mesh",
           "make_run_mesh", "sharded_segment_step", "clients_padded",
           "pad_batch_clients", "unpad_scan_output"]


@functools.lru_cache(maxsize=8)
def _sharded_segment_step_cached(model, ccfg, spec: ScanSpec, mesh):
    fn = jax.vmap(make_segment_step(model, ccfg, spec),
                  in_axes=(0, None, None) + (0,) * 14)
    rep = NamedSharding(mesh, P(REPLICA_AXIS))   # leading-axis shard …
    full = NamedSharding(mesh, P())              # … t0 / eval_any replicated
    # pytree-prefix shardings: one leaf sharding covers a whole operand
    # subtree (carry pytree included)
    in_shardings = (rep, full, full) + (rep,) * 14
    return jax.jit(fn, in_shardings=in_shardings, out_shardings=rep)


def _carry_specs():
    """PartitionSpec pytree of a replica-stacked SegmentCarry on the 2-D
    mesh: params/key/eval_slot shard only over replicas; the per-client
    selector-state vectors ((R, N_pad) leaves) also shard over clients;
    scalar selector fields ((R,) round/frozen) stay client-replicated."""
    rep = P(REPLICA_AXIS)
    rc = P(REPLICA_AXIS, CLIENT_AXIS)
    return SegmentCarry(
        params=rep,
        sel_state=DeviceSelectorState(
            valuation=ValuationState(sv=rc, counts=rc, initialised=rc),
            round=rep, rr_order=rc, active=rc, frozen=rep),
        key=rep, eval_slot=rep)


@functools.lru_cache(maxsize=8)
def _client_sharded_step_cached(model, ccfg, spec: ScanSpec, mesh):
    # the scan body only emits the cross-shard collectives when the spec
    # names the client axis — a mismatch would deadlock or miscompute
    assert spec.round.client_axis == CLIENT_AXIS, spec.round.client_axis
    fn = jax.vmap(make_segment_step(model, ccfg, spec),
                  in_axes=(0, None, None) + (0,) * 14)
    rep = P(REPLICA_AXIS)
    rc = P(REPLICA_AXIS, CLIENT_AXIS)
    carry = _carry_specs()
    # operands after carry: t0, eval_any_seg, xs, ys, nv, sigma, x_val,
    # y_val, x_test, y_test, fractions, epochs_tables, fault_tables,
    # d_scheds, eval_masks, strategy_ids.  fractions stays replicated
    # (exact (N,) vector, read whole by selection); the epochs and fault
    # tables shard their trailing client axis.
    in_specs = (carry, P(), P(), rc, rc, rc, rc, rep, rep, rep, rep, rep,
                P(REPLICA_AXIS, None, CLIENT_AXIS),
                P(REPLICA_AXIS, None, CLIENT_AXIS), rep, rep, rep)
    out_specs = SegmentOutput(carry=carry, selections=rep, epochs=rep,
                              sv=rep, utility_evals=rep, sv_truncated=rep,
                              test_acc=rep, val_loss=rep, granted=rep,
                              quarantined=rep)
    # check_vma=False: the round outputs ARE replicated over clients (the
    # psum-combined cohort is identical on every shard) but shard_map's
    # replication checker cannot prove it through the scan
    sm = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jax.jit(sm)


def sharded_segment_step(model, ccfg, spec: ScanSpec, mesh):
    """Compiled segment step for `mesh`: replica-sharded jit on a 1-D
    replica mesh, explicit shard_map when the mesh has a client axis of
    size > 1; cached like `jitted_segment_step` so all segments (and
    repeat runs) share one executable."""
    if CLIENT_AXIS in mesh.axis_names and mesh.shape[CLIENT_AXIS] > 1:
        return _client_sharded_step_cached(model, ccfg, spec, mesh)
    if mesh.shape[REPLICA_AXIS] <= 1:
        from repro.engine.round_engine import jitted_segment_step
        return jitted_segment_step(model, ccfg, spec, vmapped=True)
    return _sharded_segment_step_cached(model, ccfg, spec, mesh)


# --------------------------------------------------------------------------
# client-axis padding: N must divide the shard count, so batches are padded
# to N_pad = ceil(N / shards) * shards; pad rows are zeros that no path ever
# reads (selection slices the gathered state to exact N, gathers only touch
# real ids, and `put_back` keeps pad rows at their initial values)
# --------------------------------------------------------------------------

def clients_padded(n_clients: int, shards: int) -> int:
    """Smallest multiple of `shards` >= n_clients."""
    return -(-n_clients // shards) * shards


def _pad_axis(x, axis: int, target: int):
    pad = target - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def pad_batch_clients(batch, shards: int):
    """Zero-pad every client-axis array of a ReplicaBatch to a multiple of
    `shards`: data stacks (xs/ys/nv/sigma, axis 1), the epochs and fault
    tables (axis 2), and the (R, N) selector-state vectors.  Fractions
    and params are untouched (replicated, exact-N)."""
    n = batch.xs.shape[1]
    n_pad = clients_padded(n, shards)
    if n_pad == n:
        return batch
    sel_state = jax.tree.map(
        lambda x: _pad_axis(x, 1, n_pad) if x.ndim >= 2 else x,
        batch.carry.sel_state)
    return batch._replace(
        carry=batch.carry._replace(sel_state=sel_state),
        xs=_pad_axis(batch.xs, 1, n_pad),
        ys=_pad_axis(batch.ys, 1, n_pad),
        nv=_pad_axis(batch.nv, 1, n_pad),
        sigma=_pad_axis(batch.sigma, 1, n_pad),
        epochs_tables=_pad_axis(batch.epochs_tables, 2, n_pad),
        fault_tables=_pad_axis(batch.fault_tables, 2, n_pad))


def unpad_scan_output(out, n_clients: int):
    """Drop the pad rows from a ScanRunOutput's final selector state so
    downstream consumers (`results_from_scan`) see the exact (R, N)
    vectors a dense run would produce."""
    sel_state = jax.tree.map(
        lambda x: x[:, :n_clients] if x.ndim >= 2 else x,
        out.sel_state)
    return out._replace(sel_state=sel_state)
