"""run_grid — the experiment-grid executor (DESIGN.md §12).

Pipeline: validate the GridSpec -> set up every cell (same rng/key
streams as a solo run at that cell's config) -> partition cells by
capability -> per partition, stack the replica operands, place them on
the replica mesh, and drive the segmented scan -> rebuild per-cell
FLResults and re-interleave them into grid order.
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import tree_stack
from repro.core.selection_jax import poc_d_schedule
from repro.engine.round_engine import SegmentCarry
from repro.engine.schedule import eval_mask
from repro.grid.partition import (
    Partition, PartitionReport, interleave, partition_cells,
)
from repro.grid.segments import ReplicaBatch, run_segments, segment_plan
from repro.grid.spec import CellFailure, GridResult, GridSpec


def _pad_cap(arr: np.ndarray, cap: int) -> np.ndarray:
    """Zero-pad axis 1 (per-client capacity) of (N, cap_i, ...) to `cap`."""
    if arr.shape[1] == cap:
        return arr
    widths = [(0, 0), (0, cap - arr.shape[1])] + [(0, 0)] * (arr.ndim - 2)
    return np.pad(arr, widths)


def _build_batch(part: Partition, cfgs, setups, sel_specs,
                 rounds: int) -> ReplicaBatch:
    """Stack one partition's cells along a leading replica axis.  Replicas
    may have different per-client capacities (each seed re-partitions its
    data); stacks pad to the partition max — padding is never read because
    minibatch indices are sampled below each client's n_valid."""
    from repro.engine.scan_engine import build_epochs_table, build_fault_table

    idxs = part.cell_indices
    sub = [setups[i] for i in idxs]
    cap = max(int(s.xs.shape[1]) for s in sub)
    stack = np.stack
    return ReplicaBatch(
        carry=SegmentCarry(
            params=tree_stack([s.params for s in sub]),
            sel_state=tree_stack([s.sel_state for s in sub]),
            key=jnp.stack([s.key for s in sub]),
            eval_slot=jnp.zeros((len(sub),), jnp.int32)),
        xs=jnp.asarray(stack([_pad_cap(np.asarray(s.xs), cap)
                              for s in sub])),
        ys=jnp.asarray(stack([_pad_cap(np.asarray(s.ys), cap)
                              for s in sub])),
        nv=jnp.asarray(stack([np.asarray(s.n_valid) for s in sub])),
        sigma=jnp.asarray(stack([s.sigma_k_all for s in sub])),
        x_val=jnp.asarray(stack([np.asarray(s.x_val) for s in sub])),
        y_val=jnp.asarray(stack([np.asarray(s.y_val) for s in sub])),
        x_test=jnp.asarray(stack([np.asarray(s.x_test) for s in sub])),
        y_test=jnp.asarray(stack([np.asarray(s.y_test) for s in sub])),
        fractions=jnp.asarray(stack([np.asarray(s.fractions, np.float32)
                                     for s in sub])),
        epochs_tables=jnp.asarray(stack([
            build_epochs_table(cfgs[i], setups[i]) for i in idxs])),
        fault_tables=jnp.asarray(stack([
            build_fault_table(cfgs[i], setups[i]) for i in idxs])),
        d_scheds=jnp.asarray(stack([
            poc_d_schedule(sel_specs[i], rounds) for i in idxs])),
        eval_masks=jnp.asarray(stack([
            eval_mask(rounds, cfgs[i].eval_every) for i in idxs])),
        strategy_ids=jnp.asarray(part.strategy_ids, jnp.int32),
    )


# Revision of the segment-snapshot layout (the SegmentCarry pytree plus
# the stacked segment outputs saved next to it): bump whenever either
# structure changes so stale checkpoint dirs fail with an actionable
# version-skew error instead of an opaque structure mismatch from
# load_pytree.  1 = PR-3 (params, sel_state, key); 2 = + eval_slot
# (DESIGN.md §13); 3 = + per-round `granted` cohort sizes in the segment
# outputs (DESIGN.md §18); 4 = + per-round `quarantined` counts in the
# segment outputs (DESIGN.md §19).
CARRY_FORMAT = 4

# Revision of the cell -> partition assignment rule.  Folded into the
# checkpoint fingerprint because segment snapshots are tagged by
# partition index ("p0-seg0000.npz"): a partitioning change re-numbers
# the tags, so resuming across it would restore the wrong cells' state.
# 1 = capability pair; 2 = capability pair x upload_codec (§18).
PARTITION_REV = 2


def _check_fingerprint(checkpoint_dir: str, spec: GridSpec,
                       rounds_per_segment: int, resume: bool) -> None:
    """Refuse to resume another grid's checkpoints: segment snapshots are
    only distinguished by tree structure/shapes, so a config change that
    keeps shapes (seeds, knobs, a same-capability selector swap) would
    otherwise silently restore the previous experiment's results."""
    import hashlib
    import json
    import os

    fp = hashlib.sha256(repr(
        (spec.base, spec.cells, rounds_per_segment,
         PARTITION_REV)).encode()).hexdigest()
    path = os.path.join(checkpoint_dir, "grid.json")
    if os.path.exists(path):
        with open(path) as f:
            saved = json.load(f)
        if resume and saved.get("carry_format", 1) != CARRY_FORMAT:
            raise ValueError(
                f"checkpoint_dir {checkpoint_dir!r} holds segments in "
                f"carry format {saved.get('carry_format', 1)} but this "
                f"version writes format {CARRY_FORMAT} (the SegmentCarry "
                "layout changed); the snapshots cannot be resumed — "
                "point the run at a fresh directory")
        if resume and saved.get("fingerprint") != fp:
            raise ValueError(
                f"checkpoint_dir {checkpoint_dir!r} holds segments of a "
                "DIFFERENT grid (config fingerprint mismatch); point the "
                "run at a fresh directory or pass resume=False to "
                "overwrite")
    os.makedirs(checkpoint_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump({"fingerprint": fp, "carry_format": CARRY_FORMAT}, f)


def run_grid(spec: GridSpec, *, data=None, model=None,
             rounds_per_segment: int = 0,
             checkpoint_dir: Optional[str] = None, resume: bool = True,
             shard: bool = True, max_segments: Optional[int] = None,
             compile_stats: bool = False, telemetry=None,
             isolate_cells: bool = True, retries: int = 0,
             retry_backoff_s: float = 0.05) -> Optional[GridResult]:
    """Execute a grid.  Returns None if `max_segments` stopped the run
    before completion (the checkpoints on disk are the resume point).

    Graceful degradation (§19): with `isolate_cells=True` (default) a
    partition whose dispatch raises no longer kills the sweep — its
    cells come back as `CellFailure` entries (error + traceback payload,
    one `cell_failed` telemetry event per cell) in `GridResult.results`
    while every other partition completes normally.  Spec validation,
    the segment plan, and checkpoint fingerprint checks still raise
    up-front: those are caller errors, not cell failures.  `retries` /
    `retry_backoff_s` pass through to `run_segments` for transient
    per-segment retry before a partition is declared failed.

    * `rounds_per_segment=K` chains T/K dispatches of one compiled
      K-round segment per partition instead of a single whole-run scan —
      bit-identical results, checkpointable at every boundary.
    * `checkpoint_dir` snapshots each segment (carry + outputs); with
      `resume=True` a rerun restores the checkpointed prefix and only
      dispatches what is missing.
    * `shard=True` places the replica axis on a 1-D device mesh
      (repro.grid.shard) whenever >1 local device divides the partition's
      replica count; with one device it is the plain vmap path.  With
      `spec.base.clients_shards > 1` the mesh gains a client axis and the
      per-client state additionally shards over it (DESIGN.md §16) —
      batches are zero-padded to a shard multiple and results unpadded
      back, bit-identical to the dense grid.
    * `data` may be one dataset (shared by every cell) or a sequence with
      one dataset per cell (e.g. per-seed datasets of a benchmark table).
    * `telemetry` (repro.telemetry.Telemetry, default None = zero-cost)
      emits the grid's structured event stream (DESIGN.md §15): run_start
      with provenance, per-segment events + heartbeat (run_segments),
      per-cell `round_metrics`/`eval` unrolled at partition boundaries,
      checkpoint events, run_end.  Deliberately NOT part of GridSpec, so
      the checkpoint fingerprint — and resumability — are unaffected.
    """
    from repro.engine.scan_engine import make_scan_spec, results_from_scan
    from repro.federated.compression import codec_nbytes
    from repro.federated.server import setup_run
    from repro.grid.shard import (
        CLIENT_AXIS, make_run_mesh, pad_batch_clients, unpad_scan_output,
    )

    t_start = time.perf_counter()
    cfgs = spec.validate()
    segment_plan(spec.base.rounds, rounds_per_segment)  # fail fast
    if spec.base.clients_shards > 1 and not shard:
        raise ValueError("clients_shards > 1 requires shard=True (the "
                         "client axis lives on the run mesh)")
    # a per-cell sequence is a plain list/tuple; SynthDataset itself is a
    # NamedTuple (hence a tuple), so ``_fields`` distinguishes the two
    if isinstance(data, (list, tuple)) and not hasattr(data, "_fields"):
        if len(data) != len(cfgs):
            raise ValueError(f"got {len(data)} datasets for "
                             f"{len(cfgs)} grid cells")
        cell_data = list(data)
    else:
        cell_data = [data] * len(cfgs)
    from repro.telemetry.metrics import emit_scan_rounds, run_end_payload
    from repro.telemetry.profile import trace_capture
    from repro.telemetry.trace import stage

    # one capture window (telemetry.trace_dir) over the cells' set-ups
    # and every partition, so it holds the `repro.setup` spans too
    with trace_capture(telemetry, label="grid"):
        setups = [setup_run(c, d, model) for c, d in zip(cfgs, cell_data)]
        model = setups[0].model
        sel_specs = [s.sel_spec for s in setups]
        # the codec joins the partition key: it is jit-static inside the
        # round body, so each codec group gets its own executable
        # (DESIGN.md §18)
        partitions = partition_cells(sel_specs,
                                     [c.upload_codec for c in cfgs])

        if checkpoint_dir:
            _check_fingerprint(checkpoint_dir, spec, rounds_per_segment,
                               resume)

        if telemetry is not None:
            from repro.telemetry.events import provenance
            telemetry.emit(
                "run_start", run_id=telemetry.run_id, kind="grid",
                cells=len(cfgs), partitions=len(partitions),
                rounds=spec.base.rounds,
                rounds_per_segment=rounds_per_segment,
                checkpoint_dir=checkpoint_dir, provenance=provenance())

        per_partition: list = []
        reports: list = []
        n_segments = 1
        compile_s = 0.0
        peaks: list = []   # per-partition compiled peak bytes
        cards: list = []   # per-partition step cost cards (§17)
        for pi, part in enumerate(partitions):
            t_part = time.perf_counter()
            try:
                live = bool(telemetry is not None and telemetry.live_tap)
                mesh = (make_run_mesh(len(part.cell_indices),
                                      spec.base.clients_shards)
                        if shard else None)
                client_sharded = (mesh is not None
                                  and CLIENT_AXIS in mesh.axis_names)
                scan_spec = make_scan_spec(
                    cfgs[part.cell_indices[0]], part.specs, live_tap=live,
                    client_axis=CLIENT_AXIS if client_sharded
                    else None)._replace(
                        rounds_per_segment=rounds_per_segment)
                with stage("operands"):
                    batch = _build_batch(part, cfgs, setups, sel_specs,
                                         spec.base.rounds)
                    if client_sharded:
                        batch = pad_batch_clients(batch,
                                                  spec.base.clients_shards)
                if telemetry is not None:
                    telemetry.heartbeat(
                        f"partition {pi + 1}/{len(partitions)} "
                        f"({part.key.label}, "
                        f"{len(part.cell_indices)} cells)", force=True)
                out, report = run_segments(
                    model, cfgs[part.cell_indices[0]].client, scan_spec,
                    batch, checkpoint_dir=checkpoint_dir, tag=f"p{pi}-",
                    resume=resume, max_segments=max_segments, mesh=mesh,
                    compile_stats=compile_stats, telemetry=telemetry,
                    retries=retries, retry_backoff_s=retry_backoff_s)
                compile_s += report.compile_time_s
                peaks.append(report.peak_bytes)
                cards.append(report.cost_card)
                if out is None:
                    if telemetry is not None:
                        telemetry.heartbeat(
                            f"partition {pi + 1}: stopped at max_segments="
                            f"{max_segments} ({report.dispatches} "
                            "dispatched); checkpoints are the resume "
                            "point", force=True)
                    return None
                with stage("results"):
                    if client_sharded:
                        out = unpad_scan_output(out, spec.base.n_clients)
                    n_segments = report.n_segments
                    # the partition's cells ran fused: they share ITS
                    # duration (not the grid's running total, which would
                    # bill later partitions for earlier ones' work)
                    wall = time.perf_counter() - t_part
                    results = []
                    evals_total = 0
                    for j, idx in enumerate(part.cell_indices):
                        out_j = jax.tree.map(lambda x: x[j], out)
                        res = results_from_scan(
                            cfgs[idx], setups[idx], out_j, wall_time_s=wall,
                            seed=cfgs[idx].seed,
                            dispatches=report.n_segments,
                            uses_shapley=part.key.needs_sv,
                            compile_time_s=report.compile_time_s)
                        evals_total += res.shapley_evals
                        results.append(res)
                        if telemetry is not None:
                            emit_scan_rounds(
                                telemetry, out_j,
                                uses_shapley=part.key.needs_sv,
                                codec_bytes=codec_nbytes(
                                    cfgs[idx].upload_codec,
                                    setups[idx].params),
                                model_bytes=setups[idx].model_bytes,
                                emask=eval_mask(spec.base.rounds,
                                                cfgs[idx].eval_every),
                                cell=idx)
                per_partition.append(results)
                reports.append(PartitionReport(
                    label=part.key.label, cell_indices=part.cell_indices,
                    needs_sv=part.key.needs_sv,
                    uses_local_losses=part.key.uses_local_losses,
                    n_strategies=len(part.specs),
                    dispatches=report.dispatches,
                    shapley_evals=evals_total,
                    bytes_resident=report.bytes_resident,
                    flops_per_dispatch=report.flops_per_dispatch,
                    peak_bytes=report.peak_bytes,
                    upload_codec=part.key.upload_codec))
            except Exception as e:
                # cell isolation (§19): a raising partition degrades to
                # per-cell CellFailure entries instead of killing the
                # sweep.  KeyboardInterrupt (BaseException) still aborts.
                if not isolate_cells:
                    raise
                import traceback as _tb
                tb = _tb.format_exc()
                failures = []
                for idx in part.cell_indices:
                    if telemetry is not None:
                        telemetry.emit(
                            "cell_failed", cell=idx, error=repr(e),
                            selector=cfgs[idx].selector,
                            seed=cfgs[idx].seed, partition=part.key.label)
                    failures.append(CellFailure(
                        cell=idx, selector=cfgs[idx].selector,
                        seed=cfgs[idx].seed, partition=part.key.label,
                        error=repr(e), traceback=tb))
                per_partition.append(failures)
                reports.append(PartitionReport(
                    label=part.key.label, cell_indices=part.cell_indices,
                    needs_sv=part.key.needs_sv,
                    uses_local_losses=part.key.uses_local_losses,
                    n_strategies=len(part.specs), dispatches=0,
                    shapley_evals=0, bytes_resident=0,
                    upload_codec=part.key.upload_codec))
                if telemetry is not None:
                    telemetry.heartbeat(
                        f"partition {pi + 1}/{len(partitions)} FAILED "
                        f"({part.key.label}): {e!r} — "
                        f"{len(part.cell_indices)} cells degraded",
                        force=True)

    results = interleave(len(spec.cells), partitions, per_partition)
    wall = time.perf_counter() - t_start
    if telemetry is not None:
        accs = [r.final_acc for r in results if r.final_acc == r.final_acc]
        mem_fields = {}
        if any(p is not None for p in peaks):
            # compiled peak (per device) of the largest partition's step
            mem_fields["peak_bytes"] = max(
                p for p in peaks if p is not None)
        live_cards = [c for c in cards if c is not None]
        if live_cards:
            # the grid-level cost card is the heaviest partition's — the
            # executable whose peak bounds the run's memory footprint
            mem_fields["cost_card"] = max(
                live_cards, key=lambda c: c.get("peak_bytes") or 0)
        telemetry.emit("compile", seconds=compile_s,
                       program="grid_segments", **mem_fields)
        telemetry.emit("run_end", **run_end_payload(
            rounds=spec.base.rounds, wall_time_s=wall,
            compile_time_s=compile_s,
            final_acc=sum(accs) / len(accs) if accs else float("nan"),
            utility_evals=sum(r.shapley_evals for r in results),
            upload_bytes=sum(r.upload_bytes for r in results),
            download_bytes=sum(r.download_bytes for r in results),
            dispatches=sum(rep.dispatches for rep in reports)))
    return GridResult(
        spec=spec,
        results=results,
        partitions=reports,
        rounds_per_segment=rounds_per_segment,
        n_segments=n_segments,
        wall_time_s=wall)
