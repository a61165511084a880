"""engine="scan": a whole federated run as ONE compiled program.

The batched engine (round_engine.RoundEngine) fused each round into a
single dispatch but left strategy logic on the host, so a T-round run
still pays T device→host→device syncs — selection reads the round's
Shapley values, so the chain cannot pipeline.  Here the device-resident
selector stack (repro.core.selection_jax) moves selection and valuation
into the trace and `make_run_scan` rolls the T rounds into one `lax.scan`:
the whole run — selection, straggler E_k gathers, local training, upload
codec, GTG-Shapley, ModelAverage, cumulative-SV updates, cadenced evals —
is a single dispatch (DESIGN.md §11).

This module is the host-side orchestration: it precomputes the run's
static tables (per-round epoch budgets, the Power-of-Choice candidate
schedule), invokes the cached executable, and rebuilds the usual FLResult
bookkeeping (byte accounting, virtual-clock replay, eval history) from
the scan's stacked outputs.

Parity contract: with deadline-derived or absent stragglers, an
`engine="scan"` run produces the same selections (bit-identical) and
final params (to jit-fusion tolerance) as `engine="batched"` at the same
seed — tests/test_engine.py pins greedyfed, fedavg, and power_of_choice.
With `straggler_frac > 0` the paper's random E_k draw cannot be replayed
on-device in the legacy stream order; the scan engine pre-draws a (T, N)
table instead (schedule.straggler_epochs_table) — same distribution,
different stream.
"""
from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.selection_jax import poc_d_schedule
from repro.engine.round_engine import RoundSpec, ScanSpec, jitted_run_scan
from repro.engine.schedule import (
    VirtualClock, deadline_epochs_table, eval_mask, round_duration_s,
    straggler_epochs_table,
)
from repro.federated.compression import codec_nbytes

PyTree = Any


def build_epochs_table(cfg, s) -> np.ndarray:
    """(T, N) int32 local-epoch budgets for every round of a scan run.

    At straggler_rev >= 1 the random-straggler table was already drawn by
    `setup_run` (same rng position, same values) and is shared with the
    loop/batched engines — all three are stream-identical.  The lazy draw
    below only serves the legacy straggler_rev=0 path."""
    e = cfg.client.epochs
    if s.clock is not None:
        return deadline_epochs_table(s.clock, cfg.schedule, cfg.rounds, e)
    if s.epochs_table is not None:
        return s.epochs_table
    if s.straggler_ids:
        return straggler_epochs_table(s.rng, cfg.rounds, cfg.n_clients,
                                      s.straggler_ids, e)
    return np.full((cfg.rounds, cfg.n_clients), e, np.int32)


def build_fault_table(cfg, s) -> np.ndarray:
    """(T, N) int32 fault codes for a scan run (§19); zeros when faults
    are off so the operand slot keeps one uniform signature per shape —
    the codes are dead operands in clean traces and get DCE'd."""
    if s.fault_table is not None:
        return np.asarray(s.fault_table, np.int32)
    return np.zeros((cfg.rounds, cfg.n_clients), np.int32)


def scan_operands(cfg, s) -> tuple:
    """The positional operands of a solo run's `jitted_run_scan` call,
    everything after the leading `params`: (xs, ..., sel_state, key).
    The single source of that call contract — `run_federated_scan` and
    `benchmarks/engine_bench._scan_steady_state` both build their calls
    from it, so an operand reorder cannot silently desynchronise them."""
    return (s.xs, s.ys, s.n_valid, jnp.asarray(s.sigma_k_all),
            s.x_val, s.y_val, s.x_test, s.y_test, jnp.asarray(s.fractions),
            jnp.asarray(build_epochs_table(cfg, s)),
            jnp.asarray(build_fault_table(cfg, s)),
            jnp.asarray(poc_d_schedule(s.sel_spec, cfg.rounds)),
            jnp.asarray(eval_mask(cfg.rounds, cfg.eval_every)),
            jnp.asarray(0, jnp.int32), s.sel_state, s.key)


def make_scan_spec(cfg, selector_specs: tuple, *, live_tap: bool = False,
                   client_axis: str = None) -> ScanSpec:
    """ScanSpec for an FLConfig; `selector_specs` may hold several
    strategies for a switch-dispatched mixed batch (superset semantics:
    SV is computed if ANY strategy needs it).  `live_tap` opts the trace
    into the in-scan telemetry callback (DESIGN.md §15); `client_axis`
    bakes the client-sharding collectives into the round trace
    (DESIGN.md §16 — set it iff the step runs inside the client-axis
    shard_map)."""
    needs_sv = any(sp.uses_shapley for sp in selector_specs)
    max_iters = cfg.shapley_max_iters or 50 * cfg.m
    rspec = RoundSpec(needs_sv=needs_sv, shapley_impl=cfg.shapley_impl,
                      shapley_eps=cfg.shapley_eps,
                      shapley_max_iters=max_iters,
                      sv_chunk=cfg.sv_chunk,
                      upload_codec=cfg.upload_codec,
                      client_axis=client_axis,
                      faults=cfg.faults, quarantine=cfg.quarantine,
                      quarantine_z=cfg.quarantine_z)
    # eval_every is NOT in the spec: the cadence is a (T,) bool operand
    # (schedule.eval_mask), so one executable serves every cadence
    return ScanSpec(round=rspec, selectors=tuple(selector_specs),
                    rounds=cfg.rounds, live_tap=live_tap)


def results_from_scan(cfg, s, out, *, wall_time_s: float, seed: int,
                      dispatches: int, uses_shapley: bool,
                      compile_time_s: float = 0.0):
    """Rebuild the host-side FLResult bookkeeping from a ScanRunOutput."""
    from repro.federated.server import FLConfig, FLResult  # cycle-free at call time
    import dataclasses

    sels = np.asarray(out.selections)
    epochs = np.asarray(out.epochs)
    selections = [row.astype(np.int64) for row in sels]

    # charge uploads at the ACTUAL granted-cohort size per round (dropout
    # strategies can grant fewer than m active clients), matching the
    # loop engine's per-selected-client accounting (replicated.py)
    # shapes only, read from the output: the solo scan donated s.params
    codec_bytes = codec_nbytes(cfg.upload_codec, out.params)
    upload_bytes = codec_bytes * int(np.asarray(out.granted).sum())
    download_bytes = s.model_bytes * cfg.m * cfg.rounds

    vclock = VirtualClock() if s.clock is not None else None
    if vclock is not None:
        for t in range(cfg.rounds):
            vclock.advance(round_duration_s(s.clock, cfg.schedule,
                                            sels[t], epochs[t]))

    acc = np.asarray(out.test_acc)
    vloss = np.asarray(out.val_loss)
    emask = eval_mask(cfg.rounds, cfg.eval_every)
    # the in-scan eval-slot counter (SegmentCarry.eval_slot) must agree
    # with the host-side mask the curve is rebuilt from — a mismatch means
    # the replica ran a different cadence than this cell's config says
    # (e.g. a mis-stacked eval table under the replica vmap)
    n_evals = int(np.asarray(out.eval_count))
    if n_evals != int(emask.sum()):
        raise RuntimeError(
            f"eval-slot counter recorded {n_evals} in-scan evals but the "
            f"cell's eval mask (rounds={cfg.rounds}, "
            f"eval_every={cfg.eval_every}) expects {int(emask.sum())}")
    test_acc, val_loss_hist = [], []
    for t in np.flatnonzero(emask):
        test_acc.append((int(t) + 1, float(acc[t])))
        val_loss_hist.append((int(t) + 1, float(vloss[t])))

    total_evals = int(np.asarray(out.utility_evals).sum()) if uses_shapley else 0
    final_cfg = cfg if cfg.seed == seed else dataclasses.replace(cfg, seed=seed)
    return FLResult(
        config=final_cfg,
        test_acc=test_acc,
        val_loss=val_loss_hist,
        final_acc=test_acc[-1][1] if test_acc else float("nan"),
        sv_final=np.asarray(out.sel_state.valuation.sv),
        selection_counts=np.asarray(out.sel_state.valuation.counts),
        selections=selections,
        shapley_evals=total_evals,
        wall_time_s=wall_time_s,
        params=out.params,
        upload_bytes=upload_bytes,
        download_bytes=download_bytes,
        sim_time_s=vclock.now_s if vclock is not None else 0.0,
        dispatches=dispatches,
        compile_time_s=compile_time_s,
        execute_time_s=max(wall_time_s - compile_time_s, 0.0),
        quarantined_total=int(np.asarray(out.quarantined).sum()),
    )


def _sharded_scan_batch(cfg, s, mesh):
    """The 1-replica ReplicaBatch of a client-sharded solo run.

    The data stacks from `setup_run(..., client_mesh=mesh)` are already
    (N_pad, ...) arrays sharded over CLIENT_AXIS; they gain their leading
    replica axis through a jit with explicit out_shardings — a local
    per-shard reshape, never a gather.  Host-side operands (sigma, the
    epochs tables, the initial selector state) are zero-padded to N_pad;
    fractions stays the exact (N,) vector (replicated, read whole by
    selection).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.engine.round_engine import SegmentCarry
    from repro.grid.segments import ReplicaBatch
    from repro.grid.shard import CLIENT_AXIS, clients_padded
    from repro.engine.schedule import eval_mask as emask_fn

    n_pad = clients_padded(cfg.n_clients, cfg.clients_shards)

    def pad_rows(a, axis=0):
        a = np.asarray(a)
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, n_pad - a.shape[axis])
        return np.pad(a, widths)

    expand = jax.jit(lambda a: a[None], out_shardings=NamedSharding(
        mesh, P(None, CLIENT_AXIS)))

    def rep1(a):
        return jnp.asarray(a)[None]

    sel_state = jax.tree.map(
        lambda x: jnp.asarray(pad_rows(x))[None] if x.ndim >= 1
        else jnp.asarray(x)[None], s.sel_state)
    carry = SegmentCarry(
        params=jax.tree.map(rep1, s.params), sel_state=sel_state,
        key=jnp.asarray(s.key)[None],
        eval_slot=jnp.zeros((1,), jnp.int32))
    return ReplicaBatch(
        carry=carry,
        xs=expand(s.xs), ys=expand(s.ys), nv=expand(s.n_valid),
        sigma=jnp.asarray(pad_rows(s.sigma_k_all))[None],
        x_val=rep1(s.x_val), y_val=rep1(s.y_val),
        x_test=rep1(s.x_test), y_test=rep1(s.y_test),
        fractions=jnp.asarray(s.fractions, jnp.float32)[None],
        epochs_tables=jnp.asarray(
            pad_rows(build_epochs_table(cfg, s), axis=1))[None],
        fault_tables=jnp.asarray(
            pad_rows(build_fault_table(cfg, s), axis=1))[None],
        d_scheds=jnp.asarray(poc_d_schedule(s.sel_spec, cfg.rounds))[None],
        eval_masks=jnp.asarray(emask_fn(cfg.rounds, cfg.eval_every))[None],
        strategy_ids=jnp.zeros((1,), jnp.int32))


def _run_scan_sharded(cfg, s, spec, t_start, *, telemetry, ctimer):
    """Client-sharded solo run: the one scan dispatch goes through the
    shard_map segment step on a (1, clients_shards) run mesh; outputs are
    unpadded + replica-squeezed back into the dense run's exact shapes.
    Bit-identical to the dense scan at equal config (DESIGN.md §16)."""
    from repro.grid.segments import run_segments
    from repro.grid.shard import make_run_mesh, unpad_scan_output
    from repro.telemetry.trace import stage

    spec_sel = s.sel_spec
    # deterministic rebuild of the mesh setup_run sharded the data on
    # (Mesh is hashable/comparable, so the step cache keys correctly)
    mesh = make_run_mesh(1, cfg.clients_shards)
    with ctimer, stage("operands"):
        batch = _sharded_scan_batch(cfg, s, mesh)
    out_b, report = run_segments(s.model, cfg.client, spec, batch,
                                 mesh=mesh, telemetry=telemetry)
    with stage("results"):
        out_b = unpad_scan_output(out_b, cfg.n_clients)
        out = jax.tree.map(lambda x: x[0], out_b)
        res = results_from_scan(cfg, s, out,
                                wall_time_s=time.perf_counter() - t_start,
                                seed=cfg.seed,
                                dispatches=report.n_segments,
                                uses_shapley=spec_sel.uses_shapley,
                                compile_time_s=(ctimer.seconds
                                                + report.compile_time_s))
    if telemetry is not None:
        from repro.telemetry.metrics import emit_scan_rounds, run_end_payload
        telemetry.emit("compile", seconds=res.compile_time_s,
                       program="run_scan_client_sharded",
                       cost_card=report.cost_card)
        emit_scan_rounds(
            telemetry, out, uses_shapley=spec_sel.uses_shapley,
            codec_bytes=codec_nbytes(cfg.upload_codec, s.params),
            model_bytes=s.model_bytes,
            emask=eval_mask(cfg.rounds, cfg.eval_every))
        telemetry.emit("run_end", **run_end_payload(
            rounds=cfg.rounds, wall_time_s=res.wall_time_s,
            compile_time_s=res.compile_time_s, final_acc=res.final_acc,
            utility_evals=res.shapley_evals,
            upload_bytes=res.upload_bytes, download_bytes=res.download_bytes,
            sv_rounds=cfg.rounds if spec_sel.uses_shapley else 0,
            truncated_rounds=int(np.asarray(out.sv_truncated).sum())
            if spec_sel.uses_shapley else 0,
            dispatches=report.n_segments))
    return res


def run_federated_scan(cfg, s, t_start: float, *, telemetry=None,
                       ctimer=None):
    """Execute `cfg.rounds` federated rounds as one scan dispatch.

    `s` is the RunSetup from `server.setup_run` — the rng/key streams it
    consumed match the other engines, so the scan starts from identical
    partitions, params, and selector order.

    With `cfg.clients_shards > 1` the dispatch routes through the
    client-sharded shard_map path (`_run_scan_sharded`, DESIGN.md §16);
    results are bit-identical to the dense run.

    `telemetry=None` is the zero-cost default: no extra dispatches, no
    in-trace callbacks, bit-identical outputs.  With a sink attached the
    stacked ScanRunOutput is unrolled into per-round events after the
    dispatch (host-side, §15), and the compile event carries the scan
    executable's cost card (§17); `telemetry.live_tap` additionally
    selects the tap-carrying executable and routes its in-scan
    callbacks; with `telemetry.trace_dir` (a capture window that
    `run_federated` opened) the dispatch is waited on inside its span.
    Host spans: `repro.operands`, `repro.scan`, `repro.results`.
    """
    from repro.telemetry.trace import CompileTimer, live_sink, stage

    spec_sel = s.sel_spec
    live = bool(telemetry is not None and telemetry.live_tap)
    if ctimer is None:
        ctimer = CompileTimer()
    if cfg.clients_shards > 1:
        from repro.launch.mesh import CLIENT_AXIS
        spec = make_scan_spec(cfg, (spec_sel,), live_tap=live,
                              client_axis=CLIENT_AXIS)
        return _run_scan_sharded(cfg, s, spec, t_start,
                                 telemetry=telemetry, ctimer=ctimer)
    spec = make_scan_spec(cfg, (spec_sel,), live_tap=live)
    capturing = bool(telemetry is not None and telemetry.trace_dir)

    with stage("operands"):
        operands = scan_operands(cfg, s)
    with ctimer:
        run = jitted_run_scan(s.model, cfg.client, spec)
        with live_sink(telemetry if live else None), stage("scan"):
            # s.params is donated on TPU/GPU: nothing below may read it;
            # out.params has the same avals where shapes are needed
            out = run(s.params, *operands)
            if live or capturing:
                # drain the in-scan debug callbacks before the sink
                # detaches — taps must land inside the run's stream —
                # and keep capture-window spans covering execution, not
                # just the dispatch enqueue
                jax.block_until_ready(out.params)

    with stage("results"):
        res = results_from_scan(cfg, s, out,
                                wall_time_s=time.perf_counter() - t_start,
                                seed=cfg.seed, dispatches=1,
                                uses_shapley=spec_sel.uses_shapley,
                                compile_time_s=ctimer.seconds)
    if telemetry is not None:
        from repro.telemetry.metrics import emit_scan_rounds, run_end_payload
        from repro.telemetry.profile import cached_cost_card
        telemetry.emit("compile", seconds=ctimer.seconds, program="run_scan",
                       cost_card=cached_cost_card(run, out.params, *operands))
        emit_scan_rounds(
            telemetry, out, uses_shapley=spec_sel.uses_shapley,
            codec_bytes=codec_nbytes(cfg.upload_codec, out.params),
            model_bytes=s.model_bytes,
            emask=eval_mask(cfg.rounds, cfg.eval_every))
        telemetry.emit("run_end", **run_end_payload(
            rounds=cfg.rounds, wall_time_s=res.wall_time_s,
            compile_time_s=res.compile_time_s, final_acc=res.final_acc,
            utility_evals=res.shapley_evals,
            upload_bytes=res.upload_bytes, download_bytes=res.download_bytes,
            sv_rounds=cfg.rounds if spec_sel.uses_shapley else 0,
            truncated_rounds=int(np.asarray(out.sv_truncated).sum())
            if spec_sel.uses_shapley else 0,
            dispatches=1))
    return res
