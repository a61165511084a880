"""Segmented execution: one compiled K-round segment, chained T/K times.

The whole-run scan returns only final state — a killed 400-round grid
restarts from zero (ROADMAP "checkpoint/restart of scan runs").  The
segment step (`round_engine.make_segment_step`) scans the SAME per-round
body for K = `rounds_per_segment` rounds and surfaces the carry (params,
selector state, rng key) to the host between dispatches, so:

  * execution stays O(1) dispatch per segment (T/K dispatches per run,
    ONE compiled executable reused across segments and across runs);
  * `checkpoint/ckpt.py` snapshots the carry — and the segment's stacked
    outputs — at every boundary;
  * a killed run resumes from the last complete segment bit-identically:
    the carry is the exact scan state, so selections, params, and the key
    stream continue as if never interrupted.

Chaining is bit-identical to the unsegmented scan because both scan the
same body over the same (t, epochs_row, d) sequence — segmentation only
changes where the host observes the carry.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.ckpt import (
    CheckpointCorruptError, load_carry, save_carry,
)
from repro.engine.round_engine import (
    ScanRunOutput, ScanSpec, SegmentCarry, jitted_segment_step,
)

PyTree = Any


class ReplicaBatch(NamedTuple):
    """A partition's replica-stacked scan operands (leading axis R)."""
    carry: SegmentCarry          # stacked params / selector state / keys
    xs: jax.Array                # (R, N, cap, ...)
    ys: jax.Array
    nv: jax.Array
    sigma: jax.Array
    x_val: jax.Array
    y_val: jax.Array
    x_test: jax.Array
    y_test: jax.Array
    fractions: jax.Array
    epochs_tables: jax.Array     # (R, T, N) int32
    fault_tables: jax.Array      # (R, T, N) int32 fault codes (§19)
    d_scheds: jax.Array          # (R, T) int32
    eval_masks: jax.Array        # (R, T) bool per-replica eval cadences
    strategy_ids: jax.Array      # (R,) int32 index into the partition specs


class SegmentRunReport(NamedTuple):
    n_segments: int
    dispatches: int              # segments dispatched by THIS call
    resumed_segments: int        # segments restored from checkpoints
    bytes_resident: int
    flops_per_dispatch: float
    compile_time_s: float = 0.0  # jit trace+lower+compile in THIS call
    # XLA memory_analysis() peak of the compiled segment step (per device
    # under sharding); None unless compile_stats/telemetry asked for the
    # probe or the backend has no analysis
    peak_bytes: Optional[int] = None
    # the full per-executable cost card (telemetry.profile) of the
    # segment step — flops, bytes accessed, memory classes, roofline;
    # populated under the same gate as peak_bytes
    cost_card: Optional[dict] = None


def segment_plan(rounds: int, rounds_per_segment: int) -> tuple[int, int]:
    """(K, n_segments); K=0 means unsegmented.  K must divide T so every
    segment reuses the one compiled executable."""
    k = rounds_per_segment or rounds
    if k <= 0 or rounds % k != 0:
        raise ValueError(
            f"rounds_per_segment={rounds_per_segment} must divide "
            f"rounds={rounds} (one executable serves every segment)")
    return k, rounds // k


def batch_bytes(batch: ReplicaBatch) -> int:
    """Device-resident bytes of the stacked operands + carry."""
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(batch)
               if hasattr(x, "shape") and hasattr(x, "dtype"))


def _out_like(spec: ScanSpec, n_replicas: int, k_rounds: int) -> dict:
    m = spec.selectors[0].m
    r, k = n_replicas, k_rounds
    return {
        "selections": np.zeros((r, k, m), np.int32),
        "epochs": np.zeros((r, k, m), np.int32),
        "sv": np.zeros((r, k, m), np.float32),
        "utility_evals": np.zeros((r, k), np.int32),
        "sv_truncated": np.zeros((r, k), bool),
        "test_acc": np.zeros((r, k), np.float32),
        "val_loss": np.zeros((r, k), np.float32),
        "granted": np.zeros((r, k), np.int32),
        "quarantined": np.zeros((r, k), np.int32),
    }


def _seg_path(checkpoint_dir: str, tag: str, seg: int) -> str:
    return os.path.join(checkpoint_dir, f"{tag}seg{seg:04d}.npz")


def saved_segments(checkpoint_dir: str, tag: str) -> int:
    """Length of the contiguous checkpointed-segment prefix on disk."""
    pat = re.compile(re.escape(tag) + r"seg(\d{4})\.npz$")
    have = set()
    for p in glob.glob(os.path.join(checkpoint_dir, f"{tag}seg*.npz")):
        mt = pat.search(os.path.basename(p))
        if mt:
            have.add(int(mt.group(1)))
    n = 0
    while n in have:
        n += 1
    return n


def _to_out_dict(out) -> dict:
    return {
        "selections": out.selections, "epochs": out.epochs, "sv": out.sv,
        "utility_evals": out.utility_evals,
        "sv_truncated": out.sv_truncated,
        "test_acc": out.test_acc, "val_loss": out.val_loss,
        "granted": out.granted, "quarantined": out.quarantined,
    }


def run_segments(model, ccfg, spec: ScanSpec, batch: ReplicaBatch, *,
                 checkpoint_dir: Optional[str] = None, tag: str = "",
                 resume: bool = True, max_segments: Optional[int] = None,
                 mesh=None, compile_stats: bool = False, telemetry=None,
                 retries: int = 0, retry_backoff_s: float = 0.05
                 ) -> tuple[Optional[ScanRunOutput], SegmentRunReport]:
    """Drive one partition's replica batch through all T/K segments.

    Returns (ScanRunOutput, report); the output is None when
    `max_segments` stopped the run early (the checkpoint prefix on disk
    is then the resume point — used by the kill/restart tests and by any
    externally killed run).

    Hardened resume (§19): a checkpoint that fails integrity checks
    (truncated write, digest mismatch) is treated as absent — the run
    falls back to the last intact segment boundary, emits a
    `checkpoint_corrupt` event, and recomputes forward (overwriting the
    bad file at the next boundary).  `retries` > 0 additionally retries
    a raising segment dispatch up to that many times with exponential
    backoff (`retry_backoff_s` doubling per attempt), emitting a
    `segment_retry` event per attempt — transient executor failures
    (preempted device, flaky interconnect) stop killing 400-round runs.

    `telemetry` (default None: zero extra dispatches, async dispatch
    chain untouched) emits `segment_start`/`segment_end` events with the
    aggregate gauges of `metrics.segment_counters`, checkpoint events,
    and a throttled per-segment heartbeat with an ETA from the mean
    dispatched-segment time plus the compiled per-device peak bytes, so
    a long grid surfaces memory pressure without opening the JSONL.
    Per-segment timing blocks on the segment's outputs — observed
    segments are timed honestly instead of billing a segment for its
    predecessors' async queue.  With a sink attached the first
    dispatched segment also emits a `compile` event carrying the step's
    cost card (telemetry.profile — an AOT probe, cached per executable,
    zero extra dispatches).
    """
    import time

    from repro.telemetry.metrics import segment_counters
    from repro.telemetry.profile import cached_cost_card
    from repro.telemetry.trace import CompileTimer, live_sink, stage

    k_rounds, n_segments = segment_plan(spec.rounds,
                                        spec.rounds_per_segment)
    n_replicas = int(batch.strategy_ids.shape[0])
    seg_spec = spec._replace(rounds_per_segment=k_rounds)
    ctimer = CompileTimer()
    live = bool(telemetry is not None and telemetry.live_tap)

    with ctimer:
        if mesh is not None:
            from repro.grid.shard import sharded_segment_step
            step = sharded_segment_step(model, ccfg, seg_spec, mesh)
        else:
            step = jitted_segment_step(model, ccfg, seg_spec, vmapped=True)

    carry = batch.carry
    operands = (batch.xs, batch.ys, batch.nv, batch.sigma, batch.x_val,
                batch.y_val, batch.x_test, batch.y_test, batch.fractions)
    # the in-scan eval cond fires where ANY replica's mask is set; the OR
    # row stays unbatched under the vmap so the cond remains a real branch
    eval_any = jnp.asarray(np.asarray(batch.eval_masks).any(axis=0))

    # ---- resume: restore the contiguous checkpointed prefix --------------
    outs: list[dict] = []
    start = 0
    out_like = _out_like(seg_spec, n_replicas, k_rounds)
    if checkpoint_dir and resume:
        limit = min(saved_segments(checkpoint_dir, tag), n_segments)
        start = limit
        for seg in range(limit):
            path = _seg_path(checkpoint_dir, tag, seg)
            try:
                snap = load_carry(path, {"carry": carry, "out": out_like},
                                  telemetry=telemetry)
            except CheckpointCorruptError as e:
                # fall back to the last intact boundary; the rounds from
                # here on are recomputed (bit-identical — same carry,
                # same tables) and the bad file overwritten on the way
                if telemetry is not None:
                    telemetry.emit("checkpoint_corrupt", path=path,
                                   segment=seg, tag=tag, error=str(e))
                start = seg
                break
            outs.append(snap["out"])
            carry = snap["carry"]

    flops = float("nan")
    peak_bytes = None
    card = None
    dispatched = 0
    seg_seconds: list[float] = []
    for seg in range(start, n_segments):
        if max_segments is not None and dispatched >= max_segments:
            return None, SegmentRunReport(
                n_segments, dispatched, start, batch_bytes(batch), flops,
                ctimer.seconds, peak_bytes, card)
        t0 = jnp.asarray(seg * k_rounds, jnp.int32)
        sl = slice(seg * k_rounds, (seg + 1) * k_rounds)
        args = (carry, t0, eval_any[sl], *operands,
                batch.epochs_tables[:, sl], batch.fault_tables[:, sl],
                batch.d_scheds[:, sl], batch.eval_masks[:, sl],
                batch.strategy_ids)
        if telemetry is not None:
            t_seg = time.perf_counter()
            telemetry.emit("segment_start", segment=seg,
                           t0=seg * k_rounds, rounds=k_rounds, tag=tag,
                           replicas=n_replicas)
        # the step donates the carry on TPU/GPU: a dispatch that fails
        # after launch has consumed it, so a retry re-dispatches a copy
        # taken before the launch, never the donated buffers
        backup = jax.tree.map(jnp.copy, carry) if retries else None
        attempt = 0
        while True:
            try:
                with ctimer, live_sink(telemetry if live else None), \
                        stage("segment"):
                    out = step(*args)
                    if telemetry is not None or retries:
                        # taps must land (and the segment be timed) before
                        # the next dispatch is enqueued; under retry, force
                        # async dispatch errors to surface HERE
                        jax.block_until_ready(out.carry.params)
                break
            except Exception:
                # KeyboardInterrupt is BaseException — never swallowed
                if attempt >= retries:
                    raise
                attempt += 1
                if telemetry is not None:
                    telemetry.emit("segment_retry", segment=seg,
                                   attempt=attempt, tag=tag)
                time.sleep(retry_backoff_s * (2 ** (attempt - 1)))
                args = (jax.tree.map(jnp.copy, backup),) + args[1:]
        if (compile_stats or telemetry is not None) and seg == start:
            # the step's cost card (one cached AOT probe, §17): flops,
            # bytes, per-device peak memory, roofline terms
            # out.carry stands in for the donated input carry: same avals
            card = cached_cost_card(step, out.carry, *args[1:])
            if card is not None:
                flops = card.get("flops", float("nan"))
                peak_bytes = card.get("peak_bytes")
            if telemetry is not None:
                telemetry.emit("compile", seconds=ctimer.seconds,
                               program=f"segment_step:{tag or 'solo'}",
                               cost_card=card)
        carry = out.carry
        dispatched += 1
        if telemetry is not None:
            secs = time.perf_counter() - t_seg
            seg_seconds.append(secs)
            telemetry.emit("segment_end", segment=seg, tag=tag,
                           **segment_counters(out, secs))
            mean_s = sum(seg_seconds) / len(seg_seconds)
            eta_s = mean_s * (n_segments - seg - 1)
            peak_txt = ("" if peak_bytes is None
                        else f" peak {peak_bytes / 1e6:.0f}MB/dev")
            telemetry.heartbeat(
                f"{tag or 'seg'} {seg + 1}/{n_segments} "
                f"({k_rounds} rounds x {n_replicas} replicas, "
                f"{secs:.2f}s) eta {eta_s:.0f}s{peak_txt}")
        if checkpoint_dir:
            save_carry(_seg_path(checkpoint_dir, tag, seg),
                       {"carry": out.carry, "out": _to_out_dict(out)},
                       telemetry=telemetry)
        outs.append(_to_out_dict(out))

    stacked = {k: jnp.concatenate([o[k] for o in outs], axis=1)
               for k in outs[0]}
    result = ScanRunOutput(
        params=carry.params, sel_state=carry.sel_state,
        selections=stacked["selections"], epochs=stacked["epochs"],
        sv=stacked["sv"], utility_evals=stacked["utility_evals"],
        sv_truncated=stacked["sv_truncated"],
        test_acc=stacked["test_acc"], val_loss=stacked["val_loss"],
        granted=stacked["granted"], quarantined=stacked["quarantined"],
        eval_count=carry.eval_slot)
    report = SegmentRunReport(n_segments, dispatched, start,
                              batch_bytes(batch), flops, ctimer.seconds,
                              peak_bytes, card)
    return result, report
