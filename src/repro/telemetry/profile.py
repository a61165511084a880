"""Device-cost profiling: per-executable cost cards + trace capture.

The PR-6 event stream records *when* things happened; this module records
*what they cost* (DESIGN.md §17).  Two tools:

  * `cost_card(jitted, *args)` — one AOT lower+compile, three probes
    unified (launch.compat `cost_analysis_of` / `memory_stats_of` plus
    the roofline terms of launch.roofline): compiled flops, bytes
    accessed, the XLA memory-analysis byte classes with derived
    `peak_bytes`, arithmetic intensity (flops / bytes accessed), and, on
    a TPU, the roofline split against that chip's published peaks
    (compute-bound vs memory-bound seconds; a CPU has no published peaks
    and its cards carry no roofline block; `cost_analysis()` runs on the
    post-SPMD module, so every figure is per-device).  `cached_cost_card`
    memoises by (executable, arg avals) — the engines call it on every
    run but a warm executable re-pays nothing, keeping the
    BENCH_telemetry host-overhead gate honest.
    Engines attach the card to their `compile` telemetry events, so the
    JSONL stream answers "which stage burns the flops/bytes" without a
    profiler in the loop.

  * `trace_capture(telemetry, label)` — the opt-in programmatic
    `jax.profiler.start_trace`/`stop_trace` window (`Telemetry(trace_dir=
    ...)`), opened before `setup_run` so it holds the host layers'
    spans too: artifacts land in `<trace_dir>/<run_id>/`, and on exit a
    `profile` event reports per-stage wall seconds recovered from the
    §15 `TraceAnnotation` spans in the capture's own trace.  The
    in-scan `named_scope` stages additionally name the HLO regions for
    device timelines (TPU); the capture window is how those profiles get
    collected.  Nested/concurrent captures degrade gracefully: if the
    profiler is already tracing, the event says `captured=False` with
    no walls instead of raising.

Everything here is observation-only: no extra device dispatches, and a
telemetry-off run never reaches this module.
"""
from __future__ import annotations

import contextlib
import glob
import os
from typing import Any, Iterator, Optional

import jax

from repro.launch.compat import aot_compile, cost_analysis_of, memory_stats_of
from repro.telemetry.trace import SPAN_PREFIX

from repro.launch.roofline import peaks_for


def cost_card_of_compiled(compiled) -> Optional[dict]:
    """Assemble a cost card from an already-compiled executable."""
    card: dict = dict(cost_analysis_of(compiled))
    mem = memory_stats_of(compiled)
    if mem:
        card.update(mem)
    if not card:
        return None
    flops = card.get("flops")
    bytes_acc = card.get("bytes_accessed")
    if flops is not None and bytes_acc:
        card["intensity_flops_per_byte"] = flops / bytes_acc
    device = jax.devices()[0]
    if device.platform != "cpu" and (flops is not None
                                     or bytes_acc is not None):
        peak = peaks_for(device.device_kind)
        compute_s = (flops or 0.0) / peak.flops
        memory_s = (bytes_acc or 0.0) / peak.hbm_bw
        card["roofline"] = {
            "device_kind": device.device_kind,
            "compute_s": compute_s,
            "memory_s": memory_s,
            "dominant": "compute" if compute_s >= memory_s else "memory",
            "ridge_intensity_flops_per_byte": peak.flops / peak.hbm_bw,
        }
    return card


def cost_card(jitted, *args, **kwargs) -> Optional[dict]:
    """One lower+compile, every cost probe: the per-executable cost card
    for `jitted` at these args (avals only — donated buffers are safe).
    None when the backend exposes no analysis at all."""
    compiled = aot_compile(jitted, *args, **kwargs)
    if compiled is None:
        return None
    return cost_card_of_compiled(compiled)


# (jitted, arg-aval signature) -> card.  Keys hold strong references,
# which is what we want: the engines' jitted callables are process-wide
# lru-cached anyway (round_engine), so entries are few and long-lived.
_CARD_CACHE: dict = {}


def _aval_sig(args, kwargs):
    leaves, treedef = jax.tree.flatten((args, kwargs))
    return (treedef, tuple(
        (leaf.shape, str(leaf.dtype)) if hasattr(leaf, "shape")
        and hasattr(leaf, "dtype") else repr(leaf) for leaf in leaves))


def cached_cost_card(jitted, *args, **kwargs) -> Optional[dict]:
    """`cost_card` memoised on (executable, arg shapes/dtypes).

    The AOT probe costs a fresh lower+compile on first sight of a shape;
    every later call (reruns, bench reps, further segments of the same
    grid) is a dict lookup.  A None result is cached too — a backend
    without analysis shouldn't re-pay the failed compile each round.
    """
    try:
        key = (jitted, _aval_sig(args, kwargs))
        hash(key)
    except TypeError:
        return cost_card(jitted, *args, **kwargs)
    if key not in _CARD_CACHE:
        _CARD_CACHE[key] = cost_card(jitted, *args, **kwargs)
    return _CARD_CACHE[key]


# ---- the capture window --------------------------------------------------

def stage_wall_from_trace(trace_dir: str) -> Optional[dict]:
    """Per-stage wall seconds from a profiler capture's own trace.

    `jax.profiler.stop_trace` writes `plugins/profile/<ts>/*.xplane.pb`;
    the §15 `TraceAnnotation` spans appear on its host planes as events
    named `repro.<stage>` with nanosecond durations.  Returns
    {stage: seconds} summed over all matching spans (newest capture under
    `trace_dir` wins), or None when no readable trace exists.  The
    Chrome-trace export beside it is not read: it keeps only the first
    million events, which the profiler's Python tracer can fill before a
    run's last span.  `named_scope` stages annotate device-op timelines
    instead and stay in the artifact for offline viewers.
    """
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    try:
        planes = ProfileData.from_file(paths[-1]).planes
        walls: dict[str, float] = {}
        for plane in planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        stage = ev.name[len(SPAN_PREFIX):]
                        walls[stage] = (walls.get(stage, 0.0)
                                        + ev.duration_ns / 1e9)
        return walls or None
    except Exception:
        return None


@contextlib.contextmanager
def trace_capture(telemetry, label: str = "run") -> Iterator[Any]:
    """Profiler capture window around a run's set-up and dispatches
    (opt-in).

    No-op (yields None) unless `telemetry` carries a `trace_dir`.  Active
    windows start `jax.profiler.start_trace` into the run_id-stamped
    directory, yield that directory, and on exit stop the trace and emit
    one `profile` event: where the artifacts are, whether the profiler
    captured, and per-stage wall seconds read from the capture (empty
    when it did not).  The caller must block on its dispatches inside
    the window (the engines do) so spans cover execution, not enqueue.
    """
    if telemetry is None or not getattr(telemetry, "trace_dir", None):
        yield None
        return
    tdir = os.path.join(telemetry.trace_dir, telemetry.run_id)
    started = False
    try:
        jax.profiler.start_trace(tdir)
        started = True
    except Exception:
        pass   # profiler already tracing / unavailable: nothing captured
    try:
        yield tdir
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception:
                started = False
        walls = stage_wall_from_trace(tdir) if started else None
        telemetry.emit("profile", trace_dir=tdir, label=label,
                       captured=started, stage_wall_s=walls or {})
