"""Stage tracing: profiler annotation, compile-time split, live tap.

Three tools, all bit-neutral by construction (DESIGN.md §15):

  * `stage(name)` / `named_stage(name)` — stage annotation.  `stage` is a
    host-side `jax.profiler.TraceAnnotation` (a named span on the
    profiler timeline, on the device trace's clock) at each host layer
    boundary of a run: `setup` (setup_run), `operands` (building and
    placing a dispatch's operands), `scan` / `segment` (the dispatch),
    `results` (reading the outputs back).  `named_stage` is the in-trace
    `jax.named_scope` (names the HLO ops of a region, so profiles of the
    fused scan attribute time to select/train/shapley/aggregate/eval
    instead of one opaque dispatch; `gather` and `codec` are sub-stages
    inside `train`).  Both are pure metadata.

  * `CompileTimer` — attributes jit compilation via `jax.monitoring`
    duration events (`/jax/core/compile/...`: trace, MLIR lowering,
    backend compile).  A module-level listener fans durations into every
    active timer, so `FLResult.wall_time_s` can be split into
    compile vs execute without re-dispatching or AOT double-compiles.
    Warm executables emit no events, so a cached run reports ~0 compile.

  * the live tap — an *opt-in* `jax.debug.callback` planted in the scan
    body (`ScanSpec.live_tap`, round_engine.py) that streams `round_tap`
    events while the one-dispatch scan is still executing.  The host side
    here is a process-global sink set around the dispatch
    (`live_sink(...)`); the callback routes to it.  Caveats (§15): the
    tap recompiles the scan (callbacks are part of the trace), events may
    arrive out of round order (`ordered=False`), and under the replica
    vmap the callback fires per replica WITHOUT a cell index — per-cell
    attribution is the job of the host-side segment-boundary aggregation,
    the tap is a liveness/diagnostics stream.  It must stay bit-neutral;
    tests/test_telemetry.py pins selections/params/evals across
    off / host-side / live-tap.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Iterator

import jax

# stage names used by the engines; kernels/profiles key off these
STAGES = ("select", "train", "shapley", "aggregate", "eval")

# prefix every host/trace span carries; profile.py recovers per-stage
# wall time by summing spans with this prefix out of a capture window
SPAN_PREFIX = "repro."


def stage(name: str) -> jax.profiler.TraceAnnotation:
    """Host-side profiler span `repro.<name>` around a host layer or a
    dispatch: one `TraceAnnotation`, on the device trace's clock."""
    return jax.profiler.TraceAnnotation(f"{SPAN_PREFIX}{name}")


def named_stage(name: str):
    """In-trace scope: names the HLO of a region (zero-cost metadata)."""
    return jax.named_scope(f"repro.{name}")


# ---- compile-time attribution (jax.monitoring) ---------------------------

_COMPILE_EVENT_PREFIX = "/jax/core/compile"
_active_timers: list["CompileTimer"] = []
_listener_lock = threading.Lock()
_listener_registered = False


def _on_duration(key: str, seconds: float, **_kw) -> None:
    if key.startswith(_COMPILE_EVENT_PREFIX):
        for t in _active_timers:
            t.seconds += seconds


def _ensure_listener() -> None:
    global _listener_registered
    with _listener_lock:
        if not _listener_registered:
            try:
                jax.monitoring.register_event_duration_secs_listener(
                    _on_duration)
            except AttributeError:   # very old jax: no monitoring API
                pass
            _listener_registered = True


class CompileTimer:
    """Accumulates jit trace+lower+compile seconds while active.

    Re-enterable: one timer may wrap several regions of the same run
    (setup, then the dispatch), accumulating into `.seconds`.  Nesting
    two different timers double-counts nothing per timer — each active
    timer sees every compile in its own window, which is exactly the
    "how much of THIS run's wall time was compilation" question.
    """

    def __init__(self) -> None:
        self.seconds = 0.0

    def __enter__(self) -> "CompileTimer":
        _ensure_listener()
        _active_timers.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _active_timers.remove(self)


# ---- the in-scan live tap ------------------------------------------------

_live_sink = None


@contextlib.contextmanager
def live_sink(telemetry) -> Iterator[None]:
    """Route `round_tap` callbacks to `telemetry` for the enclosed
    dispatch.  The caller must block on the dispatch's outputs before
    leaving the context so in-flight callbacks have landed."""
    global _live_sink
    prev = _live_sink
    _live_sink = telemetry
    try:
        yield
    finally:
        _live_sink = prev


def round_tap(t, strategy_id, sel, sv, utility_evals, sv_truncated) -> None:
    """The `jax.debug.callback` target planted by `ScanSpec.live_tap`.

    Fires once per round (per replica under the grid vmap) with that
    round's device values; a no-op unless a sink is installed, so a
    tap-compiled executable is safe to reuse without telemetry.
    """
    tel = _live_sink
    if tel is None:
        return
    tel.emit("round_tap", round=t, origin="device",
             strategy_id=strategy_id, selections=sel, sv=sv,
             utility_evals=utility_evals, sv_truncated=sv_truncated)


def attach_live_tap(t, strategy_id, sel, sv, utility_evals,
                    sv_truncated) -> None:
    """Plant the tap in a traced scan body (round_engine calls this)."""
    jax.debug.callback(round_tap, t, strategy_id, sel, sv, utility_evals,
                       sv_truncated, ordered=False)
