"""Schema-versioned structured telemetry: the JSONL event stream.

`Telemetry` is the one handle every engine takes (always as an optional
keyword defaulting to None — the telemetry-off path adds zero dispatches
and leaves every output bit-identical, DESIGN.md §15).  Events are
append-only JSONL records with a fixed envelope:

    {"v": 1, "seq": 0, "t_s": 0.000012, "event": "run_start", ...}

  * `v`    — the stream schema version (SCHEMA_VERSION); bump on any
             incompatible field change so downstream parsers can refuse
             streams they do not understand;
  * `seq`  — per-sink monotonic sequence number (gap-free, so a consumer
             can detect a truncated or interleaved stream);
  * `t_s`  — seconds since the sink was created (`time.perf_counter`
             based: monotonic, never wall-clock-adjusted).

Event types and their required payload fields are in `REQUIRED_FIELDS`;
`validate_events` is the pure-python contract checker (the satellite
test gate) — envelope present, types known, seq gap-free, and round
indices strictly increasing per (run, cell) for the host-authoritative
`round_metrics`/`eval` streams.  The device-originated `round_tap`
stream (trace.py) is exempt from ordering: `jax.debug.callback` makes no
cross-round ordering promise.

`provenance()`/`write_bench_json` stamp benchmark artifacts (BENCH_*.json)
with git rev, timestamp, backend, device count, and jax version so every
number on disk says where it came from.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import uuid
from typing import Any, IO, Optional

SCHEMA_VERSION = 1

# envelope fields every event carries (emitted by `Telemetry.emit`)
ENVELOPE_FIELDS = ("v", "seq", "t_s", "event")

# event type -> payload fields that MUST be present (beyond the envelope)
# `compile` events may additionally carry a `cost_card` (profile.py): the
# per-executable flops/bytes/peak-memory/roofline block; `profile` events
# close a jax.profiler capture window (trace dir + per-stage wall).
REQUIRED_FIELDS: dict[str, tuple] = {
    "run_start": ("run_id", "kind"),
    "compile": ("seconds",),
    "profile": ("trace_dir",),
    "segment_start": ("segment", "t0"),
    "segment_end": ("segment", "seconds"),
    "round_metrics": ("round", "selections", "epochs", "utility_evals",
                      "sv_truncated", "upload_bytes", "download_bytes"),
    "round_tap": ("round",),          # device-origin live tap (trace.py)
    "serve_step": ("step",),          # serving-tier decode steps
    "eval": ("round", "test_acc", "val_loss"),
    "checkpoint_save": ("path", "nbytes"),
    "checkpoint_load": ("path",),
    # §19 fault/robustness stream: corrupted checkpoints detected at
    # resume, bounded segment retries, and isolated grid-cell failures
    "checkpoint_corrupt": ("path",),
    "segment_retry": ("segment", "attempt"),
    "cell_failed": ("cell", "error"),
    "run_end": ("wall_time_s",),
}

# host-authoritative per-round streams whose `round` index must be
# strictly increasing within one (run, cell); the async `round_tap`
# stream is deliberately NOT here (see module docstring)
_ORDERED_ROUND_EVENTS = ("round_metrics", "eval")


class TelemetryError(ValueError):
    """An event stream violated the schema contract."""


def _sanitize(x: Any) -> Any:
    """Coerce numpy/jax scalars and arrays into plain JSON-able python.

    Done at emit time (not dump time) so the in-memory `events` list a
    test inspects is exactly what a JSONL reader would parse back.
    """
    if isinstance(x, (str, int, float, bool)) or x is None:
        return x
    if isinstance(x, dict):
        return {str(k): _sanitize(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_sanitize(v) for v in x]
    item = getattr(x, "item", None)       # numpy / jax zero-dim scalars
    tolist = getattr(x, "tolist", None)   # numpy / jax arrays
    if tolist is not None and getattr(x, "ndim", 0):
        return _sanitize(tolist())
    if item is not None:
        return _sanitize(item())
    return str(x)


class Telemetry:
    """A telemetry sink: JSONL event stream + throttled progress heartbeat.

    * `path=None` keeps events in memory only (`.events`); with a path,
      every event is appended (and flushed, so an externally killed run
      leaves a readable prefix — the kill/resume contract).
    * `live_tap=True` opts the scan engines into the in-scan
      `jax.debug.callback` stream (`round_tap` events).  Trace-affecting
      but bit-neutral: it recompiles the scan with callbacks attached and
      must not change any output (pinned by tests/test_telemetry.py).
    * `trace_dir` opts the engines into a programmatic
      `jax.profiler.start_trace`/`stop_trace` capture window around the
      run's set-up and dispatches (profile.trace_capture): artifacts
      land in a run_id-stamped subdirectory and a `profile` event
      reports the per-stage wall recovered from the §15 span
      annotations.
    * `heartbeat_every_s` throttles progress lines (0 = every call);
      lines go to `stream` (default stderr), never into the event file.
    """

    def __init__(self, path: Optional[str] = None, *,
                 live_tap: bool = False, heartbeat_every_s: float = 0.0,
                 stream: Optional[IO] = None, run_id: Optional[str] = None,
                 trace_dir: Optional[str] = None):
        self.path = path
        self.live_tap = bool(live_tap)
        self.trace_dir = trace_dir
        self.run_id = run_id or f"run-{uuid.uuid4().hex[:8]}"
        self.events: list[dict] = []
        self.heartbeat_every_s = float(heartbeat_every_s)
        self._stream = stream if stream is not None else sys.stderr
        self._seq = 0
        self._t0 = time.perf_counter()
        self._last_hb = -float("inf")
        self._f: Optional[IO] = None
        if path:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            self._f = open(path, "a")

    # ---- event stream ----------------------------------------------------
    def emit(self, event: str, **fields) -> dict:
        if event not in REQUIRED_FIELDS:
            raise TelemetryError(f"unknown event type {event!r}; known: "
                                 f"{sorted(REQUIRED_FIELDS)}")
        missing = [f for f in REQUIRED_FIELDS[event] if f not in fields]
        if missing:
            raise TelemetryError(
                f"event {event!r} missing required fields {missing}")
        rec = {"v": SCHEMA_VERSION, "seq": self._seq,
               "t_s": round(time.perf_counter() - self._t0, 6),
               "event": event}
        rec.update({k: _sanitize(v) for k, v in fields.items()})
        self._seq += 1
        self.events.append(rec)
        if self._f is not None:
            json.dump(rec, self._f)
            self._f.write("\n")
            self._f.flush()
        return rec

    # ---- progress heartbeat ---------------------------------------------
    def heartbeat(self, msg: str, *, force: bool = False) -> None:
        now = time.perf_counter()
        if not force and now - self._last_hb < self.heartbeat_every_s:
            return
        self._last_hb = now
        print(f"[telemetry {self.run_id}] {msg}", file=self._stream,
              flush=True)

    # ---- lifecycle -------------------------------------------------------
    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None

    def __enter__(self) -> "Telemetry":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def read_events(path: str) -> list[dict]:
    """Parse a JSONL event file back into a list of event dicts."""
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                events.append(json.loads(line))
    return events


def read_events_prefix(path: str) -> tuple[list[dict], Optional[dict]]:
    """Parse a JSONL event file tolerating a truncated/corrupt tail.

    A killed run's append+flush stream leaves a readable prefix whose
    last line may be cut mid-record; this returns `(events, cut)` where
    `events` is the parseable prefix and `cut` is None for a clean file
    or `{"line", "reason", "raw"}` describing the first bad line — the
    cut is REPORTED, never silently swallowed, and everything after it
    is ignored (a flushed-JSONL stream cannot have valid records after
    a corrupt one unless the file was externally edited).
    """
    events: list[dict] = []
    cut = None
    with open(path) as f:
        for i, line in enumerate(f):
            stripped = line.strip()
            if not stripped:
                continue
            try:
                events.append(json.loads(stripped))
            except ValueError as e:
                cut = {"line": i, "reason": str(e), "raw": stripped[:120]}
                break
    return events, cut


def validate_events(events) -> int:
    """Pure-python schema check over an event stream; returns the count.

    Raises TelemetryError on: missing envelope fields, version mismatch,
    unknown event type, non-gap-free `seq`, missing required payload
    fields, or a non-increasing `round` index within one (run, cell) for
    the ordered streams (`round_metrics`, `eval`).  Runs are delimited by
    `run_start` events, so one file may hold many runs (e.g. a killed
    grid resumed into the same path).

    Merged multi-process streams (telemetry.merge) annotate every event
    with its source `shard` and renumber `seq` globally; ordering scopes
    (the seq chain aside) are then tracked per shard, so interleaved
    per-process round streams validate without false positives.
    """
    prev_seq = None
    run_ordinals: dict = {}
    last_round: dict[tuple, int] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise TelemetryError(f"event {i} is not an object: {ev!r}")
        for f in ENVELOPE_FIELDS:
            if f not in ev:
                raise TelemetryError(f"event {i} missing envelope "
                                     f"field {f!r}: {ev}")
        if ev["v"] != SCHEMA_VERSION:
            raise TelemetryError(
                f"event {i} has schema version {ev['v']!r}; this "
                f"validator understands {SCHEMA_VERSION}")
        kind = ev["event"]
        if kind not in REQUIRED_FIELDS:
            raise TelemetryError(f"event {i} has unknown type {kind!r}")
        missing = [f for f in REQUIRED_FIELDS[kind] if f not in ev]
        if missing:
            raise TelemetryError(
                f"event {i} ({kind}) missing required fields {missing}")
        seq = ev["seq"]
        if prev_seq is not None and seq != prev_seq + 1:
            raise TelemetryError(
                f"event {i} breaks the seq chain: {prev_seq} -> {seq}")
        prev_seq = seq
        shard = ev.get("shard")
        if kind == "run_start":
            run_ordinals[shard] = run_ordinals.get(shard, -1) + 1
        if kind in _ORDERED_ROUND_EVENTS:
            scope = (shard, run_ordinals.get(shard, -1), kind,
                     ev.get("cell"))
            rnd = ev["round"]
            if not isinstance(rnd, int):
                raise TelemetryError(
                    f"event {i} ({kind}) round index must be an int, "
                    f"got {rnd!r}")
            if scope in last_round and rnd <= last_round[scope]:
                raise TelemetryError(
                    f"event {i} ({kind}, cell={ev.get('cell')}) round "
                    f"index not increasing: {last_round[scope]} -> {rnd}")
            last_round[scope] = rnd
    return len(events)


# ---- provenance-stamped benchmark artifacts ------------------------------

def _git_rev() -> Optional[str]:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or None if out.returncode == 0 else None
    except (OSError, subprocess.SubprocessError):
        return None


def provenance() -> dict:
    """Where a number came from: rev, time, backend, devices, versions."""
    import jax
    return {
        "git_rev": _git_rev(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "jax_version": jax.__version__,
        "python_version": sys.version.split()[0],
    }


def write_bench_json(path: str, report: dict) -> dict:
    """The single BENCH_*.json writer: stamp provenance, dump sorted.

    Every benchmark artifact goes through here (benchmarks/engine_bench
    and friends) so each carries its `schema` tag (the caller's, e.g.
    "bench_selection/v1") plus a `provenance` block — no more hand-rolled
    json.dump blocks with unattributed numbers.
    """
    if "schema" not in report:
        raise ValueError("bench reports must carry a 'schema' tag "
                         "(e.g. 'bench_selection/v1')")
    stamped = dict(report)
    stamped["provenance"] = provenance()
    with open(path, "w") as f:
        json.dump(_sanitize(stamped), f, indent=2, sort_keys=True)
        f.write("\n")
    return stamped
