"""repro.telemetry — structured observability for every engine (§15/§17).

`Telemetry` is the sink all engines accept (`telemetry=None` default:
zero dispatches, bit-identical outputs); `events` defines the
schema-versioned JSONL stream and its validator plus the provenance-
stamped BENCH writer; `metrics` aggregates host-side gauges at segment
boundaries; `trace` carries stage annotation, the compile-time split,
and the opt-in in-scan live tap; `report` renders summaries from JSONL.

The §17 analysis tier on top of the stream: `profile` attaches per-
executable cost cards to compile events and drives the opt-in profiler
capture window; `merge` folds per-process JSONL shards into one
validated stream; `regress` diffs the BENCH_*.json artifacts against
committed baselines and keeps the BENCH_trajectory.json ledger.
"""
from repro.telemetry.events import (
    SCHEMA_VERSION, Telemetry, TelemetryError, provenance, read_events,
    read_events_prefix, validate_events, write_bench_json,
)
from repro.telemetry.metrics import (
    emit_scan_rounds, run_end_payload, segment_counters,
)
from repro.telemetry.profile import (
    cached_cost_card, cost_card, stage_wall_from_trace, trace_capture,
)
from repro.telemetry.trace import (
    CompileTimer, live_sink, named_stage, stage,
)

__all__ = [
    "SCHEMA_VERSION", "Telemetry", "TelemetryError", "provenance",
    "read_events", "read_events_prefix", "validate_events",
    "write_bench_json",
    "emit_scan_rounds", "run_end_payload", "segment_counters",
    "cached_cost_card", "cost_card", "stage_wall_from_trace",
    "trace_capture",
    "CompileTimer", "live_sink", "named_stage", "stage",
]
