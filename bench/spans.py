"""Device idle time inside the program's host spans, per call.

The program marks each host layer of a run with a `TraceAnnotation`
named `repro.<layer>` (`telemetry.trace.stage`): `repro.setup` around
`setup_run`, `repro.operands` around building and placing a dispatch's
operands, `repro.scan` / `repro.segment` around the dispatch, and
`repro.results` around reading the outputs back.  They are written to
the same profiler trace as the device's ops and the harness's
`bench.call`, on the calling thread's line, so `trace_reduce.load` keeps
them among `Summary.spans`.

`idle_ms_per_call` intersects the first device's idle gaps in the
traced window (`Summary.idle_gaps`) with the union of the intervals of
the spans of one name (a span nested in another of its name counts
once) and divides by the number of `bench.call` spans.
`unspanned_idle_ms_per_call` gives the idle time that falls in no
`repro.*` span: host work outside every layer's span shows there.  Both
return None where the window holds no call, or no span to read (a
program that writes none).
"""
from __future__ import annotations

import bisect
from typing import Optional

from bench.trace_reduce import CALL_SPAN

PREFIX = "repro."


def merged(intervals) -> list:
    """Disjoint, sorted (start, end) intervals covering the same time."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap_ns(gaps, intervals) -> float:
    """Time that the disjoint (start, end) gaps share with the union of
    `intervals`."""
    cover = merged(intervals)
    ends = [e for _, e in cover]
    total = 0.0
    for gs, ge in gaps:
        i = bisect.bisect_right(ends, gs)   # the first to end after gs
        while i < len(cover) and cover[i][0] < ge:
            s, e = cover[i]
            total += min(e, ge) - max(s, gs)
            i += 1
    return total


def _calls(summary) -> int:
    return sum(1 for s in summary.spans if s.name == CALL_SPAN)


def _intervals(summary, keep) -> list:
    return [(s.start_ns, s.start_ns + s.dur_ns) for s in summary.spans
            if keep(s.name)]


def idle_ms_per_call(summary, name: str) -> Optional[float]:
    """Device idle time inside host spans named `name`, in ms per call."""
    calls = _calls(summary)
    spans = _intervals(summary, lambda n: n == name)
    if not calls or not spans:
        return None
    return 1e-6 * overlap_ns(summary.idle_gaps(), spans) / calls


def unspanned_idle_ms_per_call(summary) -> Optional[float]:
    """Device idle time inside no `repro.*` host span, in ms per call."""
    calls = _calls(summary)
    spans = _intervals(summary, lambda n: n.startswith(PREFIX))
    if not calls or not spans:
        return None
    gaps = summary.idle_gaps()
    idle = sum(e - s for s, e in gaps)
    return 1e-6 * (idle - overlap_ns(gaps, spans)) / calls
