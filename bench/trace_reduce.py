"""From a profiler trace of the window to the per-layer readings.

`load` reads the `.xplane.pb` that `jax.profiler` wrote: the device
planes' op events and the host's spans (`TraceAnnotation`s, the Python
functions the profiler records, among them the harness's `bench.call`
around each call).  On a TPU an op event is named by its HLO instruction
and carries no scope path, so each op's path is looked up in the HLO
that XLA dumped when it compiled the op's module (`hlo_scopes`): the
`op_name` metadata of the instruction, whose `repro.<stage>` parts are
the program's `named_stage` scopes.  Control-flow ops (a scan's while, a
cond) span the ops they run; scope and kernel time count only leaf ops,
so nothing is counted twice.  `Summary` then gives:

- the traced window: from the first `bench.call` span's start to the
  last one's end;
- busy time: per device, the union of its op intervals inside the
  window; averaged over the devices;
- idle gaps: the parts of the window in which a device ran nothing, each
  attributed to the innermost host span that covers its middle;
- scope time: per device, the summed duration of leaf ops whose scope
  path holds a given scope (`repro.shapley`); averaged over devices;
- kernel time: the same for leaf ops whose instruction is a kernel's
  (`prefix_avg_kernel.2`);
- `breakdown`: the ten ops that took most device time, and the host
  spans that the longest idle time fell in.

A fusion that XLA made of ops from two scopes carries the one `op_name`
XLA gave the fusion instruction, that of its root op; its whole time
goes to that scope.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import NamedTuple, Optional

CALL_SPAN = "bench.call"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?'
                    r'metadata=\{[^}]*?op_name="([^"]*)"')


class Op(NamedTuple):
    name: str        # HLO instruction, e.g. 'fusion.53'
    start_ns: float
    dur_ns: float
    scope: str       # named-scope path of the op ('' if none)
    device: int
    leaf: bool = True  # runs no other op inside its interval


class Span(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of (start, end) intervals clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list:
    """(start, end) stretches of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if e <= cur:
            continue
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


class Summary:
    def __init__(self, ops: list, spans: list, n_devices: int):
        self.ops = ops
        self.spans = sorted(spans, key=lambda s: s.start_ns)
        self._starts = [s.start_ns for s in self.spans]
        self.n_devices = max(1, n_devices)
        calls = [s for s in spans if s.name == CALL_SPAN]
        if calls:
            self.lo = min(s.start_ns for s in calls)
            self.hi = max(s.start_ns + s.dur_ns for s in calls)
        elif ops:
            self.lo = min(o.start_ns for o in ops)
            self.hi = max(o.start_ns + o.dur_ns for o in ops)
        else:
            self.lo = self.hi = 0.0

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def _per_device(self):
        by = {}
        for o in self.ops:
            by.setdefault(o.device, []).append(
                (o.start_ns, o.start_ns + o.dur_ns))
        return by

    @property
    def busy_s(self) -> float:
        by = self._per_device()
        return sum(union_ns(iv, self.lo, self.hi)
                   for iv in by.values()) * 1e-9 / self.n_devices

    def idle_gaps(self) -> list:
        """Idle stretches of the first device, longest first."""
        by = self._per_device()
        if not by:
            return [(self.lo, self.hi)] if self.hi > self.lo else []
        iv = by[min(by)]
        return sorted(gaps(iv, self.lo, self.hi),
                      key=lambda g: g[0] - g[1])

    def host_span_at(self, t: float) -> str:
        """The innermost host span that covers time t: of nested spans,
        the one that started last."""
        i = bisect.bisect_right(self._starts, t)
        for s in reversed(self.spans[max(0, i - 100_000):i]):
            if s.start_ns + s.dur_ns >= t:
                return s.name
        return "no host span"

    def scope_s(self, scope: str) -> float:
        tot = sum(o.dur_ns for o in self.ops
                  if o.leaf and scope in o.scope.split("/")
                  and self.lo <= o.start_ns <= self.hi)
        return tot * 1e-9 / self.n_devices

    def kernel_s(self, kernel: str) -> float:
        tot = sum(o.dur_ns for o in self.ops
                  if o.leaf and o.name.startswith(kernel)
                  and self.lo <= o.start_ns <= self.hi)
        return tot * 1e-9 / self.n_devices

    def breakdown(self, top: int = 10) -> dict:
        per_op = {}
        for o in self.ops:
            if o.leaf and self.lo <= o.start_ns <= self.hi:
                stage = [p for p in o.scope.split("/")
                         if p.startswith("repro.")]
                key = o.name + (f" ({stage[-1]})" if stage else "")
                per_op[key] = per_op.get(key, 0.0) + o.dur_ns
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        per_span = {}
        for s, e in self.idle_gaps():
            name = self.host_span_at((s + e) / 2)
            per_span[name] = per_span.get(name, 0.0) + (e - s)
        idle = sorted(per_span.items(), key=lambda kv: -kv[1])[:top]
        n = self.n_devices
        return {"device_ops": [[k, v * 1e-9 / n] for k, v in ops],
                "idle_gaps": [[k, v * 1e-9] for k, v in idle]}


class Context(NamedTuple):
    """What a per-layer metric's reader is given."""
    summary: Summary
    cell: str
    config: dict           # the configuration's file
    traffic: dict          # the traffic's file
    fl: dict               # protocol keys with the traffic's overrides
    replicas: int          # runs in one pass of the window
    rounds: int            # rounds finished in the traced window (all runs)
    window_s: float        # wall time of the traced window
    utility_evals_per_run: float
    counters: dict         # the warm-up call's FLResult counters, per run
    peaks: object          # peaks.Peaks of the chip
    chips: int


def mark_leaves(ops: list) -> list:
    """Ops of one device with `leaf` False where another op of that device
    starts inside their interval (a while or a cond and what it runs)."""
    ops = sorted(ops, key=lambda o: (o.start_ns, -o.dur_ns))
    out = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        parent = nxt is not None and nxt.start_ns < o.start_ns + o.dur_ns
        out.append(o._replace(leaf=not parent))
    return out


def hlo_scopes(dump_dir: str) -> dict:
    """{module name: {instruction: op_name}} from the HLO that XLA dumped
    after optimizations (`--xla_dump_to`, `--xla_dump_hlo_as_text`)."""
    out = {}
    for path in glob.glob(os.path.join(dump_dir, "*after_optimizations.txt")):
        module = os.path.basename(path).split(".")[1]
        table = out.setdefault(module, {})
        with open(path) as f:
            for line in f:
                m = _INSTR.match(line)
                if m:
                    table[m.group(1)] = m.group(2)
    return out


def instruction(event_name: str) -> str:
    """'%fusion.53 = bf16[...] fusion(...)' -> 'fusion.53'."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def load(tdir: str, n_devices: int, scopes: Optional[dict] = None
         ) -> Summary:
    """Read the newest trace under `tdir` (jax.profiler's layout);
    `scopes` is `hlo_scopes` of the dump made when the traced modules
    compiled."""
    from jax.profiler import ProfileData

    scopes = scopes or {}
    paths = sorted(glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    pd = ProfileData.from_file(paths[-1])
    ops, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                (e.start_ns, e.start_ns + e.duration_ns,
                 e.name.split("(")[0])
                for e in (lines[MODULES_LINE].events
                          if MODULES_LINE in lines else ()))
            starts = [m[0] for m in modules]
            dev_ops = []
            for e in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
                name = instruction(e.name)
                i = bisect.bisect_right(starts, e.start_ns) - 1
                table = (scopes.get(modules[i][2], {})
                         if i >= 0 and e.start_ns <= modules[i][1] else {})
                dev_ops.append(Op(name, e.start_ns, e.duration_ns,
                                  table.get(name, ""), dev))
            ops.extend(mark_leaves(dev_ops))
        elif plane.name.startswith("/host:"):
            # the host thread that made the calls: its annotations and
            # the Python functions the profiler recorded on it
            for line in plane.lines:
                events = [Span(e.name, e.start_ns, e.duration_ns)
                          for e in line.events]
                if any(e.name == CALL_SPAN for e in events):
                    spans.extend(events)
    return Summary(ops, spans, n_devices)
