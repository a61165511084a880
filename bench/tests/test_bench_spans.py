"""Device idle time inside the program's host spans, and the readers of
the host and gather metrics, on a hand-built trace."""
import types

import pytest

from bench import harness, spans
from bench import trace_reduce as tr
from bench.trace_reduce import Op, Span

GRID = "mnist-mlp.grid-fedavg-q8"
MLP = "mnist-mlp.greedyfed"


def _summary():
    # two calls, [0, 100] and [100, 200] ns; the device runs [30, 60],
    # [90, 110] and [150, 170]: idle [0, 30], [60, 90], [110, 150],
    # [170, 200] (130 ns)
    calls = [Span("bench.call", 0, 100), Span("bench.call", 100, 100)]
    host = [
        Span("repro.setup", 0, 20),
        Span("repro.setup", 5, 10),         # nested in the one above
        Span("repro.operands", 20, 10),
        Span("repro.segment", 30, 30),
        Span("repro.results", 60, 20),
        Span("$stack", 80, 5),              # a Python function: no layer
        Span("repro.setup", 100, 20),
        Span("repro.operands", 120, 25),
        Span("repro.segment", 145, 30),
        Span("repro.results", 175, 20),
    ]
    ops = [Op("fusion.1", 30, 30, "x/repro.train/repro.gather", 0),
           Op("fusion.2", 90, 20, "x/repro.train", 0),
           Op("fusion.3", 150, 20, "x/repro.train/repro.gather", 0)]
    return tr.Summary(ops, calls + host, n_devices=1)


def test_overlap_of_gaps_with_a_union_of_spans():
    assert spans.merged([(5, 15), (0, 20), (30, 40), (40, 45)]) == [
        (0, 20), (30, 45)]
    gaps = [(0, 30), (60, 90), (110, 150)]
    assert spans.overlap_ns(gaps, [(0, 20), (5, 15), (25, 70)]) == 35
    assert spans.overlap_ns(gaps, []) == 0
    assert spans.overlap_ns([], [(0, 10)]) == 0


def test_idle_inside_a_span_name_counts_nested_spans_once_per_call():
    s = _summary()
    # setup: gap [0, 30] ∩ [0, 20] = 20 (the nested span adds nothing),
    # gap [110, 150] ∩ [100, 120] = 10; 30 ns over 2 calls
    assert spans.idle_ms_per_call(s, "repro.setup") == pytest.approx(
        15e-6)
    # operands: [20, 30] = 10, [120, 145] = 25
    assert spans.idle_ms_per_call(s, "repro.operands") == pytest.approx(
        17.5e-6)
    # results: [60, 80] = 20, [175, 195] = 20
    assert spans.idle_ms_per_call(s, "repro.results") == pytest.approx(
        20e-6)
    # segment: [145, 150] = 5 and [170, 175] = 5
    assert spans.idle_ms_per_call(s, "repro.segment") == pytest.approx(
        5e-6)


def test_unspanned_idle_is_the_rest_of_the_idle_time():
    s = _summary()
    # idle 130 ns; in repro.* spans 30 + 35 + 40 + 10 = 115; the rest is
    # [80, 90] (a Python function, no layer) and [195, 200]
    assert spans.unspanned_idle_ms_per_call(s) == pytest.approx(7.5e-6)
    total = sum(e - st for st, e in s.idle_gaps())
    parts = sum(spans.idle_ms_per_call(s, n) for n in (
        "repro.setup", "repro.operands", "repro.segment", "repro.results"))
    assert parts + spans.unspanned_idle_ms_per_call(s) == pytest.approx(
        total * 1e-6 / 2)


def test_none_without_calls_or_spans():
    no_calls = tr.Summary([Op("a", 0, 10, "", 0)],
                          [Span("repro.setup", 0, 5)], n_devices=1)
    assert spans.idle_ms_per_call(no_calls, "repro.setup") is None
    assert spans.unspanned_idle_ms_per_call(no_calls) is None
    bare = tr.Summary([Op("a", 0, 10, "", 0)],
                      [Span("bench.call", 0, 20), Span("$stack", 10, 5)],
                      n_devices=1)
    assert spans.idle_ms_per_call(bare, "repro.setup") is None
    assert spans.unspanned_idle_ms_per_call(bare) is None
    assert spans.idle_ms_per_call(_summary(), "repro.gather") is None


def _readers(cell):
    return {name: read for name, (_, read)
            in harness.metric_readers(harness.benchmark(), cell).items()}


def test_the_six_readers():
    ctx = types.SimpleNamespace(summary=_summary(), rounds=4)
    mlp, grid = _readers(MLP), _readers(GRID)
    assert mlp["host_idle_ms.setup"](ctx) == pytest.approx(15e-6)
    assert mlp["host_idle_ms.unspanned"](ctx) == pytest.approx(7.5e-6)
    assert grid["host_idle_ms.setup.grid"](ctx) == pytest.approx(15e-6)
    assert grid["host_idle_ms.operands.grid"](ctx) == pytest.approx(
        17.5e-6)
    assert grid["host_idle_ms.unspanned.grid"](ctx) == pytest.approx(
        7.5e-6)
    # gather: fusion.1 and fusion.3, 50 ns over 4 replica-rounds; it
    # nests in repro.train (70 ns), which counts it too
    assert grid["stage_ms.gather.grid"](ctx) == pytest.approx(12.5e-6)
    assert grid["stage_ms.train.grid"](ctx) == pytest.approx(17.5e-6)
    assert "stage_ms.gather.grid" not in mlp
    assert "host_idle_ms.operands.grid" not in mlp


def test_readers_find_nothing_in_a_program_without_the_spans():
    # the trace of a program that writes no setup, operands or gather
    # reading: the readers return None and raise nothing
    calls = [Span("bench.call", 0, 100)]
    ops = [Op("fusion.1", 30, 30, "x/repro.train", 0)]
    ctx = types.SimpleNamespace(
        summary=tr.Summary(ops, calls + [Span("repro.segment", 20, 50)],
                           n_devices=1), rounds=4)
    grid = _readers(GRID)
    for name in ("host_idle_ms.setup.grid", "host_idle_ms.operands.grid",
                 "stage_ms.gather.grid"):
        assert grid[name](ctx) is None, name
    # idle [0, 30] and [60, 100]; [20, 30] and [60, 70] inside the segment
    assert grid["host_idle_ms.unspanned.grid"](ctx) == pytest.approx(50e-6)
