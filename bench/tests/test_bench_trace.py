"""The trace reduction on a hand-built trace."""
import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Op, Span


def _summary():
    # window: two calls, [0, 100] and [120, 200] ns
    spans = [Span("bench.call", 0, 100), Span("bench.call", 120, 80),
             Span("setup_run", 0, 20), Span("results", 180, 20)]
    ops = [
        Op("fusion.1", 20, 30, "jit(run_scan)/while/body/repro.train/dot", 0),
        Op("prefix_avg_kernel", 40, 20,
           "jit(run_scan)/while/body/repro.shapley/prefix_avg", 0),
        Op("fusion.2", 70, 30, "jit(run_scan)/while/body/repro.shapley", 0),
        Op("fusion.1", 140, 30, "jit(run_scan)/while/body/repro.train/dot",
           0),
        Op("outside", 300, 10, "", 0),      # after the window
    ]
    return tr.Summary(ops, spans, n_devices=1)


def test_union_merges_overlaps_and_clips():
    assert tr.union_ns([(0, 10), (5, 20), (30, 40)], 0, 100) == 30
    assert tr.union_ns([(0, 10), (5, 20), (30, 40)], 8, 35) == 17
    assert tr.union_ns([], 0, 10) == 0


def test_gaps_are_the_complement():
    assert tr.gaps([(20, 60), (40, 50), (70, 100)], 0, 120) == [
        (0, 20), (60, 70), (100, 120)]
    assert tr.gaps([(0, 200)], 10, 20) == []


def test_busy_and_window():
    s = _summary()
    assert s.window_s == pytest.approx(200e-9)
    # busy: [20, 60] u [70, 100] u [140, 170] = 100 ns
    assert s.busy_s == pytest.approx(100e-9)


def test_scope_and_kernel_time():
    s = _summary()
    assert s.scope_s("repro.train") == pytest.approx(60e-9)
    assert s.scope_s("repro.shapley") == pytest.approx(50e-9)
    assert s.scope_s("repro.eval") == 0
    assert s.kernel_s("prefix_avg") == pytest.approx(20e-9)


def test_idle_gaps_by_host_span():
    s = _summary()
    b = s.breakdown()
    # gaps [0,20], [60,70], [100,140], [170,200], each named by the
    # innermost span over its middle: setup_run, bench.call, bench.call
    # (the second call starts at 120), results
    idle = dict(b["idle_gaps"])
    assert idle == pytest.approx({"setup_run": 20e-9, "bench.call": 50e-9,
                                  "results": 30e-9})
    assert s.host_span_at(110) == "no host span"
    ops = dict(b["device_ops"])
    assert ops["fusion.1 (repro.train)"] == pytest.approx(60e-9)
    assert not any(k.startswith("outside") for k in ops)


def test_two_devices_average():
    ops = [Op("a", 0, 10, "x/repro.train", 0), Op("a", 0, 30, "x/repro.train", 1)]
    s = tr.Summary(ops, [Span("bench.call", 0, 40)], n_devices=2)
    assert s.busy_s == pytest.approx(20e-9)
    assert s.scope_s("repro.train") == pytest.approx(20e-9)


def test_leaves_and_nesting():
    # a while op that runs two ops, then a lone op
    ops = tr.mark_leaves([Op("while.1", 0, 100, "scan", 0),
                          Op("fusion.2", 10, 20, "scan/repro.train", 0),
                          Op("fusion.3", 40, 30, "scan/repro.shapley", 0),
                          Op("copy.4", 120, 10, "", 0)])
    leaf = {o.name: o.leaf for o in ops}
    assert leaf == {"while.1": False, "fusion.2": True, "fusion.3": True,
                    "copy.4": True}
    s = tr.Summary(ops, [Span("bench.call", 0, 200)], n_devices=1)
    assert s.busy_s == pytest.approx(110e-9)
    assert s.scope_s("repro.train") == pytest.approx(20e-9)
    assert dict(s.breakdown()["device_ops"]) == pytest.approx(
        {"fusion.3 (repro.shapley)": 30e-9, "fusion.2 (repro.train)": 20e-9,
         "copy.4": 10e-9})


def test_hlo_scopes_and_instruction_names(tmp_path):
    (tmp_path / "module_0007.jit_run_scan.sm_8.0_gpu_after_optimizations.txt"
     ).write_text(
        'HloModule jit_run_scan\n'
        '%fused (p: f32[2]) -> f32[2] {\n'
        '  ROOT %add.1 = f32[2]{0} add(f32[2]{0} %p, f32[2]{0} %p), '
        'metadata={op_name="jit(run_scan)/while/body/repro.aggregate/add"}\n'
        '}\n'
        '  %fusion.53 = bf16[4]{0} fusion(f32[4]{0} %x), kind=kOutput, '
        'calls=%fused, metadata={op_name="jit(run_scan)/while/body/'
        'repro.shapley/dot_general" source_file="a.py" source_line=3}\n'
        '  %copy.2 = f32[4]{0} copy(f32[4]{0} %y)\n')
    got = tr.hlo_scopes(str(tmp_path))
    assert got == {"jit_run_scan": {
        "add.1": "jit(run_scan)/while/body/repro.aggregate/add",
        "fusion.53": "jit(run_scan)/while/body/repro.shapley/dot_general"}}
    assert tr.instruction(
        "%fusion.53 = bf16[450,5000,200]{1,0} fusion(f32[4] %a), "
        "kind=kOutput") == "fusion.53"
