"""DESIGN.md §17 — the analysis tier above the telemetry stream.

Cost cards (per-executable flops/bytes/peak + roofline) on every compile
event, the opt-in profiler capture window, multi-shard JSONL merge
(killed-shard prefixes included), and the bench-regression gate with its
BENCH_trajectory.json ledger.
"""
import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.federated.client import ClientConfig
from repro.federated.server import FLConfig, run_federated
from repro.telemetry import (
    SCHEMA_VERSION, Telemetry, TelemetryError, cached_cost_card, cost_card,
    read_events_prefix, trace_capture, validate_events,
)
from repro.telemetry.merge import merge_files, merge_streams
from repro.telemetry.trace import stage

# same shape as tests/test_telemetry.py so the process-wide jitted-run
# caches are warm when the suites run together
TINY = dict(n_clients=8, m=3, rounds=6, n_train=600, n_val=100, n_test=100,
            eval_every=3,
            client=ClientConfig(epochs=2, batches_per_epoch=2, batch_size=16))


# ---- cost cards ----------------------------------------------------------

def test_cost_card_populated_and_cached():
    """The AOT probe unifies flops / memory / roofline into one card, and
    the cache returns the identical object on a warm (fn, avals) key."""
    f = jax.jit(lambda a, b: jnp.tanh(a @ b))
    x = jnp.ones((64, 64), jnp.float32)
    card = cost_card(f, x, x)
    assert card is not None
    assert card["flops"] > 0
    assert card["bytes_accessed"] > 0
    assert card["peak_bytes"] is not None and card["peak_bytes"] > 0
    assert card["intensity_flops_per_byte"] == pytest.approx(
        card["flops"] / card["bytes_accessed"])
    # roofline terms only against a chip's published peaks: a CPU card
    # carries none (launch.roofline.PEAKS)
    if jax.devices()[0].platform == "cpu":
        assert "roofline" not in card
    else:
        roof = card["roofline"]
        assert roof["dominant"] in ("compute", "memory")
        assert roof["compute_s"] >= 0 and roof["memory_s"] >= 0
    again = cached_cost_card(f, x, x)
    third = cached_cost_card(f, x, x)
    assert again is third                     # dict lookup, no recompile
    assert again.keys() == card.keys()


def test_cost_card_survives_donated_args():
    """The probe lowers on avals, so a buffer consumed by a donating
    dispatch still yields a card afterwards."""
    f = jax.jit(lambda a: a * 2.0, donate_argnums=(0,))
    x = jnp.ones((32,), jnp.float32)
    f(x)                                      # x's buffer is now donated
    card = cost_card(f, x)
    assert card is not None and card["bytes_accessed"] > 0


def test_scan_compile_event_carries_cost_card():
    """The whole-run scan's compile event answers "what does this
    executable cost" without a profiler in the loop."""
    cfg = FLConfig(engine="scan", selector="greedyfed", **TINY)
    tel = Telemetry()
    run_federated(cfg, telemetry=tel)
    validate_events(tel.events)
    [compile_ev] = [e for e in tel.events if e["event"] == "compile"]
    card = compile_ev["cost_card"]
    assert card["flops"] > 0 and card["bytes_accessed"] > 0
    assert card["peak_bytes"] > 0
    if jax.devices()[0].platform == "cpu":
        assert "roofline" not in card
    else:
        assert card["roofline"]["dominant"] in ("compute", "memory")


def test_grid_cost_cards_and_heartbeat_peak(tmp_path):
    """Segmented grid: the per-partition segment_step compile event and
    the aggregate grid_segments event both carry cards, the capture
    window recovers per-stage walls, and the throttled heartbeat surfaces
    the compiled per-device peak next to the ETA."""
    from repro.grid import GridSpec, run_grid

    base = FLConfig(engine="scan", selector="greedyfed",
                    **dict(TINY, rounds=4, eval_every=2))
    gspec = GridSpec.product(base, selectors=["greedyfed"], seeds=[0])
    hb = io.StringIO()
    tel = Telemetry(stream=hb, trace_dir=str(tmp_path / "traces"))
    run_grid(gspec, rounds_per_segment=2, telemetry=tel)
    validate_events(tel.events)

    compiles = {e["program"]: e for e in tel.events
                if e["event"] == "compile"}
    assert set(compiles) == {"segment_step:p0-", "grid_segments"}
    for ev in compiles.values():
        assert ev["cost_card"]["flops"] > 0
        assert ev["cost_card"]["peak_bytes"] > 0

    [prof] = [e for e in tel.events if e["event"] == "profile"]
    assert prof["label"] == "grid"
    assert prof["captured"] is True
    assert prof["stage_wall_s"].get("segment", 0) > 0
    # the window opens before the cells' set-ups, so it holds them too
    assert prof["stage_wall_s"].get("setup", 0) > 0
    assert "source" not in prof

    beats = hb.getvalue()
    assert "eta" in beats and "peak" in beats and "MB/dev" in beats


def test_trace_capture_noop_without_trace_dir():
    tel = Telemetry()
    with trace_capture(tel, label="x") as rec:
        assert rec is None
    assert [e for e in tel.events if e["event"] == "profile"] == []


def test_trace_capture_unit(tmp_path):
    """An explicit capture window around a stage()-annotated dispatch
    emits one `profile` event with that stage's wall seconds."""
    tel = Telemetry(trace_dir=str(tmp_path / "tr"))
    x = jnp.ones((128, 128), jnp.float32)
    f = jax.jit(lambda a: a @ a)
    with trace_capture(tel, label="unit"):
        with stage("unit_op"):
            jax.block_until_ready(f(x))
    [prof] = [e for e in tel.events if e["event"] == "profile"]
    assert prof["captured"] is True
    assert prof["stage_wall_s"]["unit_op"] > 0
    validate_events(tel.events)


def test_scan_capture_window_holds_setup(tmp_path):
    """A solo run's capture window opens before `setup_run`: its walls
    hold every host layer's span, each read from the capture's trace."""
    cfg = FLConfig(engine="scan", selector="greedyfed", **TINY)
    tel = Telemetry(trace_dir=str(tmp_path / "tr"))
    run_federated(cfg, telemetry=tel)
    validate_events(tel.events)
    [prof] = [e for e in tel.events if e["event"] == "profile"]
    assert prof["label"] == "run_scan" and prof["captured"] is True
    walls = prof["stage_wall_s"]
    for name in ("setup", "operands", "scan", "results"):
        assert walls.get(name, 0) > 0, name


def test_trace_capture_without_profiler_reports_no_walls(tmp_path):
    """With the profiler already tracing the window cannot start: the
    event says so and carries no walls (nothing else times the spans)."""
    tel = Telemetry(trace_dir=str(tmp_path / "inner"))
    with jax.profiler.trace(str(tmp_path / "outer")):
        with trace_capture(tel, label="nested"):
            with stage("unit_op"):
                jax.block_until_ready(jnp.ones((8,)) * 2)
    [prof] = [e for e in tel.events if e["event"] == "profile"]
    assert prof["captured"] is False
    assert prof["stage_wall_s"] == {}
    assert "source" not in prof
    validate_events(tel.events)


# ---- truncated streams ---------------------------------------------------

def _emit_run(tel: Telemetry, run_id: str, rounds: int = 2) -> Telemetry:
    tel.emit("run_start", run_id=run_id, kind="solo")
    for t in range(rounds):
        tel.emit("eval", round=t, test_acc=0.5 + t, val_loss=1.0 - t)
    tel.emit("run_end", wall_time_s=0.1)
    return tel


def test_read_events_prefix_reports_the_cut(tmp_path):
    """A killed run's JSONL tail (half-written record) loads as a
    validating prefix and the cut is reported, never swallowed."""
    path = str(tmp_path / "killed.jsonl")
    with Telemetry(path) as tel:
        _emit_run(tel, "r-dead")
    with open(path, "a") as f:
        f.write('{"v": 1, "seq": 4, "t_s": 9.9, "eve')   # the kill
    events, cut = read_events_prefix(path)
    assert len(events) == 4
    assert validate_events(events) == 4
    assert cut is not None and cut["line"] == 4
    assert cut["raw"].startswith('{"v": 1,')


def test_read_events_prefix_clean_file(tmp_path):
    path = str(tmp_path / "clean.jsonl")
    with Telemetry(path) as tel:
        _emit_run(tel, "r-ok")
    events, cut = read_events_prefix(path)
    assert cut is None and len(events) == 4


# ---- shard merge ---------------------------------------------------------

def test_merge_single_shard_is_identity():
    """K=1 merge adds no shard annotations and renumbers nothing."""
    tel = _emit_run(Telemetry(), "r-solo")
    merged = merge_streams([tel.events])
    assert merged == tel.events
    assert all("shard" not in ev and "src_seq" not in ev for ev in merged)


def test_merge_two_shards_validates_and_preserves_shard_order():
    a = _emit_run(Telemetry(run_id="r-multi"), "r-multi", rounds=3)
    b = _emit_run(Telemetry(run_id="r-multi"), "r-multi", rounds=3)
    merged = merge_streams([a.events, b.events])
    assert len(merged) == len(a.events) + len(b.events)
    assert validate_events(merged) == len(merged)      # shard-scoped rounds
    assert [ev["seq"] for ev in merged] == list(range(len(merged)))
    for i, shard in enumerate((a, b)):
        src = [ev["src_seq"] for ev in merged if ev["shard"] == i]
        assert src == [ev["seq"] for ev in shard.events]  # per-sink order


def test_merge_filters_by_run_id():
    a = _emit_run(Telemetry(), "r-want")
    b = _emit_run(Telemetry(), "r-stray")
    merged = merge_streams([a.events, b.events], run_id="r-want")
    assert merged == a.events                          # stray excluded -> K=1
    with pytest.raises(TelemetryError, match="no shard announces"):
        merge_streams([a.events, b.events], run_id="r-absent")


def test_merge_rejects_invalid_shard():
    a = _emit_run(Telemetry(), "r-bad")
    broken = [dict(ev) for ev in a.events]
    broken[2]["seq"] = 99                              # gap in the chain
    with pytest.raises(TelemetryError, match="shard 0"):
        merge_streams([broken])


def test_merge_files_tolerates_killed_shard(tmp_path):
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    with Telemetry(pa) as ta:
        _emit_run(ta, "r-files")
    with open(pa, "a") as f:
        f.write('{"trunc')
    with Telemetry(pb) as tb:
        _emit_run(tb, "r-files")
    merged, reports = merge_files([pa, pb])
    assert validate_events(merged) == 8
    assert reports[0]["cut"] is not None and reports[1]["cut"] is None

    from repro.telemetry.merge import main
    out = str(tmp_path / "merged.jsonl")
    assert main([pa, pb, "-o", out]) == 0
    with open(out) as f:
        assert len(f.readlines()) == 8
    assert main([pa, pb, "--strict"]) == 1             # refuse the cut


# ---- report CLI ----------------------------------------------------------

def test_report_json_embeds_schema_version(tmp_path, capsys):
    from repro.telemetry.report import main

    path = str(tmp_path / "ev.jsonl")
    with Telemetry(path) as tel:
        _emit_run(tel, "r-rep")
    assert main([path, "--json", "--validate"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["schema_version"] == SCHEMA_VERSION
    assert len(payload["rows"]) == 1


def test_report_validate_exits_nonzero_on_malformed(tmp_path, capsys):
    from repro.telemetry.report import main

    path = str(tmp_path / "bad.jsonl")
    with Telemetry(path) as tel:
        _emit_run(tel, "r-bad")
    events, _ = read_events_prefix(path)
    events[1]["seq"] = 7                               # break the chain
    with open(path, "w") as f:
        for ev in events:
            f.write(json.dumps(ev) + "\n")
    assert main([path, "--validate"]) == 1
    assert "validation FAILED" in capsys.readouterr().err


# ---- bench regression gate -----------------------------------------------

def _write_bench(path, off_us: float, host_pct: float = 0.5):
    from repro.telemetry.events import write_bench_json
    write_bench_json(str(path), {
        "schema": "bench_telemetry/v1",
        "e2e_us": {"off": off_us},
        "overhead_pct": {"host": host_pct},
    })


def test_regress_lookup_paths():
    from repro.telemetry.regress import lookup

    obj = {"a": {"b": [10, {"c": 42}]}}
    assert lookup(obj, "a.b[0]") == 10
    assert lookup(obj, "a.b[1].c") == 42
    assert lookup(obj, "a.missing") is None
    assert lookup(obj, "a.b[9]") is None


def test_regress_clean_pass_then_injected_regression(tmp_path):
    """Seeded baselines pass (exit 0, one trajectory entry); a 2x latency
    injection regresses (exit 1); the ledger records both."""
    from repro.telemetry.regress import main

    bench = tmp_path / "bench"
    bench.mkdir()
    baselines = str(tmp_path / "baselines")
    traj = bench / "BENCH_trajectory.json"
    _write_bench(bench / "BENCH_telemetry.json", off_us=1000.0)
    assert main(["--bench-dir", str(bench), "--baselines", baselines,
                 "--seed"]) == 0

    assert main(["--bench-dir", str(bench),
                 "--baselines", baselines]) == 0
    ledger = json.loads(traj.read_text())
    assert ledger["schema"] == "bench_trajectory/v1"
    assert len(ledger["entries"]) == 1
    assert ledger["entries"][0]["status"] == "pass"
    assert ledger["entries"][0]["metrics_regressed"] == 0

    _write_bench(bench / "BENCH_telemetry.json", off_us=2000.0)  # 2x
    assert main(["--bench-dir", str(bench),
                 "--baselines", baselines]) == 1
    ledger = json.loads(traj.read_text())
    assert len(ledger["entries"]) == 2
    assert ledger["entries"][1]["status"] == "regressed"
    recs = ledger["entries"][1]["benches"]["BENCH_telemetry.json"]["metrics"]
    bad = [r for r in recs if r["status"] == "regressed"]
    assert [r["path"] for r in bad] == ["e2e_us.off"]
    assert bad[0]["ratio"] == pytest.approx(2.0)


def test_regress_abs_tol_band(tmp_path):
    """overhead_pct.host is banded in absolute points: 0.5 -> 2.9 stays
    inside the 3-point band, 0.5 -> 4.0 regresses."""
    from repro.telemetry.regress import main

    bench = tmp_path / "bench"
    bench.mkdir()
    baselines = str(tmp_path / "baselines")
    _write_bench(bench / "BENCH_telemetry.json", 1000.0, host_pct=0.5)
    main(["--bench-dir", str(bench), "--baselines", baselines, "--seed"])
    _write_bench(bench / "BENCH_telemetry.json", 1000.0, host_pct=2.9)
    assert main(["--bench-dir", str(bench), "--baselines", baselines,
                 "--trajectory", "none"]) == 0
    _write_bench(bench / "BENCH_telemetry.json", 1000.0, host_pct=4.0)
    assert main(["--bench-dir", str(bench), "--baselines", baselines,
                 "--trajectory", "none"]) == 1


def test_regress_schema_change_is_incomparable_not_fail(tmp_path):
    from repro.telemetry.events import write_bench_json
    from repro.telemetry.regress import run_check

    bench = tmp_path / "bench"
    bench.mkdir()
    baselines = tmp_path / "baselines"
    baselines.mkdir()
    _write_bench(bench / "BENCH_telemetry.json", 1000.0)
    write_bench_json(str(baselines / "BENCH_telemetry.json"),
                     {"schema": "bench_telemetry/v0"})
    entry = run_check(str(bench), str(baselines), None)
    assert entry["status"] == "pass" and entry["metrics_checked"] == 0
    assert any("incomparable" in n for n in entry["notes"])


def test_repo_baselines_are_seeded_and_pass():
    """The committed benchmarks/baselines/ match the committed BENCH
    artifacts (same rev), so the gate passes out of the box."""
    import os

    from repro.telemetry.regress import run_check

    root = os.path.join(os.path.dirname(__file__), "..")
    if not os.path.isdir(os.path.join(root, "benchmarks", "baselines")):
        pytest.skip("baselines not seeded")
    entry = run_check(root, os.path.join(root, "benchmarks", "baselines"),
                      None)                            # no ledger append
    assert entry["status"] == "pass"
    assert entry["metrics_checked"] >= 20
