"""The harness finds every part of a cell by name from its files alone,
a cell can be added by adding files, and `run.py` refuses a CPU."""
import json
import os
import re
import shutil
import subprocess
import sys

from bench import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    return harness.benchmark()


def test_benchmark_file_keeps_the_contract_shape():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"]
    assert b["command"][1].startswith("bench/")
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and NAME.match(c["name"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], []).append(m["name"])
        for w in m["workloads"]:
            assert w in {x["name"] for x in b["workloads"]}
            # each cell that reads the metric reports what it moves
            assert w in e2e[m["moves"]].get("workloads", [w])
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}


def test_every_cell_is_found_by_name():
    b = _bench()
    for w in b["workloads"]:
        c = harness.find_cell(w["name"], b)
        cfg = c["config"]
        entry = {x["name"]: x for x in b["configs"]}[w["config"]]
        assert cfg["name"] == w["config"]
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        assert os.path.exists(os.path.join(harness.BENCH, "configs",
                                           cfg["name"] + ".py"))
        assert c["traffic"]["runner"] in ("solo", "grid")
        assert c["traffic"]["program_seeds"]
        check = c["check"]
        assert 0 <= check["sv_rounds"] <= cfg["fl"]["rounds"]
        assert "window_mismatch" in check["limits"]
        readers = harness.metric_readers(b, w["name"])
        assert readers, "every cell reports a per-layer metric"
        assert all(callable(read) for _, read in readers.values())
        e2e = [m["name"] for m in b["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2


def test_a_cell_is_added_by_files_alone(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "traffic" / "stragglers.json").write_text(
        json.dumps({"runner": "solo", "program_seeds": [0, 1],
                    "fl": {"selector": "greedyfed",
                           "upload_codec": "identity",
                           "straggler_frac": 0.3}}))
    (tmp_path / "bench" / "checks" / "mnist-mlp.stragglers.json").write_text(
        json.dumps({"sv_rounds": 1, "limits": {"sel_mismatch": 0}}))
    (tmp_path / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return None\n")
    b["workloads"].append({"name": "mnist-mlp.stragglers",
                           "config": "mnist-mlp", "traffic": "stragglers",
                           "chips": 1, "why": "masked straggler epochs"})
    rate = {m["name"]: m for m in b["end_to_end"]}["rounds_per_s"]
    rate["workloads"].append("mnist-mlp.stragglers")
    b["per_layer"].append({"name": "new_metric", "unit": "%",
                           "better": "higher", "source": "device_trace",
                           "layer": "scan body", "moves": "rounds_per_s",
                           "workloads": ["mnist-mlp.stragglers"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    copy = harness.load_module(str(tmp_path / "bench" / "harness.py"),
                               "bench_harness_copy")
    bb = copy.benchmark()
    c = copy.find_cell("mnist-mlp.stragglers", bb)
    assert c["traffic"]["fl"]["straggler_frac"] == 0.3
    assert c["config"]["name"] == "mnist-mlp"
    readers = copy.metric_readers(bb, "mnist-mlp.stragglers")
    assert list(readers) == ["new_metric"]
    assert [m["name"] for m in copy.end_to_end(bb, "mnist-mlp.stragglers")
            ] == ["rounds_per_s", "peak_hbm_gib", "setup_s"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mnist-mlp.greedyfed",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_cpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_run_refuses_a_bare_benchmark_directory(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()
