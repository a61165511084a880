"""One run of one cell: set-up, the measured window, the comparison.

Everything a cell is made of is found by name from files:
`BENCHMARK.json` names the cell's configuration and traffic; the
configuration's file is `configs/<name>.json` (its plain model beside it
as `configs/<name>.py`), the traffic's is `traffic/<name>.json`, the
comparison's valued rounds and limits are `checks/<cell>.json`, and each
per-layer metric is read by `metrics/<metric>.py`.  Adding a cell, a
configuration, a traffic mix or a metric adds files and entries only.

A run:
1. makes the cell's data and the runs of one pass from the traffic and
   `--seed` (`traffic.py`);
2. warms up with one whole pass: each call of the entry at the cell's
   shapes, with an in-memory telemetry sink that records each round's
   cohort, Shapley values and counters for the comparison (the same
   compiled programs the window runs: telemetry changes what the host
   keeps, not the program); everything up to here is `setup_s`;
3. measures whole passes back to back until `seconds` have passed
   (the rate: rounds of every run finished in the window over its wall
   time), or, with `trace`, traces `TRACED_PASSES` whole passes and
   reads the per-layer metrics from the device trace;
4. reads the peak device memory, frees the system's state, and compares
   what the warm-up pass produced with the plain reference
   (`reference.py`, `compare.py`), and the window's last pass with the
   warm-up pass bitwise.
"""
from __future__ import annotations

import gc
import importlib.util
import io
import json
import os
import shutil
import sys
import time
import traceback
from typing import Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
WORK = os.path.join(ROOT, ".bench_cache")    # git-ignored, fixed path
HLO_DUMP = os.path.join(WORK, "hlo")
TRACED_PASSES = 1
GIB = 1024 ** 3


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(name: str, bench: Optional[dict] = None) -> dict:
    """The cell's entry, configuration, traffic and check, by name."""
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    wl = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(ROOT, configs[wl["config"]]["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     wl["traffic"] + ".json"))
    check = load_json(os.path.join(BENCH, "checks", name + ".json"))
    return {"workload": wl, "config": config, "traffic": traffic,
            "check": check}


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def end_to_end(bench: dict, cell: str) -> list:
    """The end-to-end metrics this cell reports.  The harness measures
    three quantities and names each by its unit: the window's rate of
    finished rounds (`rounds/s`), the device's peak memory (`GiB`) and
    the set-up time (`s`)."""
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def metric_readers(bench: dict, cell: str) -> dict:
    """{metric: (entry, read function)} of the per-layer metrics this cell
    reports, each read by `metrics/<name>.py`."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        mod = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"),
                          "bench_metric_" + m["name"].replace(".", "_"))
        out[m["name"]] = (m, mod.read)
    return out


def require_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU: platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs


def prepare_env(trace: bool) -> None:
    """Environment a run needs before JAX starts: the TPU runtime's logs
    under the checkout and, in a traced run, XLA's dump of the system's
    compiled run and segment programs (appended to XLA_FLAGS)."""
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(WORK, "tpu_logs"))
    if trace:
        shutil.rmtree(HLO_DUMP, ignore_errors=True)
        os.environ["XLA_FLAGS"] = " ".join(filter(None, [
            os.environ.get("XLA_FLAGS", ""), f"--xla_dump_to={HLO_DUMP}",
            "--xla_dump_hlo_as_text",
            "--xla_dump_hlo_module_re=.*(run_scan|segment_step).*"]))


def use_work_dirs(trace: bool = False) -> None:
    """JAX's compile cache under the checkout, at a fixed path.  A traced
    run compiles afresh instead, so that XLA dumps what it compiles."""
    import jax
    os.makedirs(WORK, exist_ok=True)
    if trace:
        jax.config.update("jax_enable_compilation_cache", False)
        return
    jax.config.update("jax_compilation_cache_dir", os.path.join(WORK, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def peak_bytes(device) -> int:
    """The device's peak memory: its buffers' peak (`peak_bytes_in_use`)
    plus the compiled programs' scratch, which the TPU runtime reserves
    apart from buffers and counts only in `peak_bytes_reserved`."""
    stats = device.memory_stats() or {}
    return (int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0)))


def _malformed(results: list, group: list, rounds: int,
               finite: bool = False) -> bool:
    """A result with the wrong number of runs or rounds, or (`finite`)
    weights that are not finite."""
    import jax
    import numpy as np
    if len(results) != len(group):
        return True
    if any(len(r.selections) != rounds for r in results):
        return True
    return finite and not all(
        np.all(np.isfinite(np.asarray(l)))
        for r in results for l in jax.tree.leaves(r.params))


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, cell=None,
             err=sys.stderr) -> dict:
    """One run of cell `name`; returns the result line's object.  `cell`
    (as `find_cell` returns it) stands in for the files, in tests."""
    import jax
    from repro.federated.compression import TOPK_FRAC
    from repro.telemetry.events import Telemetry

    from bench import compare, reference
    from bench.traffic import make_plan, merged_fl

    bench = benchmark()
    c = cell or find_cell(name, bench)
    wl, config, traffic, check = (c["workload"], c["config"], c["traffic"],
                                  c["check"])
    devs = require_chips(wl["chips"]) if require_tpu else jax.devices()
    fl = merged_fl(config, traffic)
    if fl["topk_frac"] != TOPK_FRAC:
        raise ValueError(f"configuration keeps {fl['topk_frac']} of a "
                         f"leaf; the system keeps {TOPK_FRAC}")

    plan = make_plan(config, traffic, seed)
    groups = plan.groups()
    warm, progs = [], []
    for group in groups:
        tel = Telemetry(stream=io.StringIO(), heartbeat_every_s=1e9)
        res = plan.call(group, telemetry=tel)
        if _malformed(res, group, plan.rounds, finite=True):
            raise RuntimeError("a warm-up call returned a malformed result")
        warm += res
        progs += [compare.prog_out(r, tel.events,
                                   None if plan.runner == "solo" else i)
                  for i, r in enumerate(res)]
    setup_s = time.perf_counter() - t_start

    tdir = os.path.join(WORK, "trace")
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir)
    attempted = failed = passes_ok = 0
    last = None
    t0 = time.perf_counter()
    while True:
        done, whole = [], True
        for group in groups:
            with jax.profiler.TraceAnnotation("bench.call"):
                try:
                    res = plan.call(group)
                    if _malformed(res, group, plan.rounds):
                        whole = False
                        failed += len(group)
                        print("a window call returned a malformed result",
                              file=err)
                    else:
                        done += res
                except Exception:
                    whole = False
                    failed += len(group)
                    traceback.print_exc(file=err)
            attempted += len(group)
        if whole:
            passes_ok += 1
            last = done
        del done
        elapsed = time.perf_counter() - t0
        if (attempted >= TRACED_PASSES * plan.runs if trace
                else elapsed >= seconds):
            break
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    if last is not None and _malformed(last, plan.cfgs, plan.rounds,
                                       finite=True):
        failed += plan.runs
        passes_ok -= 1
        print("the window's last pass returned weights that are not "
              "finite", file=err)
    rounds_done = passes_ok * plan.runs * plan.rounds
    peak = max(peak_bytes(d) for d in devs[:wl["chips"]])

    # what the comparison reads, on the host; then free the system's state
    window_mismatch = (sum(compare.outputs_differ(a, b)
                           for a, b in zip(warm, last))
                       if last is not None else compare.UNREAD)
    counters = {k: sum(getattr(r, k) for r in warm) / len(warm)
                for k in ("shapley_evals", "upload_bytes", "download_bytes",
                          "quarantined_total")}
    del warm, last
    gc.collect()

    proto = reference.Protocol.of(config, traffic)
    per_run = []
    for cfg, prog in zip(plan.cfgs, progs):
        valued = compare.valued_rounds(prog, proto, check["sv_rounds"],
                                       seed, cfg.seed)
        ref = reference.run(
            config, traffic, plan.data, cfg.seed, valued=valued,
            cohorts=prog.selections if proto.selector == "greedyfed"
            else None)
        per_run.append(compare.numbers(prog, ref, proto))
    nums = compare.combine(per_run)
    nums["window_mismatch"] = window_mismatch
    limits = check["limits"]
    correct = (failed == 0 and passes_ok > 0
               and compare.verdict(nums, limits))

    if trace:
        metrics = per_layer(bench, name, c, fl, plan, tdir, window_s,
                            rounds_done, counters, devs[:wl["chips"]])
        shutil.rmtree(tdir, ignore_errors=True)
        shutil.rmtree(HLO_DUMP, ignore_errors=True)
    else:
        measured = {"rounds/s": rounds_done / window_s, "GiB": peak / GIB,
                    "s": setup_s}
        metrics = {m["name"]: {"value": measured[m["unit"]],
                               "unit": m["unit"]}
                   for m in end_to_end(bench, name)}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": wl["chips"] if require_tpu else len(devs),
              "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = metrics.pop("_busy_s")
        device["window_s"] = metrics.pop("_window_s")
        out["breakdown"] = metrics.pop("_breakdown")
    out["checks"] = {k: {"value": nums[k], "limit": limits.get(k)}
                     for k in sorted(nums)}
    return out


def per_layer(bench, name, cell, fl, plan, tdir, window_s, rounds_done,
              counters, devs) -> dict:
    """The cell's per-layer metrics from the trace of the window."""
    from bench import trace_reduce
    from bench.peaks import peaks_for

    summary = trace_reduce.load(tdir, n_devices=len(devs),
                                scopes=trace_reduce.hlo_scopes(HLO_DUMP))
    ctx = trace_reduce.Context(
        summary=summary, cell=name, config=cell["config"],
        traffic=cell["traffic"], fl=fl, replicas=plan.runs,
        rounds=rounds_done, window_s=window_s,
        utility_evals_per_run=counters["shapley_evals"], counters=counters,
        peaks=peaks_for(devs[0].device_kind), chips=len(devs))
    out = {}
    for metric, (entry, read) in metric_readers(bench, name).items():
        value = read(ctx)
        if value is not None:
            out[metric] = {"value": value, "unit": entry["unit"]}
    out["_busy_s"] = summary.busy_s
    out["_window_s"] = summary.window_s
    out["_breakdown"] = summary.breakdown()
    return out


def report(out: dict, err=sys.stderr) -> None:
    """Each number compared beside its limit: the last lines of stderr."""
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}", file=err)
    err.flush()
