#!/usr/bin/env python3
"""Read the two ends that a cell's limits are set between, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,... \
        --control-seeds 7,8,9

For each of `--seeds`: the cell's warm-up pass (one whole call of the
entry per call of a pass, as the benchmark makes it) compared with the
plain reference as a run of that seed compares it, which gives the
lower readings.  For each of `--control-seeds`: the control (the
reference computed in bfloat16, in the system's place) and each planted
fault (`reference.FAULTS`, in the reference put in the system's place)
compared with the reference, which give the upper readings; and, for
GreedyFed, the system's own values run through a wrong greedy rule
(`SELECTION_FAULTS`), read by `greedy_gap`.  One JSON line per
comparison on stdout.  The benchmark's own runs never run this.
"""
import argparse
import io
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SELECTION_FAULTS = ("argmin", "sum", "slot")


def as_prog(r, compare):
    """A reference run, read as the system's output."""
    return compare.ProgOut(
        selections=r.selections, sv=r.sv, truncated=r.truncated,
        utility_evals=r.utility_evals, evals=r.evals, params=r.params,
        upload_bytes=r.upload_bytes, download_bytes=r.download_bytes)


def wrong_greedy(prog, proto, rule: str) -> np.ndarray:
    """The cohorts a greedy phase with `rule` would take on the system's
    per-round values: `argmin` takes the M smallest cumulative values,
    `sum` ranks by their sum instead of their mean, `slot` alters one
    slot of the first greedy cohort."""
    n, m, rr = proto.n_clients, proto.m, proto.rr_rounds
    sel = np.array(prog.selections, np.int64)
    total = np.zeros(n)
    counts = np.zeros(n)
    for t in range(sel.shape[0]):
        if t >= rr:
            mean = total / np.maximum(counts, 1)
            if rule == "argmin":
                sel[t] = np.argsort(mean, kind="stable")[:m]
            elif rule == "sum":
                sel[t] = np.argsort(-total, kind="stable")[:m]
            elif rule == "slot" and t == rr:
                left = np.setdiff1d(np.arange(n), sel[t])
                sel[t, 0] = left[0]
        counts[sel[t]] += 1
        total[sel[t]] += prog.sv[t]
    return sel


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import compare, harness, reference
    from bench.traffic import make_plan

    harness.prepare_env(False)
    harness.require_chips(1)
    harness.use_work_dirs()
    from repro.telemetry.events import Telemetry

    c = harness.find_cell(args.workload)
    config, traffic, check = c["config"], c["traffic"], c["check"]
    proto = reference.Protocol.of(config, traffic)
    greedy = proto.selector == "greedyfed"
    progs = {}

    def program(plan, seed):
        """The system's runs of `seed`'s pass: {program seed: ProgOut};
        each run once per data seed in this process."""
        data_seed = traffic.get("data_seed", seed)
        out = {}
        for group in plan.groups():
            key = (data_seed, tuple(cfg.seed for cfg in group))
            if key not in progs:
                tel = Telemetry(stream=io.StringIO(), heartbeat_every_s=1e9)
                res = plan.call(group, telemetry=tel)
                progs[key] = [compare.prog_out(
                    r, tel.events, None if plan.runner == "solo" else i)
                    for i, r in enumerate(res)]
                del res
            out.update({cfg.seed: p for cfg, p in zip(group, progs[key])})
        return out

    def compared(seed, cfg, prog, **kw):
        valued = compare.valued_rounds(prog, proto, check["sv_rounds"],
                                       seed, cfg.seed)
        return reference.run(config, traffic, plan.data, cfg.seed,
                             valued=valued,
                             cohorts=prog.selections if greedy else None,
                             **kw)

    def emit(kind, seed, per_run, seconds):
        print(json.dumps({"kind": kind, "seed": seed, "seconds": seconds,
                          **compare.combine(per_run)}), flush=True)

    for seed in [int(s) for s in args.seeds.split(",") if s]:
        plan = make_plan(config, traffic, seed)
        t = time.perf_counter()
        runs = program(plan, seed)
        prog_s = time.perf_counter() - t
        t = time.perf_counter()
        per = [compare.numbers(runs[cfg.seed],
                               compared(seed, cfg, runs[cfg.seed]), proto)
               for cfg in plan.cfgs]
        emit("program", seed, per, time.perf_counter() - t)
        print(json.dumps({"kind": "program_s", "seed": seed,
                          "seconds": prog_s}), flush=True)

    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        plan = make_plan(config, traffic, seed)
        runs = program(plan, seed)
        refs = {cfg.seed: compared(seed, cfg, runs[cfg.seed])
                for cfg in plan.cfgs}
        for kind, kw in [("control", {"precision": "bfloat16"})] + [
                (f, {"fault": f}) for f in reference.FAULTS]:
            t = time.perf_counter()
            per = []
            for cfg in plan.cfgs:
                other = compared(seed, cfg, runs[cfg.seed], **kw)
                nums = compare.numbers(as_prog(other, compare),
                                       refs[cfg.seed], proto)
                # the cohorts are the system's: its greedy choice is not
                # the reference's to read here
                nums.pop("greedy_gap", None)
                per.append(nums)
            emit(kind, seed, per, time.perf_counter() - t)
        if greedy:
            for rule in SELECTION_FAULTS:
                per = [{"greedy_gap": compare.greedy_gap(
                    wrong_greedy(p, proto, rule), p.sv, proto.n_clients,
                    proto.rr_rounds)} for p in runs.values()]
                emit(rule, seed, per, 0.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
