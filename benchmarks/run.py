"""Benchmark entry point: one function per paper table/figure + micro/roofline.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only table1,...]

Prints ``name,us_per_call,derived`` CSV for micro-benchmarks and the
accuracy tables for the paper reproductions.  Default (quick) mode scales
the paper protocol down for CPU (benchmarks/fl_common.py); --full uses the
paper's N=300/T=400.
"""
from __future__ import annotations

import argparse
import time

BENCHES = ["kernels", "engine", "table1", "table2", "table3", "table4",
           "fig1", "roofline"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale N=300/T=400 (hours on CPU)")
    ap.add_argument("--only", default=None,
                    help=f"comma list from {BENCHES}")
    ap.add_argument("--seeds", default="0,1")
    ap.add_argument("--engine", default="loop",
                    choices=["loop", "batched", "scan"],
                    help="table execution path; 'scan' fuses each cell's "
                         "seeds into one repro.grid dispatch")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    seeds = tuple(int(s) for s in args.seeds.split(","))
    only = args.only.split(",") if args.only else BENCHES

    t0 = time.time()
    if "kernels" in only:
        from benchmarks.kernel_bench import run as kb
        print("\n# micro-benchmarks (name,us_per_call,derived)")
        for row in kb():
            print(row)

    if "engine" in only:
        from benchmarks.engine_bench import run as eb
        print("\n# round engine: loop vs batched (name,us,derived)")
        for row in eb(full=args.full):
            print(row)

    fl = dict(full=args.full, seeds=seeds, engine=args.engine)
    if "table1" in only:
        from benchmarks.table1_data_heterogeneity import run as t1
        t1(**fl)
    if "table2" in only:
        from benchmarks.table2_timing_constraints import run as t2
        t2(**fl)
    if "table3" in only:
        from benchmarks.table3_stragglers import run as t3
        t3(**fl)
    if "table4" in only:
        from benchmarks.table4_privacy import run as t4
        t4(**fl)
    if "fig1" in only:
        from benchmarks.fig1_convergence import run as f1
        f1(full=args.full, seeds=seeds[:1])
    if "roofline" in only:
        from benchmarks.roofline_table import run as rt
        print("\n# roofline table (from experiments/dryrun — run "
              "`python -m repro.launch.dryrun` first)")
        rt()
    print(f"\n# total bench wall time: {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
