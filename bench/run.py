#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process starts on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The last line of standard output is one
JSON object: `correct`, `attempted`, `failed`, `metrics` (the cell's
end-to-end metrics, or with `--trace 1` its per-layer metrics), `device`,
with `--trace 1` a `breakdown`, and last `checks`, each number the
comparison made beside its limit; the same numbers are the last lines of
standard error.  Without a TPU, or with fewer chips than the cell asks
for, it exits with code 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    # before JAX starts: the TPU runtime's logs under the checkout, and in
    # a traced run the HLO dump that names each op's scope
    harness.prepare_env(bool(args.trace))
    try:
        harness.require_chips(1)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.use_work_dirs(bool(args.trace))
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.report(out)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
