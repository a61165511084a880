"""`mfu` of the grid cell, where it moves
`grid_rounds_per_s`: the same reading as `mfu.py`."""
import os

from bench.harness import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
read = load_module(os.path.join(HERE, "mfu.py"),
                   "bench_metric_mfu_base").read
