"""`host_idle_ms.unspanned` of the grid cell, where it moves
`grid_rounds_per_s`: the same reading as `host_idle_ms.unspanned.py`."""
import os

from bench.harness import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
read = load_module(os.path.join(HERE, "host_idle_ms.unspanned.py"),
                   "bench_metric_host_idle_ms_unspanned_base").read
