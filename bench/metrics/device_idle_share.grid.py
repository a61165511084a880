"""`device_idle_share` of the grid cell, where it moves
`grid_rounds_per_s`: the same reading as `device_idle_share.py`."""
import os

from bench.harness import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
read = load_module(os.path.join(HERE, "device_idle_share.py"),
                   "bench_metric_device_idle_share_base").read
