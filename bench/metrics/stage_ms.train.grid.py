"""`stage_ms.train` of the grid cell, where it moves
`grid_rounds_per_s`: the same reading as `stage_ms.train.py`."""
import os

from bench.harness import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
read = load_module(os.path.join(HERE, "stage_ms.train.py"),
                   "bench_metric_stage_ms_train_base").read
