"""Operation and byte counts against hand-worked numbers; the peaks."""
import json
import os

import pytest

from bench import flops, peaks
from bench.trace_reduce import Context, Summary

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_macs_per_row():
    # 784*200 + 200*100 + 100*10
    assert flops.macs_per_row(_config("mnist-mlp")) == 177_800


def test_params():
    # 784*200+200 + 200*100+100 + 100*10+10
    assert flops.n_params(_config("mnist-mlp")) == 178_110


def test_prefix_avg_walk_work():
    # 150 walks of 3: 450 prefix models of D = 178,110 f32; 3 FLOPs an
    # element; read the 3 client rows once, write the 450 models
    f, b = flops.prefix_avg_work(_config("mnist-mlp"), 150, 3)
    assert f == 3 * 450 * 178_110
    assert b == 4 * 178_110 * 453


def test_delta_codec_work():
    f, b = flops.delta_codec_work(_config("mnist-mlp"), 24)
    assert b == 8 * 24 * 178_110


def test_run_flops_mlp_round():
    cfg = _config("mnist-mlp")
    fl = cfg["fl"]
    one = dict(fl, rounds=1)
    # one round, 452 utility forwards over 5,000 rows, no eval
    got = flops.run_flops(cfg, one, 452, 0)
    utility = 452 * 5000 * 2 * 177_800
    train = 3 * 2 * 177_800 * 3 * 25 * 32
    assert got == utility + train
    assert utility == pytest.approx(8.04e11, rel=1e-3)


def test_eval_rounds():
    assert flops.eval_rounds(400, 50) == 8
    assert flops.eval_rounds(10, 5) == 2
    assert flops.eval_rounds(10, 4) == 3


def test_peaks_lookup_raises_on_unknown_kind():
    assert peaks.peaks_for("TPU v5 lite").flops == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


def test_mfu_reader_on_hand_numbers():
    from bench.harness import BENCH, load_module
    cfg = _config("mnist-mlp")
    fl = dict(cfg["fl"], rounds=1, eval_every=1)
    ctx = Context(summary=Summary([], [], 1), cell="c", config=cfg,
                  traffic={}, fl=fl, replicas=1, rounds=10, window_s=1.0,
                  utility_evals_per_run=452, counters={},
                  peaks=peaks.peaks_for("TPU v5 lite"), chips=1)
    mfu = load_module(os.path.join(BENCH, "metrics", "mfu.py"), "m").read
    per_run = flops.run_flops(cfg, fl, 452, 1)
    assert mfu(ctx) == pytest.approx(100 * 10 * per_run / 197e12)
    # nothing traced: no reading, never a 0 share
    assert mfu(ctx._replace(rounds=0)) is None
    roof = load_module(os.path.join(BENCH, "metrics",
                                    "prefix_avg_roofline.py"), "r").read
    assert roof(ctx) is None
