"""Operations and bytes a federated round needs, from its shapes.

Counts are of the work the protocol requires, whatever implements it:
multiply-adds of the model's layers times 2 per row (the configuration's
plain model counts them: `configs/<name>.py` `macs_per_row` and
`n_params`); local training as forward plus backward, 3x the forward;
utility forwards as many as the run's own count of utility evaluations
says, each over the validation set; and at eval rounds a forward over
the test set and one over the validation set.
"""
from __future__ import annotations


def _model(config: dict):
    from bench.reference import load_model
    return load_model(config)


def macs_per_row(config: dict) -> int:
    """Multiply-adds of one forward pass of one input row."""
    return int(_model(config).macs_per_row(config))


def n_params(config: dict) -> int:
    """Weights and biases of the model: D, the length of one upload."""
    return int(_model(config).n_params(config))


def run_flops(config: dict, fl: dict, utility_evals: int,
              eval_rounds: int) -> float:
    """FLOPs one whole run requires: `utility_evals` is the run's own
    count of utility evaluations (GTG), `eval_rounds` its evals."""
    fwd = 2 * macs_per_row(config)
    train = (3 * fwd * fl["m"] * fl["rounds"] * fl["epochs"]
             * fl["batches_per_epoch"] * fl["batch_size"])
    utility = fwd * fl["n_val"] * utility_evals
    evals = fwd * (fl["n_test"] + fl["n_val"]) * eval_rounds
    return float(train + utility + evals)


def prefix_avg_work(config: dict, walks: int, m: int) -> tuple:
    """(FLOPs, bytes) of one round's prefix models: R*M running-sum
    averages of D-wide f32 rows.  Each prefix model takes a multiply-add
    of one client row and a divide (3 FLOPs an element); the least
    traffic reads each of the M client rows once and writes R*M models."""
    d = n_params(config)
    flops = 3 * walks * m * d
    nbytes = 4 * d * (m + walks * m)
    return float(flops), float(nbytes)


def delta_codec_work(config: dict, rows: int) -> tuple:
    """(FLOPs, bytes) of one codec roundtrip over `rows` deltas: read and
    write each f32 element once; a handful of operations an element
    (abs-max, quantise, dequantise, the top-k compare passes aside)."""
    d = n_params(config)
    return float(4 * rows * d), float(2 * 4 * rows * d)


def eval_rounds(rounds: int, eval_every: int) -> int:
    """Rounds that evaluate: every eval_every-th and the last."""
    return rounds // eval_every + (0 if rounds % eval_every == 0 else 1)
