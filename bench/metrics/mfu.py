"""Whole-run model FLOP utilization, in % of the chips' bf16 peak.

FLOPs the runs of the traced window require (`flops.run_flops`: local
training as forward + backward, the utility forwards the run itself
counted, the eval forwards), over the traced window's wall time, over
chips x peak.  Host set-up inside each call counts as time, as it does
for the cell's rate of rounds."""
from bench import flops


def read(ctx):
    fl, config = ctx.fl, ctx.config
    if ctx.window_s <= 0 or ctx.rounds <= 0:
        return None
    per_run = flops.run_flops(config, fl, ctx.utility_evals_per_run,
                              flops.eval_rounds(fl["rounds"],
                                                fl["eval_every"]))
    runs = ctx.rounds / fl["rounds"]
    return 100.0 * per_run * runs / ctx.window_s / (ctx.chips
                                                     * ctx.peaks.flops)
