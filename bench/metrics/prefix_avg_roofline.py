"""Roofline share of the `prefix_avg` Pallas kernel (the GTG walks'
running-sum prefix models), in %.

Work from the walk shapes (`flops.prefix_avg_work`), for the prefix
models the runs of the traced window built: their utility evaluations
less the two per round that value the old and the new model.  The
least time is the larger of FLOPs over the bf16 peak and bytes over the
HBM bandwidth; the share is that over the kernel's device time.  The
bytes bound it: 3 FLOPs against 4 bytes an element.  Leaves under the
kernel's block width (the biases, the MLP head) are built outside the
kernel but counted in the work: at most 1% of D for the cells here."""
from bench import flops


def read(ctx):
    seconds = ctx.summary.kernel_s("prefix_avg")
    if seconds <= 0 or ctx.rounds <= 0:
        return None
    fl = ctx.fl
    m, walks = fl["m"], fl["walks_per_client"] * fl["m"]
    runs = ctx.rounds / fl["rounds"]
    models = (ctx.utility_evals_per_run - 2 * fl["rounds"]) * runs
    valued_rounds = models / (walks * m)
    f1, b1 = flops.prefix_avg_work(ctx.config, walks, m)
    least = max(f1 * valued_rounds / ctx.peaks.flops,
                b1 * valued_rounds / ctx.peaks.hbm_bw)
    return 100.0 * least / seconds
