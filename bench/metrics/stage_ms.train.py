"""Device time of the ops under the round body's `repro.train` scope
(`telemetry.trace.named_stage("train")`), in ms per round of the traced
window (a grid's rounds count once per replica), averaged over chips.
The upload codec's `repro.codec` scope lies inside `repro.train`, so its
time counts here too."""


def read(ctx):
    seconds = ctx.summary.scope_s("repro.train")
    if seconds <= 0 or ctx.rounds <= 0:
        return None
    return 1e3 * seconds / ctx.rounds
