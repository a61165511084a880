"""Device time of the ops under the round body's `repro.shapley` scope
(`telemetry.trace.named_stage("shapley")`), in ms per round of the traced
window (a grid's rounds count once per replica), averaged over chips."""


def read(ctx):
    seconds = ctx.summary.scope_s("repro.shapley")
    if seconds <= 0 or ctx.rounds <= 0:
        return None
    return 1e3 * seconds / ctx.rounds
