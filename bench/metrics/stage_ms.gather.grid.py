"""Device time of the ops under the round body's `repro.gather` scope
(`telemetry.trace.named_stage("gather")` around the cohort gather of the
selected clients' stacks), in ms per replica-round of the traced window,
averaged over chips.  The scope lies inside `repro.train`, so this time
counts in `stage_ms.train.grid` too."""


def read(ctx):
    seconds = ctx.summary.scope_s("repro.gather")
    if seconds <= 0 or ctx.rounds <= 0:
        return None
    return 1e3 * seconds / ctx.rounds
