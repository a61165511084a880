"""Device idle time inside the program's `repro.setup` host span
(`federated.server.setup_run`: the partition, the padded client stacks
and their placement, model and selector init, the tables), in ms per
call of the traced window (`spans.py`)."""
from bench import spans


def read(ctx):
    return spans.idle_ms_per_call(ctx.summary, "repro.setup")
