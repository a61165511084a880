"""Device idle time inside the program's `repro.operands` host span
(`grid.runner._build_batch`: stacking the cells' client stacks and
tables along the replica axis and placing them), in ms per call of the
traced window (`spans.py`)."""
from bench import spans


def read(ctx):
    return spans.idle_ms_per_call(ctx.summary, "repro.operands")
