"""Device idle time inside no `repro.*` host span of the program, in ms
per call of the traced window (`spans.py`): host work outside every
layer's span (`setup`, `operands`, `scan` / `segment`, `results`), the
harness's own between calls included, shows here."""
from bench import spans


def read(ctx):
    return spans.unspanned_idle_ms_per_call(ctx.summary)
