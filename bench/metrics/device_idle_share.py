"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips: 100 * (1 - busy / window), where busy is
the union of the device's op intervals.  Stands in for the host layer
(`setup_run`, the grid's stacking), which has no span of its own."""


def read(ctx):
    s = ctx.summary
    if s.window_s <= 0 or not s.ops:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
