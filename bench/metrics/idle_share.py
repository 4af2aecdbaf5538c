"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return (1.0 - t.busy_s / t.window_s) * 100.0
