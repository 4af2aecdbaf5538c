"""Mean wall time of one engine step (``ServingEngine._dwn_step``: copy
in, forward, copy out), from the scheduler's own counters over the
window: busy seconds over steps."""


def read(ctx):
    c = ctx.counters
    if not c.get("steps"):
        return None
    return c["busy_s"] / c["steps"] * 1e3
