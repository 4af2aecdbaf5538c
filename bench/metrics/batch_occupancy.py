"""Real rows over padded bucket rows, in %, summed over the window's steps
in the program's step log.

The window's steps are the newest ``counters["steps"]`` records: the
closed loop counts the steps from the window's first launch to its
drain, and none runs after it.  Nothing to read (None) where no step ran, where the
ring no longer holds them, or in a program without the step log.
"""


def read(ctx):
    try:
        from repro.serving import steplog
    except ImportError:
        return None
    recs = steplog.last(ctx.counters.get("steps", 0))
    if not recs:
        return None
    return recs.occupancy_pct()
