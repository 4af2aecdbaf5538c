"""Real prompt tokens over padded step tokens (batch x length), in %,
summed over the window's steps in the program's step log.

The window's steps are the newest ``counters["steps"]`` records: the
closed loop counts the steps from the window's first launch to its
drain, and none runs after it.  Nothing to read (None) where no step ran,
where the ring no longer holds them, or in a program whose step log
keeps no token counts.
"""


def read(ctx):
    try:
        from repro.serving import steplog
    except ImportError:
        return None
    recs = steplog.last(ctx.counters.get("steps", 0))
    if not recs or not hasattr(recs, "token_occupancy_pct"):
        return None
    return recs.token_occupancy_pct()
