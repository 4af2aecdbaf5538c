"""Share of the window's engine steps launched while the step before them
was still in flight (the ``overlapped`` column of the program's step
log), in %.

The window's steps are the newest ``counters["steps"]`` records: the
closed loop counts the steps from the window's first launch to its
drain, and none runs after it.  Nothing to read (None) where no step ran,
where the ring no longer holds them, or in a program whose step log has
no such column.
"""


def read(ctx):
    try:
        from repro.serving import steplog
    except ImportError:
        return None
    recs = steplog.last(ctx.counters.get("steps", 0))
    if not recs or not hasattr(recs, "overlap_pct"):
        return None
    return recs.overlap_pct()
