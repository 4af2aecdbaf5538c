"""99th percentile, over answered requests, of the time from submission
to the request's first step (the scheduler's ``t_start - t_submit``)."""


def read(ctx):
    return ctx.counters.get("queue_ms_p99")
