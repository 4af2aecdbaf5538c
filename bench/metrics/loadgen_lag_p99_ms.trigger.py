"""99th percentile of how late the load generator submitted a request
after its intended arrival (host clock)."""


def read(ctx):
    return ctx.counters.get("loadgen_lag_p99_ms")
