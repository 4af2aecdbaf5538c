"""Share of the served forward's roofline: the least time the chip could
take for the traced forward calls (``bench/work.py``) over the device
time of the jitted backend step's operations, per chip."""

from bench import work


def read(ctx):
    t = ctx.trace
    if t is None or not t.forward_calls or t.forward_s <= 0:
        return None
    rows = ctx.counters["bucket_rows"] // ctx.chips
    least, _ = work.least_time_s(
        rows * work.infer_ops_per_sample(ctx.config),
        work.infer_bytes_per_call(ctx.config, rows), ctx.peaks)
    return t.forward_calls * least / t.forward_s * 100.0
