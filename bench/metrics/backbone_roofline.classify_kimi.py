"""Share of the Kimi Linear classify step's roofline: the mean least time
of the window's steps, each at its own (batch, length) shape
(``bench/work_kimi.py``: operations over the bf16 peak, bytes over HBM
bandwidth, whichever is larger), times the traced calls of the jitted
step (module ``jit_traced_classify``), over their device time, per chip.

The shapes come from the program's step log (``bucket`` and
``bucket_tokens`` of the window's newest ``counters["steps"]`` records);
nothing to read (None) without a trace of the step, or in a program whose
step log keeps no token counts.
"""

from bench import work_kimi


def read(ctx):
    t = ctx.trace
    if t is None or not t.forward_calls or t.forward_s <= 0:
        return None
    try:
        from repro.serving import steplog
    except ImportError:
        return None
    recs = steplog.last(ctx.counters.get("steps", 0))
    if not recs or not hasattr(recs, "bucket_tokens") \
            or not recs.bucket_tokens.all():
        return None
    least = [work_kimi.least_step_s(ctx.config, int(b), int(bt // b),
                                    ctx.peaks)
             for b, bt in zip(recs.bucket, recs.bucket_tokens)]
    return (sum(least) / len(least) * t.forward_calls / t.forward_s
            * 100.0)
