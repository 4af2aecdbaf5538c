"""Share of the KDA recurrence's roofline in the Kimi Linear classify
cell: the mean least time of the window's steps' KDA recurrences, each
step at its own (batch, length) shape (``bench/work_kimi.py``:
operations over the bf16 peak, bytes over HBM bandwidth, whichever is
larger), times the traced calls of the jitted step (module
``jit_traced_classify``), over the device seconds of the trace's
operations whose name contains ``kda_chunk`` (the Pallas kernel), per
chip.

Nothing to read (None) without a trace of the step, in a program whose
step log keeps no token counts, or in one that runs no ``kda_chunk``.
"""

from bench import work_kimi

KERNEL = "kda_chunk"


def read(ctx):
    t = ctx.trace
    if t is None or not t.forward_calls:
        return None
    kernel_s = sum(s for name, s in t.ops.items() if KERNEL in name)
    if kernel_s <= 0:
        return None
    try:
        from repro.serving import steplog
    except ImportError:
        return None
    recs = steplog.last(ctx.counters.get("steps", 0))
    if not recs or not hasattr(recs, "bucket_tokens") \
            or not recs.bucket_tokens.all():
        return None
    least = [work_kimi.kda_least_step_s(ctx.config, int(b), int(bt // b),
                                        ctx.peaks)
             for b, bt in zip(recs.bucket, recs.bucket_tokens)]
    return sum(least) / len(least) * t.forward_calls / kernel_s * 100.0
