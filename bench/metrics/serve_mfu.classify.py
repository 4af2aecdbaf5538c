"""The whole served step's share of the chips' bf16 peak: the backbone's
operations for each prompt answered in the window, at its own length
(``bench/work_granite.py``, no padding), over the window's seconds, over
chips times the peak."""

from bench import work_granite


def read(ctx):
    lengths = ctx.counters.get("served_lengths")
    if not lengths:
        return None
    flops = sum(work_granite.sequence_flops(ctx.config, n) for n in lengths)
    return (flops / ctx.counters["window_s"]
            / (ctx.chips * ctx.peaks["bf16_flops_per_s"]) * 100.0)
