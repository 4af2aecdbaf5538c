"""The whole served step's share of the chips' int8 peak: the DWN's
operations per sample (``bench/work.py``) times the samples served per
second in the traced window, over chips times the peak."""

from bench import work


def read(ctx):
    rate = ctx.e2e.get("serve_samples_per_s")
    if not rate:
        return None
    ops = work.infer_ops_per_sample(ctx.config) * rate
    return ops / (ctx.chips * ctx.peaks["int8_ops_per_s"]) * 100.0
