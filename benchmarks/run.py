"""Benchmark harness: every benchmark family behind one command —
paper tables/figures, roofline, kernels, training, and the sweep smoke.

Usage: PYTHONPATH=src python -m benchmarks.run [--only table1,fig5]
Each benchmark prints ``name,us_per_call,derived`` CSV rows followed by its
markdown table.
"""

import argparse
import sys
import traceback


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (table1_hardware, table2_literature, table3_quantization,
                   cosim_smoke, fig2_encoding, fig5_breakdown, fig6_pareto,
                   roofline_report, kernels_bench, sweep_smoke, train_bench)
    benches = {
        "table1": table1_hardware.run,
        "table2": table2_literature.run,
        "table3": table3_quantization.run,
        "fig2": fig2_encoding.run,
        "fig5": fig5_breakdown.run,
        "fig6": fig6_pareto.run,
        "roofline": roofline_report.run,
        "kernels": kernels_bench.run,
        "sweep": sweep_smoke.run,
        "cosim": cosim_smoke.run,
        "train": train_bench.run,
    }
    only = [s for s in args.only.split(",") if s]
    failures = 0
    for name, fn in benches.items():
        if only and name not in only:
            continue
        print(f"\n{'=' * 70}\n== {name}\n{'=' * 70}", flush=True)
        try:
            fn()
        except Exception:
            failures += 1
            traceback.print_exc()
    print(f"\nbenchmarks done; failures={failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
