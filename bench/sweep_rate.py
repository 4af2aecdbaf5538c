"""Find the knee of an open-loop cell once: its highest sustained rate.

    python3 bench/sweep_rate.py --workload jsc-sm.trigger --seed 1 \
        --seconds 10 --rates 50000,100000,150000,200000

Builds the cell's engine once, then runs one window per offered rate
(samples/s) with the cell's own mix otherwise, and prints one JSON line
per rate: the share of requests answered within the deadline, the p99
latency from intended arrival (unanswered requests ranked last), the
generator's lag, the scheduler's queue, and the samples served per
second.  The knee is the highest rate at which at least 99% of requests
meet the deadline and the queue does not grow; the cell's mix then fixes
its rate at about 4/5 of it.  The cell has to be in ``BENCHMARK.json``
(with any rate) for the sweep to find it.  Needs the chip, like
``run.py``.
"""

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, samples/s")
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.load_benchmark(), args.workload)
    harness.enable_compile_cache()
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    null = lambda name: contextlib.nullcontext()
    session = harness.driver(cell).setup(cell, args.seed, args.seconds, null)
    for rate in [float(r) for r in args.rates.split(",")]:
        session.plan(args.seconds, rate)
        t = time.perf_counter()
        win = session.window(args.seconds)
        c = win.counters
        print(json.dumps({
            "rate_samples_per_s": rate, "attempted": win.attempted,
            "failed": win.failed, "ok_share": c["ok_share"],
            "p99_ms": win.metrics["serve_p99_ms"],
            "queue_ms_p99": c["queue_ms_p99"],
            "loadgen_lag_p99_ms": c["loadgen_lag_p99_ms"],
            "queue_depth_max_samples": c["queue_depth_max_samples"],
            "step_ms": c["busy_s"] / max(1, c["steps"]) * 1e3,
            "served_samples_per_s": c["served_samples"] / args.seconds,
            "wall_s": time.perf_counter() - t}), flush=True)
    session.finish()
    return 0


if __name__ == "__main__":
    sys.exit(main())
