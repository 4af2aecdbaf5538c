"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload jsc-lg.offline --seed 7 --seconds 10 \
        --trace 0

``BENCHMARK.json`` names the cells; ``bench/harness.py`` says how a cell's
files are found.  The run needs a TPU with as many chips as the cell asks
for and exits non-zero without printing a result where JAX finds none.
``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled window.  Standard error ends with each
number the correctness check compared, beside its limit.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    return harness.main(args.workload, args.seed, args.seconds,
                        bool(args.trace), t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
