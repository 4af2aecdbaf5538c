"""Read the numbers the correctness check compares, for setting limits.

    python3 bench/calibrate.py --workload jsc-lg.offline --seeds 1,2,3 \
        --seconds 3

For each seed, in one process: set the cell up as a run does, run a
window at the cell's own load, and print one JSON line with

* ``program``: the numbers ``check()`` compares for the sound program
  (their largest over a dozen seeds is a limit's lower reading);
* ``control``: the same numbers with the plain reference, computed in the
  precision below the configuration's (bfloat16 for float32), put in the
  program's place, on the same rows (their smallest is the upper
  reading).

Needs the chip, like ``run.py``.
"""

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def serve_readings(session) -> dict:
    from bench import reference, serving
    program = {c.name: c.value for c in session.check()}
    th, mapping, tables = session.weights
    picked = session.sample.items if hasattr(session, "sample") else \
        session.answered
    rows = [session.payloads[k] for k, _ in picked]
    x = np.concatenate(rows)
    ctl_c, ctl_p = reference.infer(x, th, mapping, tables,
                                   session.cell.config["classes"],
                                   dtype=reference.bf16())
    control = serving.compare_answers(
        session.weights, session.cell.config, [x],
        [(ctl_c, ctl_p)])
    return {"program": program,
            "control": {c.name: c.value for c in control},
            "rows_checked": int(x.shape[0])}


def main(argv=None) -> int:
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.load_benchmark(), args.workload)
    harness.enable_compile_cache()
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    null = lambda name: contextlib.nullcontext()
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        session = harness.driver(cell).setup(cell, seed, args.seconds, null)
        session.window(args.seconds)
        session.finish()
        out = serve_readings(session)
        print(json.dumps(dict(seed=seed, wall_s=time.perf_counter() - t,
                              **out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
