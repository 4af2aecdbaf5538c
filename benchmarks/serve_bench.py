"""Serving benchmark: throughput + latency per backend per serving preset.

Serves an identical, seeded request stream through every registered DWN
datapath backend on each serving preset (JSC sm/md/lg plus the MNIST
sm/md rows — synthetic-fallback data in CI) via the ServingEngine,
and records throughput and p50/p99/p999 latency plus shed-rate and
queue-depth fields to ``BENCH_serve.json`` at the repo root — the
serving-level companion of ``BENCH_kernels.json``.  Rows share their
metric names with the open-loop latency–throughput curve that
``benchmarks/load_harness.py`` stores under ``"curve"`` in the same file
(this bench preserves that section when it rewrites the record; the
closed-loop rows here never shed, so their ``shed_rate`` is 0 by
construction).

The engine starts with ``backend="auto"`` and autotuning on, so the
fused-packed rows run the *tuned* kernel config for each bucket (rows
and LUTs per step from the persistent autotune cache, docs/autotune.md);
the chosen config is recorded per cell.

Per backend the engine first serves one warmup request so the
per-(backend, bucket) compile is excluded from the timed stream, matching
how a long-running server amortizes compiles.  Wall times on CPU are the
interpret-mode emulation for the Pallas backend; the cross-backend
*ordering* (packed vs float) is the TPU-relevant signal.

Regression gate: every cell is compared against the committed
``BENCH_serve.json``; if any *previously-winning* backend regresses by
more than 15% throughput, the flagged cells are **re-measured once**
(fresh engine, same seeded stream) and the bench exits non-zero only if
the second pass confirms the drop — interpret-mode wall times on a
shared 2-core CI host jitter up to ~2x run-to-run, so a single slow pass
is evidence of a noisy neighbor, not a regression.  Both passes are
recorded in the ``regression`` block.  Set ``SERVE_BENCH_NO_GATE=1`` to
record without gating, e.g. when moving the baseline to new hardware.
"""

import json
import os
import time

from .common import csv_row, ROOT

BENCH_JSON = ROOT / "BENCH_serve.json"

PRESETS = ("dwn-jsc-sm", "dwn-jsc-md", "dwn-jsc-lg")
#: second-workload rows (repro.workloads: synthetic fallback in CI).
#: Recorded alongside the JSC rows but *never* gated — the regression
#: gate below is scoped to dwn-jsc-* so MNIST rows can't fail a build
#: while their baselines settle.
MNIST_PRESETS = ("dwn-mnist-sm", "dwn-mnist-md")
REQUESTS = 32
BATCH = 64
REGRESSION_PCT = 15.0


def _stream(engine, rng_seed=0):
    """Serve the seeded REQUESTS x BATCH stream; returns (thru, lat)."""
    import numpy as np
    from repro.serving.scheduler import latency_stats
    rng = np.random.default_rng(rng_seed)
    t0 = time.perf_counter()
    for _ in range(REQUESTS):
        engine.submit(engine.make_request(
            BATCH, seed=int(rng.integers(2**31))))
    done = engine.drain()
    wall = time.perf_counter() - t0
    served = sum(r.size for r in done)
    # compute_ms = datapath latency per step; queue wait is an
    # artifact of pre-submitting the whole stream
    lat = latency_stats(done)["compute_ms"]
    return round(served / wall, 1), lat


def _load_baseline():
    try:
        with open(BENCH_JSON) as fh:
            return json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None


def _regression_block(record, baseline):
    """Compare each cell vs the committed record; flag >15% throughput
    drops of any previously-winning backend."""
    block = {"threshold_pct": REGRESSION_PCT, "cells": [], "failed": []}
    if not baseline:
        return block
    for preset, old in baseline.get("presets", {}).items():
        if not preset.startswith("dwn-jsc-"):
            # only the JSC rows gate; other workloads (MNIST, ...) are
            # recorded for tracking but never fail the build
            continue
        new = record["presets"].get(preset)
        old_backends = old.get("backends", {})
        if not new or not old_backends:
            continue
        winner = max(old_backends,
                     key=lambda b: old_backends[b].get(
                         "throughput_samples_per_s", 0.0))
        old_thru = old_backends[winner]["throughput_samples_per_s"]
        new_thru = new["backends"].get(winner, {}).get(
            "throughput_samples_per_s", 0.0)
        regressed = new_thru < old_thru * (1 - REGRESSION_PCT / 100)
        cell = {"preset": preset, "backend": winner,
                "baseline_throughput": old_thru,
                "throughput": new_thru,
                "delta_pct": round((new_thru / old_thru - 1) * 100, 1)
                if old_thru else 0.0,
                "regressed": regressed}
        block["cells"].append(cell)
        if regressed:
            block["failed"].append(f"{preset}/{winner}")
    return block


def _confirm_regressions(block) -> None:
    """Re-measure each flagged cell once and keep only confirmed drops.

    Wall-clock throughput on a shared CI host is noisy (interpret-mode
    cells jitter up to ~2x run-to-run); a single slow pass must not fail
    the build.  Each flagged (preset, winner-backend) cell gets one fresh
    engine + the same seeded stream; the cell stays failed only if the
    second pass *also* breaches the threshold.  Both passes land in the
    recorded cell (``throughput`` / ``confirm_throughput``).
    """
    from repro.serving import ServingEngine

    block["failed"] = []
    for cell in block["cells"]:
        if not cell["regressed"]:
            continue
        print(f"regression flagged for {cell['preset']}/{cell['backend']} "
              f"({cell['delta_pct']}%); re-measuring to confirm...")
        engine = ServingEngine(cell["preset"], max_bucket=BATCH,
                               min_bucket=8, n_train=2000, verify=True,
                               backend="auto", autotune=True)
        engine.use_backend(cell["backend"])
        engine.warmup(BATCH)
        thru, _ = _stream(engine)
        old_thru = cell["baseline_throughput"]
        confirmed = thru < old_thru * (1 - REGRESSION_PCT / 100)
        cell["confirm_throughput"] = thru
        cell["confirm_delta_pct"] = (round((thru / old_thru - 1) * 100, 1)
                                     if old_thru else 0.0)
        cell["regressed"] = confirmed
        if confirmed:
            block["failed"].append(f"{cell['preset']}/{cell['backend']}")
        else:
            print(f"  not confirmed: second pass {thru} vs baseline "
                  f"{old_thru} — treating first pass as noise")


def run():
    from repro.serving import ServingEngine, available_backends

    baseline = _load_baseline()
    record = {"stream": {"requests": REQUESTS, "batch": BATCH},
              "presets": {}}
    for preset in PRESETS + MNIST_PRESETS:
        # backend="auto" + autotune=True: startup tunes the fused kernel
        # per bucket and calibrates every bit-exact backend, so the
        # per-backend rows below all serve their steady-state best
        engine = ServingEngine(preset, max_bucket=BATCH, min_bucket=8,
                               n_train=2000, verify=True, backend="auto",
                               autotune=True)
        tuned = {int(b): cfg.to_dict()
                 for b, cfg in engine.tuned_configs.items()}
        per_backend = {}
        for backend in available_backends():
            engine.use_backend(backend)
            # compile the (backend, BATCH) bucket outside timing
            engine.warmup(BATCH)
            thru, lat = _stream(engine)
            per_backend[backend] = {
                "throughput_samples_per_s": thru,
                "latency_ms_p50": lat["p50"],
                "latency_ms_p99": lat["p99"],
                "latency_ms_p999": lat["p999"],
                "shed_rate": 0.0,
                "queue_depth_max_requests": REQUESTS,
            }
            if backend == "fused-packed":
                per_backend[backend]["config"] = tuned.get(BATCH)
            csv_row(f"serve/{preset}/{backend}",
                    lat["p50"] * 1e3,
                    f"thru={thru};p99_ms={lat['p99']}")
        # auto-select row: per-bucket calibration picks the fastest
        # bit-exact backend serving its tuned kernel config
        engine.use_backend("auto")
        engine.warmup(BATCH)
        thru, lat = _stream(engine)
        auto_row = {
            "throughput_samples_per_s": thru,
            "latency_ms_p50": lat["p50"],
            "latency_ms_p99": lat["p99"],
            "latency_ms_p999": lat["p999"],
            "shed_rate": 0.0,
            "queue_depth_max_requests": REQUESTS,
            "choice": dict(engine.auto.choice),
            "configs": {b: (cfg.to_dict() if cfg else None)
                        for b, cfg in engine.auto.configs.items()},
        }
        csv_row(f"serve/{preset}/auto", lat["p50"] * 1e3,
                f"thru={thru};choice={engine.auto.choice}")
        record["presets"][preset] = {
            "luts": engine.cfg.dwn_luts,
            "bit_exact_vs_oracle": engine.bit_exact,
            "autotune": tuned,
            "backends": per_backend,
            "auto": auto_row,
        }

    record["regression"] = _regression_block(record, baseline)
    if record["regression"]["failed"]:
        # flaky-host guard: a single slow pass needs a confirming second
        # measurement before it can fail the build
        _confirm_regressions(record["regression"])
    if baseline and "curve" in baseline:
        # the open-loop curve belongs to benchmarks/load_harness.py;
        # carry it through unchanged when this bench rewrites the record
        record["curve"] = baseline["curve"]
    with open(BENCH_JSON, "w") as fh:
        json.dump(record, fh, indent=2)
    n_presets = len(PRESETS) + len(MNIST_PRESETS)
    print(f"\nwritten {BENCH_JSON.name}: "
          f"{n_presets} presets x {len(record['presets'][PRESETS[0]]['backends'])} "
          f"backends, {REQUESTS}x{BATCH} samples each")
    failed = record["regression"]["failed"]
    if failed:
        msg = (f"serve bench regression gate: previously-winning backends "
               f"dropped >{REGRESSION_PCT:.0f}% throughput in both "
               f"measurement passes: {failed}")
        if os.environ.get("SERVE_BENCH_NO_GATE") == "1":
            print(f"WARNING (gate disabled): {msg}")
        else:
            print(f"ERROR: {msg}")
            raise SystemExit(1)


if __name__ == "__main__":
    run()
