"""Training benchmark: scan-compiled engine vs the pre-PR reference loop.

Trains the md JSC preset (md-360) twice with identical protocol (same
seed, batch, epochs — therefore the same minibatch order and schedule
step count): once through the frozen pre-PR python-per-minibatch loop
(``repro.training.reference``) and once through the scan-compiled engine
(``repro.training.engine``).  Epochs of the two engines are
**interleaved** (ref epoch e, scan epoch e, ...) so both see the same
machine conditions, and the headline speedup is the median of per-epoch
wall-clock ratios over the steady-state epochs (epoch 0 carries each
engine's compile and is reported separately).

An epoch's wall-clock includes its end-of-epoch eval, exactly like the
``train_dwn`` history ``sec`` field: the reference pays its fresh-jit
eval per epoch (the pre-PR behavior), the scan engine its cached
evaluator.  Units: seconds per epoch; ``steps_per_s`` counts optimizer
steps.

Also measured: the vmapped multi-seed batch trainer
(``train_dwn_batch``) against sequential scan runs, the loss/param
trajectory parity between the engines at fixed seed, and the resilient
parallel sweep executor against the serial in-process grid runner on the
tiny grid (``sweep_executor`` row — parallel-vs-serial wall-clock).

Writes ``BENCH_train.json`` at the repo root (one record per run,
overwritten) — the training-side companion of ``BENCH_kernels.json``.
"""

import json
import os
import time

import numpy as np

from .common import csv_row, ROOT

BENCH_JSON = ROOT / "BENCH_train.json"

PRESET = "md-360"
N_TRAIN, N_TEST = 4000, 1000
BATCH = 128
# timed epochs (after the compile epoch); CI runs the 2-epoch shape
EPOCHS = int(os.environ.get("TRAIN_BENCH_EPOCHS", "4"))
SEEDS = (0, 1)        # batch-trainer axis
SWEEP_WORKERS = int(os.environ.get("TRAIN_BENCH_SWEEP_WORKERS", "2"))


def bench_sweep_executor(workers: int = SWEEP_WORKERS) -> dict:
    """Tiny sweep grid, serial in-process vs the resilient parallel
    executor (fresh caches for both, so each run computes every point).

    On a 2-core CPU the parallel win is modest — worker spawn + per-process
    JAX compile is amortized over only 6 points — but the row pins the
    overhead so regressions in executor dispatch show up; on multi-core
    hosts it approaches the worker count.
    """
    import shutil
    import tempfile

    from repro.sweep import (ExecutorSettings, SweepSettings, run_grid,
                             run_grid_parallel)

    settings = SweepSettings(n_train=512, n_test=256, accuracy=False,
                             kernel=False, serve=False)
    tmp = tempfile.mkdtemp(prefix="sweep_exec_bench_")
    try:
        t0 = time.perf_counter()
        serial = run_grid("tiny", settings, cache_dir=f"{tmp}/serial")
        serial_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        par = run_grid_parallel("tiny", settings,
                                cache_dir=f"{tmp}/parallel",
                                executor=ExecutorSettings(workers=workers))
        parallel_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    assert len(par.points) == len(serial.points)
    assert par.executor["failed"] == []
    csv_row("train/sweep_executor/tiny", parallel_s * 1e6,
            f"serial_s={serial_s:.2f};parallel_s={parallel_s:.2f};"
            f"workers={workers}")
    return {
        "grid": "tiny", "points": len(par.points), "workers": workers,
        "units": "wall-clock seconds for the full grid, fresh cache",
        "serial_wall_s": round(serial_s, 3),
        "parallel_wall_s": round(parallel_s, 3),
        "speedup": round(serial_s / parallel_s, 2),
        "executor": {k: par.executor[k]
                     for k in ("computed", "restarts", "worker_deaths",
                               "stragglers_redispatched",
                               "workers_spawned")},
    }


def run(epochs: int = EPOCHS):
    import jax
    from repro.core import JSC_PRESETS
    from repro.data.jsc import load_jsc
    from repro.training import ReferenceTrainer, ScanTrainer, train_dwn_batch

    data = load_jsc(N_TRAIN, N_TEST, seed=0)
    cfg = JSC_PRESETS[PRESET]

    ref = ReferenceTrainer(cfg, data, batch=BATCH, seed=0)
    scan = ScanTrainer(cfg, data, batch=BATCH, seed=0)

    def ref_epoch():
        t0 = time.perf_counter()
        losses = ref.run_epoch()
        ref.evaluate()                      # pre-PR: fresh jit per epoch
        return np.asarray(losses), time.perf_counter() - t0

    def scan_epoch():
        t0 = time.perf_counter()
        losses = scan.run_epochs(1)[0]
        scan.evaluate()                     # cached evaluator
        return losses, time.perf_counter() - t0

    ref_s, scan_s = [], []
    loss_diff = 0.0
    for e in range(epochs + 1):             # epoch 0 = compile epoch
        rl, rt = ref_epoch()
        sl, st = scan_epoch()
        loss_diff = max(loss_diff, float(np.abs(rl - sl).max()))
        if e == 0:
            compile_s = {"reference": round(rt, 3), "scan": round(st, 3)}
        else:
            ref_s.append(rt)
            scan_s.append(st)
            csv_row(f"train/{PRESET}/epoch{e}", st * 1e6,
                    f"ref_s={rt:.2f};scan_s={st:.2f};x={rt / st:.2f}")

    ratios = [r / s for r, s in zip(ref_s, scan_s)]
    speedup = float(np.median(ratios))
    steps = scan.steps_per_epoch

    # trajectory parity on params too (scores move by ~1e-6 from the
    # reassociated-but-equal backward; tables/bits stay bit-identical)
    pdiff = jax.tree.map(
        lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).max()),
        ref.params, scan.params)

    # vmapped multi-seed batch trainer vs sequential scan runs
    t0 = time.perf_counter()
    out = train_dwn_batch(cfg, data, epochs=2, seeds=SEEDS, batch=BATCH,
                          eval_final=False)
    t_seq = 0.0
    for s in SEEDS:
        t1 = time.perf_counter()
        tr = ScanTrainer(cfg, data, batch=BATCH, seed=s)
        tr.run_epochs(2)
        t_seq += time.perf_counter() - t1

    record = {
        "preset": PRESET,
        "note": "speedup is hardware-dependent: the scan engine removes "
                "the x_soft einsum, the variadic-argmax lowering, two "
                "Adam memory passes, per-batch re-encode, per-step "
                "dispatch + float(loss) syncs, and per-epoch eval "
                "recompiles.  On a 2-core CPU the remaining step sits at "
                "the memory-bandwidth floor of the (m*n*C) score-tree "
                "passes shared by both engines (~2x there); on "
                "accelerator backends, where dispatch/sync and the "
                "eliminated GEMM dominate, the gap is larger.",
        "protocol": {"n_train": N_TRAIN, "n_test": N_TEST, "batch": BATCH,
                     "epochs": epochs, "seed": 0,
                     "steps_per_epoch": steps},
        "units": {"epoch_s": "wall-clock seconds per epoch incl. its "
                             "end-of-epoch eval; median over interleaved "
                             "steady-state epochs",
                  "steps_per_s": "optimizer steps per second"},
        "reference_loop": {
            "epoch_s": round(float(np.median(ref_s)), 3),
            "epoch_s_all": [round(t, 3) for t in ref_s],
            "steps_per_s": round(steps / float(np.median(ref_s)), 1),
            "host_syncs_per_epoch": steps + 1,   # float(loss)/step + eval
        },
        "scan_engine": {
            "epoch_s": round(float(np.median(scan_s)), 3),
            "epoch_s_all": [round(t, 3) for t in scan_s],
            "steps_per_s": round(steps / float(np.median(scan_s)), 1),
            "host_syncs_per_epoch": 1,           # losses fetched per epoch
        },
        "compile_epoch_s": compile_s,
        "speedup_epoch_wallclock": round(speedup, 2),
        "speedup_per_epoch": [round(r, 2) for r in ratios],
        "parity": {"max_step_loss_diff": loss_diff,
                   "max_param_diff": pdiff},
        "batch_trainer": {
            "seeds": list(SEEDS), "epochs": 2,
            "vmapped_wall_s": round(out.wall_s, 3),
            "sequential_wall_s": round(t_seq, 3),
            "speedup": round(t_seq / out.wall_s, 2),
            "data_parallel": out.data_parallel,
        },
        "sweep_executor": bench_sweep_executor(),
    }
    with open(BENCH_JSON, "w") as fh:
        json.dump(record, fh, indent=2)
    print(f"\nwritten {BENCH_JSON.name}: {PRESET} "
          f"ref {record['reference_loop']['epoch_s']}s/epoch vs scan "
          f"{record['scan_engine']['epoch_s']}s/epoch -> "
          f"{record['speedup_epoch_wallclock']}x "
          f"(parity max loss diff {loss_diff:.2e})")
    return record


if __name__ == "__main__":
    run()
