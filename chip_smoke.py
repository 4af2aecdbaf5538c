#!/usr/bin/env python3
"""Chip smoke: the DWN train -> pack -> serve path, once, on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python3 chip_smoke.py             # one chip (the default)
    python3 chip_smoke.py --chips 4   # the paths that exist only across chips

One chip, at the full width of ``dwn-jsc-lg`` (F=16 features, T=200
thermometer bits, m=2400 LUTs of fan-in 6, 5 classes):

1. train one epoch of 2048 seeded JSC rows at batch 128 with the scan
   engine, then freeze and pack (``DWNArtifact``);
2. serve it through ``ServingEngine`` on the ``fused-packed`` Pallas
   backend (autotuned, startup-verified), at bucket 256 and at 4096 — the
   serve CLI's full batch — through the sync ``submit``/``drain`` path
   and the continuous ``serve()`` path;
3. check every answer against ``apply_hard`` run on the host CPU, and
   check that the served step's compiled program holds the Pallas kernel
   (``tpu_custom_call``), i.e. it did not run in interpret mode.

``--chips 4`` runs only data-parallel serving at bucket 4096 against a
single-device engine on the same requests, and ``train_dwn_batch`` over
four seeds sharded across the chips against the same run unsharded.

Earlier lines report each phase; the last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script exits non-zero, printing no such line, when JAX finds no TPU
or when any phase fails.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "dwn-jsc-lg"
N_TRAIN, TRAIN_BATCH = 2048, 128
SMALL, FULL = 256, 4096          # serving buckets checked
SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(chips: int):
    """The device list, or exit when it is not ``chips`` TPU chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX platform is "
                 f"{devices[0].platform!r}); this script runs only on one")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU chips, "
                 f"JAX sees {len(devices)}")
    return devices


class Phase:
    """Times one phase and logs its duration (seconds, host clock)."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        log(f"[{self.name}] start")
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            log(f"[{self.name}] done in "
                f"{time.perf_counter() - self.t0:.3f} s")


def host_reference(frozen, x):
    """``apply_hard`` (counts, first-argmax) on the host CPU."""
    import jax
    import numpy as np
    from repro.core.classifier import predict
    from repro.core.model import apply_hard
    with jax.default_device(jax.devices("cpu")[0]):
        counts = apply_hard(frozen, np.asarray(x))
        return np.asarray(counts, np.float32), np.asarray(predict(counts))


def same(out, ref) -> bool:
    import numpy as np
    return (np.array_equal(np.asarray(out[0], np.float32), ref[0])
            and np.array_equal(np.asarray(out[1]), ref[1]))


def requests(data, sizes, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    n = data.x_test.shape[0]
    return [data.x_test[rng.integers(0, n, size)] for size in sizes]


def engine(art, **kw):
    from repro.serving import ServingEngine
    return ServingEngine(art, backend="fused-packed", autotune=True,
                         verify=True, seed=SEED, **kw)


def check_kernel_compiled(eng, buckets) -> None:
    """The served fused step holds the Mosaic kernel at every bucket."""
    import jax
    import jax.numpy as jnp
    F = eng.spec.dwn_config().num_features
    step = eng.backends["fused-packed"]
    for bucket in buckets:
        x = jax.ShapeDtypeStruct((bucket, F), jnp.float32)
        text = step.step_for(bucket).lower(x).compile().as_text()
        assert "tpu_custom_call" in text, \
            f"bucket {bucket}: no tpu_custom_call in the served step"
        log(f"bucket {bucket}: served step holds tpu_custom_call")


def one_chip() -> None:
    from repro.dwn import DWNArtifact, get_spec
    from repro.workloads import load_workload

    spec = get_spec(ARCH)
    with Phase("train"):
        data = load_workload(spec.workload, N_TRAIN, FULL, seed=SEED)
        art = DWNArtifact(spec).train(data, epochs=1, batch=TRAIN_BATCH,
                                      seed=SEED).freeze().pack()
        loss = art.history[-1]["loss"]
        assert loss == loss and abs(loss) != float("inf"), loss
        cfg = spec.dwn_config()
        log(f"{ARCH}: F={cfg.num_features} T={cfg.bits_per_feature} "
            f"m={cfg.lut_counts} n={cfg.fan_in} C={cfg.num_classes}; "
            f"{N_TRAIN // TRAIN_BATCH} steps, epoch loss {loss}")

    with Phase("engine"):
        eng = engine(art, max_bucket=FULL, min_bucket=SMALL)
        log(f"startup bit-exact vs apply_hard: {eng.bit_exact}")
        assert eng.bit_exact.get("fused-packed") is True, eng.bit_exact
        log("tuned: " + ", ".join(f"{b}:{c.label}" for b, c in
                                  sorted(eng.tuned_configs.items())))

    with Phase("serve-sync"):
        exact = {}
        for i, x in enumerate(requests(data, [SMALL] * 3 + [FULL] * 2, 1)):
            eng.submit(x)
            (req,) = eng.drain()
            ok = same(req.result, host_reference(art.frozen, x))
            exact.setdefault(req.buckets[0], []).append(ok)
        log(f"sync bit-exact vs host apply_hard by bucket: {exact}")
        assert set(exact) == {SMALL, FULL}, exact
        assert all(all(v) for v in exact.values()), exact

    with Phase("serve-continuous"):
        xs = requests(data, [SMALL, FULL, 700, 3000, SMALL], 2)
        with eng.serve():
            pending = [eng.submit_async(x) for x in xs]
            results = [r.future.result(timeout=900) for r in pending]
        assert all(r.ok for r in results), [r.shed for r in results]
        flags = [same(r.value, host_reference(art.frozen, x))
                 for r, x in zip(results, xs)]
        log(f"continuous bit-exact vs host apply_hard: {flags}")
        assert all(flags), flags

    with Phase("kernel-check"):
        check_kernel_compiled(eng, (SMALL, FULL))
        log(f"compiles per backend and bucket: {eng.compile_counts()}")


def four_chips() -> None:
    import jax
    import numpy as np
    from repro.dwn import DWNArtifact, get_spec
    from repro.training import train_dwn_batch
    from repro.workloads import load_workload

    spec = get_spec(ARCH)
    data = load_workload(spec.workload, N_TRAIN, FULL, seed=SEED)

    with Phase("serve-data-parallel"):
        art = DWNArtifact(spec).fit(data.x_train, seed=SEED).freeze().pack()
        dp = engine(art, max_bucket=FULL, min_bucket=FULL)
        single = engine(art, max_bucket=FULL, min_bucket=FULL,
                        data_parallel=False)
        assert dp.data_parallel and dp.n_data == len(jax.devices()), \
            (dp.data_parallel, dp.n_data)
        flags = []
        for x in requests(data, [FULL] * 3, 3):
            outs = []
            for eng in (dp, single):
                eng.submit(x)
                (req,) = eng.drain()
                assert req.buckets == (FULL,), req.buckets
                outs.append(req.result)
            flags.append(same(outs[0], (np.asarray(outs[1][0], np.float32),
                                        np.asarray(outs[1][1])))
                         and same(outs[0], host_reference(art.frozen, x)))
        log(f"data-parallel over {dp.n_data} chips == single-device == "
            f"host apply_hard at bucket {FULL}: {flags}")
        assert all(flags), flags

    with Phase("train-sharded"):
        cfg = spec.dwn_config()
        runs = {dp_flag: train_dwn_batch(cfg, data, epochs=1,
                                         seeds=(0, 1, 2, 3),
                                         batch=TRAIN_BATCH,
                                         data_parallel=dp_flag,
                                         eval_final=False)
                for dp_flag in (True, False)}
        assert runs[True].data_parallel and not runs[False].data_parallel
        losses = {k: np.array([r.history[0]["loss"] for r in v.results])
                  for k, v in runs.items()}
        leaves = {k: jax.tree.leaves([r.params for r in v.results])
                  for k, v in runs.items()}
        params_equal = all(np.array_equal(a, b) for a, b in
                           zip(leaves[True], leaves[False]))
        max_diff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                       for a, b in zip(leaves[True], leaves[False]))
        log(f"sharded losses {losses[True].tolist()}")
        log(f"unsharded losses {losses[False].tolist()}")
        log(f"params bit-equal: {params_equal}, max |diff| {max_diff}")
        np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)
        assert max_diff <= 1e-4, max_diff


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the main path on one chip; 4: only the "
                         "data-parallel serving and sharded training paths")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip()
    else:
        four_chips()
    log(f"total {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
