"""Serving subsystem: backend parity, scheduler bucketing, engine + CLI.

Covers the three layers of ``repro.serving``:

* batching (``ContinuousScheduler.step_once``, as the engine's sync
  ``drain()`` runs it): admission order, power-of-two bucket padding,
  coalescing, oversize splitting, latency percentiles (pure numpy — no
  jax needed);
* backends: every registered non-oracle backend bit-exact against the
  ``apply_hard`` float oracle on all three JSC serving presets, verified
  by the engine's startup gate;
* engine: ragged request streams compile at most once per
  (backend, bucket); data-parallel shard_map serving stays bit-exact
  (8-device subprocess); the serve CLI smoke-runs end to end.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.kernels.autotune import FusedConfig, candidate_configs
from repro.serving import (ContinuousScheduler, MicrobatchScheduler,
                           ServingEngine, available_backends,
                           power_of_two_buckets)

ROOT = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# scheduler (no jax)
# ---------------------------------------------------------------------------

def test_bucket_ladder():
    sched = MicrobatchScheduler(max_bucket=64, min_bucket=8)
    assert sched.buckets == (8, 16, 32, 64)
    assert power_of_two_buckets(16, 16) == (16,)
    assert sched.bucket_for(1) == 8
    assert sched.bucket_for(8) == 8
    assert sched.bucket_for(9) == 16
    assert sched.bucket_for(64) == 64
    with pytest.raises(AssertionError):
        power_of_two_buckets(12, 64)          # min not a power of two


def _row_id_step(shapes_seen):
    """Step fn whose per-row output identifies the input row exactly."""
    def step(x):
        shapes_seen.append(x.shape[0])
        return (x[:, 0].copy(),)              # row tag
    return step


def _rows_scheduler(step, *, max_bucket, min_bucket=8):
    """The sync facade's scheduler over a stub step, whose launch runs
    the whole step (so ``collect`` passes its outputs through)."""
    return ContinuousScheduler(step, lambda outs: outs,
                               max_bucket=max_bucket, min_bucket=min_bucket)


def _drain(sched):
    """What ``ServingEngine.drain`` does: step until the queue is empty."""
    while sched.step_once():
        pass


def test_scheduler_ragged_admission_order_and_padding():
    shapes = []
    sched = _rows_scheduler(_row_id_step(shapes), max_bucket=64)
    sizes = [5, 17, 40, 3, 64, 1, 100, 2]
    reqs = []
    for i, n in enumerate(sizes):
        # payload rows tagged with (request id, row) so results are traceable
        x = np.full((n, 4), float(i), np.float32)
        x[:, 0] = i * 1000 + np.arange(n)
        reqs.append(sched.submit(x))
    _drain(sched)

    # every request served, results routed back to the right request
    assert all(r.future.result(timeout=0).ok for r in reqs)
    for i, r in enumerate(reqs):
        expect = i * 1000 + np.arange(sizes[i], dtype=np.float32)
        np.testing.assert_array_equal(r.result[0], expect)

    # admission order: service start and completion times never decrease
    # with rid
    for t in ([r.t_start for r in reqs], [r.t_done for r in reqs]):
        assert all(a <= b for a, b in zip(t, t[1:])), t

    # only ladder shapes ever reach the step fn (bounded JIT signatures)
    assert set(shapes) <= set(power_of_two_buckets(8, 64))

    # oversize request (100 > 64) split into max_bucket chunks
    big = reqs[sizes.index(100)]
    assert big.buckets == (64, 64)
    assert len(big.result[0]) == 100

    # latency accounting is populated and ordered
    for r in reqs:
        assert r.t_submit <= r.t_start <= r.t_done
        assert r.queue_ms >= 0 and r.compute_ms >= 0
        assert r.total_ms >= r.compute_ms


def test_scheduler_coalesces_small_requests():
    shapes = []
    sched = _rows_scheduler(_row_id_step(shapes), max_bucket=32)
    for i in range(6):
        sched.submit(np.full((4, 2), i, np.float32))
    _drain(sched)
    # 6 x 4 samples coalesce into one 24-sample microbatch -> one 32 pad
    assert shapes == [32]


def test_scheduler_serial_latency_accounting():
    sched = MicrobatchScheduler(max_bucket=8)
    sched.submit({"tokens": np.zeros((2, 4))}, size=2)
    done = sched.drain_serial(lambda payload: {"ok": True})
    assert done[0].result == {"ok": True}
    assert done[0].t_done >= done[0].t_start >= done[0].t_submit


def test_latency_stats_include_p999():
    from repro.serving.scheduler import latency_stats, percentiles
    sched = _rows_scheduler(lambda x: (x[:, 0],), max_bucket=8)
    for i in range(4):
        sched.submit(np.zeros((2, 2), np.float32))
    _drain(sched)
    stats = latency_stats(sched.completed)
    for kind in ("queue_ms", "compute_ms", "total_ms"):
        assert {"p50", "p99", "p999", "mean"} <= set(stats[kind])
    p = percentiles(range(1, 1001))
    assert p["p50"] == pytest.approx(500.5)
    assert p["p999"] == pytest.approx(1000, abs=1.1)
    assert latency_stats([]) == {}


# ---------------------------------------------------------------------------
# backends: bit-exact parity vs the oracle on all three serving presets
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["dwn-jsc-sm", "dwn-jsc-md", "dwn-jsc-lg"])
def test_backend_parity_vs_oracle(arch):
    engine = ServingEngine(arch, max_bucket=32, min_bucket=8, n_train=1200,
                           verify=True)
    non_oracle = [b for b in available_backends() if b != "float-oracle"]
    assert sorted(engine.bit_exact) == sorted(non_oracle)
    assert all(engine.bit_exact.values()), engine.bit_exact


def test_backend_parity_multiblock_bucket():
    # buckets >= 128 exercise the fused kernel's multi-block batch grid;
    # the startup probe runs at max_bucket so this is verified, not assumed
    engine = ServingEngine("dwn-jsc-sm", max_bucket=256, min_bucket=8,
                           n_train=800, verify=True)
    assert all(engine.bit_exact.values()), engine.bit_exact
    assert 256 in engine.backends["fused-packed"].compiles


def test_backend_registry_and_config_selection():
    assert {"fused-packed", "packed-xla", "float-oracle"} <= set(
        available_backends())
    # dwn_datapath on the arch picks the backend; CLI arg overrides
    eng = ServingEngine("dwn-jsc-sm-xla", max_bucket=16, n_train=600,
                        verify=False)
    assert eng.backend.name == "packed-xla"
    eng = ServingEngine("dwn-jsc-sm", max_bucket=16, n_train=600,
                        backend="float-oracle", verify=False)
    assert eng.backend.name == "float-oracle"


def _refused_by_spec():
    from repro.dwn import DWNSpec
    DWNSpec(preset="sm-50", datapath="auto")


def _refused_by_engine():
    ServingEngine("dwn-jsc-sm", max_bucket=16, n_train=600, verify=False,
                  backend="auto")


@pytest.mark.parametrize("make", [_refused_by_spec, _refused_by_engine],
                         ids=["spec", "engine"])
def test_unregistered_datapath_refused_naming_the_backends(make):
    """Every bucket serves on one registered backend, chosen at
    construction: a name outside the registry ("auto" included) is
    refused, and the error lists what is registered."""
    with pytest.raises(ValueError, match="'auto'") as err:
        make()
    for name in available_backends():
        assert name in str(err.value)


def test_engine_autotunes_only_on_request(sm_engine):
    """``autotune=True`` tunes the fused kernel at every ladder bucket
    before anything compiles, reports the chosen configs, and serves
    bit-exact answers on them; an engine built without it serves the
    default blocks."""
    assert sm_engine.tuned_configs == {}
    assert "autotune" not in sm_engine.report()
    eng = ServingEngine("dwn-jsc-sm", max_bucket=32, min_bucket=8,
                        n_train=800, autotune=True, verify=False)
    assert eng.backend.name == "fused-packed"
    assert sorted(eng.tuned_configs) == sorted(eng.scheduler.buckets)
    rep = eng.report()
    assert set(rep["autotune"]) == set(eng.scheduler.buckets)
    for bucket, cfg in rep["autotune"].items():
        assert FusedConfig.from_dict(cfg) in candidate_configs(bucket)
    for n in (32, 5, 17):
        eng.submit(eng.make_request(n, seed=n))
    done = eng.drain()
    assert [r.size for r in done] == [32, 5, 17]
    oracle = eng.backends["float-oracle"]
    for r in done:
        counts, pred = oracle(r.payload)
        np.testing.assert_array_equal(np.asarray(r.result[0]), counts)
        np.testing.assert_array_equal(np.asarray(r.result[1]), pred)


# ---------------------------------------------------------------------------
# the packed answer: one buffer per step, unpacked on the host
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sm_engine():
    return ServingEngine("dwn-jsc-sm", max_bucket=256, min_bucket=8,
                         n_train=800, verify=False)


def _ragged_batch(engine, bucket: int) -> np.ndarray:
    """A third short of ``bucket`` real rows, zero-padded as the
    scheduler pads them."""
    n = bucket - bucket // 3
    x = engine.make_request(n, seed=bucket)
    return np.concatenate([x, np.zeros((bucket - n,) + x.shape[1:],
                                       x.dtype)])


@pytest.mark.parametrize("bucket", [8, 256, 4096])
@pytest.mark.parametrize("backend", available_backends())
def test_step_answer_equals_backend_and_oracle(sm_engine, backend, bucket):
    """``_dwn_step`` unpacks the one copied buffer into counts and
    predictions equal to the bound backend's and the oracle's, in the
    dtypes and shapes the backend's own step gives."""
    import jax
    x = _ragged_batch(sm_engine, bucket)
    sm_engine.use_backend(backend)
    bound = sm_engine.backends[backend]
    counts, pred = sm_engine._dwn_step(x)
    raw = jax.eval_shape(bound._fn, x)
    C = sm_engine.model.num_classes
    assert (counts.shape, pred.shape) == ((bucket, C), (bucket,))
    assert (counts.dtype, pred.dtype) == (raw[0].dtype, raw[1].dtype)
    for want in (bound(x), sm_engine.backends["float-oracle"](x)):
        assert (want[0].dtype, want[1].dtype) == (counts.dtype, pred.dtype)
        np.testing.assert_array_equal(counts, want[0])
        np.testing.assert_array_equal(pred, want[1])


@pytest.mark.parametrize("backend", available_backends())
def test_step_returns_one_packed_array(sm_engine, backend):
    """The jitted step has one int32 output, ``(C+1)·B`` long, not a
    tuple; its module stays ``jit_traced``, which the benchmark's trace
    reader looks for."""
    import jax
    import jax.numpy as jnp
    bound = sm_engine.backends[backend]
    x = jax.ShapeDtypeStruct((256, sm_engine.data.x_test.shape[1]),
                             jnp.float32)
    lowered = bound.step_for(256).lower(x)
    C = sm_engine.model.num_classes
    assert isinstance(lowered.out_info, jax.ShapeDtypeStruct)
    assert lowered.out_info.shape == ((C + 1) * 256,)
    assert lowered.out_info.dtype == jnp.int32
    assert lowered.as_text().startswith("module @jit_traced ")


@pytest.mark.parametrize("shards", [1, 3])
@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.uint32])
def test_pack_answer_round_trips_bit_for_bit(dtype, shards):
    """Counts are bitcast, not converted: a 32-bit dtype, NaN payloads
    included, comes back bit for bit; shards that each pack their own
    rows, laid end to end, unpack in row order."""
    import jax.numpy as jnp
    from repro.serving.backends import pack_answer, unpack_answer
    rng = np.random.default_rng(0)
    counts = rng.integers(-2**31, 2**31, (12, 5)).astype(np.int32) \
        .view(dtype)
    pred = rng.integers(0, 5, 12).astype(np.int32)
    buf = np.concatenate([
        np.asarray(pack_answer(jnp.asarray(c), jnp.asarray(p)))
        for c, p in zip(np.split(counts, shards), np.split(pred, shards))])
    assert buf.shape == (6 * 12,) and buf.dtype == np.int32
    c, p = unpack_answer(buf, shards, 5, counts.dtype, pred.dtype)
    assert (c.dtype, p.dtype) == (counts.dtype, pred.dtype)
    np.testing.assert_array_equal(c.view(np.int32), counts.view(np.int32))
    np.testing.assert_array_equal(p, pred)


def test_pack_answer_refuses_other_widths():
    import jax.numpy as jnp
    from repro.serving.backends import pack_answer
    with pytest.raises(TypeError, match="32-bit"):
        pack_answer(jnp.zeros((4, 5), jnp.bfloat16),
                    jnp.zeros((4,), jnp.int32))


# ---------------------------------------------------------------------------
# engine: ragged stream, compile bound, report
# ---------------------------------------------------------------------------

def test_engine_ragged_stream_compiles_once_per_bucket():
    engine = ServingEngine("dwn-jsc-sm", max_bucket=64, min_bucket=8,
                           n_train=800, verify=True)
    rng = np.random.default_rng(0)
    sizes = [5, 17, 64, 3, 100, 23, 64, 9, 2, 31]
    for n in sizes:
        engine.submit(engine.make_request(n, seed=int(rng.integers(2**31))))
    done = engine.drain()
    assert sum(r.size for r in done) == sum(sizes)

    # at most one XLA trace per (backend, bucket), buckets from the ladder
    for backend, per_bucket in engine.compile_counts().items():
        assert set(per_bucket) <= set(engine.scheduler.buckets), backend
        assert all(v == 1 for v in per_bucket.values()), (backend, per_bucket)

    # predictions bit-exact vs the oracle for every request
    oracle = engine.backends["float-oracle"]
    for r in done:
        counts, pred = oracle(r.payload)
        np.testing.assert_array_equal(np.asarray(r.result[0]), counts)
        np.testing.assert_array_equal(np.asarray(r.result[1]), pred)

    rep = engine.report()
    assert rep["served"] == sum(sizes)
    assert rep["latency"]["queue_ms"]["p50"] >= 0
    assert rep["latency"]["compute_ms"]["p50"] > 0
    assert rep["bit_exact_vs_oracle"] == {"fused-packed": True,
                                          "packed-xla": True}


# ---------------------------------------------------------------------------
# data-parallel sharding (8 fake host devices, subprocess)
# ---------------------------------------------------------------------------

DP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, sys.argv[1])
    import numpy as np
    from repro.serving import ServingEngine

    eng = ServingEngine("dwn-jsc-sm", max_bucket=64, min_bucket=8,
                        n_train=800, backend="packed-xla")
    for n in (64, 17, 40, 8):
        eng.submit(eng.make_request(n, seed=n))
    done = eng.drain()
    oracle = eng.backends["float-oracle"]
    exact = True
    for r in done:
        counts, pred = oracle(r.payload)
        exact &= np.array_equal(np.asarray(r.result[0]), counts)
        exact &= np.array_equal(np.asarray(r.result[1]), pred)
    rep = eng.report()
    print("RESULT " + json.dumps({
        "devices": rep["devices"], "dp": rep["data_parallel"],
        "exact": bool(exact), "served": rep["served"],
        "startup_check": rep["bit_exact_vs_oracle"]}))
""")


def test_engine_data_parallel_shard_map():
    proc = subprocess.run(
        [sys.executable, "-c", DP_SCRIPT, str(ROOT / "src")],
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stdout[-2000:]
    out = json.loads(line[0][len("RESULT "):])
    assert out["devices"] == 8 and out["dp"] is True
    assert out["exact"] is True
    assert out["served"] == 64 + 17 + 40 + 8
    assert out["startup_check"] == {"fused-packed": True, "packed-xla": True}


# ---------------------------------------------------------------------------
# CLI smoke
# ---------------------------------------------------------------------------

def test_serve_cli_smoke():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "dwn-jsc-sm",
         "--reduced", "--requests", "4", "--batch", "32", "--ragged"],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=str(ROOT))
    assert proc.returncode == 0, proc.stderr[-3000:]
    rep = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rep["mode"] == "dwn-classify"
    assert rep["datapath"] == "fused-packed"
    assert rep["bit_exact_vs_oracle"] == {"fused-packed": True,
                                          "packed-xla": True}
    assert rep["served"] >= 4
    assert rep["latency_ms_p50"] > 0
    assert all(v == 1 for per in rep["compiles"].values()
               for v in per.values())
