"""The serving step log (``serving/steplog.py``): one ring record and the
``serve.*`` profiler spans per served step.

Each step is driven three ways: ``ContinuousScheduler.step_once`` over a
stub step that marks the engine's four phases, and a small CPU
``ServingEngine`` through its sync ``drain`` and its continuous loop.
"""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import steplog
from repro.serving.backends import BoundBackend, available_backends, \
    get_backend
from repro.serving.continuous import ContinuousScheduler

PATHS = ("stub", "sync", "async")
SPANS = {"serve." + p for p in ("step", "wait") + steplog.PHASES}


@pytest.fixture(scope="module")
def engine():
    from repro.serving import ServingEngine
    return ServingEngine("dwn-jsc-sm", max_bucket=128, min_bucket=8,
                         n_train=800, backend="packed-xla", verify=False)


def _stub_step(x):
    """Stands in for ``ServingEngine._dwn_step``: the same four phases."""
    with steplog.phase("h2d"):
        y = np.array(x)
    with steplog.phase("dispatch"):
        y = y[:, 0] * 2.0
    with steplog.phase("device"):
        y = y + 0.0
    with steplog.phase("d2h"):
        return (np.asarray(y),)


def _identity(outs):
    """``collect`` for the stub step, whose launch runs the whole step."""
    return outs


def _serve(path, engine, sizes):
    """Serve one request of each size, one step each; the steps' records."""
    mark = steplog.mark()
    if path == "stub":
        sched = ContinuousScheduler(_stub_step, _identity,
                                    max_bucket=128, min_bucket=8)
        for n in sizes:
            sched.submit(np.ones((n, 3), np.float32))
            assert sched.step_once() == n
    elif path == "sync":
        for n in sizes:
            engine.submit(engine.make_request(n, seed=n))
            engine.drain()
    else:
        with engine.serve():
            for n in sizes:
                req = engine.submit_async(engine.make_request(n, seed=n))
                assert req.future.result(timeout=60.0).ok
    return steplog.since(mark)


@pytest.mark.parametrize("path", PATHS)
def test_every_phase_recorded_and_summing_to_the_step(engine, path):
    recs = _serve(path, engine, [128, 64, 128])
    assert len(recs) == 3
    assert (recs.phase_ns > 0).all(), recs.phase_ns
    np.testing.assert_array_equal(recs.phase_ns.sum(axis=1), recs.step_ns)
    np.testing.assert_array_equal(recs.rows, [128, 64, 128])
    np.testing.assert_array_equal(recs.requests, [1, 1, 1])
    np.testing.assert_array_equal(recs.moe_fallbacks, 0)


@pytest.mark.parametrize("path", PATHS)
def test_occupancy_of_a_partial_bucket(engine, path):
    recs = _serve(path, engine, [100])
    assert (recs.rows[0], recs.bucket[0]) == (100, 128)
    assert recs.occupancy_pct() == 78.125


@pytest.mark.parametrize("path", ("sync", "async"))
def test_compiles_on_the_first_use_of_a_bucket_only(engine, path):
    fresh = [b for b in engine.scheduler.buckets
             if b not in engine.backend.compiles]
    bucket = fresh[-1]
    recs = _serve(path, engine, [bucket, bucket])
    np.testing.assert_array_equal(recs.bucket, [bucket, bucket])
    np.testing.assert_array_equal(recs.compiles, [1, 0])


@pytest.mark.parametrize("path", PATHS)
def test_one_device_to_host_copy_per_step(engine, path):
    """The engine's step issues one copy, of its packed answer; the stub,
    which copies nothing from a device, counts none."""
    recs = _serve(path, engine, [128, 64, 128])
    want = 0 if path == "stub" else 1
    np.testing.assert_array_equal(recs.d2h_copies, [want] * 3)
    assert recs.summary()["d2h_copies"] == 3 * want


def _stub_launch(x):
    """The stub step's first half: ``_dwn_launch``'s two phases."""
    with steplog.phase("h2d"):
        y = np.array(x)
    with steplog.phase("dispatch"):
        return y[:, 0] * 2.0


def _stub_collect(y):
    """Its second half: ``Launched.collect``'s two phases."""
    with steplog.phase("device"):
        y = y + 0.0
    with steplog.phase("d2h"):
        return (np.asarray(y),)


@pytest.mark.parametrize("halves", ("stub", "engine"))
def test_steps_in_flight_keep_their_own_phases(engine, halves):
    """A backlog through the thread loop: every step after the first is
    launched before its predecessor is collected, each record still holds
    all six of its own phases, summing to its step time, and the loop's
    busy time is no more than its wall time."""
    launch, collect = ((_stub_launch, _stub_collect) if halves == "stub"
                       else (engine._dwn_launch, engine._dwn_step))
    sched = ContinuousScheduler(launch, collect, max_bucket=128,
                                min_bucket=8)
    sizes = [128, 64, 100, 128, 300]
    reqs = [sched.submit(engine.make_request(n, seed=n)) for n in sizes]
    mark = steplog.mark()
    sched.start()
    sched.stop(drain=True)
    recs = steplog.since(mark)
    assert len(recs) == sched.steps == 6
    np.testing.assert_array_equal(recs.overlapped, [0, 1, 1, 1, 1, 1])
    np.testing.assert_array_equal(recs.rows, [128] * 5 + [80])
    assert (recs.phase_ns > 0).all(), recs.phase_ns
    np.testing.assert_array_equal(recs.phase_ns.sum(axis=1), recs.step_ns)
    assert recs.summary()["overlap_pct"] == pytest.approx(500 / 6, abs=1e-3)
    counters = sched.counters()
    assert 0 < counters["busy_s"] <= counters["session_wall_s"]
    if halves == "engine":
        oracle = engine.backends["float-oracle"]
        for req, n in zip(reqs, sizes):
            res = req.future.result(timeout=0)
            x = engine.make_request(n, seed=n)
            for got, want in zip(res.value, oracle(x)):
                np.testing.assert_array_equal(got, want)


def _overlap_reader():
    import importlib.util
    path = (Path(__file__).resolve().parents[1] / "bench" / "metrics"
            / "overlap_share.py")
    spec = importlib.util.spec_from_file_location("overlap_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("case", ["filled", "no_steps", "no_column"])
def test_overlap_share_reader(monkeypatch, case):
    """The benchmark's ``overlap_share`` reads the window's records, and
    nothing from a step log without the ``overlapped`` column."""
    from types import SimpleNamespace
    ring = steplog.Ring(8)
    monkeypatch.setattr(steplog, "RING", ring)
    phases = [1] * len(steplog.PHASES)
    ring.record(phases, len(phases), 8, 8, 1, 0)        # before the window
    for overlapped in (0, 1, 1, 1):
        ring.record(phases, len(phases), 8, 8, 1, 0, 1, overlapped=overlapped)
    steps = 0 if case == "no_steps" else 4
    if case == "no_column":
        monkeypatch.delattr(steplog.Records, "overlap_pct")
    got = _overlap_reader().read(SimpleNamespace(counters={"steps": steps}))
    assert got == (75.0 if case == "filled" else None)


@pytest.mark.parametrize("capacity", (4, steplog.CAPACITY))
def test_ring_wraps_at_capacity(capacity):
    ring = steplog.Ring(capacity)
    phases = [1] * len(steplog.PHASES)
    mark = ring.mark()
    for i in range(capacity + 3):
        assert ring.record(phases, len(phases), i + 1, 8, 1, 0) == i
    recs = ring.last(capacity)
    np.testing.assert_array_equal(recs.rows,
                                  np.arange(capacity + 3)[3:] + 1)
    assert ring.last(capacity + 1) is None
    assert ring.since(mark) is None
    np.testing.assert_array_equal(ring.since(ring.mark() - 2).rows,
                                  [capacity + 2, capacity + 3])
    assert len(ring.last(0)) == 0


@pytest.mark.parametrize("path", ("sync", "async"))
def test_report_has_a_steps_block(engine, path):
    _serve(path, engine, [32])
    steps = engine.report()["steps"]
    assert steps["count"] >= 1
    assert set(steps["ms"]) == {"step", *steplog.PHASES}
    assert all(v["mean"] > 0 and v["p99"] >= 0 for v in steps["ms"].values())
    assert 0 < steps["occupancy_pct"] <= 100
    assert steps["compiles"] >= 0
    assert 1 <= steps["d2h_copies"] <= steps["count"]


def test_profiler_trace_holds_the_serve_spans(engine, tmp_path):
    """The spans land on a host plane of a CPU trace under their bare
    names, with the step's counts as stats."""
    import threading
    with jax.profiler.trace(str(tmp_path)):
        with engine.serve():
            threading.Event().wait(0.02)     # the loop waits once at least
            for n in (100, 128):
                engine.submit_async(engine.make_request(n, seed=n)) \
                    .future.result(timeout=60.0)
    (path,) = Path(tmp_path).rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    seen = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    seen.setdefault(ev.name, []).append(dict(ev.stats))
    assert set(seen) == SPANS
    rows = sorted(s["rows"] for s in seen["serve.step"])
    assert rows == [100, 128]
    for stats in seen["serve.step"]:
        assert stats["bucket"] == 128 and stats["requests"] == 1
        assert "step" in stats


@pytest.mark.parametrize("backend", available_backends())
def test_served_module_keeps_its_name(engine, backend):
    """The benchmark finds the forward in a device trace by its module
    name; the step lowers as ``jit_traced``, inside the ``dwn_forward``
    scope."""
    bound = BoundBackend(get_backend(backend), engine.model)
    x = jax.ShapeDtypeStruct((256, engine.data.x_test.shape[1]),
                             jnp.float32)
    lowered = bound.step_for(256).lower(x)
    assert lowered.as_text().startswith("module @jit_traced ")
    assert "dwn_forward" in lowered.as_text(debug_info=True)
