"""Resilient sweep executor: parallel==serial parity, chaos-driven
worker deaths, crash-loop failure reporting, resume-with-zero-recompute,
straggler speculation, preemption draining.

Worker processes are *spawned* (each imports JAX fresh), so every test
here pays a few seconds of process startup — settings are kept minimal
(no kernel/serve axes, tiny splits)."""

import json

import pytest

from repro.runtime.fault import PreemptionHandler
from repro.sweep import SweepPoint, SweepResult, SweepSettings, run_grid
from repro.sweep.executor import (ChaosSpec, ExecutorSettings,
                                  run_grid_parallel)

FAST = SweepSettings(n_train=256, n_test=128, accuracy=False,
                     kernel=False, serve=False)

POINTS = [SweepPoint("sm-10", "TEN"),
          SweepPoint("sm-10", "PEN", input_bits=4),
          SweepPoint("sm-50", "TEN"),
          SweepPoint("sm-50", "PEN", input_bits=4)]


def _labels(result):
    return [r.point.label for r in result.points]


# ---------------------------------------------------------------------------
# parity + resume
# ---------------------------------------------------------------------------

def test_parallel_matches_serial(tmp_path):
    """Same grid through both executors: identical hardware numbers and
    accuracies (workers are seeded identically), plus the executor
    provenance block."""
    settings = SweepSettings(n_train=256, n_test=128, accuracy=True,
                             kernel=False, serve=False)
    pts = POINTS[:2]
    serial = run_grid(pts, settings, cache_dir=None)
    par = run_grid_parallel(pts, settings, cache_dir=tmp_path / "c",
                            executor=ExecutorSettings(workers=2))
    assert _labels(par) == _labels(serial)
    for a, b in zip(par.points, serial.points):
        assert a.total_luts == b.total_luts
        assert a.accuracy == b.accuracy
        assert not a.failed
    assert par.executor["mode"] == "parallel"
    assert par.executor["computed"] == 2
    assert par.executor["failed"] == []
    assert serial.executor["mode"] == "serial"


def test_resume_zero_recomputed_points(tmp_path):
    """The chaos-resume invariant's happy path: a completed run re-runs
    entirely from the cache — zero computed points."""
    first = run_grid_parallel(POINTS, FAST, cache_dir=tmp_path,
                              executor=ExecutorSettings(workers=2))
    assert first.executor["computed"] == len(POINTS)
    again = run_grid_parallel(POINTS, FAST, cache_dir=tmp_path,
                              executor=ExecutorSettings(workers=2))
    assert again.executor["computed"] == 0
    assert again.executor["cache_hits"] == len(POINTS)
    assert all(r.cached for r in again.points)
    # and the serial runner resumes from the same cache
    serial = run_grid(POINTS, FAST, cache_dir=tmp_path)
    assert serial.executor["computed"] == 0
    assert serial.executor["cache_hits"] == len(POINTS)


def test_executor_block_json_roundtrip(tmp_path):
    res = run_grid_parallel(POINTS[:1], FAST, cache_dir=None,
                            executor=ExecutorSettings(workers=1))
    f = tmp_path / "sweep.json"
    res.save(f)
    loaded = SweepResult.load(f)
    assert loaded.executor == res.executor
    assert json.loads(f.read_text())["executor"]["mode"] == "parallel"


# ---------------------------------------------------------------------------
# chaos: worker death, crash loop, per-point failure
# ---------------------------------------------------------------------------

def test_chaos_worker_kill_run_survives(tmp_path):
    """Every worker hard-exits after each completed point (node-loss
    chaos): the dispatcher respawns workers and the grid completes with
    no failed and no recomputed points."""
    res = run_grid_parallel(
        POINTS, FAST, cache_dir=tmp_path,
        executor=ExecutorSettings(workers=1, chaos="kill-after-1"))
    assert res.executor["computed"] == len(POINTS)
    assert res.executor["failed"] == []
    assert res.executor["worker_deaths"] >= len(POINTS) - 1
    assert res.executor["workers_spawned"] >= len(POINTS) - 1
    # all committed before each death -> resume is pure cache
    again = run_grid_parallel(POINTS, FAST, cache_dir=tmp_path,
                              executor=ExecutorSettings(workers=1))
    assert again.executor["computed"] == 0
    assert again.executor["cache_hits"] == len(POINTS)


def test_chaos_crash_loop_fails_points_without_spinning(tmp_path):
    """raise-always: every attempt raises; each point must exhaust its
    bounded restart budget and be reported failed — the run terminates
    instead of spinning."""
    res = run_grid_parallel(
        POINTS[:2], FAST, cache_dir=tmp_path,
        executor=ExecutorSettings(workers=1, chaos="raise-always",
                                  max_restarts=1))
    assert len(res.points) == 2
    assert all(r.failed and r.error for r in res.points)
    assert sorted(res.executor["failed"]) == sorted(_labels(res))
    # max_restarts=1 -> exactly 2 attempts per point, 1 retry each
    assert res.executor["in_worker_retries"] == 2


def test_chaos_one_failed_point_does_not_abort_grid(tmp_path):
    """A single persistently-failing point is reported failed; the rest
    of the grid completes and caches normally."""
    res = run_grid_parallel(
        POINTS, FAST, cache_dir=tmp_path,
        executor=ExecutorSettings(workers=2, chaos="raise-point-0",
                                  max_restarts=1))
    by = {r.point.label: r for r in res.points}
    assert by[POINTS[0].label].failed
    assert "injected persistent fault" in by[POINTS[0].label].error
    ok = [r for r in res.points if not r.failed]
    assert len(ok) == len(POINTS) - 1
    assert res.executor["failed"] == [POINTS[0].label]
    # the failed point renders, the table row says so
    assert "FAILED" in res.table()
    # on re-run the healthy points are cache hits; only the (no longer
    # chaos-injected) failed point computes
    again = run_grid_parallel(POINTS, FAST, cache_dir=tmp_path,
                              executor=ExecutorSettings(workers=2))
    assert again.executor["cache_hits"] == len(POINTS) - 1
    assert again.executor["computed"] == 1
    assert not any(r.failed for r in again.points)


def test_chaos_raise_after_exercises_in_worker_retry(tmp_path):
    """raise-after-N fires once per worker; the in-worker Supervisor
    retries and the point still completes (no parent-side restart)."""
    res = run_grid_parallel(
        POINTS[:2], FAST, cache_dir=tmp_path,
        executor=ExecutorSettings(workers=1, chaos="raise-after-1"))
    assert res.executor["computed"] == 2
    assert res.executor["failed"] == []
    assert res.executor["in_worker_retries"] == 1
    assert res.executor["restarts"] == 0


def test_chaos_spec_parsing():
    assert ChaosSpec.parse(None) == ChaosSpec()
    assert ChaosSpec.parse("kill-after-3").kill_after == 3
    assert ChaosSpec.parse("raise-after-1").raise_after == 1
    assert ChaosSpec.parse("raise-always").raise_always
    assert ChaosSpec.parse("raise-point-2").raise_point == 2
    s = ChaosSpec.parse("stall-0:2.5")
    assert s.stall_index == 0 and s.stall_s == 2.5
    with pytest.raises(ValueError, match="unknown chaos"):
        ChaosSpec.parse("set-fire-to-rack")
    with pytest.raises(ValueError):
        run_grid_parallel(POINTS[:1], FAST, cache_dir=None,
                          executor=ExecutorSettings(chaos="bogus"))


# ---------------------------------------------------------------------------
# stragglers
# ---------------------------------------------------------------------------

def test_straggler_speculative_redispatch(tmp_path):
    """A stalled first attempt is flagged against the robust-z threshold
    of completed-point wall times and speculatively re-dispatched; the
    fresh attempt wins and the grid never gates on the stalled worker."""
    pts = [SweepPoint("sm-10", "TEN")] + \
          [SweepPoint("sm-10", "PEN", input_bits=b) for b in range(4, 9)]
    res = run_grid_parallel(
        pts, FAST, cache_dir=tmp_path,
        executor=ExecutorSettings(workers=2, chaos="stall-0:15.0",
                                  straggler_min_samples=3))
    assert res.executor["stragglers_redispatched"] >= 1
    assert res.executor["failed"] == []
    assert len([r for r in res.points if not r.failed]) == len(pts)
    # the run must have finished long before the 15s stall elapsed
    assert res.executor["wall_s"] < 15.0


# ---------------------------------------------------------------------------
# preemption
# ---------------------------------------------------------------------------

def test_preemption_before_start_interrupts_resumably(tmp_path):
    pre = PreemptionHandler(install=False)
    pre.requested = True
    res = run_grid_parallel(POINTS, FAST, cache_dir=tmp_path,
                            executor=ExecutorSettings(workers=2),
                            preemption=pre)
    assert res.executor["interrupted"]
    assert res.executor["remaining"] == len(POINTS)
    assert res.executor["remaining_points"] == [p.label for p in POINTS]
    assert res.points == []


def test_preemption_mid_run_drains_and_resumes(tmp_path):
    """Preemption requested while the grid is in flight: the run stops
    early but every completed point is cached, so the follow-up run
    computes exactly the complement — zero recomputed points."""
    import threading
    pre = PreemptionHandler(install=False)
    t = threading.Timer(2.0, lambda: setattr(pre, "requested", True))
    t.start()
    try:
        first = run_grid_parallel(POINTS, FAST, cache_dir=tmp_path,
                                  executor=ExecutorSettings(workers=1),
                                  preemption=pre)
    finally:
        t.cancel()
    done = first.executor["computed"]
    resumed = run_grid_parallel(POINTS, FAST, cache_dir=tmp_path,
                                executor=ExecutorSettings(workers=1))
    assert resumed.executor["cache_hits"] == done
    assert resumed.executor["computed"] == len(POINTS) - done
    assert len(resumed.points) == len(POINTS)
    assert not any(r.failed for r in resumed.points)


# ---------------------------------------------------------------------------
# artifacts
# ---------------------------------------------------------------------------

def test_executor_persists_point_artifacts(tmp_path):
    """Every computed point checkpoints as a loadable packed DWNArtifact
    (runtime.checkpoint.save_artifact) when artifact_dir is set."""
    from repro.runtime.checkpoint import load_artifact
    adir = tmp_path / "artifacts"
    res = run_grid_parallel(
        POINTS[:2], FAST, cache_dir=tmp_path / "c",
        executor=ExecutorSettings(workers=2, artifact_dir=str(adir)))
    assert res.executor["computed"] == 2
    subdirs = sorted(p for p in adir.iterdir() if p.is_dir())
    assert len(subdirs) == 2
    art = load_artifact(subdirs[0])
    assert art.stage == "packed"
    assert art.spec.preset in ("sm-10", "sm-50")


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------

def test_chip_count_stays_off_jax_and_pins_one_chip(monkeypatch):
    """The dispatcher counts chips from device files, never via JAX; a
    CPU-only run counts none, and a pinned worker sees one chip."""
    from repro.sweep import executor
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert executor._tpu_chips() == 0
    env = executor._chip_env(3)
    assert env["TPU_VISIBLE_CHIPS"] == "3"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_ADDRESSES"] == \
        f"localhost:{env['TPU_PROCESS_PORT']}"


def test_tpu_host_runs_at_most_one_worker_per_chip(tmp_path, monkeypatch):
    """Asked for four workers on a two-chip host, the run starts two and
    still computes every point."""
    from repro.sweep import executor
    monkeypatch.setattr(executor, "_tpu_chips", lambda: 2)
    res = run_grid_parallel(POINTS, FAST, cache_dir=tmp_path,
                            executor=ExecutorSettings(workers=4))
    assert res.executor["workers"] == 2
    assert res.executor["workers_spawned"] == 2
    assert res.executor["computed"] == len(POINTS)
