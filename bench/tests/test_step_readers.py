"""The engine-step readers (``metrics/{d2h_ms,h2d_ms,device_wait_ms,
step_host_ms,batch_occupancy}.py``) on a filled step log, and the runs in
which they find nothing to read."""

import sys
from types import SimpleNamespace

import pytest

from bench import harness
from repro.serving import steplog

MS = 1_000_000
#: the window's phase times: batch, h2d, dispatch, device, d2h, resolve
PHASE_NS = [MS // 10, MS // 5, 3 * MS // 10, 2 * MS // 5, 2 * MS, MS // 20]
ROWS = [4096, 4096, 2048]
EXPECTED = {
    "d2h_ms.offline": 2.0,
    "h2d_ms.offline": 0.2,
    "device_wait_ms.offline": 0.4,
    "step_host_ms.offline": 0.45,
    "batch_occupancy.offline": 100.0 * sum(ROWS) / (3 * 4096),
}


@pytest.fixture
def ring(monkeypatch):
    """A small ring holding two steps before the window, then the
    window's three."""
    ring = steplog.Ring(8)
    monkeypatch.setattr(steplog, "RING", ring)
    for _ in range(2):
        ring.record([9 * MS] * 6, 54 * MS, 8, 4096, 1, 1)
    for rows in ROWS:
        ring.record(PHASE_NS, sum(PHASE_NS), rows, 4096, 1, 0)
    return ring


def _read(metric, steps):
    cell = harness.resolve(harness.load_benchmark(), "jsc-lg.offline")
    assert metric in {m["name"] for m in cell.per_layer}
    ctx = SimpleNamespace(counters={"steps": steps})
    return harness.reader(cell, metric).read(ctx)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_on_a_filled_ring(ring, metric):
    assert _read(metric, len(ROWS)) == pytest.approx(EXPECTED[metric])


def test_time_readers_partition_the_step(ring):
    parts = ("h2d_ms", "device_wait_ms", "d2h_ms", "step_host_ms")
    total = sum(_read(f"{p}.offline", len(ROWS)) for p in parts)
    assert total == pytest.approx(sum(PHASE_NS) / MS)


@pytest.mark.parametrize("case", ["no_steps", "ring_overwritten",
                                  "no_step_log"])
@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_finds_nothing(ring, monkeypatch, metric, case):
    steps = len(ROWS)
    if case == "no_steps":
        steps = 0
    elif case == "ring_overwritten":
        for rows in ROWS * 3:
            ring.record(PHASE_NS, sum(PHASE_NS), rows, 4096, 1, 0)
        steps = ring.capacity + 1
    else:
        # a program that predates the step log
        import repro.serving
        monkeypatch.delattr(repro.serving, "steplog")
        monkeypatch.setitem(sys.modules, "repro.serving.steplog", None)
    assert _read(metric, steps) is None
