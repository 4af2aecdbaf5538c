"""The reduction from a profiler trace to busy time, forward time and the
breakdown, on small traces with known answers."""

import json
from pathlib import Path

import pytest

from bench import trace

MS = 1_000_000  # ns


def plane(name, **lines):
    return {"name": name, "lines": [{"name": k, "events": v}
                                    for k, v in lines.items()]}


def hand_trace():
    """One chip, a 10 ms window: two forward modules of 2 ms, each two
    ops, with idle gaps while the host submits and waits."""
    dev = plane("/device:TPU:0",
                **{"XLA Modules": [("jit_traced(1)", 1 * MS, 2 * MS),
                                   ("jit_traced(1)", 5 * MS, 2 * MS),
                                   ("jit_other", 8 * MS, 1 * MS)],
                   "XLA Ops": [("fusion.1", 1 * MS, 1 * MS),
                               ("custom-call", 2 * MS, 1 * MS),
                               ("fusion.1", 5 * MS, 1 * MS),
                               ("custom-call", 6 * MS, 1 * MS),
                               ("copy", 8 * MS, 1 * MS),
                               ("late", 11 * MS, 1 * MS)]})
    host = plane("/host:CPU",
                 python=[("bench.window", 0, 10 * MS),
                         ("bench.submit", 0, 1 * MS),
                         ("bench.wait", 3 * MS, 2 * MS),
                         ("bench.wait", 7 * MS, 3 * MS)])
    return [host, dev]


def test_busy_forward_and_gaps_of_a_hand_trace():
    s = trace.reduce(hand_trace(), chips=1)
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx(0.005)      # "late" is outside
    assert s.forward_calls == 2
    assert s.forward_s == pytest.approx(0.004)
    assert s.ops["fusion.1"] == pytest.approx(0.002)
    assert s.gaps == pytest.approx({"bench.submit": 0.001,
                                    "bench.wait": 0.004})
    b = s.breakdown()
    assert b["device_ops"][0][1] == pytest.approx(0.002)
    assert b["idle_gaps"][0] == ["bench.wait", pytest.approx(0.004)]


def test_busy_is_averaged_over_the_cells_chips():
    planes = hand_trace()
    other = json.loads(json.dumps(planes[1]))
    other["name"] = "/device:TPU:1"
    other["lines"][1]["events"] = [("fusion.1", 0, 10 * MS)]
    s = trace.reduce(planes + [other], chips=2)
    assert s.busy_s == pytest.approx((0.005 + 0.010) / 2)
    assert trace.reduce(planes + [other], chips=1).busy_s == \
        pytest.approx(0.005)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce([hand_trace()[0]], chips=1)
