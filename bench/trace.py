"""Reduce a profiler trace to the benchmark's device numbers.

``summarize(dir, chips)`` reads the ``.xplane.pb`` that ``jax.profiler``
wrote under ``dir`` and returns a :class:`Summary`:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device (its ``XLA Ops`` line), inside the window, averaged over the
  cell's chips; ``window_s``: the window's length, from the harness's own
  ``bench.window`` span on the host;
* ``forward_s`` / ``forward_calls``: device time and count, per chip, of
  the served forward: executions of the jitted backend step (the module
  whose name starts ``jit_traced``, the name ``BoundBackend`` gives it);
* ``ops``: device seconds per operation name, per chip;
* ``gaps``: idle device seconds, each gap named by what the host was doing
  at its middle (the shortest host span around that instant).

``reduce(planes, chips)`` does the work on plain data, so that a test can
feed it a small recorded trace: ``planes`` is a list of
``{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns], ...]}]}``.
"""

from __future__ import annotations

import collections
import dataclasses
import heapq
from pathlib import Path

WINDOW_SPAN = "bench.window"
FORWARD_MODULE = "jit_traced"


@dataclasses.dataclass
class Summary:
    busy_s: float
    window_s: float
    forward_s: float
    forward_calls: float
    ops: dict
    gaps: dict

    def breakdown(self) -> dict:
        top = lambda d: [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(self.ops), "idle_gaps": top(self.gaps)}


def load(trace_dir) -> list[dict]:
    """The trace's planes as plain data."""
    import jax
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(str(paths[-1]))
    planes = []
    for plane in data.planes:
        lines = [{"name": line.name,
                  "events": [(e.name, e.start_ns, e.duration_ns)
                             for e in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def summarize(trace_dir, chips: int) -> Summary:
    return reduce(load(trace_dir), chips)


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def reduce(planes: list[dict], chips: int) -> Summary:
    devices = sorted((p for p in planes
                      if p["name"].startswith("/device:TPU:")
                      and _line(p, "XLA Ops")),
                     key=lambda p: int(p["name"].rsplit(":", 1)[1]))[:chips]
    if not devices:
        raise ValueError("the trace has no TPU device plane with XLA Ops")
    host = [ev for p in planes if p["name"].startswith("/host:")
            for line in p["lines"] for ev in line["events"]]
    window = [ev for ev in host if ev[0] == WINDOW_SPAN]
    if window:
        w0 = window[0][1]
        w1 = w0 + window[0][2]
    else:
        evs = [ev for p in devices for ev in _line(p, "XLA Ops")]
        w0 = min(ev[1] for ev in evs)
        w1 = max(ev[1] + ev[2] for ev in evs)

    busy = fwd = calls = 0.0
    ops = collections.Counter()
    first_union = None
    for p in devices:
        ivs = []
        for name, s, d in _line(p, "XLA Ops"):
            s, e = max(s, w0), min(s + d, w1)
            if e > s:
                ivs.append((s, e))
                ops[name] += (e - s) / 1e9
        merged = _union(ivs)
        if first_union is None:
            first_union = merged
        busy += sum(e - s for s, e in merged) / 1e9
        for name, s, d in _line(p, "XLA Modules"):
            if name.startswith(FORWARD_MODULE) and w0 <= s < w1:
                fwd += d / 1e9
                calls += 1
    n = len(devices)
    gaps = _name_gaps(first_union, w0, w1, host)
    return Summary(busy_s=busy / n, window_s=(w1 - w0) / 1e9,
                   forward_s=fwd / n, forward_calls=calls / n,
                   ops={k: v / n for k, v in ops.items()}, gaps=gaps)


def _name_gaps(busy, w0, w1, host):
    """Idle seconds on the first chip, by the host span around each gap's
    middle (the shortest one that contains it)."""
    spans = sorted((s, s + d, name) for name, s, d in host
                   if name != WINDOW_SPAN and d > 0)
    out = collections.Counter()
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    active, j = [], 0        # heap of (duration, end, name) begun by now
    for g0, g1 in zip(edges[::2], edges[1::2]):
        if g1 <= g0:
            continue
        mid = (g0 + g1) / 2
        while j < len(spans) and spans[j][0] <= mid:
            s, e, nm = spans[j]
            heapq.heappush(active, (e - s, e, nm))
            j += 1
        # gaps come in time order, so a span that ended is done for good
        while active and active[0][1] <= mid:
            heapq.heappop(active)
        out[active[0][2] if active else "host: no span"] += (g1 - g0) / 1e9
    return dict(out)
