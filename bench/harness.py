"""The benchmark's harness: finds a cell's parts by name and runs it once.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``bench/configs/<config>.json``: the configuration's sizes and source;
* ``bench/mixes/<traffic>.json``: the mix's parameters, and in
  ``driver`` the module under ``bench/drivers/`` that drives it;
* ``bench/metrics/<metric>.py``: a reader with ``read(ctx)`` returning the
  metric's value, or None where the run has nothing for it to read.  A
  metric split by the cells it reports in (``idle_share.offline``,
  ``idle_share.trigger``) may share one reader, found by the name before
  the first dot (``bench/metrics/idle_share.py``).

A driver module has ``setup(cell, seed, seconds, span) -> session``; the session
has ``window(seconds) -> Window``, ``finish()`` (drains and frees the
program's state) and ``check() -> list[Compared]``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_out" / "trace"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list          # BENCHMARK.json metric entries
    per_layer: list
    bench_dir: Path = BENCH


@dataclasses.dataclass
class Window:
    """What a driver measured in one window."""
    metrics: dict             # end-to-end metric name -> value
    counters: dict            # what per-layer readers read
    attempted: int
    failed: int


@dataclasses.dataclass
class Compared:
    """One number the correctness check compared, with its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


# ---------------------------------------------------------------------------
# resolving a cell
# ---------------------------------------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(entry: dict, cell: str, cell_e2e: set[str]) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry.get("moves") in cell_e2e if "moves" in entry else True


def resolve(bench: dict, name: str, bench_dir: Path = BENCH) -> Cell:
    """The cell ``name`` with its configuration, mix and metrics."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((bench_dir.parent / configs[w["config"]]["file"])
                        .read_text())
    mix = json.loads((bench_dir / "mixes" / f"{w['traffic']}.json")
                     .read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, int(w["chips"]), config, mix, e2e, per_layer,
                bench_dir)


def driver(cell: Cell):
    """The mix's driver module, ``bench/drivers/<driver>.py``."""
    return _load_module(cell.bench_dir / "drivers"
                        / f"{cell.mix['driver']}.py")


def reader(cell: Cell, metric: str):
    """The per-layer metric's reader: ``bench/metrics/<metric>.py``, else
    the one its name before the first dot names."""
    d = cell.bench_dir / "metrics"
    path = d / f"{metric}.py"
    return _load_module(path if path.exists()
                        else d / f"{metric.split('.')[0]}.py")


def _load_module(path: Path):
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# devices, cache, spans
# ---------------------------------------------------------------------------

def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, holding
    every program, so that only a checkout's first run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(chips: int):
    """The TPU devices, or :class:`NoChip`; never the CPU.  A one-chip
    cell runs on the first chip of any host; a cell on several chips
    spans the whole host, so it needs exactly that many."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no accelerator: {e}") from None
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips or (chips > 1 and len(devs) != chips):
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def span_factory(tracing: bool):
    """``span(name)``: a profiler annotation while tracing, else nothing."""
    if not tracing:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..100) of a non-empty list."""
    v = sorted(values)
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float) -> dict:
    """Set up, measure one window, check it, and return the result line."""
    import jax
    from bench import trace as trace_mod
    from bench import work
    devs = require_chips(cell.chips)
    span = span_factory(trace)
    session = driver(cell).setup(cell, seed, seconds, span)
    setup_s = time.perf_counter() - t_start

    summary = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        with jax.profiler.trace(str(TRACE_DIR), profiler_options=opts):
            with span("bench.window"):
                win = session.window(seconds)
    else:
        win = session.window(seconds)
    used = devs[:cell.chips]
    mem = [d.memory_stats() or {} for d in used]
    peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    session.finish()
    if trace:
        summary = trace_mod.summarize(TRACE_DIR, chips=len(used))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    compared = session.check()

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    metrics = {}
    out = {"correct": bool(compared) and all(c.ok for c in compared),
           "attempted": win.attempted, "failed": win.failed}
    if trace:
        ctx = SimpleNamespace(counters=win.counters, trace=summary,
                              e2e=win.metrics, config=cell.config,
                              mix=cell.mix, chips=len(used),
                              peaks=work.peaks(devs[0].device_kind))
        for m in cell.per_layer:
            value = reader(cell, m["name"]).read(ctx)
            if value is None:
                # left out of the line rather than made up, but not in
                # silence: a declared metric that goes missing is a fault
                print(f"bench: per-layer metric {m['name']} found nothing "
                      f"to read in this run", file=sys.stderr)
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
    else:
        values = dict(win.metrics, setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    if summary is not None:
        out["breakdown"] = summary.breakdown()
    out["compared"] = {c.name: {"value": _finite(c.value),
                                "limit": c.limit} for c in compared}
    return out


def _finite(v):
    """A number JSON can hold: a non-finite reading prints as a string."""
    return v if math.isfinite(v) else str(v)


def main(workload: str, seed: int, seconds: float, trace: bool, *,
         t_start: float) -> int:
    bench = load_benchmark()
    cell = resolve(bench, workload)
    enable_compile_cache()
    try:
        result = run(cell, seed, seconds, trace, t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


__all__ = ["Cell", "Compared", "NoChip", "Window", "load_benchmark", "main",
           "percentile", "resolve", "run"]
