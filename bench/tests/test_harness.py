"""The harness finds a new configuration, mix and cell from data files
alone, and dry-runs them on the CPU (the look for a chip skipped)."""

import json
import shutil
import time
from types import SimpleNamespace

import pytest

from bench import harness

TINY_MIXES = {
    "tiny-closed": {"driver": "closed_loop", "request_rows": 16,
                    "in_flight": 2, "max_bucket": 16, "min_bucket": 16,
                    "payloads": 3, "check_requests": 4},
    "tiny-open": {"driver": "open_loop", "rate_samples_per_s": 400,
                  "size_lo": 1, "size_hi": 8, "deadline_ms": 2000.0,
                  "max_queue_samples": 64, "max_bucket": 8,
                  "min_bucket": 8, "schedule_seed": 3,
                  "check_requests": 1000},
}


@pytest.fixture(scope="module")
def tmp_bench(tmp_path_factory):
    """A copy of ``bench/`` with a new config, two mixes and two cells
    added as files, and a ``BENCHMARK.json`` that names them."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = json.loads((harness.BENCH / "configs" / "dwn-jsc-sm.json")
                     .read_text())
    cfg.update(name="tiny-sm", n_fit=500, n_train=500)
    (root / "bench" / "configs" / "tiny-sm.json").write_text(
        json.dumps(cfg))
    for name, mix in TINY_MIXES.items():
        (root / "bench" / "mixes" / f"{name}.json").write_text(
            json.dumps(mix))
    bench = harness.load_benchmark()
    bench["configs"].append({"name": "tiny-sm", "source": "test",
                             "file": "bench/configs/tiny-sm.json",
                             "reduced": [], "why": "test"})
    for mix in TINY_MIXES:
        bench["workloads"].append({"name": f"tiny.{mix}", "config": "tiny-sm",
                                   "traffic": mix, "chips": 1, "why": "t"})
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    e2e["serve_samples_per_s"]["workloads"].append("tiny.tiny-closed")
    bench["end_to_end"].append({
        "name": "serve_p99_ms", "unit": "ms", "better": "lower",
        "bound": 0.25, "source": "host_clock",
        "workloads": ["tiny.tiny-open"]})
    (root / "bench" / "metrics" / f"{NEW_METRIC}.py").write_text(
        "def read(ctx):\n    return ctx.counters.get('served_samples')\n")
    bench["per_layer"].append({
        "name": NEW_METRIC, "unit": "samples", "better": "higher",
        "source": "host_clock", "layer": "engine step",
        "moves": "serve_samples_per_s", "workloads": ["tiny.tiny-closed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


NEW_METRIC = "served_rows.tiny"


def test_new_metric_from_a_file(tmp_bench):
    bench = harness.load_benchmark(tmp_bench)
    cell = harness.resolve(bench, "tiny.tiny-closed", tmp_bench / "bench")
    assert [m["name"] for m in cell.per_layer] == [NEW_METRIC]
    ctx = SimpleNamespace(counters={"served_samples": 48})
    assert harness.reader(cell, NEW_METRIC).read(ctx) == 48
    other = harness.resolve(bench, "tiny.tiny-open", tmp_bench / "bench")
    assert NEW_METRIC not in [m["name"] for m in other.per_layer]


def test_every_committed_cell_resolves():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        cell = harness.resolve(bench, w["name"])
        assert harness.driver(cell).setup
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in names
            assert harness.reader(cell, m["name"]).read


@pytest.mark.parametrize("mix", sorted(TINY_MIXES))
def test_new_cell_from_files_dry_runs(no_chip_check, tmp_bench, mix):
    bench = harness.load_benchmark(tmp_bench)
    cell = harness.resolve(bench, f"tiny.{mix}", tmp_bench / "bench")
    assert cell.config["name"] == "tiny-sm" and cell.mix == TINY_MIXES[mix]
    out = harness.run(cell, seed=2**31 + 11, seconds=0.5, trace=False,
                      t_start=time.perf_counter())
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0
    assert "setup_s" in out["metrics"] and len(out["metrics"]) == 2
    assert list(out)[-1] == "compared"


def test_no_chip_is_an_error():
    with pytest.raises(harness.NoChip):
        harness.require_chips(1)


def test_metric_split_by_cells_shares_one_reader(tmp_bench):
    """``idle_share.<anything>`` finds ``metrics/idle_share.py``; a file of
    the metric's own name wins over it."""
    bench = harness.load_benchmark(tmp_bench)
    cell = harness.resolve(bench, "tiny.tiny-closed", tmp_bench / "bench")
    trace = SimpleNamespace(busy_s=1.0, window_s=4.0)
    ctx = SimpleNamespace(trace=trace, counters={"served_samples": 7})
    assert harness.reader(cell, "idle_share.new-cell").read(ctx) == 75.0
    assert harness.reader(cell, NEW_METRIC).read(ctx) == 7


def test_declared_metric_with_nothing_to_read_is_reported(
        no_chip_check, tmp_bench, capsys):
    bench = harness.load_benchmark(tmp_bench)
    cell = harness.resolve(bench, "tiny.tiny-closed", tmp_bench / "bench")
    cell.per_layer = [dict(cell.per_layer[0], name="step_ms.tiny")]
    summary = SimpleNamespace(busy_s=0.1, window_s=0.5,
                              breakdown=lambda: {})
    from bench import trace, work
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(trace, "summarize", lambda d, chips: summary)
        mp.setattr(work, "peaks", lambda kind: {})
        mp.setattr(harness, "reader", lambda c, m: SimpleNamespace(
            read=lambda ctx: None))
        out = harness.run(cell, seed=3, seconds=0.3, trace=True,
                          t_start=time.perf_counter())
    assert "step_ms.tiny" not in out["metrics"]
    assert "step_ms.tiny found nothing to read" in capsys.readouterr().err
