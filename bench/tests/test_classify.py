"""The granite classify cell: its reference, work counts and resolution,
and a dry run of the whole cell on the CPU at a tiny size."""

import dataclasses
import json
import shutil

import jax
import numpy as np
import pytest

from bench import granite_weights, harness, work_granite
from bench.reference_granite import Reference, feature_error

CELL = "granite-h-small.classify"
CONFIG = harness.BENCH / "configs" / "granite-4.0-h-small.dwn-head.json"


def tiny_config(held: int = 4) -> dict:
    """The configuration file's keys at the program's reduced widths
    (``ArchConfig.reduced``, four layers and 8 experts), registered as
    arch ``granite-tiny``."""
    cfg = json.loads(CONFIG.read_text())
    cfg.update(
        arch="granite-tiny", hidden_size=64, intermediate_size=32,
        shared_intermediate_size=48, num_attention_heads=4,
        num_key_value_heads=2, num_experts_per_tok=2, vocab_size=251,
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
        mamba_chunk_size=8, attention_multiplier=1 / 16,
        layer_types=["mamba", "mamba", "attention", "mamba"],
        num_hidden_layers=4, num_local_experts=held,
        published={"num_hidden_layers": 4, "num_local_experts": 8},
        feature_err_limit=0.02)
    return cfg


@pytest.fixture(scope="module")
def tiny_arch():
    from repro.configs import get_arch
    from repro.configs.registry import _REGISTRY
    arch = dataclasses.replace(
        get_arch("granite-4.0-h-small").reduced(), name="granite-tiny",
        num_layers=4, layer_types=("mamba", "mamba", "attention", "mamba"),
        num_experts=8)
    _REGISTRY.setdefault("granite-tiny", arch)
    return arch


def program_features(arch, cfg, seed, prompts):
    """The program's features of each prompt, one at a time, from the
    benchmark's weights."""
    import jax.numpy as jnp
    from repro.models import api
    from repro.workloads.lm_head import pool_features
    params = granite_weights.model(cfg, seed)
    out = []
    for toks in prompts:
        cols = api.logit_columns(params, arch, jnp.asarray(toks[None]), 16,
                                 tp=1)
        out.append(np.asarray(pool_features(cols))[0])
    return np.stack(out)


def test_reference_matches_program_and_float8_control_fails(tiny_arch):
    """At the tiny widths (4 layers, d_model 64) the program reads
    0.011-0.013 over three seeds and the float8 control 0.031-0.054, so
    the tiny configuration's limit is 0.02."""
    from bench.drivers.classify import program_arch
    cfg = tiny_config()
    limit = cfg["feature_err_limit"]
    arch = program_arch(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 251, n) for n in (40, 64, 17)]
    served = program_features(arch, cfg, 3, prompts)
    args = (prompts, granite_weights.embedding(cfg, 3),
            lambda i: granite_weights.layer(cfg, 3, i),
            granite_weights.final_norm(cfg))
    ref = Reference(cfg).features(*args)
    control = Reference(cfg, act_dtype=jax.numpy.float8_e4m3fn).features(
        *args)
    assert feature_error(served, ref) < limit
    assert feature_error(control, ref) > limit


def test_work_counts_at_published_widths():
    """The hand count in PERF.md (section 3)."""
    cfg = json.loads(CONFIG.read_text())
    assert work_granite.sequence_flops(cfg, 1) == 2 * (1_310_139_392 + 8192)
    assert work_granite.weight_params(cfg) == 2_003_716_736
    assert work_granite.step_flops(cfg, 8, 1024) == \
        8 * (2_620_278_784 * 1024 + 8192 * 1024 * 1025)


def test_cell_resolves_with_its_metrics():
    cell = harness.resolve(harness.load_benchmark(), CELL)
    assert cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["serve_samples_per_s",
                                                    "setup_s"]
    assert [m["name"] for m in cell.per_layer] == [
        "step_ms.classify", "device_wait_ms.classify",
        "idle_share.classify", "token_occupancy.classify",
        "backbone_roofline.classify", "serve_mfu.classify"]
    for m in cell.per_layer:
        assert harness.reader(cell, m["name"]).read


def test_jsc_lg_offline_resolves_as_before():
    cell = harness.resolve(harness.load_benchmark(), "jsc-lg.offline")
    assert [m["name"] for m in cell.end_to_end] == ["serve_samples_per_s",
                                                    "setup_s"]
    assert [m["name"] for m in cell.per_layer] == [
        "step_ms.offline", "dwn_forward_roofline.offline",
        "serve_mfu.offline", "idle_share.offline", "d2h_ms.offline",
        "h2d_ms.offline", "device_wait_ms.offline", "step_host_ms.offline",
        "batch_occupancy.offline"]


TINY_MIX = {"driver": "classify", "in_flight": 4, "prompts": 6,
            "length_median": 12, "length_sigma": 0.7, "length_min": 4,
            "length_max": 32, "schedule_seed": 0, "min_length": 8,
            "step_tokens": 64,
            "check_requests": 4}


def test_tiny_cell_runs_correct(tiny_arch, tmp_path, no_chip_check):
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(tiny_config()))
    (root / "bench" / "mixes" / "tiny-classify.json").write_text(
        json.dumps(TINY_MIX))
    bench = harness.load_benchmark()
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.classify", "config": "tiny",
                               "traffic": "tiny-classify", "chips": 1,
                               "why": "t"})
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    e2e["serve_samples_per_s"]["workloads"].append("tiny.classify")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.resolve(harness.load_benchmark(root), "tiny.classify",
                           root / "bench")
    out = harness.run(cell, 5, 1.0, False, t_start=0.0)
    assert out["correct"], out["compared"]
    assert out["compared"]["rows_off"]["value"] == 0
    assert out["metrics"]["serve_samples_per_s"]["value"] > 0


def test_the_step_cut_by_the_close_counts_by_its_share():
    from bench.drivers.classify import _cut_share
    # 4 prompts of a step from 9.9 to 10.3 s, 8 of the next to 10.7 s
    late = [(10.3, 9.9)] * 4 + [(10.7, 10.3)] * 8
    assert _cut_share(late, 10.0) == pytest.approx(1.0)
    assert _cut_share([], 10.0) == 0.0
    # a step that started after the close counts nothing
    assert _cut_share([(10.4, 10.1)] * 3, 10.0) == 0.0
