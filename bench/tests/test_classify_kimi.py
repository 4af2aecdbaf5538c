"""The Kimi Linear classify cell: its reference, work counts and
resolution, and a dry run of the whole cell on the CPU at a tiny size."""

import dataclasses
import json
import shutil

import jax
import numpy as np
import pytest

from bench import harness, kimi_weights, work_kimi
from bench.reference_kimi import Reference, feature_error

CELL = "kimi-linear.classify"
CONFIG = harness.BENCH / "configs" / "Kimi-Linear-48B-A3B-Instruct.dwn-head.json"


def tiny_config(held: int = 4) -> dict:
    """The configuration file's keys at the program's reduced widths
    (``ArchConfig.reduced``, four layers and 8 experts), registered as
    arch ``kimi-tiny``."""
    cfg = json.loads(CONFIG.read_text())
    la = {"full_attn_layers": [3], "head_dim": 16, "kda_layers": [1, 2, 4],
          "num_heads": 4, "short_conv_kernel_size": 4}
    cfg.update(
        arch="kimi-tiny", hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=4, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, num_experts_per_token=2,
        vocab_size=251, kda_gate_rank=8, num_hidden_layers=4,
        num_experts=held, linear_attn_config=la,
        published={"num_hidden_layers": 4, "num_experts": 8,
                   "linear_attn_config": la},
        feature_err_limit=0.25)
    return cfg


@pytest.fixture(scope="module")
def tiny_arch():
    from repro.configs import get_arch
    from repro.configs.registry import _REGISTRY
    arch = dataclasses.replace(
        get_arch("kimi-linear-48b-a3b-instruct").reduced(), name="kimi-tiny",
        num_layers=4, layer_types=("kda", "kda", "mla", "kda"))
    _REGISTRY.setdefault("kimi-tiny", arch)
    return arch


def program_features(arch, cfg, seed, prompts):
    """The program's features of each prompt, one at a time, from the
    benchmark's weights."""
    import jax.numpy as jnp
    from repro.models import api
    from repro.workloads.lm_head import pool_features
    params = kimi_weights.model(cfg, seed)
    out = []
    for toks in prompts:
        cols = api.logit_columns(params, arch, jnp.asarray(toks[None]), 16,
                                 tp=1)
        out.append(np.asarray(pool_features(cols))[0])
    return np.stack(out)


def test_reference_matches_program_and_float8_control_fails(tiny_arch):
    """At the tiny widths (4 layers, d_model 64, 8 experts top-2) a token
    whose router scores tie within bf16 rounding takes another expert in
    the program, so the program reads up to ~0.1; the float8 control
    reads 0.5 and more.  The tiny configuration's limit is 0.25."""
    from bench.drivers.classify_kimi import program_arch
    cfg = tiny_config()
    limit = cfg["feature_err_limit"]
    arch = program_arch(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 251, n) for n in (40, 64, 17)]
    served = program_features(arch, cfg, 3, prompts)
    args = (prompts, kimi_weights.embedding(cfg, 3),
            lambda i: kimi_weights.layer(cfg, 3, i),
            kimi_weights.final_norm(cfg), kimi_weights.lm_head(cfg, 3))
    ref = Reference(cfg).features(*args)
    control = Reference(cfg, act_dtype=jax.numpy.float8_e4m3fn).features(
        *args)
    assert feature_error(served, ref) < limit
    assert feature_error(control, ref) > limit


def test_program_arch_refuses_a_file_that_differs(tiny_arch):
    from bench.drivers.classify_kimi import program_arch
    cfg = tiny_config()
    assert program_arch(cfg).layer_types == ("kda", "kda", "mla", "kda")
    with pytest.raises(ValueError, match="configuration file says"):
        program_arch(dict(cfg, routed_scaling_factor=2.5))


def test_work_counts_at_published_widths():
    """The hand count in PERF.md (section 3)."""
    cfg = json.loads(CONFIG.read_text())
    assert work_kimi.sequence_flops(cfg, 1) == 943_407_104
    assert work_kimi.weight_params(cfg) == 1_998_239_680
    assert work_kimi.step_flops(cfg, 8, 1024) == pytest.approx(
        8 * 2 * (471_683_072 * 1024 + 2 * 32 * 320 * 1024 * 1025 / 2))
    pk = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # 6 KDA layers x 8192 tokens x 49,280 bytes, bound by bandwidth
    assert work_kimi.kda_least_step_s(cfg, 8, 1024, pk) == pytest.approx(
        6 * 8192 * 49_280 / 819e9)


def test_cell_resolves_with_its_metrics():
    cell = harness.resolve(harness.load_benchmark(), CELL)
    assert cell.chips == 1
    assert cell.mix["driver"] == "classify_kimi"
    assert [m["name"] for m in cell.end_to_end] == ["serve_samples_per_s",
                                                    "setup_s"]
    assert [m["name"] for m in cell.per_layer] == [
        "step_ms.classify_kimi", "device_wait_ms.classify_kimi",
        "token_occupancy.classify_kimi", "idle_share.classify_kimi",
        "backbone_roofline.classify_kimi", "serve_mfu.classify_kimi",
        "kda_roofline.classify_kimi"]
    for m in cell.per_layer:
        assert harness.reader(cell, m["name"]).read


def test_kda_roofline_reads_the_kernel_ops_only():
    from types import SimpleNamespace
    reader = harness._load_module(
        harness.BENCH / "metrics" / "kda_roofline.classify_kimi.py")
    trace = SimpleNamespace(forward_calls=10.0, forward_s=2.0,
                            ops={"fusion.1": 1.0})
    ctx = SimpleNamespace(trace=trace, counters={"steps": 0}, config={},
                          peaks={})
    assert reader.read(ctx) is None           # no kda_chunk ran
    assert reader.read(SimpleNamespace(trace=None)) is None


TINY_MIX = {"driver": "classify_kimi", "in_flight": 4, "prompts": 6,
            "length_median": 12, "length_sigma": 0.7, "length_min": 4,
            "length_max": 32, "schedule_seed": 0, "min_length": 8,
            "step_tokens": 64, "check_requests": 4}


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
