"""Per-architecture smoke tests (mandated): reduced config, one forward +
one train step on CPU, asserting output shapes and no NaNs."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch, list_archs, SHAPES, cell_supported
from repro.configs.registry import assigned_archs
from repro.models import api

ARCHS = ["granite-moe-3b-a800m", "mixtral-8x7b", "whisper-large-v3",
         "mamba2-1.3b", "qwen3-8b", "phi3-mini-3.8b", "qwen2-7b",
         "qwen3-14b", "recurrentgemma-2b", "llava-next-34b",
         "granite-4.0-h-small", "kimi-linear-48b-a3b-instruct"]
#: archs with a decode cache (granite-4.0-h-small and Kimi Linear serve
#: whole prompts to the DWN head; they have no prefill/decode path)
DECODE_ARCHS = [a for a in ARCHS if a not in (
    "granite-4.0-h-small", "kimi-linear-48b-a3b-instruct")]


def _batch(cfg, B=2, S=24, key=None):
    key = key or jax.random.PRNGKey(0)
    b = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
         "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "encdec":
        b["frames"] = jax.random.normal(
            key, (B, cfg.enc_frames, cfg.d_model), jnp.float32) * 0.1
    if cfg.family == "vlm":
        b["patches"] = jax.random.normal(
            key, (B, cfg.num_patches, cfg.d_model), jnp.float32) * 0.02
    return b


def test_all_assigned_archs_registered():
    assert sorted(ARCHS) == assigned_archs()


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    cfg = get_arch(arch)
    # spot checks of the published dims
    full = {
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 512, 49155),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
        "mamba2-1.3b": (48, 2048, 0, 0, 0, 50280),
        "qwen3-8b": (36, 4096, 32, 8, 12288, 151936),
        "phi3-mini-3.8b": (32, 3072, 32, 32, 8192, 32064),
        "qwen2-7b": (28, 3584, 28, 4, 18944, 152064),
        "qwen3-14b": (40, 5120, 40, 8, 17408, 151936),
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
        "llava-next-34b": (60, 7168, 56, 8, 20480, 64000),
        "granite-4.0-h-small": (40, 4096, 32, 8, 768, 100352),
        "kimi-linear-48b-a3b-instruct": (27, 2304, 32, 32, 1024, 163840),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == full, (arch, got, full)


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_arch(arch).reduced()
    mod = api.module_for(cfg)
    params = mod.init_params(jax.random.PRNGKey(0), cfg, tp=1)
    batch = _batch(cfg)
    B, S = batch["tokens"].shape

    logits, *_ = mod.forward(params, cfg, batch, tp=1)
    exp_S = S + (cfg.num_patches if cfg.family == "vlm" else 0)
    assert logits.shape == (B, exp_S, cfg.vocab_padded(1))
    assert np.isfinite(np.asarray(logits, np.float32)).all()

    step, opt = api.make_train_step(cfg, tp=1)
    opt_state = opt.init(params)
    # jitted, as the trainer runs it: eagerly, every leaf's update
    # compiles its own small programs
    params2, opt_state2, metrics = jax.jit(step)(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    for leaf in jax.tree.leaves(params2):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_smoke_decode_step(arch):
    cfg = get_arch(arch).reduced()
    mod = api.module_for(cfg)
    params = mod.init_params(jax.random.PRNGKey(1), cfg, tp=1)
    batch = _batch(cfg, B=2, S=16)
    logits, cache = mod.prefill(params, cfg, batch, tp=1, cache_len=20)
    assert logits.shape == (2, cfg.vocab_padded(1))
    nxt = jnp.full((2, 1), 3, jnp.int32)
    logits2, cache2 = mod.decode_step(params, cfg, cache, nxt, tp=1)
    assert logits2.shape == (2, cfg.vocab_padded(1))
    assert np.isfinite(np.asarray(logits2, np.float32)).all()


def test_long_500k_skips_documented():
    skipped = [a for a in ARCHS
               if not cell_supported(get_arch(a), SHAPES["long_500k"])[0]]
    # the archs with full-attention layers skip (granite-4.0-h-small's 4
    # of 40 and Kimi Linear's 7 MLA layers of 27 keep a dense KV cache);
    # SSM/hybrid/SWA run
    assert sorted(skipped) == sorted([
        "granite-moe-3b-a800m", "whisper-large-v3", "qwen3-8b",
        "phi3-mini-3.8b", "qwen2-7b", "qwen3-14b", "llava-next-34b",
        "granite-4.0-h-small", "kimi-linear-48b-a3b-instruct"])
    runnable = sorted(set(ARCHS) - set(skipped))
    assert runnable == sorted(["mixtral-8x7b", "mamba2-1.3b",
                               "recurrentgemma-2b"])
