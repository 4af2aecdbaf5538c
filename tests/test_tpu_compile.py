"""Ahead-of-time compiles of the served DWN kernels for a TPU v5e.

Interpret mode runs a Pallas kernel's Python, not Mosaic, so it cannot
show whether the chip's compiler accepts the kernel or whether its tiles
fit VMEM.  These tests compile the served fused kernel and the
packed-xla step for a described v5e chip that is not attached, at the
width of every registered DWN preset — JSC (F=16, T=200, C=5), MNIST
(F=196, C=10) and the LM head (F=16, T=64, C=5), all fan-in 6 — and at
a two-layer stack; granite-4.0-h-small's held experts at their
published widths, with both sizes of their sorted buffer; and the
``kda_chunk`` kernel and the whole Kimi Linear backbone step of the
``kimi-linear.classify`` cell at its four step shapes.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.model import DWNConfig, FrozenDWN, apply_hard_packed
from repro.kernels import autotune
from repro.kernels.fused import ops as f_ops

# name -> (F, T, lut_counts, C)
WIDTHS = {
    "jsc-sm-50": (16, 200, (50,), 5),
    "jsc-md-360": (16, 200, (360,), 5),
    "jsc-lg-2400": (16, 200, (2400,), 5),
    "mnist-sm-100": (196, 8, (100,), 10),
    "mnist-md-500": (196, 8, (500,), 10),
    "mnist-lg-2000": (196, 16, (2000,), 10),
    "lm-head-50": (16, 64, (50,), 5),
    "jsc-two-layer": (16, 200, (96, 50), 5),
}
FAN_IN = 6


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _frozen(name: str, seed: int = 0) -> FrozenDWN:
    F, T, luts, C = WIDTHS[name]
    rng = np.random.default_rng(seed)
    th = np.sort(rng.uniform(-1, 1, (F, T)).astype(np.float32), axis=1)
    inputs, mappings, tables = F * T, [], []
    for m in luts:
        mappings.append(rng.integers(0, inputs, (m, FAN_IN)).astype(np.int32))
        tables.append(rng.integers(0, 2, (m, 2 ** FAN_IN)).astype(np.int32))
        inputs = m
    cfg = DWNConfig(num_features=F, bits_per_feature=T, lut_counts=luts,
                    fan_in=FAN_IN, num_classes=C)
    return FrozenDWN(cfg, th, mappings, tables)


def _compile(fn, batch: int, features: int, sharding):
    x = jax.ShapeDtypeStruct((batch, features), jnp.float32,
                             sharding=sharding)
    return jax.jit(fn).lower(x).compile()


def _fused(fr: FrozenDWN, config=None):
    return f_ops.make_forward_packed(
        fr.thresholds, fr.mapping_idx, fr.tables_bin, fr.cfg.num_classes,
        interpret=False, config=config)


@pytest.mark.parametrize("name", sorted(WIDTHS))
def test_fused_kernel_compiles_for_v5e(one_chip, name):
    """The served kernel lowers to Mosaic and fits the chip at B=256."""
    fr = _frozen(name)
    compiled = _compile(_fused(fr), 256, fr.cfg.num_features, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize(
    "bucket,config",
    [(b, c) for b in (256, 4096) for c in autotune.candidate_configs(b)],
    ids=lambda v: v.label if isinstance(v, autotune.FusedConfig) else str(v))
def test_every_tuning_candidate_compiles_at_lg(one_chip, bucket, config):
    """Every config the tuner may pick compiles at lg-2400 for the
    smallest served bucket and the serve CLI's full batch — a refused
    candidate would stop engine startup."""
    fr = _frozen("jsc-lg-2400")
    compiled = _compile(_fused(fr, config), bucket, 16, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_packed_xla_step_compiles_at_lg(one_chip):
    """The plain-XLA packed backend fits one chip at lg-2400, B=256."""
    fr = _frozen("jsc-lg-2400")
    compiled = _compile(lambda x: apply_hard_packed(fr, x), 256, 16,
                        one_chip)
    mem = compiled.memory_analysis()
    assert "tpu_custom_call" not in compiled.as_text()
    assert 0 < mem.temp_size_in_bytes < 16 * 2 ** 30


def test_packed_answer_is_linear_on_v5e(one_chip):
    """The served step's answer compiles to one 1-D int32 output at lg,
    B=4096: a 1-D array is held in the host's linear order, so its copy
    to the host needs no un-tiling or transpose."""
    from repro.serving.backends import pack_answer
    fr = _frozen("jsc-lg-2400")
    fwd = _fused(fr)
    compiled = _compile(lambda x: pack_answer(*fwd(x)), 4096, 16, one_chip)
    entry = next(l for l in compiled.as_text().splitlines()
                 if l.startswith("ENTRY"))
    assert entry.rstrip(" {").endswith("-> s32[24576]"), entry
    assert "tpu_custom_call" in compiled.as_text()


def test_held_experts_compile_for_v5e(one_chip):
    """granite-4.0-h-small's MoE share on one chip (9 of 72 experts, top
    10, d_model 4096, width 768) over an 8 x 1024 step with a token mask:
    one program holding the sized buffer (20,480 rows) and the worst case
    (73,728), chosen on the device."""
    from repro.models import layers as L
    D, F, E, n, k = 4096, 768, 72, 9, 10

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    p = {"router": arg((D, E)), "w_gate": arg((n, D, F)),
         "w_up": arg((n, D, F)), "w_down": arg((n, F, D))}
    assert L.held_rows(8 * 1024, k, n, E) == 20480
    compiled = jax.jit(lambda p, x, m: L.moe_apply(
        p, x, top_k=k, first_expert=0, token_mask=m)).lower(
        p, arg((8, 1024, D), jnp.bfloat16), arg((8, 1024), jnp.bool_)
    ).compile()
    text = compiled.as_text()
    assert "conditional(" in text
    assert "f32[20480,4096]" in text and "f32[73728,4096]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 30


def test_kda_chunk_compiles_for_v5e(one_chip):
    """The KDA kernel at Kimi Linear's widths (32 heads of 128) on a
    2 x 4096 step, four heads a grid step."""
    from repro.kernels.kda import kernel as K
    B, S, H, d = 2, 4096, 32, 128

    def arg(dtype):
        return jax.ShapeDtypeStruct((B, S, H * d), dtype, sharding=one_chip)
    compiled = jax.jit(lambda *x: K.kda_chunk(
        *x, heads=H, chunk=64, head_block=4)).lower(
        *[arg(jnp.bfloat16)] * 4, arg(jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch,length", [(16, 512), (8, 1024), (4, 2048),
                                          (2, 4096)])
def test_kimi_backbone_step_compiles_for_v5e(one_chip, monkeypatch, batch,
                                             length):
    """The served backbone step of the Kimi Linear cell (8 layers at the
    published widths, 32 of 256 experts held, bf16 weights, a token
    mask): KDA in the ``kda_chunk`` kernel, and weights plus temporaries
    inside one chip's 16 GB."""
    import dataclasses
    from repro.configs import get_arch
    from repro.models import api
    from repro.workloads.lm_head import FEATS, pool_features
    # the kernel's wrapper picks interpret mode off the TPU: compile what
    # the chip runs
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    arch = get_arch("kimi-linear-48b-a3b-instruct")
    cfg = dataclasses.replace(arch, num_layers=8,
                              layer_types=arch.layer_types[:8],
                              experts_held=32)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=one_chip),
        api.abstract_params(cfg, tp=1))

    def step(p, tokens, lengths):
        real = jnp.arange(tokens.shape[1]) < lengths[:, None]
        cols, fallbacks = api.logit_columns_and_fallbacks(
            p, cfg, tokens, FEATS, tp=1, token_mask=real)
        return pool_features(cols, lengths), fallbacks

    compiled = jax.jit(step).lower(
        params, jax.ShapeDtypeStruct((batch, length), jnp.int32,
                                     sharding=one_chip),
        jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
