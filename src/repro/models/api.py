"""Model-zoo API: family registry + step factories.

Every architecture family module exposes the same interface
(init_params/param_axes/loss_fn/prefill/decode_step/init_cache/cache_axes);
this module dispatches on ``cfg.family`` and builds the jit-able steps the
launchers lower:

    make_train_step(cfg, tp, num_micro)  -> step(params, opt, batch)
    make_prefill(cfg, tp)                -> fn(params, batch)
    make_decode_step(cfg, tp)            -> fn(params, cache, tokens)
    input_specs(cfg, shape, tp)          -> ShapeDtypeStruct batch stand-ins
    abstract_params(cfg, tp)             -> eval_shape'd params
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, ShapeConfig
from ..optim.adam import Adam
from ..optim.grad import clip_by_global_norm
from . import transformer, mamba2, rglru, whisper, dwn_arch, granite
from . import layers as L

MODULES = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,
    "ssm": mamba2,
    "hybrid": rglru,
    "encdec": whisper,
    "dwn": dwn_arch,
    "ssm_moe": granite,
}


def module_for(cfg: ArchConfig):
    return MODULES[cfg.family]


# ---------------------------------------------------------------------------
# abstract params / input specs (dry-run stand-ins; no allocation)
# ---------------------------------------------------------------------------

def abstract_params(cfg: ArchConfig, tp: int = 16):
    mod = module_for(cfg)
    return jax.eval_shape(
        lambda k: mod.init_params(k, cfg, tp), jax.random.PRNGKey(0))


def param_axes(cfg: ArchConfig):
    return module_for(cfg).param_axes(cfg)


def batch_specs(cfg: ArchConfig, shape: ShapeConfig, *,
                micro: bool = False) -> dict:
    """ShapeDtypeStructs for one step's data batch.

    For train shapes with gradient accumulation, ``micro=True`` prepends
    the (num_micro, batch/num_micro, ...) microbatch axes.
    """
    B, S = shape.global_batch, shape.seq_len
    lead: tuple = (B,)
    if micro and shape.num_microbatches > 1:
        m = shape.num_microbatches
        assert B % m == 0, (B, m)
        lead = (m, B // m)
    i32 = jnp.int32
    bf16 = L.COMPUTE_DTYPE
    if cfg.family == "dwn":
        # samples = global_batch x seq_len (feature vectors, not tokens)
        n = shape.global_batch * shape.seq_len
        if micro and shape.num_microbatches > 1:
            m = shape.num_microbatches
            batch = {"features": jax.ShapeDtypeStruct(
                (m, n // m, cfg.d_model), jnp.float32)}
            if shape.kind == "train":
                batch["labels"] = jax.ShapeDtypeStruct((m, n // m), i32)
            return batch
        batch = {"features": jax.ShapeDtypeStruct((n, cfg.d_model),
                                                  jnp.float32)}
        if shape.kind == "train":
            batch["labels"] = jax.ShapeDtypeStruct((n,), i32)
        return batch
    if shape.kind == "decode":
        batch = {"tokens": jax.ShapeDtypeStruct(lead + (1,), i32)}
        return batch
    batch = {"tokens": jax.ShapeDtypeStruct(lead + (S,), i32)}
    if shape.kind == "train":
        batch["labels"] = jax.ShapeDtypeStruct(lead + (S,), i32)
    if cfg.family == "encdec":
        batch["frames"] = jax.ShapeDtypeStruct(
            lead + (cfg.enc_frames, cfg.d_model), bf16)
    if cfg.family == "vlm":
        batch["patches"] = jax.ShapeDtypeStruct(
            lead + (cfg.num_patches, cfg.d_model), bf16)
    return batch


def batch_axes(cfg: ArchConfig, shape: ShapeConfig, *, micro: bool = False):
    """Logical axes for the batch pytree (see partition.DEFAULT_RULES)."""
    from ..sharding.partition import logical
    lead = ("micro", "batch") if (micro and shape.num_microbatches > 1) \
        else ("batch",)
    if cfg.family == "dwn":
        ax = {"features": logical(*lead, None, name="batch.features")}
        if shape.kind == "train":
            ax["labels"] = logical(*lead, name="batch.labels")
        return ax
    seq = "seq_sp" if shape.global_batch == 1 else None   # SP for B=1
    ax = {"tokens": logical(*lead, None if shape.kind == "decode" else seq,
                            name="batch.tokens")}
    if shape.kind == "train":
        ax["labels"] = logical(*lead, seq, name="batch.labels")
    if cfg.family == "encdec" and shape.kind != "decode":
        ax["frames"] = logical(*lead, None, None, name="batch.frames")
    if cfg.family == "vlm" and shape.kind != "decode":
        ax["patches"] = logical(*lead, None, None, name="batch.patches")
    return ax


def abstract_cache(cfg: ArchConfig, shape: ShapeConfig, tp: int = 16):
    mod = module_for(cfg)
    return jax.eval_shape(
        functools.partial(mod.init_cache, cfg, shape.global_batch,
                          shape.seq_len, tp))


def cache_axes(cfg: ArchConfig, shape: ShapeConfig):
    seq_shard = shape.global_batch == 1          # SP for long-context B=1
    return module_for(cfg).cache_axes(cfg, seq_shard=seq_shard)


# ---------------------------------------------------------------------------
# steps
# ---------------------------------------------------------------------------

def make_optimizer(lr: float = 3e-4) -> Adam:
    return Adam(lr=lr, b1=0.9, b2=0.95, weight_decay=0.1)


def make_train_step(cfg: ArchConfig, tp: int = 16, *, num_micro: int = 1,
                    opt: Adam | None = None, clip_norm: float = 1.0):
    """Returns step(params, opt_state, batch) -> (params, opt_state, metrics).

    With num_micro > 1, batch leaves carry a leading (num_micro, micro_b)
    pair and gradients are accumulated with a lax.scan — the FSDP/TP
    collectives for the weights still happen once per microbatch (gather)
    but the gradient all-reduce happens once per step.
    """
    mod = module_for(cfg)
    opt = opt or make_optimizer()

    def loss_of(params, data):
        return mod.loss_fn(params, cfg, data, tp=tp)

    def step(params, opt_state, batch):
        if num_micro == 1:
            loss, grads = jax.value_and_grad(loss_of)(params, batch)
        else:
            def micro_body(carry, data):
                acc_loss, acc_g = carry
                l, g = jax.value_and_grad(loss_of)(params, data)
                return (acc_loss + l, jax.tree.map(jnp.add, acc_g, g)), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss_sum, gsum), _ = jax.lax.scan(
                micro_body, (jnp.zeros(()), zeros), batch)
            inv = 1.0 / num_micro
            loss = loss_sum * inv
            grads = jax.tree.map(lambda g: g * inv, gsum)
        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    return step, opt


def make_prefill(cfg: ArchConfig, tp: int = 16, *, cache_len: int | None = None):
    mod = module_for(cfg)

    def fn(params, batch):
        return mod.prefill(params, cfg, batch, tp=tp, cache_len=cache_len)

    return fn


def logit_columns(params, cfg: ArchConfig, tokens, cols: int, *,
                  tp: int = 16):
    """The first ``cols`` columns of the full-sequence logits (B, S, cols):
    what a classification head pools (:func:`logit_columns_and_fallbacks`
    on unpadded prompts, without its count)."""
    return logit_columns_and_fallbacks(params, cfg, tokens, cols, tp=tp)[0]


def logit_columns_and_fallbacks(params, cfg: ArchConfig, tokens, cols: int,
                                *, tp: int = 16, token_mask=None):
    """:func:`logit_columns` and an int32 count of the MoE layer calls
    whose held experts fell back to the worst-case buffer.

    A family module may compute the columns without the rest of the
    vocabulary (its ``logit_columns``, which returns both); it then also
    takes ``token_mask`` (B, S), the real tokens of right-padded prompts
    (None: all), and routes only those through its experts.  Other
    families ignore the mask and count 0."""
    mod = module_for(cfg)
    if hasattr(mod, "logit_columns"):
        return mod.logit_columns(params, cfg, tokens, cols, tp=tp,
                                 token_mask=token_mask)
    logits = mod.forward(params, cfg, {"tokens": tokens}, tp=tp)[0]
    return logits[..., :cols], jnp.zeros((), jnp.int32)


def held_moe_layers(cfg: ArchConfig) -> int:
    """MoE layer calls of one forward on the held-experts path, those
    :func:`logit_columns_and_fallbacks` counts: every layer of the
    ``ssm_moe`` family, none elsewhere."""
    return cfg.num_layers if cfg.family == "ssm_moe" else 0


def make_decode_step(cfg: ArchConfig, tp: int = 16):
    mod = module_for(cfg)

    def fn(params, cache, batch):
        return mod.decode_step(params, cfg, cache, batch["tokens"], tp=tp)

    return fn
