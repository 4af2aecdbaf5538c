"""Mamba-2 and attention mixers, each followed by a MoE with a shared
expert: the Granite 4.0-H block (family ``ssm_moe``).

Layer ``i`` is ``cfg.layer_types[i]`` ("mamba" or "attention")::

    h += r * mixer(RMSNorm(h))
    x  = RMSNorm(h)
    h += r * (MoE(x) + shared(x))

with ``r = cfg.residual_multiplier``; the embedding is scaled by
``cfg.embedding_multiplier`` and the (tied) logits are divided by
``cfg.logits_scaling``.  The Mamba-2 mixer is ``mamba2.mixer``; attention
is GQA with no positional embedding and softmax scale
``cfg.attention_multiplier``; the MoE holds ``cfg.experts_held`` of the
router's ``cfg.num_experts`` experts (0: all), the first ones, and gives
their part of the layer with nothing dropped (``layers.moe_apply`` with
``first_expert``): one device's share under expert parallelism, run
without the exchange.  The held experts run on a sorted buffer sized to
twice the pairs a layer routes to them on average
(``layers.held_rows``), and on the worst-case buffer, exactly as before,
in a layer call whose pairs overflow it; :func:`hidden` counts those
calls.  With a ``token_mask`` only real tokens route: padding positions
route no pair and their MoE output is 0.  Every mixer is causal, so
right padding never reaches a real position and the mask changes no
answer there.

Layers are kinds apart, so parameters are a list with one dict per layer
and the forward is unrolled.  Named scopes: ``granite.mamba``,
``granite.attention``, ``granite.moe``, ``granite.shared_expert``.

The serving path is whole-prompt: :func:`logit_columns` gives the first
columns of the logits at every position, the input of the DWN head's
pooling, and the count of MoE layer calls that fell back to the
worst-case buffer.  There is no decode cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import layers as L
from . import mamba2

Array = jax.Array


def _layout(cfg: ArchConfig, tp: int) -> L.HeadLayout:
    return L.make_head_layout(cfg.num_heads, cfg.num_kv_heads, tp)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key: Array, cfg: ArchConfig, kind: str,
                layout: L.HeadLayout):
    km, ke, ks = jax.random.split(key, 3)
    if kind == "mamba":
        mixer = mamba2._init_block(km, cfg)
        del mixer["ln"]
    else:
        mixer = L.init_attention(km, cfg.d_model, layout, cfg.head_dim_)
    moe = L.init_moe(ke, cfg.d_model, cfg.d_ff, cfg.num_experts)
    n = cfg.experts_held or cfg.num_experts
    moe = dict(moe, **{w: moe[w][:n] for w in ("w_gate", "w_up", "w_down")})
    return {"ln1": L.init_rms_norm(cfg.d_model), "mixer": mixer,
            "ln2": L.init_rms_norm(cfg.d_model), "moe": moe,
            "shared": L.init_swiglu(ks, cfg.d_model, cfg.shared_ff)}


def init_params(key: Array, cfg: ArchConfig, tp: int = 16):
    layout = _layout(cfg, tp)
    ke, kl = jax.random.split(key)
    keys = jax.random.split(kl, cfg.num_layers)
    return {
        "embed": L.init_embedding(ke, cfg.vocab_padded(tp), cfg.d_model),
        "layers": [_init_layer(k, cfg, kind, layout)
                   for k, kind in zip(keys, cfg.layer_types)],
        "final_norm": L.init_rms_norm(cfg.d_model),
    }


def param_axes(cfg: ArchConfig):
    def layer(kind):
        mixer = (mamba2._block_axes(cfg) if kind == "mamba"
                 else L.axes_attention())
        mixer.pop("ln", None)
        return {"ln1": L.axes_rms_norm(), "mixer": mixer,
                "ln2": L.axes_rms_norm(), "moe": L.axes_moe(ep=True),
                "shared": L.axes_swiglu()}
    return {"embed": L.axes_embedding(),
            "layers": [layer(kind) for kind in cfg.layer_types],
            "final_norm": L.axes_rms_norm()}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _attention(p, cfg: ArchConfig, h: Array, layout: L.HeadLayout) -> Array:
    q, k, v = L.qkv_project(p, h, layout, positions=None, rope_theta=None)
    o = L.attention_chunked(q, k, v, layout, causal=True,
                            kv_chunk=min(cfg.attn_chunk, h.shape[1]),
                            scale=cfg.attention_multiplier or None)
    return L.attn_output(p, o)


def _layer(lp, cfg: ArchConfig, kind: str, x: Array,
           layout: L.HeadLayout, token_mask: Array | None):
    r = cfg.residual_multiplier
    h = L.rms_norm(x, lp["ln1"]["scale"], cfg.norm_eps)
    if kind == "mamba":
        with jax.named_scope("granite.mamba"):
            out, _ = mamba2.mixer(lp["mixer"], cfg, h)
    else:
        with jax.named_scope("granite.attention"):
            out = _attention(lp["mixer"], cfg, h, layout)
    x = x + r * out
    h = L.rms_norm(x, lp["ln2"]["scale"], cfg.norm_eps)
    with jax.named_scope("granite.moe"):
        y, aux, fallback = L.moe_apply(lp["moe"], h, top_k=cfg.top_k,
                                       first_expert=0, token_mask=token_mask)
    with jax.named_scope("granite.shared_expert"):
        y = y + L.swiglu(lp["shared"], h)
    return x + r * y, aux, fallback


@functools.partial(jax.jit, static_argnames=("cfg", "tp"))
def hidden(params, cfg: ArchConfig, tokens: Array, *, tp: int = 16,
           token_mask: Array | None = None):
    """Final-normalised hidden states (B, S, D), the summed aux loss and
    the count of layers whose held experts ran on the worst-case buffer
    (int32).  ``token_mask`` (B, S): the real tokens, None for all.

    Jitted by itself so that an eager caller runs one compiled program
    rather than every op of the unrolled layers; inside a jitted step it
    is inlined."""
    layout = _layout(cfg, tp)
    x = L.embed(params["embed"], tokens) * cfg.embedding_multiplier
    aux = jnp.zeros((), jnp.float32)
    fallbacks = jnp.zeros((), jnp.int32)
    for lp, kind in zip(params["layers"], cfg.layer_types):
        x, a, f = _layer(lp, cfg, kind, x, layout, token_mask)
        aux, fallbacks = aux + a, fallbacks + f
    h = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    return h, aux, fallbacks


def _logits(params, cfg: ArchConfig, h: Array, cols: int | None = None):
    table = params["embed"]["table"]
    if cols is not None:
        table = table[:cols]
    logits = jnp.einsum("bsd,vd->bsv", h.astype(L.COMPUTE_DTYPE),
                        table.astype(L.COMPUTE_DTYPE))
    return logits / cfg.logits_scaling


def forward(params, cfg: ArchConfig, batch, *, tp: int = 16):
    """Full-sequence forward -> (logits (B, S, Vp), aux, None)."""
    h, aux, _ = hidden(params, cfg, batch["tokens"], tp=tp)
    return _logits(params, cfg, h), aux, None


def logit_columns(params, cfg: ArchConfig, tokens: Array, cols: int, *,
                  tp: int = 16, token_mask: Array | None = None):
    """``forward``'s logits[..., :cols] without the rest of the vocab, and
    :func:`hidden`'s count of worst-case buffers."""
    h, _, fallbacks = hidden(params, cfg, tokens, tp=tp,
                             token_mask=token_mask)
    return _logits(params, cfg, h, cols), fallbacks


def loss_fn(params, cfg: ArchConfig, batch, *, tp: int = 16) -> Array:
    logits, aux, _ = forward(params, cfg, batch, tp=tp)
    ce = L.cross_entropy_loss(logits[:, :-1], batch["labels"][:, 1:],
                              vocab_real=cfg.vocab_size)
    return ce + 0.01 * aux


def _no_cache(*args, **kwargs):
    raise NotImplementedError(
        "ssm_moe models serve whole prompts (logit_columns); they have no "
        "decode cache")


prefill = decode_step = init_cache = _no_cache


def cache_axes(cfg: ArchConfig, *, seq_shard: bool = False):
    return _no_cache()
