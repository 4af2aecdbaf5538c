"""KDA (Kimi Delta Attention) and MLA mixers over sigmoid-routed MoE
feed-forwards: the Kimi Linear block (family ``kda_mla_moe``).

Layer ``i`` is ``cfg.layer_types[i]`` ("kda" or "mla")::

    h += mixer(RMSNorm(h))
    h += ffn(RMSNorm(h))

where ``ffn`` is a dense SwiGLU of width ``cfg.dense_ff`` in the first
``cfg.first_dense_layers`` layers and elsewhere the routed experts plus a
shared SwiGLU expert.  A final RMSNorm, an untied ``lm_head``, no
multipliers.

* KDA, per head (``cfg.kda_heads`` heads of ``cfg.head_dim``): q, k, v
  are ``SiLU(causal_conv(W x))`` (depthwise, width ``cfg.ssm_conv``, no
  bias); q and k L2-normalised per head, q scaled by ``head_dim^-1/2``;
  the log decay per key channel ``g = -exp(A_log[h]) softplus(W_f_up
  W_f_down x + dt_bias)``; ``beta = sigmoid(W_b x)``; the gated delta
  rule (``kernels.kda``) in the ``kda_chunk`` Pallas kernel; the output
  ``W_o (RMSNorm_head(o) * sigmoid(W_g_up W_g_down x + b_g))``.  Both
  gates have rank ``cfg.kda_gate_rank``.
* MLA without positional embedding: ``q = W_q x`` (nope + rope parts per
  head), ``[c, k_pe] = W_kva x``, ``c = RMSNorm(c)``, ``[k_nope, v] =
  W_kvb c``, ``k = [k_nope, k_pe]`` with ``k_pe`` shared by every head;
  causal softmax at scale ``(qk_nope + qk_rope)^-1/2``; ``W_o``.  The
  value head size differs from the query/key one.
* The experts (``layers.moe_apply``, ``router="sigmoid"``): the top-k of
  ``sigmoid(x W_r) + score_bias``, weighted by the chosen scores
  renormalised, times ``cfg.routed_scaling``.  The layer holds
  ``cfg.experts_held`` of the router's ``cfg.num_experts`` (0: all), the
  first ones, and gives their part with nothing dropped, on the
  held-experts path: the granite block's (``models.granite``), with its
  token mask and its count of calls that fell back to the worst-case
  buffer.

Every mixer is causal, so right padding never reaches a real position.
Named scopes: ``kimi.kda``, ``kimi.mla``, ``kimi.moe``,
``kimi.shared_expert``, ``kimi.dense_mlp``.  The serving path is
whole-prompt (:func:`logit_columns`); there is no decode cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..kernels.kda import ops as kda_ops
from ..kernels.kda.ref import kda_recurrent
from ..sharding.partition import logical
from . import layers as L
from .mamba2 import _causal_conv

Array = jax.Array


def _mla_layout(cfg: ArchConfig, tp: int) -> L.HeadLayout:
    return L.make_head_layout(cfg.num_heads, cfg.num_heads, tp)


def _is_dense(cfg: ArchConfig, i: int) -> bool:
    return i < cfg.first_dense_layers


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, L.PARAM_DTYPE) * fan_in ** -0.5


def _init_kda(key: Array, cfg: ArchConfig):
    D, H, d, r = cfg.d_model, cfg.kda_heads, cfg.head_dim_, cfg.kda_gate_rank
    ks = iter(jax.random.split(key, 16))
    dt = jnp.exp(jax.random.uniform(next(ks), (H * d,), L.PARAM_DTYPE,
                                    jnp.log(1e-3), jnp.log(0.1)))
    return {
        "wq": _normal(next(ks), (D, H * d), D),
        "wk": _normal(next(ks), (D, H * d), D),
        "wv": _normal(next(ks), (D, H * d), D),
        "conv_q": _normal(next(ks), (cfg.ssm_conv, H * d), cfg.ssm_conv),
        "conv_k": _normal(next(ks), (cfg.ssm_conv, H * d), cfg.ssm_conv),
        "conv_v": _normal(next(ks), (cfg.ssm_conv, H * d), cfg.ssm_conv),
        "w_f_down": _normal(next(ks), (D, r), D),
        "w_f_up": _normal(next(ks), (r, H * d), r),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),       # softplus^-1
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H, dtype=L.PARAM_DTYPE)),
        "w_b": _normal(next(ks), (D, H), D),
        "w_g_down": _normal(next(ks), (D, r), D),
        "w_g_up": _normal(next(ks), (r, H * d), r),
        "b_g": jnp.zeros((H * d,), L.PARAM_DTYPE),
        "o_norm": L.init_rms_norm(d),
        "wo": _normal(next(ks), (H * d, D), H * d),
    }


def _init_mla(key: Array, cfg: ArchConfig):
    D, H, R = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kq, ka, kb, ko = jax.random.split(key, 4)
    return {"wq": _normal(kq, (D, H, dn + dr), D),
            "w_kva": _normal(ka, (D, R + dr), D),
            "kv_norm": L.init_rms_norm(R),
            "w_kvb": _normal(kb, (R, H, dn + dv), R),
            "wo": _normal(ko, (H, dv, D), H * dv)}


def _init_layer(key: Array, cfg: ArchConfig, i: int):
    km, kf, ks = jax.random.split(key, 3)
    kind = cfg.layer_types[i]
    layer = {"ln1": L.init_rms_norm(cfg.d_model),
             "mixer": (_init_kda if kind == "kda" else _init_mla)(km, cfg),
             "ln2": L.init_rms_norm(cfg.d_model)}
    if _is_dense(cfg, i):
        layer["mlp"] = L.init_swiglu(kf, cfg.d_model, cfg.dense_ff)
        return layer
    moe = L.init_moe(kf, cfg.d_model, cfg.d_ff, cfg.num_experts)
    n = cfg.experts_held or cfg.num_experts
    moe = dict(moe, **{w: moe[w][:n] for w in ("w_gate", "w_up", "w_down")})
    moe["score_bias"] = jnp.zeros((cfg.num_experts,), L.PARAM_DTYPE)
    layer["moe"] = moe
    layer["shared"] = L.init_swiglu(ks, cfg.d_model, cfg.shared_ff)
    return layer


def init_params(key: Array, cfg: ArchConfig, tp: int = 16):
    ke, ku, kl = jax.random.split(key, 3)
    keys = jax.random.split(kl, cfg.num_layers)
    return {
        "embed": L.init_embedding(ke, cfg.vocab_padded(tp), cfg.d_model),
        "layers": [_init_layer(k, cfg, i) for i, k in enumerate(keys)],
        "final_norm": L.init_rms_norm(cfg.d_model),
        "lm_head": L.init_unembed(ku, cfg.d_model, cfg.vocab_padded(tp)),
    }


def _axes_kda():
    a = lambda *ax, n: logical(*ax, name="kda." + n)
    return {"wq": a("embed", "ff", n="wq"), "wk": a("embed", "ff", n="wk"),
            "wv": a("embed", "ff", n="wv"),
            "conv_q": a(None, "ff", n="conv_q"),
            "conv_k": a(None, "ff", n="conv_k"),
            "conv_v": a(None, "ff", n="conv_v"),
            "w_f_down": a("embed", None, n="w_f_down"),
            "w_f_up": a(None, "ff", n="w_f_up"), "dt_bias": a("ff", n="dt"),
            "A_log": a(None, n="A_log"), "w_b": a("embed", None, n="w_b"),
            "w_g_down": a("embed", None, n="w_g_down"),
            "w_g_up": a(None, "ff", n="w_g_up"), "b_g": a("ff", n="b_g"),
            "o_norm": L.axes_rms_norm(), "wo": a("ff", "embed", n="wo")}


def _axes_mla():
    a = lambda *ax, n: logical(*ax, name="mla." + n)
    return {"wq": a("embed", "q_heads", None, n="wq"),
            "w_kva": a("embed", None, n="w_kva"),
            "kv_norm": L.axes_rms_norm(),
            "w_kvb": a(None, "q_heads", None, n="w_kvb"),
            "wo": a("q_heads", None, "embed", n="wo")}


def param_axes(cfg: ArchConfig):
    def layer(i):
        a = {"ln1": L.axes_rms_norm(),
             "mixer": (_axes_kda() if cfg.layer_types[i] == "kda"
                       else _axes_mla()),
             "ln2": L.axes_rms_norm()}
        if _is_dense(cfg, i):
            a["mlp"] = L.axes_swiglu()
        else:
            a["moe"] = dict(L.axes_moe(ep=True),
                            score_bias=logical(None, name="moe.score_bias"))
            a["shared"] = L.axes_swiglu()
        return a
    return {"embed": L.axes_embedding(),
            "layers": [layer(i) for i in range(cfg.num_layers)],
            "final_norm": L.axes_rms_norm(), "lm_head": L.axes_unembed()}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _l2norm(x: Array, eps: float = 1e-6) -> Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


@jax.custom_vjp
def _kda(q, k, v, g, beta):
    return kda_ops.kda(q, k, v, g, beta)


def _kda_fwd(q, k, v, g, beta):
    return _kda(q, k, v, g, beta), (q, k, v, g, beta)


def _kda_bwd(res, do):
    # the kernel has no backward of its own: differentiate the plain
    # recurrence it computes (training only; serving never comes here)
    _, vjp = jax.vjp(kda_recurrent, *res)
    return vjp(do.astype(jnp.float32))


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda_mixer(p, cfg: ArchConfig, h: Array) -> Array:
    """The KDA mixer on normalised input ``h`` (B, S, D)."""
    cd, f32 = L.COMPUTE_DTYPE, jnp.float32
    B, S, _ = h.shape
    H, d = cfg.kda_heads, cfg.head_dim_
    hc = h.astype(cd)

    def proj(w, conv):
        x = jnp.einsum("bsd,dc->bsc", hc, p[w].astype(cd))
        x = _causal_conv(x, p[conv], None)
        return jax.nn.silu(x.astype(f32)).reshape(B, S, H, d)

    def low_rank(down, up):
        return jnp.einsum("bsr,rc->bsc",
                          jnp.einsum("bsd,dr->bsr", hc, p[down].astype(cd)),
                          p[up].astype(cd), preferred_element_type=f32)

    q = _l2norm(proj("wq", "conv_q")) * d ** -0.5
    k = _l2norm(proj("wk", "conv_k"))
    v = proj("wv", "conv_v")
    g = -jnp.exp(p["A_log"].astype(f32))[:, None] * jax.nn.softplus(
        (low_rank("w_f_down", "w_f_up")
         + p["dt_bias"].astype(f32)).reshape(B, S, H, d))
    beta = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", hc, p["w_b"].astype(cd),
                                     preferred_element_type=f32))
    o = _kda(q, k, v, g, beta)
    gate = jax.nn.sigmoid(low_rank("w_g_down", "w_g_up")
                          + p["b_g"].astype(f32))
    o = L.rms_norm(o.astype(f32), p["o_norm"]["scale"], cfg.norm_eps)
    o = o.reshape(B, S, -1) * gate
    return jnp.einsum("bsc,cd->bsd", o.astype(cd), p["wo"].astype(cd))


def mla_mixer(p, cfg: ArchConfig, h: Array, layout: L.HeadLayout) -> Array:
    """MLA without positional embedding on normalised input ``h``."""
    cd = L.COMPUTE_DTYPE
    B, S, _ = h.shape
    R, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    hc = h.astype(cd)
    q = jnp.einsum("bsd,dhk->bshk", hc, p["wq"].astype(cd))
    ckv = jnp.einsum("bsd,dc->bsc", hc, p["w_kva"].astype(cd))
    c = L.rms_norm(ckv[..., :R], p["kv_norm"]["scale"], cfg.norm_eps)
    k_pe = ckv[..., R:]
    kv = jnp.einsum("bsc,chk->bshk", c, p["w_kvb"].astype(cd))
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_pe[:, :, None], (B, S, cfg.num_heads, k_pe.shape[-1]))], -1)
    o = L.attention_chunked(q, k, kv[..., dn:], layout, causal=True,
                            kv_chunk=min(cfg.attn_chunk, S))
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(cd))


def _layer(lp, cfg: ArchConfig, i: int, x: Array, layout: L.HeadLayout,
           token_mask: Array | None):
    h = L.rms_norm(x, lp["ln1"]["scale"], cfg.norm_eps)
    if cfg.layer_types[i] == "kda":
        with jax.named_scope("kimi.kda"):
            out = kda_mixer(lp["mixer"], cfg, h)
    else:
        with jax.named_scope("kimi.mla"):
            out = mla_mixer(lp["mixer"], cfg, h, layout)
    x = x + out.astype(x.dtype)
    h = L.rms_norm(x, lp["ln2"]["scale"], cfg.norm_eps)
    zero = jnp.zeros((), jnp.int32)
    if _is_dense(cfg, i):
        with jax.named_scope("kimi.dense_mlp"):
            return x + L.swiglu(lp["mlp"], h), jnp.zeros((), jnp.float32), zero
    with jax.named_scope("kimi.moe"):
        y, aux, fallback = L.moe_apply(
            lp["moe"], h, top_k=cfg.top_k, first_expert=0,
            token_mask=token_mask, router=cfg.router,
            routed_scaling=cfg.routed_scaling)
    with jax.named_scope("kimi.shared_expert"):
        y = y + L.swiglu(lp["shared"], h)
    return x + y, aux, fallback


@functools.partial(jax.jit, static_argnames=("cfg", "tp"))
def hidden(params, cfg: ArchConfig, tokens: Array, *, tp: int = 16,
           token_mask: Array | None = None):
    """Final-normalised hidden states (B, S, D), the summed aux loss and
    the count of MoE layers whose held experts ran on the worst-case
    buffer (int32).  ``token_mask`` (B, S): the real tokens, None for
    all."""
    layout = _mla_layout(cfg, tp)
    x = L.embed(params["embed"], tokens)
    aux = jnp.zeros((), jnp.float32)
    fallbacks = jnp.zeros((), jnp.int32)
    for i, lp in enumerate(params["layers"]):
        x, a, f = _layer(lp, cfg, i, x, layout, token_mask)
        aux, fallbacks = aux + a, fallbacks + f
    h = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    return h, aux, fallbacks


def _logits(params, h: Array, cols: int | None = None):
    w = params["lm_head"]["w"]
    if cols is not None:
        w = w[:, :cols]
    return L.unembed({"w": w}, h)


def forward(params, cfg: ArchConfig, batch, *, tp: int = 16):
    """Full-sequence forward -> (logits (B, S, Vp), aux, None)."""
    h, aux, _ = hidden(params, cfg, batch["tokens"], tp=tp)
    return _logits(params, h), aux, None


def logit_columns(params, cfg: ArchConfig, tokens: Array, cols: int, *,
                  tp: int = 16, token_mask: Array | None = None):
    """``forward``'s logits[..., :cols] without the rest of the vocab, and
    :func:`hidden`'s count of worst-case buffers."""
    h, _, fallbacks = hidden(params, cfg, tokens, tp=tp,
                             token_mask=token_mask)
    return _logits(params, h, cols), fallbacks


def loss_fn(params, cfg: ArchConfig, batch, *, tp: int = 16) -> Array:
    logits, aux, _ = forward(params, cfg, batch, tp=tp)
    ce = L.cross_entropy_loss(logits[:, :-1], batch["labels"][:, 1:],
                              vocab_real=cfg.vocab_size)
    return ce + 0.01 * aux


def _no_cache(*args, **kwargs):
    raise NotImplementedError(
        "kda_mla_moe models serve whole prompts (logit_columns); they have "
        "no decode cache")


prefill = decode_step = init_cache = _no_cache


def cache_axes(cfg: ArchConfig, *, seq_shard: bool = False):
    return _no_cache()
