"""Weights of the Kimi Linear cell, made by the benchmark from the run's
seed.

The program is handed them in its own layout (``repro.models.kimi_linear``:
one dict per layer); the plain reference reads the same arrays, one layer
at a time, by calling :func:`layer` again.  Every tensor is drawn on the
device in bfloat16 from a key of (seed, layer), so a layer made alone
equals the same layer made with the rest.  ``cfg`` is the configuration
file's dict (the published config.json keys, as cut); its ``assumed``
list says how each distribution was chosen.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.model_init import key_of

DTYPE = jnp.bfloat16
#: key streams: layers use (seed, 100 + i)
EMBED_STREAM, LM_HEAD_STREAM, LAYER_STREAM = 10, 12, 100


def dims(cfg: dict) -> dict:
    """The sizes the weights are made of, from the config's keys."""
    la = cfg["linear_attn_config"]
    return {"D": cfg["hidden_size"], "H": la["num_heads"],
            "d": la["head_dim"], "K": la["short_conv_kernel_size"],
            "r": cfg["kda_gate_rank"], "heads": cfg["num_attention_heads"],
            "R": cfg["kv_lora_rank"], "dn": cfg["qk_nope_head_dim"],
            "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
            "E": cfg["published"]["num_experts"], "n": cfg["num_experts"],
            "F": cfg["moe_intermediate_size"],
            "Fs": cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
            "Fd": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "k": cfg["num_experts_per_token"]}


def layer_kinds(cfg: dict) -> list[str]:
    """"kda" or "mla" for each layer held, from the 1-indexed lists."""
    kda = set(cfg["linear_attn_config"]["kda_layers"])
    return ["kda" if i + 1 in kda else "mla"
            for i in range(cfg["num_hidden_layers"])]


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, DTYPE) * DTYPE(fan_in ** -0.5)


@functools.partial(jax.jit, static_argnames=("kind", "dense", "d"))
def _layer(key, *, kind: str, dense: bool, d: tuple):
    d = dict(d)
    D, H, hd, r = d["D"], d["H"], d["d"], d["r"]
    ks = iter(jax.random.split(key, 24))
    ones = lambda n: jnp.ones((n,), DTYPE)
    if kind == "kda":
        dt = jnp.exp(jax.random.uniform(next(ks), (H * hd,), jnp.float32,
                                        np.log(1e-3), np.log(0.1)))
        mixer = {
            "wq": _normal(next(ks), (D, H * hd), D),
            "wk": _normal(next(ks), (D, H * hd), D),
            "wv": _normal(next(ks), (D, H * hd), D),
            "conv_q": _normal(next(ks), (d["K"], H * hd), d["K"]),
            "conv_k": _normal(next(ks), (d["K"], H * hd), d["K"]),
            "conv_v": _normal(next(ks), (d["K"], H * hd), d["K"]),
            "w_f_down": _normal(next(ks), (D, r), D),
            "w_f_up": _normal(next(ks), (r, H * hd), r),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(DTYPE),
            "A_log": jnp.log(jax.random.uniform(
                next(ks), (H,), jnp.float32, 1.0, 16.0)).astype(DTYPE),
            "w_b": _normal(next(ks), (D, H), D),
            "w_g_down": _normal(next(ks), (D, r), D),
            "w_g_up": _normal(next(ks), (r, H * hd), r),
            "b_g": jax.random.uniform(next(ks), (H * hd,), DTYPE, -0.5, 0.5),
            "o_norm": {"scale": ones(hd)},
            "wo": _normal(next(ks), (H * hd, D), H * hd),
        }
    else:
        hq, R, dn, dr, dv = d["heads"], d["R"], d["dn"], d["dr"], d["dv"]
        mixer = {"wq": _normal(next(ks), (D, hq, dn + dr), D),
                 "w_kva": _normal(next(ks), (D, R + dr), D),
                 "kv_norm": {"scale": ones(R)},
                 "w_kvb": _normal(next(ks), (R, hq, dn + dv), R),
                 "wo": _normal(next(ks), (hq, dv, D), hq * dv)}
    out = {"ln1": {"scale": ones(D)}, "mixer": mixer,
           "ln2": {"scale": ones(D)}}
    if dense:
        Fd = d["Fd"]
        out["mlp"] = {"w_gate": _normal(next(ks), (D, Fd), D),
                      "w_up": _normal(next(ks), (D, Fd), D),
                      "w_down": _normal(next(ks), (Fd, D), Fd)}
        return out
    n, F, Fs = d["n"], d["F"], d["Fs"]
    out["moe"] = {"router": _normal(next(ks), (D, d["E"]), D),
                  "score_bias": jax.random.uniform(next(ks), (d["E"],),
                                                   DTYPE, -0.05, 0.05),
                  "w_gate": _normal(next(ks), (n, D, F), D),
                  "w_up": _normal(next(ks), (n, D, F), D),
                  "w_down": _normal(next(ks), (n, F, D), F)}
    out["shared"] = {"w_gate": _normal(next(ks), (D, Fs), D),
                     "w_up": _normal(next(ks), (D, Fs), D),
                     "w_down": _normal(next(ks), (Fs, D), Fs)}
    return out


def layer(cfg: dict, seed: int, i: int) -> dict:
    """Layer ``i``'s weights (bf16, on the device) in the program's
    layout: ``ln1``, ``mixer`` (KDA or MLA), ``ln2``, and ``mlp`` (the
    leading dense layers) or ``moe`` (the router and its correction bias
    over every published expert, the held experts' SwiGLU weights) and
    ``shared``."""
    return _layer(key_of(seed, LAYER_STREAM + i), kind=layer_kinds(cfg)[i],
                  dense=i < cfg["first_k_dense_replace"],
                  d=tuple(sorted(dims(cfg).items())))


@functools.partial(jax.jit, static_argnames=("shape", "scale"))
def _table(key, *, shape, scale):
    return jax.random.normal(key, shape, DTYPE) * DTYPE(scale)


def embedding(cfg: dict, seed: int) -> jax.Array:
    """The (vocab, hidden) input embedding, bf16."""
    return _table(key_of(seed, EMBED_STREAM),
                  shape=(cfg["vocab_size"], cfg["hidden_size"]), scale=0.1)


def lm_head(cfg: dict, seed: int) -> jax.Array:
    """The untied (hidden, vocab) output projection, bf16."""
    D = cfg["hidden_size"]
    return _table(key_of(seed, LM_HEAD_STREAM), shape=(D, cfg["vocab_size"]),
                  scale=D ** -0.5)


def final_norm(cfg: dict) -> jax.Array:
    return jnp.ones((cfg["hidden_size"],), DTYPE)


def model(cfg: dict, seed: int) -> dict:
    """The whole backbone's weights, as the program takes them."""
    return {"embed": {"table": embedding(cfg, seed)},
            "layers": [layer(cfg, seed, i)
                       for i in range(cfg["num_hidden_layers"])],
            "final_norm": {"scale": final_norm(cfg)},
            "lm_head": {"w": lm_head(cfg, seed)}}
