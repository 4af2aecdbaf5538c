"""Weights of the granite cell, made by the benchmark from the run's seed.

The program is handed them in its own layout (``repro.models.granite``:
one dict per layer); the plain reference reads the same arrays, one layer
at a time, by calling :func:`layer` again.  Every tensor is drawn on the
device in bfloat16 from a key of (seed, layer), so a layer made alone
equals the same layer made with the rest.  ``cfg`` is the configuration
file's dict (the published config.json keys, as cut).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.model_init import _frozen_tables, key_of

DTYPE = jnp.bfloat16
#: key streams: layers use (seed, 100 + i)
EMBED_STREAM, HEAD_STREAM, LAYER_STREAM = 10, 11, 100


def dims(cfg: dict) -> dict:
    """The sizes the weights are made of, from the config's keys."""
    D, G, N = cfg["hidden_size"], cfg["mamba_n_groups"], cfg["mamba_d_state"]
    di = cfg["mamba_expand"] * D
    return {"D": D, "di": di, "H": cfg["mamba_n_heads"],
            "P": cfg["mamba_d_head"], "N": N, "G": G,
            "K": cfg["mamba_d_conv"], "conv": di + 2 * G * N,
            "heads": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"],
            "hd": D // cfg["num_attention_heads"],
            "E": cfg["published"]["num_local_experts"],
            "n": cfg["num_local_experts"], "F": cfg["intermediate_size"],
            "Fs": cfg["shared_intermediate_size"], "V": cfg["vocab_size"]}


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, DTYPE) * DTYPE(fan_in ** -0.5)


@functools.partial(jax.jit, static_argnames=("kind", "d"))
def _layer(key, *, kind: str, d: tuple):
    d = dict(d)
    D, H = d["D"], d["H"]
    ks = iter(jax.random.split(key, 16))
    ones = lambda n: jnp.ones((n,), DTYPE)
    if kind == "mamba":
        dt = jnp.exp(jax.random.uniform(next(ks), (H,), jnp.float32,
                                        np.log(1e-3), np.log(0.1)))
        mixer = {
            "w_z": _normal(next(ks), (D, d["di"]), D),
            "w_x": _normal(next(ks), (D, d["di"]), D),
            "w_bc": _normal(next(ks), (D, 2 * d["G"] * d["N"]), D),
            "w_dt": _normal(next(ks), (D, H), D),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(DTYPE),
            "A_log": jnp.log(jax.random.uniform(
                next(ks), (H,), jnp.float32, 1.0, 16.0)).astype(DTYPE),
            "D_skip": ones(H),
            "conv_w": _normal(next(ks), (d["K"], d["conv"]), d["K"]),
            "conv_b": jax.random.uniform(next(ks), (d["conv"],), DTYPE,
                                         -0.5, 0.5),
            "out_norm": {"scale": ones(d["di"])},
            "w_out": _normal(next(ks), (d["di"], D), d["di"]),
        }
    else:
        hq, hkv, hd = d["heads"], d["kv"], d["hd"]
        mixer = {"wq": _normal(next(ks), (D, hq, hd), D),
                 "wk": _normal(next(ks), (D, hkv, hd), D),
                 "wv": _normal(next(ks), (D, hkv, hd), D),
                 "wo": _normal(next(ks), (hq, hd, D), hq * hd)}
    n, F, Fs = d["n"], d["F"], d["Fs"]
    return {
        "ln1": {"scale": ones(D)}, "mixer": mixer, "ln2": {"scale": ones(D)},
        "moe": {"router": _normal(next(ks), (D, d["E"]), D),
                "w_gate": _normal(next(ks), (n, D, F), D),
                "w_up": _normal(next(ks), (n, D, F), D),
                "w_down": _normal(next(ks), (n, F, D), F)},
        "shared": {"w_gate": _normal(next(ks), (D, Fs), D),
                   "w_up": _normal(next(ks), (D, Fs), D),
                   "w_down": _normal(next(ks), (Fs, D), Fs)},
    }


def layer(cfg: dict, seed: int, i: int) -> dict:
    """Layer ``i``'s weights (bf16, on the device) in the program's
    layout: ``ln1``, ``mixer`` (Mamba-2 or attention), ``ln2``, ``moe``
    (the router over every published expert, and the held experts'
    SwiGLU weights) and ``shared``."""
    return _layer(key_of(seed, LAYER_STREAM + i), kind=cfg["layer_types"][i],
                  d=tuple(sorted(dims(cfg).items())))


@functools.partial(jax.jit, static_argnames=("shape",))
def _embedding(key, *, shape):
    return jax.random.normal(key, shape, DTYPE) * DTYPE(0.1)


def embedding(cfg: dict, seed: int) -> jax.Array:
    """The tied (vocab, hidden) embedding table, bf16."""
    return _embedding(key_of(seed, EMBED_STREAM),
                      shape=(cfg["vocab_size"], cfg["hidden_size"]))


def final_norm(cfg: dict) -> jax.Array:
    return jnp.ones((cfg["hidden_size"],), DTYPE)


def model(cfg: dict, seed: int) -> dict:
    """The whole backbone's weights, as the program takes them."""
    return {"embed": {"table": embedding(cfg, seed)},
            "layers": [layer(cfg, seed, i)
                       for i in range(cfg["num_hidden_layers"])],
            "final_norm": {"scale": final_norm(cfg)}}


def head(cfg: dict, seed: int):
    """(thresholds (F, T) f32, mapping (m, n) i32, tables (m, 2^n) i32)
    of the DWN head: uniform thresholds over (-1, 1), seeded wires and
    truth tables."""
    h = cfg["head"]
    F, T = h["features"], h["bits_per_feature"]
    edges = np.linspace(-1.0, 1.0, T + 2, dtype=np.float32)[1:-1]
    mapping, tables = _frozen_tables(key_of(seed, HEAD_STREAM), m=h["luts"],
                                     n=h["fan_in"], wires=F * T)
    return (np.tile(edges[None, :], (F, 1)), np.asarray(mapping),
            np.asarray(tables))
