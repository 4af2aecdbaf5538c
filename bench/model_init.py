"""Weights of a cell's DWN, made by the benchmark from the run's seed.

The program is handed these; the plain reference reads the same arrays.
Thresholds come from the quantiles of a seeded fit sample (the paper's
distributive placement); everything else is drawn on the device in one
jitted call, in the type it is used in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.jsc_data import JetModel, distributive_thresholds


def key_of(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any non-negative seed (also ones over 32 bits)."""
    state = np.random.SeedSequence([seed, stream]).generate_state(1)
    return jax.random.PRNGKey(int(state[0]))


def fit_thresholds(cfg: dict, seed: int) -> np.ndarray:
    """(F, T) thresholds fitted on ``cfg['n_fit']`` seeded rows."""
    rng = np.random.default_rng([seed, 1])
    x = JetModel().features(rng, cfg["n_fit"])
    return distributive_thresholds(x, cfg["bits_per_feature"])


@functools.partial(jax.jit, static_argnames=("m", "n", "wires"))
def _frozen_tables(key, *, m: int, n: int, wires: int):
    k1, k2 = jax.random.split(key)
    mapping = jax.random.randint(k1, (m, n), 0, wires, jnp.int32)
    tables = jax.random.bernoulli(k2, 0.5, (m, 2 ** n)).astype(jnp.int32)
    return mapping, tables


def frozen_weights(cfg: dict, seed: int):
    """(thresholds (F, T) f32, mapping (m, n) i32, tables (m, 2^n) i32)
    of a frozen single-layer DWN, as numpy arrays."""
    th = fit_thresholds(cfg, seed)
    mapping, tables = _frozen_tables(
        key_of(seed), m=cfg["luts"], n=cfg["fan_in"],
        wires=cfg["features"] * cfg["bits_per_feature"])
    return th, np.asarray(mapping), np.asarray(tables)

