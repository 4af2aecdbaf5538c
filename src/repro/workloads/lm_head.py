"""LM-backbone feature workload: the DWN-head-on-qwen3 task, first-class.

Promotes ``examples/dwn_head_lm.py`` from demo to registry entry.  A
reduced qwen3 backbone (fixed init key, frozen) pools its final logits
into 16 features per sequence; labels come from a fixed teacher
projection of those features, so the task isolates what a DWN head can
learn on top of frozen backbone features.  The loader materializes a
seeded (tokens -> features -> labels) split that the ordinary scan
engine trains on — from the trainer's point of view this is just
another 16-feature 5-class dataset, which is exactly why the registry
abstraction pays off.

:func:`pool_features` is the canonical feature stage: the serving
engine's ``dwn_head`` path applies the *same* pooling to its own
backbone's prefill logits, so a head trained here serves bit-exact on
matching features.

The backbone forward is the expensive part, so the workload caps split
sizes (``cap_train``/``cap_test``) and caches loads in-process.
"""

from __future__ import annotations

import numpy as np

from ..core.model import DWNConfig
from ..data.jsc import JSCData
from .base import Workload, register_workload

FEATS = 16
NUM_CLASSES = 5
SEQ_LEN = 32
BACKBONE = "qwen3-8b"

LM_HEAD_PRESETS = {
    "lm-head-50": DWNConfig(num_features=FEATS, bits_per_feature=64,
                            encoding="uniform", lut_counts=(50,),
                            num_classes=NUM_CLASSES),
}


def pool_features(logits, lengths=None):
    """Pool full-sequence backbone logits into FEATS head features.

    ``tanh(0.3 * mean-over-sequence logits[:, :FEATS])``, the mean taken
    over each sequence's first ``lengths[b]`` (real) positions; None: all
    of them.  ``logits`` (B, S, V >= FEATS) may hold only the first FEATS
    columns.  Shared by the loader and the serving engine's ``dwn_head``
    path so training and serving see the same features; right padding
    does not change a sequence's features.  The mean is summed in float32
    and rounded to the logits' type, as ``jnp.mean`` rounds it.
    """
    import jax.numpy as jnp
    B, S = logits.shape[:2]
    cols = logits[..., :FEATS]
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    real = jnp.arange(S)[None, :] < lengths[:, None]
    total = jnp.where(real[..., None], cols.astype(jnp.float32), 0.0).sum(1)
    mean = total / jnp.maximum(lengths, 1)[:, None].astype(jnp.float32)
    pooled = mean.astype(logits.dtype).astype(jnp.float32)
    return jnp.tanh(pooled * 0.3)


_BACKBONE_CACHE: dict | None = None


def _backbone():
    """The frozen reduced backbone + jitted feature fn (built once)."""
    global _BACKBONE_CACHE
    if _BACKBONE_CACHE is None:
        import jax
        from ..configs import get_arch
        from ..models import api
        cfg = get_arch(BACKBONE).reduced()
        mod = api.module_for(cfg)
        params = mod.init_params(jax.random.PRNGKey(0), cfg, tp=1)

        @jax.jit
        def features(toks):
            return pool_features(
                api.logit_columns(params, cfg, toks, FEATS, tp=1))

        # fixed teacher projection: labels = argmax(features @ Wt)
        Wt = jax.random.normal(jax.random.PRNGKey(7),
                               (FEATS, NUM_CLASSES)) * 2.0
        _BACKBONE_CACHE = {"cfg": cfg, "features": features, "Wt": Wt}
    return _BACKBONE_CACHE


def teacher_labels(feats) -> np.ndarray:
    import jax.numpy as jnp
    Wt = _backbone()["Wt"]
    return np.asarray(jnp.argmax(feats @ Wt, axis=-1), np.int32)


_SPLIT_CACHE: dict[tuple, JSCData] = {}


def _materialize(n: int, seed: int, chunk: int = 64):
    """Seeded tokens -> pooled features -> teacher labels for n sequences."""
    import jax.numpy as jnp
    bb = _backbone()
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, bb["cfg"].vocab_size, (n, SEQ_LEN)).astype(np.int32)
    xs, ys = [], []
    for i in range(0, n, chunk):
        feats = bb["features"](jnp.asarray(toks[i:i + chunk]))
        xs.append(np.asarray(feats, np.float32))
        ys.append(teacher_labels(feats))
    x = np.concatenate(xs)
    # features are already tanh-squashed to (-1, 1) — the encoder's input
    # contract — so no train-stat renormalization (it would shift the
    # serve-time features away from the train-time ones)
    x = np.clip(x, -1.0, np.nextafter(np.float32(1.0), np.float32(0.0)))
    return x, np.concatenate(ys)


def load_lm_head(n_train: int = 1024, n_test: int = 512,
                 seed: int = 0) -> JSCData:
    key = (n_train, n_test, seed)
    if key not in _SPLIT_CACHE:
        # disjoint seeded token streams per split
        x_tr, y_tr = _materialize(n_train, seed * 2 + 1)
        x_te, y_te = _materialize(n_test, seed * 2 + 2)
        _SPLIT_CACHE[key] = JSCData(x_tr, y_tr, x_te, y_te)
    return _SPLIT_CACHE[key]


LM_HEAD = register_workload(Workload(
    name="lm-head",
    num_features=FEATS,
    num_classes=NUM_CLASSES,
    loader=lambda n_train, n_test, seed=0: load_lm_head(n_train, n_test,
                                                        seed=seed),
    presets=LM_HEAD_PRESETS,
    description=("pooled qwen3-8b (reduced) backbone features, 5-class "
                 "teacher-projection labels; promotes "
                 "examples/dwn_head_lm.py to a first-class workload"),
    backbone=BACKBONE,
    cap_train=1024,
    cap_test=512,
))

__all__ = ["BACKBONE", "FEATS", "LM_HEAD", "LM_HEAD_PRESETS",
           "load_lm_head", "pool_features", "teacher_labels"]
