"""Seeded jet-substructure rows with the JSC schema, made by the benchmark.

The schema is that of the hls4ml jet-tagging set (Duarte et al. 2018,
arXiv:1804.06913): 16 real features per jet, normalised to [-1, 1), and
one of 5 jet classes.  There is no network here, so the rows are drawn
from a fixed generative model in the manner of the repository's own
surrogate (``repro.data.jsc``, copied rather than imported so that the
benchmark's inputs do not come from the program): correlated Gaussian
features through ``tanh``, and labels from sparse single-feature cuts
plus Gumbel noise.  The model itself is fixed (master seed 1234); the
run's seed only draws rows from it.
"""

from __future__ import annotations

import numpy as np

NUM_FEATURES = 16
NUM_CLASSES = 5
_HI = np.nextafter(np.float32(1.0), np.float32(0.0))


class JetModel:
    """The fixed generative model (covariance, cut rules, class offsets)."""

    def __init__(self):
        master = np.random.default_rng(1234)
        m = master.normal(0.0, 1.0, (NUM_FEATURES, NUM_FEATURES))
        cov = m @ m.T / NUM_FEATURES + 0.6 * np.eye(NUM_FEATURES)
        self.chol = np.linalg.cholesky(cov)
        self.feats = np.stack([master.permutation(NUM_FEATURES)[:5]
                               for _ in range(NUM_CLASSES)])
        self.thr = master.normal(0.0, 0.45, (NUM_CLASSES, 5))
        self.sgn = master.choice([-1.0, 1.0], (NUM_CLASSES, 5))
        self.w = np.asarray([4.5, 0.5, 0.3, 0.2, 0.15])[None, :] \
            * master.uniform(0.9, 1.1, (NUM_CLASSES, 5))

    def features(self, rng: np.random.Generator, n: int) -> np.ndarray:
        """(n, 16) float32 features in [-1, 1)."""
        u = rng.standard_normal((n, NUM_FEATURES)) @ self.chol.T
        return np.clip(np.tanh(0.8 * u), -1.0, _HI).astype(np.float32)

    def labels(self, rng: np.random.Generator, x: np.ndarray) -> np.ndarray:
        """(n,) int32 jet classes for the rows ``x``."""
        cut = (x[:, self.feats] * self.sgn[None] > self.thr[None]
               * self.sgn[None])
        score = (cut * self.w[None]).sum(-1)
        score = score + rng.gumbel(0.0, 0.5, score.shape)
        return np.argmax(score, axis=1).astype(np.int32)


def distributive_thresholds(x: np.ndarray, bits: int) -> np.ndarray:
    """(F, T) float32 thresholds at the (t+1)/(T+1) quantiles of each
    feature of ``x``, ascending: the paper's distributive placement."""
    qs = np.arange(1, bits + 1, dtype=np.float64) / (bits + 1)
    th = np.quantile(x.astype(np.float64), qs, axis=0).T
    return np.sort(th.astype(np.float32), axis=1)
