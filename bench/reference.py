"""Plain reference of a single-layer DWN, written from its definition.

It imports nothing of the program.  A DWN (Bacellar et al., "Differentiable
Weightless Neural Networks", ICML 2024; the model of Mecik & Kumm's paper):

* thermometer encoding: bit ``t`` of feature ``f`` is ``x_f > th[f, t]``,
  flattened feature-major into ``F*T`` bits;
* a LUT layer of ``m`` LUTs, each reading ``n`` of those bits (wire
  ``mapping[l, i]``) as an address ``sum_i bit_i * 2**i`` into its truth
  table of ``2**n`` entries;
* group popcount: LUT ``l`` votes for class ``l // (m / C)``;
* the prediction is the first class with the largest count.

``infer`` is NumPy; with ``dtype=bfloat16`` it is the lower-precision
control.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np


def thermometer(x: np.ndarray, th: np.ndarray) -> np.ndarray:
    """(B, F) x (F, T) -> (B, F*T) bool."""
    return (x[:, :, None] > th[None]).reshape(x.shape[0], -1)


def infer(x: np.ndarray, th: np.ndarray, mapping: np.ndarray,
          tables: np.ndarray, classes: int, *, dtype=np.float32,
          block: int = 4096):
    """Counts (B, C) int32 and first-argmax predictions (B,) int32.

    ``dtype`` is the type the comparisons are made in: float32 as the
    configuration states, or ``ml_dtypes.bfloat16`` for the control.
    """
    m, n = mapping.shape
    th = np.asarray(th, np.float32).astype(dtype)
    counts = np.empty((x.shape[0], classes), np.int32)
    rows = np.arange(m)[None, :]
    for lo in range(0, x.shape[0], block):
        xb = np.asarray(x[lo:lo + block], np.float32).astype(dtype)
        bits = thermometer(xb, th)                       # (b, F*T)
        addr = np.zeros((xb.shape[0], m), np.int32)      # (b, m)
        for i in range(n):
            addr |= bits[:, mapping[:, i]].astype(np.int32) << i
        out = tables[rows, addr]                         # (b, m)
        counts[lo:lo + block] = out.reshape(
            out.shape[0], classes, m // classes).sum(-1)
    return counts, np.argmax(counts, axis=1).astype(np.int32)


def bf16():
    return ml_dtypes.bfloat16

