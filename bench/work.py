"""Operations and bytes a DWN needs, from its definition.

The counts follow the model (thermometer, LUT layer, group popcount), not
how a kernel computes it, so a rewrite of the kernel cannot make them
stale.  ``cfg`` is a configuration file's dict (``features``,
``bits_per_feature``, ``luts``, ``fan_in``, ``classes``).
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def infer_ops_per_sample(cfg: dict) -> int:
    """F*T thermometer compares + m*n wire reads + m table reads + m
    class adds (one per LUT output)."""
    F, T = cfg["features"], cfg["bits_per_feature"]
    m, n = cfg["luts"], cfg["fan_in"]
    return F * T + m * n + m + m


def model_bytes(cfg: dict) -> int:
    """Thresholds (float32), wire indices (int32) and truth tables (one
    bit per entry), each read once per call."""
    F, T = cfg["features"], cfg["bits_per_feature"]
    m, n = cfg["luts"], cfg["fan_in"]
    return F * T * 4 + m * n * 4 + m * (2 ** n) // 8


def infer_bytes_per_call(cfg: dict, rows: int) -> int:
    """Input rows (float32) in, counts (float32) and predictions (int32)
    out, and the model once."""
    per_row = cfg["features"] * 4 + cfg["classes"] * 4 + 4
    return rows * per_row + model_bytes(cfg)


def least_time_s(ops: float, nbytes: float, pk: dict):
    """(seconds, bound): the larger of ops over the int8 peak and bytes
    over HBM bandwidth, and which of the two it is.  The forward's
    compares, reads and adds run on the vector unit, whose peak is not
    published, so the share against this bound understates how near the
    real limit the forward runs."""
    t_ops = ops / pk["int8_ops_per_s"]
    t_mem = nbytes / pk["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_mem else (t_mem, "bytes")

