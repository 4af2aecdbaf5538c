"""Public op wrappers for the fused DWN-accelerator kernels.

``make_forward_packed`` is the serving entry point: it hoists all
batch-independent operand prep out of the per-call path and returns a
closure running the served fused kernel.  Its rows per grid step come
from an optional :class:`repro.kernels.autotune.FusedConfig` — the
autotuner sweeps them per (spec, bucket, device) and persists the
winner; with no config ``DEFAULT_CONFIG`` applies.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.bitpack import WORD_BITS
from ..autotune import DEFAULT_CONFIG
from ..lut_eval.ref import selection_onehot
from .kernel import fused_dwn, fused_dwn_batch_major
from .ref import fused_dwn_ref, fused_dwn_packed_ref


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def forward(x: jax.Array, thresholds: jax.Array, mapping: jax.Array,
            tables: jax.Array, num_classes: int, *,
            interpret: bool | None = None, config=None):
    """Whole-accelerator DWN inference: features -> (counts, argmax).

    The first-argmax prediction is emitted in-kernel (ties -> lower
    class index), so callers never re-derive it.  ``config`` (a
    ``FusedConfig``) overrides the rows per grid step.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, F = x.shape
    T = thresholds.shape[1]
    m, n = mapping.shape
    g = m // num_classes
    block_b = config.block_b if config is not None else 256
    Tp = _round_up(T, 128)
    bb = min(block_b, _round_up(B, 8))
    bm = min(128, _round_up(m, 8))
    mp = _round_up(m, bm)
    thp = jnp.pad(thresholds, ((0, 0), (0, Tp - T)), constant_values=jnp.inf)
    # selection over the padded bit layout (F, Tp)
    f_of = mapping // T
    t_of = mapping % T
    mapping_p = f_of * Tp + t_of
    sel = selection_onehot(mapping_p, F * Tp)
    sel = jnp.pad(sel, ((0, 0), (0, (mp - m) * n)))
    tabs = jnp.pad(tables.astype(jnp.float32), ((0, mp - m), (0, 0)))
    cls = jax.nn.one_hot(jnp.arange(m) // g, num_classes, dtype=jnp.float32)
    cls = jnp.pad(cls, ((0, mp - m), (0, 0)))        # padded LUTs count 0
    return fused_dwn(x, thp, sel, tabs, cls, fan_in=n, block_b=bb,
                     block_m=bm, interpret=interpret)


_LANE = 128

#: first-layer LUTs per grid step of the served kernel (single-layer
#: models; deeper stacks run whole layers).  Tiling over m keeps the
#: (rows, LUTs) temporaries inside VMEM at lg widths.
BLOCK_M = 512


def _table_words(tables, m_p: int) -> np.ndarray:
    """(m, 2^n) {0,1} truth tables -> (W, m_p) int32 words, W = max(1,
    2^n/32): entry ``a`` of LUT ``j`` is bit ``a & 31`` of word ``a >> 5``
    (pad LUTs read 0)."""
    tb = np.asarray(tables) != 0
    m, A = tb.shape
    W = max(1, A // WORD_BITS)
    bits = np.zeros((m_p, W * WORD_BITS), np.uint32)
    bits[:m, :A] = tb
    weights = np.uint32(1) << np.arange(WORD_BITS, dtype=np.uint32)
    words = (bits.reshape(m_p, W, WORD_BITS) * weights).sum(
        -1, dtype=np.uint32)
    return np.ascontiguousarray(words.T).view(np.int32)


def _batch_major_operands(thresholds, mappings, tables, num_classes: int):
    """Per-layer (sel, rank, words) and the class map of
    ``kernel.fused_dwn_batch_major``, plus the layer-0 LUT tile.

    Layer 0's wire ``(j, i)`` reads bit ``t`` of feature ``f``; its
    selection column puts weight ``256**d`` on level digit ``d`` of
    ``f`` and its rank is the position of ``th[f, t]`` among feature
    ``f``'s sorted thresholds.  Later layers select a bit of the layer
    below with rank 0.  Pad LUTs select nothing, so they read address 0
    of an all-zero table and count for no class.
    """
    th = np.asarray(thresholds)
    F, T = th.shape
    assert T < 1 << 16, T
    digits = 1 if T < 256 else 2
    rank_of = np.argsort(np.argsort(th, axis=1, kind="stable"), axis=1,
                         kind="stable")
    m0 = np.asarray(mappings[0]).shape[0]
    mt = min(BLOCK_M, _round_up(m0, _LANE))
    if len(mappings) > 1:
        mt = _round_up(m0, _LANE)      # deeper layers need all of layer 0
    arrays, k_rows = [], digits * F
    for l, (mp_arr, tb) in enumerate(zip(mappings, tables)):
        idx = np.asarray(mp_arr)
        m, n = idx.shape
        m_p = _round_up(m, mt) if l == 0 else _round_up(m, _LANE)
        sel = np.zeros((n, k_rows, m_p), np.float32)
        rank = np.zeros((n, m_p), np.float32)
        cols = np.arange(m)
        for i in range(n):
            if l == 0:
                f, t = idx[:, i] // T, idx[:, i] % T
                for d in range(digits):
                    sel[i, d * F + f, cols] = 256.0 ** d
                rank[i, :m] = rank_of[f, t]
            else:
                sel[i, idx[:, i], cols] = 1.0
        arrays += [jnp.asarray(sel, jnp.bfloat16), jnp.asarray(rank),
                   jnp.asarray(_table_words(tb, m_p))]
        k_rows = m_p
    m_last = np.asarray(mappings[-1]).shape[0]
    cls = np.zeros((k_rows, _round_up(num_classes, _LANE)), np.float32)
    cls[np.arange(m_last), np.arange(m_last) // (m_last // num_classes)] = 1
    return tuple(arrays), jnp.asarray(cls, jnp.bfloat16), mt


def make_forward_packed(thresholds: jax.Array, mappings, tables,
                        num_classes: int, *,
                        interpret: bool | None = None, config=None):
    """Build ``fn(x) -> (counts, argmax)`` with operand prep done once.

    Hoists everything batch-independent out of the per-call path: wire
    selections and ranks, packed truth tables, class map.  The serving
    backends call this once per (model, tuned config) and reuse the
    closure across batches; ``forward_packed`` below stays as the
    one-shot convenience wrapper.

    Args:
      config: optional ``repro.kernels.autotune.FusedConfig``: sample rows
        (``block_b``) per grid step of ``kernel.fused_dwn_batch_major``.
        Any F, T, m and layer count.
      interpret: Pallas interpret mode; None = exactly when the default
        backend is not a TPU.

    Batches of any size work: the kernel pads internally and slices the
    ragged tail, so callers need no bucket rounding.
    """
    if not isinstance(mappings, (list, tuple)):
        mappings, tables = [mappings], [tables]
    config = config if config is not None else DEFAULT_CONFIG
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    layer_arrays, class_map, block_m = _batch_major_operands(
        thresholds, list(mappings), list(tables), num_classes)
    th = jnp.asarray(thresholds)

    def fn(x: jax.Array):
        return fused_dwn_batch_major(
            x, th, layer_arrays, class_map, num_classes=num_classes,
            block_b=config.block_b, block_m=block_m, interpret=interpret)
    return fn


def forward_packed(x: jax.Array, thresholds: jax.Array, mappings, tables,
                   num_classes: int, *, interpret: bool | None = None,
                   config=None):
    """Whole-accelerator packed DWN inference: features -> (counts, argmax).

    The serving fast path: one fused pallas_call runs encode -> every LUT
    layer -> group popcount with all bit tensors VMEM-resident.  One-shot
    wrapper over :func:`make_forward_packed`.
    """
    return make_forward_packed(thresholds, mappings, tables, num_classes,
                               interpret=interpret, config=config)(x)


__all__ = ["forward", "forward_packed", "make_forward_packed",
           "fused_dwn_ref", "fused_dwn_packed_ref"]
