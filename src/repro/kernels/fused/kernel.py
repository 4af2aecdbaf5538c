"""Pallas TPU kernels: fused DWN accelerator (beyond-paper optimization).

The paper's central finding is that thermometer *encoding* dominates
small-model hardware cost.  On TPU the same phenomenon appears as a
memory-bound unary blow-up: encoding inflates a (B, 16) feature tile into
a (B, 3200) bit tensor (x200 bytes) that a staged implementation writes
to and re-reads from HBM.  These kernels never write the bit tensor out:

``fused_dwn``
    float datapath: encode -> selection matmul (MXU) -> corner-product
    table eval (VPU) -> per-class popcount.  Grid (B/bb, m/bm); the m
    axis is the innermost (sequential) loop accumulating partial class
    counts, and the first-argmax prediction is emitted in-kernel on the
    last m step.  Not served; kept as a second datapath for tests.

``fused_dwn_batch_major``
    the served datapath, laid out for the TPU's vector units: samples on
    sublanes, LUTs on lanes.  Every step is a matmul, a compare, a
    shift or a select; nothing gathers along lanes, which Mosaic cannot
    lower.  See the function docstring for the encoding it relies on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..popcount.kernel import _first_argmax


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _fused_kernel(x_ref, th_ref, sel_ref, tab_ref, cls_ref, counts_ref,
                  idx_ref, *, fan_in: int):
    j = pl.program_id(1)
    x = x_ref[...]                                    # (B_blk, F)
    th = th_ref[...]                                  # (F, T)
    B_blk, F = x.shape
    T = th.shape[1]
    bits = (x[:, :, None] > th[None]).astype(jnp.float32)
    bits = bits.reshape(B_blk, F * T)                 # stays in VMEM
    sel = sel_ref[...]                                # (F*T, m_blk*n)
    tab = tab_ref[...]                                # (m_blk, 2^n)
    cls = cls_ref[...]                                # (m_blk, classes)
    mn = sel.shape[1]
    m_blk = mn // fan_in
    A = 2 ** fan_in
    s = jnp.dot(bits, sel, preferred_element_type=jnp.float32)
    s = s.reshape(B_blk, m_blk, fan_in)
    w = jnp.ones((B_blk, m_blk, A), jnp.float32)
    for i in range(fan_in):
        si = s[:, :, i:i + 1]
        corner_i = ((jnp.arange(A, dtype=jnp.int32) >> i) & 1).astype(
            jnp.float32)
        w = w * (si * corner_i + (1.0 - si) * (1.0 - corner_i))
    out_bits = jnp.sum(w * tab[None].astype(jnp.float32), axis=-1)
    partial = jnp.dot(out_bits, cls.astype(jnp.float32),
                      preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():
        counts_ref[...] = partial

    @pl.when(j > 0)
    def _acc():
        counts_ref[...] += partial

    # the m loop is innermost/sequential, so once the last m block has
    # accumulated, the counts block is final: emit the first-argmax
    # prediction here instead of making every caller re-derive it
    @pl.when(j == pl.num_programs(1) - 1)
    def _emit_idx():
        idx_ref[...] = _first_argmax(counts_ref[...])[:, None]


@functools.partial(jax.jit, static_argnames=("fan_in", "block_b", "block_m",
                                             "interpret"))
def fused_dwn(x: jax.Array, thresholds: jax.Array, sel_onehot: jax.Array,
              tables: jax.Array, class_map: jax.Array, *, fan_in: int = 6,
              block_b: int = 256, block_m: int = 128,
              interpret: bool = False):
    """x (B,F); thresholds (F,T); sel_onehot (F*T, m*n); tables (m, 2^n);
    class_map (m, classes) one-hot -> (counts (B, classes) f32,
    idx (B,) i32 first-argmax).  Any B works: the batch is padded
    internally to a block multiple and the tail sliced off."""
    B, F = x.shape
    T = thresholds.shape[1]
    m, classes = class_map.shape
    A = 2 ** fan_in
    bb, bm = min(block_b, B), min(block_m, m)
    assert m % bm == 0, (m, bm)
    Bp = _round_up(B, bb)
    xp = jnp.pad(x, ((0, Bp - B), (0, 0)))
    grid = (Bp // bb, m // bm)
    kernel = functools.partial(_fused_kernel, fan_in=fan_in)
    counts, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, F), lambda i, j: (i, 0)),
            pl.BlockSpec((F, T), lambda i, j: (0, 0)),
            pl.BlockSpec((F * T, bm * fan_in), lambda i, j: (0, j)),
            pl.BlockSpec((bm, A), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, classes), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, classes), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, classes), jnp.float32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(xp, thresholds, sel_onehot, tables, class_map)
    return counts[:B], idx[:B, 0]


def _lut_layer(inp, sel_ref, rank_ref, words_ref):
    """One LUT layer on a (rows, K) bf16 tile -> (rows, m_tile) int32 bits.

    Wire ``i`` of every LUT is read with one MXU matmul against a one-hot
    selection (``sel_ref[i]``, (K, m_tile)), so the gather happens on the
    MXU instead of along lanes; the wire is set where the gathered value
    exceeds ``rank_ref[i]``.  The 2^n-entry truth table is packed into
    32-bit words (``words_ref``, (W, m_tile)): one select picks the word,
    one shift picks the bit.
    """
    rank = rank_ref[...]
    addr = None
    for i in range(sel_ref.shape[0]):
        g = jnp.dot(inp, sel_ref[i], preferred_element_type=jnp.float32)
        bit = (g > rank[i:i + 1, :]).astype(jnp.int32)
        addr = bit if addr is None else addr | (bit << i)
    words = words_ref[...]
    w = jnp.broadcast_to(words[0:1, :], addr.shape)
    for k in range(1, words.shape[0]):
        w = jnp.where((addr >> 5) == k, words[k:k + 1, :], w)
    return (w >> (addr & 31)) & 1


def _to_bf16(v):
    return v.astype(jnp.float32).astype(jnp.bfloat16)


def _fused_bm_kernel(lvl_ref, *refs, num_layers: int):
    # refs: per layer (sel, rank, words), then the class map, then the two
    # output refs (counts, idx).  Grid (row tiles, layer-0 LUT tiles); the
    # LUT axis is sequential and accumulates counts into the output block.
    cls_ref = refs[3 * num_layers]
    counts_ref, idx_ref = refs[3 * num_layers + 1:]
    j = pl.program_id(1)
    bits = _lut_layer(_to_bf16(lvl_ref[...]), *refs[:3])
    for l in range(1, num_layers):
        bits = _lut_layer(_to_bf16(bits), *refs[3 * l:3 * l + 3])
    partial = jnp.dot(_to_bf16(bits), cls_ref[...],
                      preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():
        counts_ref[...] = partial

    @pl.when(j > 0)
    def _acc():
        counts_ref[...] += partial

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit_idx():
        idx_ref[...] = _first_argmax(counts_ref[...])[:, None]


@functools.partial(jax.jit, static_argnames=("num_classes", "block_b",
                                             "block_m", "interpret"))
def fused_dwn_batch_major(x: jax.Array, thresholds: jax.Array,
                          layer_arrays: tuple, class_map: jax.Array, *,
                          num_classes: int, block_b: int = 256,
                          block_m: int = 512, interpret: bool = False):
    """Whole-model DWN inference in one ``pallas_call``.

    Thermometer encoding as ranks: bit ``t`` of feature ``f`` is
    ``x_f > th[f, t]``.  With ``q_f`` the number of thresholds of feature
    ``f`` below ``x_f`` and ``r`` the position of ``th[f, t]`` in the
    sorted thresholds of ``f``, that bit equals ``q_f > r`` — ties and
    unsorted banks included.  So the wrapper reduces each feature to its
    level ``q`` (one compare-and-sum, fused by XLA, no bit tensor in
    HBM), and the kernel reads each first-layer wire as level-of-its-
    feature > rank-of-its-threshold.  Levels are split into base-256
    digits so the one-hot gather on the MXU is exact in bf16.

    Args:
      x: (B, F) features (already PEN-quantized where the model is PEN).
      thresholds: (F, T) threshold bank.
      layer_arrays: per layer ``(sel (n, K, m_p) bf16, rank (n, m_p) f32,
        words (W, m_p) i32)`` from ``ops._batch_major_operands``; layer 0
        has ``K = digits * F``, later layers ``K = m_p`` of the layer below.
      class_map: (m_last_p, C_p) bf16 one-hot of each LUT's class (zero
        rows for pad LUTs, zero columns for pad classes).
      num_classes: real class count C.
      block_b: sample rows per grid step.
      block_m: layer-0 LUTs per grid step; must divide layer 0's ``m_p``.
        Deeper stacks need every layer-0 bit at once, so they pass
        ``block_m == m_p``.

    Returns (counts (B, C) f32, idx (B,) i32 first-argmax); any B works
    (internal pad, tail sliced).
    """
    B, F = x.shape
    num_layers = len(layer_arrays) // 3
    _, K0, m0p = layer_arrays[0].shape
    digits = K0 // F
    q = jnp.sum(x[:, :, None] > thresholds[None], axis=-1, dtype=jnp.int32)
    lvl = jnp.concatenate([(q >> (8 * d)) & 255 for d in range(digits)],
                          axis=1).astype(jnp.float32)   # (B, digits*F)
    bb = min(block_b, _round_up(B, 8))
    Bp = _round_up(B, bb)
    lvl = jnp.pad(lvl, ((0, Bp - B), (0, 0)))
    bm = block_m
    assert m0p % bm == 0 and (num_layers == 1 or bm == m0p), (m0p, bm)
    Cp = class_map.shape[1]
    in_specs = [pl.BlockSpec((bb, K0), lambda i, j: (i, 0))]
    for l, arr in enumerate(layer_arrays):
        if l < 3:       # layer 0 tiles over the LUT axis
            shape = arr.shape[:-1] + (bm,)
            in_specs.append(pl.BlockSpec(
                shape, lambda i, j, nd=arr.ndim: (0,) * (nd - 1) + (j,)))
        else:
            in_specs.append(pl.BlockSpec(
                arr.shape, lambda i, j, nd=arr.ndim: (0,) * nd))
    # single-layer: class rows tile with layer 0's LUTs; deeper stacks
    # (one LUT step) read the whole last-layer map
    in_specs.append(pl.BlockSpec(
        (bm if num_layers == 1 else class_map.shape[0], Cp),
        lambda i, j: (j, 0)))
    kernel = functools.partial(_fused_bm_kernel, num_layers=num_layers)
    counts, idx = pl.pallas_call(
        kernel,
        grid=(Bp // bb, m0p // bm),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bb, Cp), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, Cp), jnp.float32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="dwn_fused_forward",
    )(lvl, *layer_arrays, class_map)
    return counts[:B, :num_classes], idx[:B, 0]
