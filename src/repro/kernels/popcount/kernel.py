"""Pallas TPU kernel: grouped popcount + argmax classification head.

FPGA -> TPU adaptation: the GPC compressor tree becomes a VPU group-sum
over the (B_blk, classes, group) VMEM tile; the argmax comparator tree
becomes a lane reduction.  Ties resolve to the lower class index (paper
§IV) via the standard max-then-first-index idiom.

Grid: (B / B_blk,).  One pass, bits never revisit HBM after the load.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.bitpack import masked_group_counts


def _first_argmax(counts):
    """First index achieving the max (ties -> lower class index); shared
    by all classifier kernels.  (B, C) f32 -> (B,) i32.  The index is a
    min over a float lane iota: Mosaic reduces only float32."""
    best = jnp.max(counts, axis=-1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, counts.shape,
                                    counts.ndim - 1).astype(jnp.float32)
    first = jnp.min(jnp.where(counts >= best, lane, float(counts.shape[-1])),
                    axis=-1)
    return first.astype(jnp.int32)


def _popcount_kernel(bits_ref, counts_ref, idx_ref, *, num_classes: int):
    bits = bits_ref[...]                                 # (B_blk, m)
    B_blk, m = bits.shape
    g = m // num_classes
    counts = bits.reshape(B_blk, num_classes, g).sum(-1)  # f32
    counts_ref[...] = counts
    idx_ref[...] = _first_argmax(counts)[:, None]


@functools.partial(jax.jit, static_argnames=("num_classes", "block_b",
                                             "interpret"))
def popcount_classify(bits: jax.Array, num_classes: int, *,
                      block_b: int = 512, interpret: bool = False):
    """bits (B, m) {0,1} f32 -> (counts (B, classes) f32, idx (B, 1) i32)."""
    B, m = bits.shape
    assert m % num_classes == 0, (m, num_classes)
    bb = min(block_b, B)
    assert B % bb == 0, (B, bb)
    kernel = functools.partial(_popcount_kernel, num_classes=num_classes)
    counts, idx = pl.pallas_call(
        kernel,
        grid=(B // bb,),
        in_specs=[pl.BlockSpec((bb, m), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bb, num_classes), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, num_classes), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        interpret=interpret,
    )(bits)
    return counts, idx[:, 0]


def _popcount_packed_kernel(words_ref, mask_ref, counts_ref, idx_ref):
    # words: (B_blk, W) uint32 packed layer-output bits; mask: (classes, W)
    # uint32 class-group masks (word boundaries need not align with group
    # boundaries).  SWAR popcount per masked word, summed over W — the GPC
    # compressor tree on 32-bit lanes.
    words = words_ref[...]
    mask = mask_ref[...]
    counts = masked_group_counts(words, mask)                # (B_blk, C)
    counts_ref[...] = counts
    idx_ref[...] = _first_argmax(counts)[:, None]


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def popcount_classify_packed(words: jax.Array, class_masks: jax.Array, *,
                             block_b: int = 512, interpret: bool = False):
    """words (B, W) uint32; class_masks (classes, W) uint32 ->
    (counts (B, classes) f32, idx (B, 1) i32).  Ties -> lower class."""
    B, W = words.shape
    classes = class_masks.shape[0]
    bb = min(block_b, B)
    assert B % bb == 0, (B, bb)
    counts, idx = pl.pallas_call(
        _popcount_packed_kernel,
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec((bb, W), lambda i: (i, 0)),
            pl.BlockSpec((classes, W), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, classes), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, classes), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        interpret=interpret,
    )(words, class_masks)
    return counts, idx[:, 0]
