"""Pallas TPU kernel: thermometer encoding.

FPGA -> TPU adaptation (DESIGN.md §3): the comparator bank becomes a
VPU broadcast-compare over a VMEM tile.  The (B, F) feature tile and the
(F, T) threshold bank tile live in VMEM; each grid step emits a
(B_blk, F_blk, T) bit tile.  T is padded to a lane multiple (128) by
ops.py so the compare vectorizes cleanly onto the 8x128 VREGs.

Grid: (B / B_blk, F / F_blk).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.bitpack import WORD_BITS


def _pack_words(bits, rows: int):
    """In-kernel bitpack: (rows, N) {0,1} -> (rows, N/32) uint32, LSB-first.

    N must be a 32-multiple (the op wrappers guarantee it); the whole pack
    is a VPU multiply-reduce, no gathers.  The sum runs in int32 (Mosaic
    has no unsigned reductions): the distinct powers of two wrap to the
    same 32 bits, which the bitcast reads back as uint32.
    """
    w = bits.reshape(rows, -1, WORD_BITS).astype(jnp.int32)
    weights = jnp.left_shift(jnp.int32(1),
                             jnp.arange(WORD_BITS, dtype=jnp.int32))
    return jax.lax.bitcast_convert_type(
        jnp.sum(w * weights, axis=-1, dtype=jnp.int32), jnp.uint32)


def _thermometer_kernel(x_ref, th_ref, out_ref):
    # x_ref: (B_blk, F_blk); th_ref: (F_blk, T); out: (B_blk, F_blk, T)
    x = x_ref[...]                                   # (B_blk, F_blk)
    th = th_ref[...]                                 # (F_blk, T)
    out_ref[...] = (x[:, :, None] > th[None]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "block_f",
                                             "interpret"))
def thermometer_encode(x: jax.Array, thresholds: jax.Array, *,
                       block_b: int = 256, block_f: int = 8,
                       interpret: bool = False) -> jax.Array:
    """x (B, F) f32, thresholds (F, T) f32 -> (B, F, T) f32 bits."""
    B, F = x.shape
    T = thresholds.shape[1]
    bb, bf = min(block_b, B), min(block_f, F)
    assert B % bb == 0 and F % bf == 0, (x.shape, bb, bf)
    grid = (B // bb, F // bf)
    return pl.pallas_call(
        _thermometer_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bf), lambda i, j: (i, j)),
            pl.BlockSpec((bf, T), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bb, bf, T), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B, F, T), jnp.float32),
        interpret=interpret,
    )(x, thresholds)


def _thermometer_packed_kernel(x_ref, th_ref, out_ref):
    # x: (B_blk, F); th: (F, T); out: (B_blk, F*T/32) uint32.  The compare
    # produces the (B_blk, F, T) bit tile in VMEM only; what reaches the
    # output (and HBM) is the packed words — 32x fewer bytes than the float
    # kernel above, and the (B, F, T) float tensor is never materialized.
    x = x_ref[...]
    th = th_ref[...]
    bits = (x[:, :, None] > th[None])                # bool (B_blk, F, T)
    out_ref[...] = _pack_words(bits.reshape(x.shape[0], -1), x.shape[0])


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def thermometer_encode_packed(x: jax.Array, thresholds: jax.Array, *,
                              block_b: int = 256,
                              interpret: bool = False) -> jax.Array:
    """x (B, F) f32, thresholds (F, T) f32 -> (B, F*T/32) uint32 words.

    Bit f*T + t of the flat bit-vector (word (f*T+t)>>5, position
    (f*T+t)&31) is ``x[b,f] > thresholds[f,t]``.  F*T must be a
    32-multiple (ops.py gates on this).
    """
    B, F = x.shape
    T = thresholds.shape[1]
    assert (F * T) % WORD_BITS == 0, (F, T)
    W = F * T // WORD_BITS
    bb = min(block_b, B)
    assert B % bb == 0, (B, bb)
    return pl.pallas_call(
        _thermometer_packed_kernel,
        grid=(B // bb,),
        in_specs=[
            pl.BlockSpec((bb, F), lambda i: (i, 0)),
            pl.BlockSpec((F, T), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, W), jnp.uint32),
        interpret=interpret,
    )(x, thresholds)
