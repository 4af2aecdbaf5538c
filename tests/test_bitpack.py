"""Packed-bitplane path: pack/unpack round-trips, packed kernels vs their
float twins, and bit-exactness of apply_hard_packed against the apply_hard
oracle on every JSC preset (TEN and PEN) plus a multi-layer stack."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.bitpack import (PackedBits, pack_bits, unpack_bits,
                                pack_bits_np, unpack_bits_np, popcount_u32,
                                popcount_u32_np, words_for_bits,
                                group_masks_np)
from repro.core import (JSC_PRESETS, init_dwn, freeze, apply_hard,
                        apply_hard_packed)
from repro.core.model import DWNConfig
from repro.data.jsc import load_jsc


# ---------------------------------------------------------------------------
# pack/unpack round-trip properties
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(st.integers(1, 300), st.integers(1, 8), st.integers(0, 2 ** 31 - 1))
def test_pack_unpack_roundtrip(num_bits, batch, seed):
    """Round-trips for arbitrary widths, including non-multiples of 32."""
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2, (batch, num_bits))
    words = pack_bits_np(bits)
    assert words.shape == (batch, words_for_bits(num_bits))
    assert words.dtype == np.uint32
    np.testing.assert_array_equal(unpack_bits_np(words, num_bits), bits)
    # JAX twins agree with NumPy twins exactly
    jwords = pack_bits(jnp.asarray(bits))
    np.testing.assert_array_equal(np.asarray(jwords), words)
    np.testing.assert_array_equal(
        np.asarray(unpack_bits(jwords, num_bits)), bits)


@settings(max_examples=60, deadline=None)
@given(st.integers(1, 9), st.integers(0, 2 ** 31 - 1))
def test_unpack_pack_identity_at_word_boundaries(words_n, seed):
    """The other direction: pack(unpack(words)) is the identity on any
    word content when num_bits fills the words exactly."""
    rng = np.random.default_rng(seed)
    words = rng.integers(0, 2 ** 32, (3, words_n), dtype=np.uint32)
    num_bits = 32 * words_n
    np.testing.assert_array_equal(
        pack_bits_np(unpack_bits_np(words, num_bits)), words)


@settings(max_examples=60, deadline=None)
@given(st.integers(1, 300), st.integers(0, 2 ** 31 - 1))
def test_unpack_pack_identity_modulo_pad(num_bits, seed):
    """At ragged widths the identity holds after zeroing the pad bits —
    and only the pad bits are dropped."""
    rng = np.random.default_rng(seed)
    words = rng.integers(0, 2 ** 32, (3, words_for_bits(num_bits)),
                         dtype=np.uint32)
    masked = words.copy()
    tail = num_bits & 31
    if tail:
        masked[:, -1] &= np.uint32((1 << tail) - 1)
    np.testing.assert_array_equal(
        pack_bits_np(unpack_bits_np(words, num_bits)), masked)


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 300), st.integers(0, 2 ** 31 - 1))
def test_pad_bits_are_zero_and_popcount_matches(num_bits, seed):
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2, (4, num_bits))
    words = pack_bits_np(bits)
    # zero-pad invariant: total popcount equals the logical bit count
    np.testing.assert_array_equal(popcount_u32_np(words).sum(-1),
                                  bits.sum(-1))
    np.testing.assert_array_equal(
        np.asarray(popcount_u32(jnp.asarray(words))).sum(-1), bits.sum(-1))


def test_lsb_first_word_order():
    """The documented convention: bit i -> word i>>5, position i&31."""
    bits = np.zeros((1, 70), np.int32)
    bits[0, 0] = 1      # word 0, bit 0
    bits[0, 33] = 1     # word 1, bit 1
    bits[0, 69] = 1     # word 2, bit 5
    words = pack_bits_np(bits)
    assert words.shape == (1, 3)
    assert words[0].tolist() == [1, 2, 32]


def test_group_masks_cover_disjoint():
    masks = group_masks_np(2400, 5)
    assert masks.shape == (5, 75)
    # disjoint and complete over the logical bits
    assert int(popcount_u32_np(masks).sum()) == 2400
    acc = np.zeros(75, np.uint32)
    for g in range(5):
        assert not np.any(acc & masks[g])
        acc |= masks[g]


def test_packedbits_is_pytree():
    p = PackedBits.pack(jnp.asarray(np.eye(3, 50)))
    out = jax.jit(lambda q: q)(p)
    assert out.num_bits == 50
    np.testing.assert_array_equal(np.asarray(out.words), np.asarray(p.words))


# ---------------------------------------------------------------------------
# packed kernels vs float kernels (interpret mode)
# ---------------------------------------------------------------------------

def _rand_model(B, F, T, m, n=6, seed=0):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    x = jax.random.uniform(k1, (B, F), minval=-1, maxval=1)
    th = jnp.sort(jax.random.uniform(k2, (F, T), minval=-1, maxval=1), 1)
    mapping = jax.random.randint(k3, (m, n), 0, F * T)
    tables = jax.random.randint(k4, (m, 2 ** n), 0, 2)
    return x, th, mapping, tables


@pytest.mark.parametrize("B,F,T", [(8, 4, 32), (37, 16, 200), (64, 1, 128)])
def test_encode_packed_kernel_matches_float(B, F, T):
    from repro.kernels.thermometer import ops as th_ops
    x, th, _, _ = _rand_model(B, F, T, 8, seed=B)
    p = th_ops.encode_packed(x, th, interpret=True)
    f = th_ops.encode(x, th, interpret=True)
    assert p.words.dtype == jnp.uint32
    assert p.num_bits == F * T
    np.testing.assert_array_equal(np.asarray(p.unpack()), np.asarray(f))


def test_encode_packed_fallback_non_word_multiple():
    """F*T not a 32-multiple takes the jnp fallback, same layout."""
    from repro.kernels.thermometer import ops as th_ops
    x, th, _, _ = _rand_model(9, 3, 7, 8, seed=5)
    p = th_ops.encode_packed(x, th, interpret=True)
    f = th_ops.encode(x, th, interpret=True)
    assert p.num_bits == 21
    np.testing.assert_array_equal(np.asarray(p.unpack()), np.asarray(f))


@pytest.mark.parametrize("B,m,C", [(16, 10, 320), (33, 50, 3200),
                                   (128, 360, 3200)])
def test_lut_eval_packed_kernel(B, m, C):
    from repro.kernels.lut_eval import ops as lut_ops
    key = jax.random.PRNGKey(m)
    bits = jax.random.bernoulli(key, 0.5, (B, C)).astype(jnp.float32)
    mapping = jax.random.randint(key, (m, 6), 0, C)
    tables = jax.random.randint(key, (m, 64), 0, 2)
    packed = PackedBits.pack(bits)
    out = lut_ops.evaluate_packed(packed, mapping, tables, interpret=True)
    ref = lut_ops.evaluate(bits, mapping, tables.astype(jnp.float32),
                           interpret=True)
    assert out.num_bits == m
    np.testing.assert_array_equal(np.asarray(out.unpack()), np.asarray(ref))


@pytest.mark.parametrize("B,classes,group", [(16, 5, 2), (37, 5, 72),
                                             (512, 10, 13)])
def test_popcount_packed_kernel(B, classes, group):
    from repro.kernels.popcount import ops as pc_ops
    key = jax.random.PRNGKey(B + classes)
    bits = jax.random.bernoulli(key, 0.4, (B, classes * group)) \
        .astype(jnp.float32)
    packed = PackedBits.pack(bits)
    counts, idx = pc_ops.classify_packed(packed, classes, interpret=True)
    rc, ri = pc_ops.classify(bits, classes, interpret=True)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ri))


@pytest.mark.parametrize("B,m", [(8, 10), (37, 50), (64, 360)])
def test_fused_packed_kernel_single_layer(B, m):
    from repro.kernels.fused import ops as f_ops
    x, th, mapping, tables = _rand_model(B, 16, 200, m, seed=m)
    counts, idx = f_ops.forward_packed(x, th, mapping, tables, 5,
                                       interpret=True)
    ref_counts, ref_idx = f_ops.forward(
        x, th, mapping, tables.astype(jnp.float32), 5, interpret=True)
    np.testing.assert_allclose(np.asarray(counts), np.asarray(ref_counts),
                               atol=1e-4)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))


@pytest.mark.parametrize("B,m", [(8, 10), (37, 50), (64, 360), (8, 1000)])
@pytest.mark.parametrize("block_b", [256, 16])
def test_fused_batch_major_variant(B, m, block_b):
    """Direct-wire batch-major variant: bit-exact vs the packed oracle
    at every preset width, ragged batches, and with a grid of >1 step
    over rows and (m=1000: two ``BLOCK_M`` tiles) over LUTs."""
    from repro.kernels.autotune import FusedConfig
    from repro.kernels.fused import ops as f_ops
    from repro.kernels.fused.ref import fused_dwn_packed_ref
    x, th, mapping, tables = _rand_model(B, 16, 200, m, seed=m + 1)
    counts, idx = f_ops.forward_packed(
        x, th, mapping, tables, 5, interpret=True,
        config=FusedConfig(block_b=block_b))
    ref_counts, ref_idx = fused_dwn_packed_ref(x, th, [mapping], [tables], 5)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(ref_counts))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))


def test_fused_batch_major_multilayer():
    """Two-layer stack through the batch-major variant (layer 0 direct
    wires -> packed continuation) == the packed-variant kernel."""
    from repro.kernels.autotune import FusedConfig
    from repro.kernels.fused import ops as f_ops
    from repro.kernels.fused.ref import fused_dwn_packed_ref
    key = jax.random.PRNGKey(7)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    x = jax.random.uniform(k1, (43, 16), minval=-1, maxval=1)
    th = jnp.sort(jax.random.uniform(k2, (16, 200), minval=-1, maxval=1), 1)
    mappings = [jax.random.randint(k3, (96, 6), 0, 3200),
                jax.random.randint(k4, (50, 6), 0, 96)]
    tables = [jax.random.randint(k5, (96, 64), 0, 2),
              jax.random.randint(k5, (50, 64), 0, 2)]
    counts, idx = f_ops.forward_packed(
        x, th, mappings, tables, 5, interpret=True,
        config=FusedConfig(block_b=16))
    ref_counts, ref_idx = fused_dwn_packed_ref(x, th, mappings, tables, 5)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(ref_counts))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))


# ---------------------------------------------------------------------------
# apply_hard_packed: bit-exact vs the float oracle on every preset
# ---------------------------------------------------------------------------

_DATA = None


def _data():
    global _DATA
    if _DATA is None:
        _DATA = load_jsc(2000, 256)
    return _DATA


@pytest.mark.parametrize("preset", sorted(JSC_PRESETS))
@pytest.mark.parametrize("frac_bits", [None, 8])
def test_apply_hard_packed_bit_exact(preset, frac_bits):
    """TEN (frac_bits=None) and PEN-quantized, all four paper presets."""
    data = _data()
    cfg = JSC_PRESETS[preset]
    params, buffers = init_dwn(jax.random.PRNGKey(1), cfg, data.x_train)
    fr = freeze(params, buffers, cfg, input_frac_bits=frac_bits)
    x = jnp.asarray(data.x_test[:96])
    oracle = np.asarray(apply_hard(fr, x))
    packed = np.asarray(apply_hard_packed(fr, x))
    np.testing.assert_array_equal(packed, oracle)


def test_apply_hard_packed_multilayer_and_fused_kernel():
    """Two-layer stack: jnp packed path AND fused packed kernel vs oracle."""
    from repro.kernels.fused import ops as f_ops
    data = _data()
    cfg = DWNConfig(lut_counts=(96, 50))
    params, buffers = init_dwn(jax.random.PRNGKey(2), cfg, data.x_train)
    fr = freeze(params, buffers, cfg)
    x = jnp.asarray(data.x_test[:64])
    oracle = np.asarray(apply_hard(fr, x))
    np.testing.assert_array_equal(np.asarray(apply_hard_packed(fr, x)),
                                  oracle)
    counts, idx = f_ops.forward_packed(
        x, jnp.asarray(fr.thresholds),
        [jnp.asarray(i) for i in fr.mapping_idx],
        [jnp.asarray(t) for t in fr.tables_bin], cfg.num_classes,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(counts), oracle)
    np.testing.assert_array_equal(np.asarray(idx), oracle.argmax(-1))


def test_apply_hard_packed_under_jit():
    data = _data()
    cfg = JSC_PRESETS["sm-50"]
    params, buffers = init_dwn(jax.random.PRNGKey(3), cfg, data.x_train)
    fr = freeze(params, buffers, cfg)
    x = jnp.asarray(data.x_test[:32])
    jitted = jax.jit(lambda xb: apply_hard_packed(fr, xb))
    np.testing.assert_array_equal(np.asarray(jitted(x)),
                                  np.asarray(apply_hard(fr, x)))
