"""Pallas TPU kernel ``kda_chunk``: the chunked KDA (Kimi Delta Attention)
recurrence, a gated delta rule with one decay per key channel.

Per head, with state ``S`` (dk x dv, float32) and, at token t, key and
query ``k_t, q_t``, value ``v_t``, log decay ``g_t <= 0`` (per key
channel) and ``beta_t``::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

The grid is (batch, head block, chunk); the chunk axis runs in order and
the state of each head stays in VMEM scratch across it.  Within a chunk
of ``C`` tokens, with ``G`` the chunk's cumulative log decay (``G_i =
g_1 + ... + g_i``, per channel) and ``S0`` the state it starts from, the
chunk's pair updates ``U`` (C x dv) solve the unit lower-triangular
system::

    (I + L) U = beta V - (beta K ⊙ exp(G)) S0,
    L_ij = beta_i sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])   (j < i)

and then::

    O   = (Q ⊙ exp(G)) S0 + M U,  M_ij = sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])  (j <= i)
    S_C = Diag(exp(G_C)) S0 + (K ⊙ exp(G_C - G))^T U

Every decay factor is an exponential of a difference that is <= 0, so
none overflows however strong the decay (a chunk's summed log decay may
pass -1000).  ``L`` and ``M`` are built in row blocks of ``SUB`` tokens:
a block's columns before it factor through the decay at the block's first
row, ``exp(G_i - G_r) exp(G_r - G_j)`` with both exponents <= 0, one
matrix product; its own diagonal block takes the pairwise differences
one column at a time.  ``U`` follows by block forward substitution, each
diagonal block inverted as ``(I + N)^-1 = (I - N)(I + N^2)(I + N^4)
(I + N^8)`` (N strictly lower, ``N^SUB = 0``).

The products with the state and the state's update take bfloat16
operands with float32 accumulation; the chunk's own triangular algebra
is float32 throughout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of one block of the chunk's triangular system
SUB = 16

_NN = (((1,), (0,)), ((), ()))          # a @ b
_NT = (((1,), (1,)), ((), ()))          # a @ b^T
_TN = (((0,), (0,)), ((), ()))          # a^T @ b
_HI = jax.lax.Precision.HIGHEST


def _dot(a, b, dims=_NN, *, exact: bool = True):
    """float32 product; ``exact=False`` rounds the operands to bfloat16."""
    if not exact:
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    return jax.lax.dot_general(a, b, dims, precision=_HI if exact else None,
                               preferred_element_type=jnp.float32)


def _unit_lower_inverse(n: jax.Array) -> jax.Array:
    """``(I + n)^-1`` for a strictly lower (SUB, SUB) ``n``."""
    s = n.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
    eye = jnp.where(rows == cols, 1.0, 0.0).astype(jnp.float32)
    p = -n                                  # (I + n)^-1 = sum_k (-n)^k
    inv = eye + p
    for _ in range(s.bit_length() - 2):     # p, p^2, p^4, p^8 for SUB = 16
        p = _dot(p, p)
        inv = inv + _dot(inv, p)
    return inv


def _chunk(q, k, kb, vb, G, s0):
    """One head's chunk: outputs (C, dv) and the state after it.

    q, k, kb (= beta k), G: (C, dk) float32; vb (= beta v): (C, dv);
    s0: (dk, dv) float32."""
    C, dk = q.shape
    eG = jnp.exp(G)
    # the state's contribution at every position of the chunk
    o_state = _dot(q * eG, s0, exact=False)
    rhs_all = vb - _dot(kb * eG, s0, exact=False)
    col = jax.lax.broadcasted_iota(jnp.int32, (SUB, C), 1)
    ri = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 1)
    u_blocks, o_blocks = [], []
    for b in range(C // SUB):
        r = b * SUB
        Gb, qb_, kbb, kbl = (a[r:r + SUB] for a in (G, q, kb, k))
        Gr = G[r:r + 1]
        if r:
            # columns before the block, through the decay at its first row
            to_r = jnp.exp(Gb - Gr)
            right = k * jnp.exp(jnp.minimum(Gr - G, 0.0))
            l_off = jnp.where(col < r, _dot(kbb * to_r, right, _NT), 0.0)
            m_off = jnp.where(col < r, _dot(qb_ * to_r, right, _NT), 0.0)
        # the diagonal block, one column at a time, pairwise differences
        d_l = jnp.zeros((SUB, SUB), jnp.float32)
        d_m = jnp.zeros((SUB, SUB), jnp.float32)
        for j in range(SUB):
            kj = kbl[j:j + 1] * jnp.exp(jnp.minimum(Gb - Gb[j:j + 1], 0.0))
            d_l = jnp.where(ci == j, jnp.sum(kbb * kj, 1, keepdims=True), d_l)
            d_m = jnp.where(ci == j, jnp.sum(qb_ * kj, 1, keepdims=True), d_m)
        d_l = jnp.where(ri > ci, d_l, 0.0)
        d_m = jnp.where(ri >= ci, d_m, 0.0)
        # block forward substitution: the rows of U before r are known
        rhs = rhs_all[r:r + SUB]
        o_b = o_state[r:r + SUB]
        if r:
            u_known = jnp.concatenate(
                u_blocks + [jnp.zeros((C - r, vb.shape[1]), jnp.float32)], 0)
            rhs = rhs - _dot(l_off, u_known)
            o_b = o_b + _dot(m_off, u_known)
        u_b = _dot(_unit_lower_inverse(d_l), rhs)
        u_blocks.append(u_b)
        o_blocks.append(o_b + _dot(d_m, u_b))
    u = jnp.concatenate(u_blocks, 0)
    g_last = G[C - 1:C]
    eye = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
                    == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1),
                    1.0, 0.0)
    decay_col = jnp.sum(eye * jnp.exp(g_last), 1, keepdims=True)  # (dk, 1)
    s1 = s0 * decay_col + _dot(k * jnp.exp(g_last - G), u, _TN, exact=False)
    return jnp.concatenate(o_blocks, 0), s1


def _kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, o_ref, s_ref, *,
            heads: int, dk: int, dv: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    f32 = jnp.float32
    for h in range(heads):
        ck, cv = slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv)
        o, s1 = _chunk(q_ref[:, ck].astype(f32), k_ref[:, ck].astype(f32),
                       kb_ref[:, ck].astype(f32), vb_ref[:, cv].astype(f32),
                       g_ref[:, ck], s_ref[h])
        o_ref[:, cv] = o.astype(o_ref.dtype)
        s_ref[h] = s1


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "head_block",
                                             "interpret"))
def kda_chunk(q, k, kb, vb, G, *, heads: int, chunk: int, head_block: int,
              interpret: bool = False):
    """The pallas_call on flat operands.

    q, k, kb: (B, S, H*dk) bfloat16; vb: (B, S, H*dv) bfloat16; G:
    (B, S, H*dk) float32, each chunk's cumulative log decay.  ``S`` is a
    multiple of ``chunk``, ``chunk`` of :data:`SUB`, and ``heads`` of
    ``head_block``.  Returns o (B, S, H*dv) bfloat16.
    """
    B, S, HK = q.shape
    dk, dv = HK // heads, vb.shape[-1] // heads
    hb = head_block
    kspec = pl.BlockSpec((None, chunk, hb * dk), lambda b, h, c: (b, c, h))
    vspec = pl.BlockSpec((None, chunk, hb * dv), lambda b, h, c: (b, c, h))
    return pl.pallas_call(
        functools.partial(_kernel, heads=hb, dk=dk, dv=dv),
        grid=(B, heads // hb, S // chunk),
        in_specs=[kspec, kspec, kspec, vspec, kspec],
        out_specs=vspec,
        out_shape=jax.ShapeDtypeStruct((B, S, heads * dv), jnp.bfloat16),
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="kda_chunk",
    )(q, k, kb, vb, G)
