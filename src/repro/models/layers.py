"""Shared building blocks for the assigned-architecture model zoo.

Conventions
-----------
* Params are plain dicts of jnp arrays; every init function has a
  ``*_axes`` twin returning the same treedef of
  :class:`repro.sharding.partition.LogicalAxes` so the partitioner can
  derive NamedShardings without touching real memory.
* Compute dtype is bf16 (TPU MXU native), params fp32, softmax/normalizers
  fp32.
* Attention uses a *padded-head layout* decided at config time
  (``HeadLayout``): query heads are padded to ``q_padded`` (dead heads have
  zero weights and a zeroed o-projection, so they contribute nothing) and
  the KV heads are activation-repeated to ``kv_padded`` so every tensor-
  parallel shard owns an integer number of q heads *and* the kv head(s)
  they attend to.  Duplicated KV heads share one weight matrix (the
  repeat happens on activations), so GQA semantics are exactly those of
  the published architecture.
* ``attention_chunked`` is a pure-JAX flash-attention: an online-softmax
  ``lax.scan`` over KV chunks.  Causal masking costs ~2x the ideal
  triangle FLOPs at the HLO level; this is a recorded baseline
  inefficiency that the perf log attacks (EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from ..sharding.annotate import hint
from ..sharding.partition import logical

Array = jax.Array
COMPUTE_DTYPE = jnp.bfloat16
PARAM_DTYPE = jnp.float32


# ---------------------------------------------------------------------------
# Head layout (TP divisibility; DESIGN.md §6)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HeadLayout:
    """Padded attention-head layout for a given tensor-parallel degree.

    q_padded   : query heads incl. dead padding (multiple of tp)
    kv_padded  : kv heads after activation-repeat (multiple of tp or == kv)
    slots      : q slots per original kv group (>= group size)
    """
    num_q: int
    num_kv: int
    q_padded: int
    kv_padded: int
    slots: int

    @property
    def kv_repeat(self) -> int:
        return self.kv_padded // self.num_kv

    @property
    def q_per_kvp(self) -> int:
        return self.q_padded // self.kv_padded


def make_head_layout(num_q: int, num_kv: int, tp: int) -> HeadLayout:
    """Choose (q_padded, kv_padded, slots) s.t. every TP shard owns whole
    q-head blocks aligned with the kv head (copy) they read.

    Three regimes (DESIGN.md §6):
      * MHA (kv == q): pad both to a multiple of tp, 1:1 q->kv mapping;
        dead kv heads are zero-padded activations.
      * GQA, kv divides tp: repeat each kv head r = tp/num_kv times
        (activation repeat — weights stay shared), pad q groups to
        ``slots = r * ceil(gs/r)`` slots; every shard then owns exactly one
        kv copy and ``slots/r`` q heads of its group.
      * GQA, kv >= tp: shard kv directly (pad kv to a multiple of tp if
        needed is not required for the assigned archs); no repeat.
    """
    assert num_q % num_kv == 0, (num_q, num_kv)
    gs = num_q // num_kv
    if num_kv == num_q:                       # MHA: pad both 1:1
        qp = _round_up(num_q, tp)
        return HeadLayout(num_q, num_kv, qp, qp, 1)
    if num_kv % tp == 0:                      # kv >= tp and divisible
        return HeadLayout(num_q, num_kv, num_q, num_kv, gs)
    if tp % num_kv == 0:                      # kv < tp: repeat kv
        r = tp // num_kv
        s = r * math.ceil(gs / r)
        qp = num_kv * s                       # multiple of tp by construction
        return HeadLayout(num_q, num_kv, qp, tp, s)
    # awkward kv (doesn't divide and isn't divisible by tp): replicate kv,
    # pad q to a multiple of tp.  The partitioner's divisibility fallback
    # will replicate the kv dims automatically.
    qp = _round_up(num_q, tp)
    return HeadLayout(num_q, num_kv, qp, num_kv, qp // num_kv)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


def layer_norm(x: Array, scale: Array, bias: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def init_rms_norm(d: int):
    return {"scale": jnp.ones((d,), PARAM_DTYPE)}


def axes_rms_norm():
    return {"scale": logical("norm", name="norm.scale")}


def init_layer_norm(d: int):
    return {"scale": jnp.ones((d,), PARAM_DTYPE),
            "bias": jnp.zeros((d,), PARAM_DTYPE)}


def axes_layer_norm():
    return {"scale": logical("norm", name="ln.scale"),
            "bias": logical("norm", name="ln.bias")}


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    inv = rope_frequencies(hd, theta)                       # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., S, hd/2)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (padded-head GQA, chunked flash, SWA/local windows)
# ---------------------------------------------------------------------------

def init_attention(key: Array, d_model: int, layout: HeadLayout,
                   head_dim: int, *, qkv_bias: bool = False,
                   qk_norm: bool = False, out_bias: bool = False):
    """Padded-layout attention params.  Dead q heads (slots beyond the real
    group size) are zero-initialized, including their o-proj rows."""
    kq, kk, kv, ko = jax.random.split(key, 4)
    std = d_model ** -0.5
    H, K, s = layout.q_padded, layout.num_kv, layout.slots
    gs = layout.num_q // layout.num_kv
    wq = jax.random.normal(kq, (d_model, H, head_dim), PARAM_DTYPE) * std
    # zero the dead q slots
    if layout.num_kv == layout.num_q:          # MHA padding: first num_q alive
        alive = (jnp.arange(H) < layout.num_q).astype(PARAM_DTYPE)
    else:                                      # GQA: slot-in-group >= gs dead
        alive = ((jnp.arange(H) % s) < gs).astype(PARAM_DTYPE)
    wq = wq * alive[None, :, None]
    p = {
        "wq": wq,
        "wk": jax.random.normal(kk, (d_model, K, head_dim), PARAM_DTYPE) * std,
        "wv": jax.random.normal(kv, (d_model, K, head_dim), PARAM_DTYPE) * std,
        "wo": jax.random.normal(ko, (H, head_dim, d_model), PARAM_DTYPE)
              * std * alive[:, None, None],
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((H, head_dim), PARAM_DTYPE)
        p["bk"] = jnp.zeros((K, head_dim), PARAM_DTYPE)
        p["bv"] = jnp.zeros((K, head_dim), PARAM_DTYPE)
    if out_bias:
        p["bo"] = jnp.zeros((d_model,), PARAM_DTYPE)
    if qk_norm:
        p["q_norm"] = jnp.ones((head_dim,), PARAM_DTYPE)
        p["k_norm"] = jnp.ones((head_dim,), PARAM_DTYPE)
    return p


def axes_attention(*, qkv_bias: bool = False, qk_norm: bool = False,
                   out_bias: bool = False):
    a = {
        "wq": logical("embed", "q_heads", "head_dim", name="attn.wq"),
        "wk": logical("embed", None, "head_dim", name="attn.wk"),
        "wv": logical("embed", None, "head_dim", name="attn.wv"),
        "wo": logical("q_heads", "head_dim", "embed", name="attn.wo"),
    }
    if qkv_bias:
        a["bq"] = logical("q_heads", "head_dim", name="attn.bq")
        a["bk"] = logical(None, "head_dim", name="attn.bk")
        a["bv"] = logical(None, "head_dim", name="attn.bv")
    if out_bias:
        a["bo"] = logical(None, name="attn.bo")
    if qk_norm:
        a["q_norm"] = logical("norm", name="attn.q_norm")
        a["k_norm"] = logical("norm", name="attn.k_norm")
    return a


def qkv_project(p, x: Array, layout: HeadLayout, *, positions: Array | None,
                rope_theta: float | None, qk_norm_eps: float = 1e-6):
    """x (B,S,D) -> q (B,S,Hp,hd), k/v (B,S,Kp,hd) in compute dtype.

    KV is computed with the *true* head count and activation-repeated to
    the padded layout, so duplicated heads share weights exactly.
    """
    cd = COMPUTE_DTYPE
    q = jnp.einsum("bsd,dhk->bshk", x.astype(cd), p["wq"].astype(cd))
    k = jnp.einsum("bsd,dhk->bshk", x.astype(cd), p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", x.astype(cd), p["wv"].astype(cd))
    if "bq" in p:
        q = q + p["bq"].astype(cd)
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], qk_norm_eps)
        k = rms_norm(k, p["k_norm"], qk_norm_eps)
    if rope_theta is not None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    r = layout.kv_repeat
    if r > 1:
        k = jnp.repeat(k, r, axis=2)
        v = jnp.repeat(v, r, axis=2)
    if k.shape[2] < layout.kv_padded:          # MHA zero-pad (dead kv heads)
        padn = layout.kv_padded - k.shape[2]
        k = jnp.pad(k, ((0, 0), (0, 0), (0, padn), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, padn), (0, 0)))
    # The padded/repeated KV must be *sharded* over the model axis even
    # though its producing weights are replicated — otherwise SPMD
    # replicates the whole attention einsum (16x compute; §Perf iter 1).
    from ..sharding.annotate import hint_heads
    q = hint_heads(q)
    k = hint_heads(k)
    v = hint_heads(v)
    return q, k, v


def attention_chunked(q: Array, k: Array, v: Array, layout: HeadLayout, *,
                      causal: bool, window: int | None = None,
                      q_offset: Array | int = 0, kv_offset: Array | int = 0,
                      kv_chunk: int = 1024, kv_len: Array | None = None,
                      scores_dtype=jnp.float32,
                      scale: float | None = None) -> Array:
    """Online-softmax flash attention, pure JAX.

    q: (B, Sq, Hp, hd); k/v: (B, Skv, Kp, hd)  (already padded layout).
    window: sliding-window size (None = unbounded).
    kv_len: optional (B,) valid kv length (decode against partial cache).
    scale: softmax scale of the scores (None = hd^-0.5).
    Returns (B, Sq, Hp, hd).
    """
    B, Sq, Hp, hd = q.shape
    Skv = k.shape[1]
    Kp = layout.kv_padded
    g = Hp // Kp
    scale = hd ** -0.5 if scale is None else scale
    nchunk = -(-Skv // kv_chunk)
    pad = nchunk * kv_chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, nchunk, kv_chunk, Kp, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nchunk, kv_chunk, Kp, hd).transpose(1, 0, 2, 3, 4)

    qg = q.reshape(B, Sq, Kp, g, hd).astype(COMPUTE_DTYPE)
    q_pos = jnp.asarray(q_offset) + jnp.arange(Sq)              # (Sq,)

    def body(carry, xs):
        o, m, l = carry                                          # o:(B,Sq,Kp,g,hd)
        kci, vci, ci = xs                                        # (B,ck,Kp,hd)
        local_idx = ci * kv_chunk + jnp.arange(kv_chunk)         # (ck,)
        kv_pos = jnp.asarray(kv_offset) + local_idx
        s = jnp.einsum("bqkgd,bckd->bqkgc", qg, kci.astype(COMPUTE_DTYPE),
                       preferred_element_type=scores_dtype) \
            .astype(jnp.float32) * scale
        mask2d = jnp.broadcast_to((local_idx < Skv)[None, :],
                                  (Sq, kv_chunk))                # tail padding
        if causal:
            mask2d = mask2d & (q_pos[:, None] >= kv_pos[None, :])
        if window is not None:
            mask2d = mask2d & (q_pos[:, None] - kv_pos[None, :] < window)
        if kv_len is not None:
            mb = mask2d[None] & (kv_pos[None, None, :]
                                 < kv_len[:, None, None])        # (B,Sq,ck)
            mask = mb[:, :, None, None, :]
        else:
            mask = mask2d[None, :, None, None, :]
        s = jnp.where(mask, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))                   # (B,Sq,Kp,g)
        # guard all-masked rows (m_new = -inf): keep them neutral
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l = l * alpha + p.sum(axis=-1)
        o = o * alpha[..., None] + jnp.einsum(
            "bqkgc,bckd->bqkgd", p.astype(COMPUTE_DTYPE),
            vci.astype(COMPUTE_DTYPE),
            preferred_element_type=jnp.float32)
        return (o, m_new, l), None

    o0 = jnp.zeros((B, Sq, Kp, g, hd), jnp.float32)
    m0 = jnp.full((B, Sq, Kp, g), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Sq, Kp, g), jnp.float32)
    (o, m, l), _ = jax.lax.scan(
        body, (o0, m0, l0), (kc, vc, jnp.arange(nchunk)))
    o = o / jnp.maximum(l[..., None], 1e-30)
    return o.reshape(B, Sq, Hp, hd).astype(COMPUTE_DTYPE)


def _attn_parts(q: Array, k: Array, v: Array, layout: HeadLayout, *,
                causal: bool, q_offset, kv_offset, kv_chunk: int,
                scores_dtype=jnp.float32):
    """attention_chunked's scan, returning unnormalized (o, m, l) parts
    so callers can combine disjoint KV ranges (online-softmax algebra)."""
    B, Sq, Hp, hd = q.shape
    Skv = k.shape[1]
    Kp = layout.kv_padded
    g = Hp // Kp
    scale = hd ** -0.5
    nchunk = -(-Skv // kv_chunk)
    pad = nchunk * kv_chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, nchunk, kv_chunk, Kp, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nchunk, kv_chunk, Kp, hd).transpose(1, 0, 2, 3, 4)
    qg = q.reshape(B, Sq, Kp, g, hd).astype(COMPUTE_DTYPE)
    q_pos = jnp.asarray(q_offset) + jnp.arange(Sq)

    def body(carry, xs):
        o, m, l = carry
        kci, vci, ci = xs
        local_idx = ci * kv_chunk + jnp.arange(kv_chunk)
        kv_pos = jnp.asarray(kv_offset) + local_idx
        s = jnp.einsum("bqkgd,bckd->bqkgc", qg, kci.astype(COMPUTE_DTYPE),
                       preferred_element_type=scores_dtype) \
            .astype(jnp.float32) * scale
        mask2d = jnp.broadcast_to((local_idx < Skv)[None, :], (Sq, kv_chunk))
        if causal:
            mask2d = mask2d & (q_pos[:, None] >= kv_pos[None, :])
        mask = mask2d[None, :, None, None, :]
        s = jnp.where(mask, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l = l * alpha + p.sum(axis=-1)
        o = o * alpha[..., None] + jnp.einsum(
            "bqkgc,bckd->bqkgd", p.astype(COMPUTE_DTYPE),
            vci.astype(COMPUTE_DTYPE), preferred_element_type=jnp.float32)
        return (o, m_new, l), None

    o0 = jnp.zeros((B, Sq, Kp, g, hd), jnp.float32)
    m0 = jnp.full((B, Sq, Kp, g), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Sq, Kp, g), jnp.float32)
    (o, m, l), _ = jax.lax.scan(body, (o0, m0, l0),
                                (kc, vc, jnp.arange(nchunk)))
    return o, m, l


def _combine_parts(a, b):
    """Merge two online-softmax parts over disjoint KV ranges."""
    o1, m1, l1 = a
    o2, m2, l2 = b
    m = jnp.maximum(m1, m2)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    a1 = jnp.where(jnp.isfinite(m1), jnp.exp(m1 - m_safe), 0.0)
    a2 = jnp.where(jnp.isfinite(m2), jnp.exp(m2 - m_safe), 0.0)
    return (o1 * a1[..., None] + o2 * a2[..., None],
            m, l1 * a1 + l2 * a2)


def attention_causal_tri(q: Array, k: Array, v: Array, layout: HeadLayout,
                         *, kv_chunk: int = 1024, leaf: int = 4096,
                         scores_dtype=jnp.float32) -> Array:
    """Block-triangular causal attention (§Perf optimization).

    The masked-flash baseline computes the full S x S score grid and
    masks half of it away.  This recursion computes the causal triangle
    with ~0.5x + O(S*leaf) of those FLOPs, statically (no dynamic
    shapes): split the sequence in half — the upper-right block is never
    computed, the lower-left block is *dense* (mask-free), and the two
    diagonal blocks recurse.  Parts merge with the online-softmax
    algebra, so results are bit-comparable to the baseline.
    """
    B, S, Hp, hd = q.shape

    def rec(q_, k_, v_, off):
        Sq = q_.shape[1]
        if Sq <= leaf:
            return _attn_parts(q_, k_, v_, layout, causal=True,
                               q_offset=off, kv_offset=off,
                               kv_chunk=min(kv_chunk, Sq),
                               scores_dtype=scores_dtype)
        half = Sq // 2
        top = rec(q_[:, :half], k_[:, :half], v_[:, :half], off)
        cross = _attn_parts(q_[:, half:], k_[:, :half], v_[:, :half],
                            layout, causal=False, q_offset=off + half,
                            kv_offset=off, kv_chunk=kv_chunk,
                            scores_dtype=scores_dtype)
        diag = rec(q_[:, half:], k_[:, half:], v_[:, half:], off + half)
        bottom = _combine_parts(cross, diag)
        return (jnp.concatenate([top[0], bottom[0]], axis=1),
                jnp.concatenate([top[1], bottom[1]], axis=1),
                jnp.concatenate([top[2], bottom[2]], axis=1))

    o, m, l = rec(q, k, v, 0)
    o = o / jnp.maximum(l[..., None], 1e-30)
    return o.reshape(B, S, Hp, hd).astype(COMPUTE_DTYPE)


def attention_decode(q: Array, k_cache: Array, v_cache: Array,
                     layout: HeadLayout, *, cur_len: Array,
                     window: int | None = None) -> Array:
    """Single-token attention against a cache.

    q: (B, 1, Hp, hd); caches: (B, Skv, Kp, hd); cur_len: (B,) or scalar —
    number of valid cache entries (the new token's k/v must already be
    written).  Window semantics assume a ring buffer of size Skv when
    window is not None (every slot is valid once cur_len >= Skv).
    """
    B, _, Hp, hd = q.shape
    Skv, Kp = k_cache.shape[1], k_cache.shape[2]
    g = Hp // Kp
    qg = q.reshape(B, Kp, g, hd).astype(COMPUTE_DTYPE)
    s = jnp.einsum("bkgd,bckd->bkgc", qg, k_cache.astype(COMPUTE_DTYPE),
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    pos = jnp.arange(Skv)
    cur = jnp.asarray(cur_len)
    cur = cur[:, None] if cur.ndim else cur[None, None]
    valid = pos[None, :] < cur                                   # (B,Skv)
    if window is not None:
        # ring buffer: valid slots are the last `window` written
        valid &= pos[None, :] >= (cur - window)
        # (when cur > Skv the ring has wrapped; slot ages are implicit and
        #  every slot is within the window because Skv == window)
        valid |= (cur > Skv)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    o = jnp.einsum("bkgc,bckd->bkgd", p.astype(COMPUTE_DTYPE),
                   v_cache.astype(COMPUTE_DTYPE),
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, Hp, hd).astype(COMPUTE_DTYPE)


def attn_output(p, o: Array) -> Array:
    """o (B,S,Hp,hd) -> (B,S,D)."""
    y = jnp.einsum("bshk,hkd->bsd", o.astype(COMPUTE_DTYPE),
                   p["wo"].astype(COMPUTE_DTYPE))
    if "bo" in p:
        y = y + p["bo"].astype(COMPUTE_DTYPE)
    return y


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_swiglu(key: Array, d_model: int, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    std_in, std_out = d_model ** -0.5, d_ff ** -0.5
    return {"w_gate": jax.random.normal(k1, (d_model, d_ff), PARAM_DTYPE) * std_in,
            "w_up": jax.random.normal(k2, (d_model, d_ff), PARAM_DTYPE) * std_in,
            "w_down": jax.random.normal(k3, (d_ff, d_model), PARAM_DTYPE) * std_out}


def axes_swiglu():
    return {"w_gate": logical("embed", "ff", name="mlp.w_gate"),
            "w_up": logical("embed", "ff", name="mlp.w_up"),
            "w_down": logical("ff", "embed", name="mlp.w_down")}


def swiglu(p, x: Array) -> Array:
    cd = COMPUTE_DTYPE
    g = hint(jnp.einsum("bsd,df->bsf", x.astype(cd), p["w_gate"].astype(cd)),
             "dp", None, "model")
    u = hint(jnp.einsum("bsd,df->bsf", x.astype(cd), p["w_up"].astype(cd)),
             "dp", None, "model")
    h = jax.nn.silu(g.astype(jnp.float32)).astype(cd) * u
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(cd))


def init_gelu_mlp(key: Array, d_model: int, d_ff: int, *, bias: bool = True):
    k1, k2 = jax.random.split(key)
    p = {"w_in": jax.random.normal(k1, (d_model, d_ff), PARAM_DTYPE) * d_model ** -0.5,
         "w_out": jax.random.normal(k2, (d_ff, d_model), PARAM_DTYPE) * d_ff ** -0.5}
    if bias:
        p["b_in"] = jnp.zeros((d_ff,), PARAM_DTYPE)
        p["b_out"] = jnp.zeros((d_model,), PARAM_DTYPE)
    return p


def axes_gelu_mlp(*, bias: bool = True):
    a = {"w_in": logical("embed", "ff", name="mlp.w_in"),
         "w_out": logical("ff", "embed", name="mlp.w_out")}
    if bias:
        a["b_in"] = logical("ff", name="mlp.b_in")
        a["b_out"] = logical(None, name="mlp.b_out")
    return a


def gelu_mlp(p, x: Array) -> Array:
    cd = COMPUTE_DTYPE
    h = hint(jnp.einsum("bsd,df->bsf", x.astype(cd), p["w_in"].astype(cd)),
             "dp", None, "model")
    if "b_in" in p:
        h = h + p["b_in"].astype(cd)
    h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(cd)
    y = jnp.einsum("bsf,fd->bsd", h, p["w_out"].astype(cd))
    if "b_out" in p:
        y = y + p["b_out"].astype(cd)
    return y


# ---------------------------------------------------------------------------
# Mixture of Experts (GShard-style capacity routing, per batch row;
# expert FFN hidden dim is tensor-parallel, tokens are data-parallel)
# ---------------------------------------------------------------------------

def init_moe(key: Array, d_model: int, d_ff: int, num_experts: int, *,
             pad_to: int = 0):
    """pad_to > num_experts adds dead experts (zero router effect via
    masking in moe_apply) so the expert dim can shard over "model" (EP)."""
    kr, k1, k2, k3 = jax.random.split(key, 4)
    std_in, std_out = d_model ** -0.5, d_ff ** -0.5
    E = max(num_experts, pad_to)
    return {
        "router": jax.random.normal(kr, (d_model, E), PARAM_DTYPE) * std_in,
        "w_gate": jax.random.normal(k1, (E, d_model, d_ff), PARAM_DTYPE) * std_in,
        "w_up": jax.random.normal(k2, (E, d_model, d_ff), PARAM_DTYPE) * std_in,
        "w_down": jax.random.normal(k3, (E, d_ff, d_model), PARAM_DTYPE) * std_out,
    }


def axes_moe(*, ep: bool = False):
    """ep=False: TP over the expert hidden dim (Megatron-style).
    ep=True:  EP — experts shard over "model", hidden dim full per shard
    (the right regime for many small experts; §Perf granite iter 3)."""
    e_ax = "experts_ep" if ep else "experts"
    f_ax = None if ep else "ff"
    return {
        "router": logical("embed", None, name="moe.router"),
        "w_gate": logical(e_ax, "embed", f_ax, name="moe.w_gate"),
        "w_up": logical(e_ax, "embed", f_ax, name="moe.w_up"),
        "w_down": logical(e_ax, f_ax, "embed", name="moe.w_down"),
    }


def moe_apply(p, x: Array, *, top_k: int, capacity_factor: float = 1.25,
              min_capacity: int = 4, num_real_experts: int = 0,
              ep: bool = False, first_expert: int | None = None,
              token_mask: Array | None = None):
    """Token-choice top-k MoE.  Returns (y, aux_loss), and with
    ``first_expert`` (y, aux_loss, fallback).

    x: (B, S, D).  The router scores every expert; each token keeps its
    ``top_k`` and their softmax weights renormalised over the k (the same
    as a softmax over the top-k logits).

    ``first_expert=None``: per-row capacity routing that drops overflow.
    Routing/dispatch is independent per batch row, so with batch-sharded
    activations no routing collective crosses shards; the only
    cross-device traffic is the TP all-reduce of the expert FFN (Megatron
    pattern) or, with ep=True, the partial-combine all-reduce.  Padded
    (dead) experts beyond ``num_real_experts`` are masked out of the
    router.

    ``first_expert=e0``: expert parallelism's share of one device, with
    nothing dropped.  ``p``'s expert weights hold the ``n`` experts
    ``e0 .. e0+n-1`` of the router's ``E``; ``y`` is what those experts
    give for the tokens routed to them (:func:`_moe_held`).  The parts
    of all ``E / n`` shares sum to the whole layer.  ``token_mask``
    (B, S), None meaning every token is real: a token outside it routes
    no pair here and its ``y`` is 0.  ``fallback`` (int32, 0 or 1) is 1
    where the routed pairs overflowed the sized buffer and the layer ran
    on the worst-case one (:func:`held_rows`).
    """
    if token_mask is not None and first_expert is None:
        raise ValueError("token_mask applies to the held-experts path "
                         "(first_expert=) only")
    B, S, D = x.shape
    E = p["router"].shape[-1]
    E_real = num_real_experts or E
    cap = max(min_capacity,
              int(math.ceil(S * top_k / E_real * capacity_factor)))
    cap = min(cap, S * top_k)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if E_real < E:
        logits = jnp.where(jnp.arange(E) < E_real, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)                      # (B,S,E)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)          # (B,S,k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    frac_probs = jnp.mean(probs, axis=(0, 1))
    if first_expert is not None:
        y, fallback = _moe_held(p, x, expert_idx, gate_vals, first_expert,
                                token_mask)
        return (y.astype(x.dtype), _aux_loss(expert_idx, frac_probs, E),
                fallback)

    # position of each (token, k) within its expert, token-major order
    flat_e = expert_idx.reshape(B, S * top_k)                    # (B,T)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)          # (B,T,E)
    pos = jnp.cumsum(onehot, axis=1) - 1                         # (B,T,E)
    pos_in_e = jnp.take_along_axis(pos, flat_e[..., None], -1)[..., 0]
    keep = pos_in_e < cap                                        # (B,T)

    # scatter token index + gate into (E, cap) slots, per batch row.
    # token-major flatten: slot j of token t is flat index t*top_k + j.
    tok_idx = (jnp.arange(S * top_k) // top_k).astype(jnp.int32)  # (T,)
    gate_flat = gate_vals.reshape(B, S * top_k)

    def scatter_row(fe, pie, kp, gv):
        # fe, pie, kp, gv: (T,) -> slot_tok (E, cap), slot_gate (E, cap)
        cols = jnp.where(kp, pie, cap)   # col `cap` is OOB -> dropped
        slot_tok = jnp.full((E, cap), S, jnp.int32) \
            .at[fe, cols].set(tok_idx, mode="drop")
        slot_gate = jnp.zeros((E, cap), jnp.float32) \
            .at[fe, cols].set(gv, mode="drop")
        return slot_tok, slot_gate

    slot_tok, slot_gate = jax.vmap(scatter_row)(
        flat_e, pos_in_e, keep, gate_flat)                       # (B,E,cap)

    # gather tokens into expert slots (index S = zero pad row)
    xpad = jnp.concatenate(
        [x, jnp.zeros((B, 1, D), x.dtype)], axis=1)              # (B,S+1,D)
    xe = _gather_slots(xpad, slot_tok)                           # (B,E,cap,D)
    e_ax = "model" if ep else None
    f_ax = None if ep else "model"
    xe = hint(xe, "dp", e_ax, None, None)

    cd = COMPUTE_DTYPE
    g = hint(jnp.einsum("becd,edf->becf", xe, p["w_gate"].astype(cd)),
             "dp", e_ax, None, f_ax)
    u = hint(jnp.einsum("becd,edf->becf", xe, p["w_up"].astype(cd)),
             "dp", e_ax, None, f_ax)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(cd) * u
    ye = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(cd))  # (B,E,cap,D)

    if ep:
        # EP combine: per-expert scatter-add back to token positions;
        # partial sums over the expert shards all-reduce a (B,S,D) tensor
        # (vs all-gathering the (B,E,cap,D) slots).
        ye = hint(ye, "dp", "model", None, None)
        yw = ye.astype(jnp.float32) * slot_gate[..., None]

        def combine_row(yw_r, tok_r):
            # yw_r (E,cap,D); tok_r (E,cap) token index (S = dropped)
            return jnp.zeros((S, D), jnp.float32).at[
                tok_r.reshape(-1)].add(yw_r.reshape(-1, D), mode="drop")

        y = jax.vmap(combine_row)(yw, slot_tok)
    else:
        # combine: for each (token, k) read its slot if kept
        flat_slot = flat_e * cap + jnp.where(keep, pos_in_e, 0)  # (B,T)
        ye_flat = ye.reshape(B, E * cap, D)
        yk = _gather_slots(ye_flat, flat_slot.reshape(B, S, top_k))
        w = (gate_vals * keep.reshape(B, S, top_k)).astype(jnp.float32)
        y = jnp.einsum("bskd,bsk->bsd", yk.astype(jnp.float32), w)

    return y.astype(x.dtype), _aux_loss(expert_idx, frac_probs, E)


def _aux_loss(expert_idx: Array, frac_probs: Array, E: int) -> Array:
    """Switch-style load-balance loss from each token's first choice."""
    frac_tokens = jnp.mean(
        jax.nn.one_hot(expert_idx[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    return E * jnp.sum(frac_tokens * frac_probs)


#: the held experts' sorted buffer is a whole number of these rows
HELD_ROWS_ALIGN = 512


def held_rows(T: int, k: int, n: int, E: int) -> int:
    """Rows of the held experts' sorted buffer for ``T`` tokens that each
    choose ``k`` of the router's ``E`` experts, ``n`` of them held here.

    Twice the mean count of pairs routed here, ``T k n / E``, rounded up
    to :data:`HELD_ROWS_ALIGN`, and never more than the worst case
    ``T * min(k, n)`` (every token picking every held expert it can).
    """
    return min(_round_up(-(-2 * T * k * n // E), HELD_ROWS_ALIGN),
               T * min(k, n))


def _moe_held(p, x: Array, expert_idx: Array, gate_vals: Array,
              first_expert: int, token_mask: Array | None = None):
    """The held experts' part of the layer, dropless: ((B, S, D) float32,
    int32 fallback).

    Every (token, choice) pair routed to a held expert is computed.  The
    pairs are sorted by expert and run through grouped matrix products
    (``lax.ragged_dot``) over the held experts' weights.  A pair of a
    token outside ``token_mask`` sorts with the pairs routed to experts
    not held here, past the rest, and is never computed.

    The sorted buffer has :func:`held_rows` rows, a size fixed by the
    shape, whenever the routed pairs fit; rows past them are masked out.
    When they do not fit (``fallback`` 1), the same computation runs on
    the worst case, ``T * min(k, n)`` rows: a token picks each expert at
    most once, so every pair fits there and none is ever dropped.  The
    device picks the branch (``lax.cond``) from the count it routed.
    """
    B, S, D = x.shape
    k = expert_idx.shape[-1]
    n = p["w_gate"].shape[0]
    E = p["router"].shape[-1]
    T = B * S
    local = expert_idx.reshape(T * k) - first_expert
    here = (local >= 0) & (local < n)
    if token_mask is not None:
        here &= jnp.repeat(token_mask.reshape(T), k)
    key = jnp.where(here, local, n)                              # n = absent
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((n + 1,), jnp.int32).at[key].add(1)[:n]
    routed = sizes.sum()
    cd = COMPUTE_DTYPE
    xt = x.reshape(T, D).astype(cd)
    gates = gate_vals.reshape(T * k)

    def run(m: int) -> Array:
        # the held experts on the first m rows of the sorted pairs
        rows = order[:m]
        tok = rows // k
        valid = jnp.arange(m) < routed
        xs = xt[tok]
        g = jax.lax.ragged_dot(xs, p["w_gate"].astype(cd), sizes,
                               preferred_element_type=jnp.float32)
        u = jax.lax.ragged_dot(xs, p["w_up"].astype(cd), sizes,
                               preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(cd)
        ye = jax.lax.ragged_dot(h, p["w_down"].astype(cd), sizes,
                                preferred_element_type=jnp.float32)
        w = jnp.where(valid, gates[rows], 0.0)
        ye = jnp.where(valid[:, None], ye, 0.0) * w[:, None]
        return jnp.zeros((T, D), jnp.float32).at[tok].add(ye)

    worst = T * min(k, n)
    m = held_rows(T, k, n, E)
    if m == worst:
        return run(worst).reshape(B, S, D), jnp.zeros((), jnp.int32)
    fallback = routed > m
    y = jax.lax.cond(fallback, lambda: run(worst), lambda: run(m))
    return y.reshape(B, S, D), fallback.astype(jnp.int32)


def _gather_slots(src: Array, idx: Array) -> Array:
    """src (B, N, D), idx (B, ...) -> (B, ..., D) via per-row take."""
    B, N, D = src.shape
    flat = idx.reshape(B, -1)

    def row(s, i):
        return jnp.take(s, i, axis=0)
    out = jax.vmap(row)(src.astype(COMPUTE_DTYPE), flat)
    return out.reshape(*idx.shape, D)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(key: Array, vocab_padded: int, d_model: int):
    return {"table": jax.random.normal(
        key, (vocab_padded, d_model), PARAM_DTYPE) * 0.01}


def axes_embedding():
    return {"table": logical("vocab", "embed", name="embed.table")}


def embed(p, tokens: Array) -> Array:
    return jnp.take(p["table"], tokens, axis=0).astype(COMPUTE_DTYPE)


def init_unembed(key: Array, d_model: int, vocab_padded: int):
    return {"w": jax.random.normal(
        key, (d_model, vocab_padded), PARAM_DTYPE) * d_model ** -0.5}


def axes_unembed():
    return {"w": logical("embed", "vocab", name="unembed.w")}


def unembed(p, x: Array) -> Array:
    return jnp.einsum("bsd,dv->bsv", x.astype(COMPUTE_DTYPE),
                      p["w"].astype(COMPUTE_DTYPE))


def cross_entropy_loss(logits: Array, labels: Array, *,
                       vocab_real: int, z_loss: float = 1e-4):
    """Next-token CE with padded-vocab masking + z-loss.

    logits: (B, S, Vp) (bf16 ok); labels: (B, S) int32 (-1 = ignore).
    """
    Vp = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    if vocab_real < Vp:
        mask = jnp.arange(Vp) < vocab_real
        lf = jnp.where(mask, lf, -1e30)
    lse = jax.nn.logsumexp(lf, axis=-1)
    lab = jnp.clip(labels, 0, Vp - 1)
    picked = jnp.take_along_axis(lf, lab[..., None], -1)[..., 0]
    nll = lse - picked
    valid = (labels >= 0).astype(jnp.float32)
    nll = nll * valid
    z = (lse ** 2) * valid
    denom = jnp.maximum(valid.sum(), 1.0)
    return (nll.sum() + z_loss * z.sum()) / denom
