"""Mamba-2 (SSD, state-space duality) — attention-free LM backbone.

Implements the chunked SSD algorithm of Dao & Gu (arXiv:2405.21060) in a
matmul-dominant form that maps onto the TPU MXU:

* the sequence is split into chunks of ``cfg.ssm_chunk``;
* within a chunk, outputs are computed with dense matmuls
  (C B^T ⊙ decay-mask) X — the "quadratic branch";
* across chunks, a ``lax.scan`` carries the (heads, headdim, state) SSM
  state — the "linear branch".

Decode is the plain SSM recurrence: h = a·h + (dt·x)·B^T;  y = C·h + D·x,
with a depthwise conv ring buffer of width ``ssm_conv``.

TP sharding: heads over "model" (64 heads / 16 = 4 per shard); B/C (the
``ngroups=1`` group dims) are replicated — they are dstate-sized vectors
per token, three orders of magnitude smaller than the head channels.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..sharding.annotate import hint, hint_act
from ..sharding.partition import logical
from . import layers as L

Array = jax.Array


def _dims(cfg: ArchConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    nheads = d_inner // cfg.ssm_headdim
    return d_inner, nheads


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_block(key: Array, cfg: ArchConfig):
    d_inner, nheads = _dims(cfg)
    D, N, G = cfg.d_model, cfg.ssm_state, cfg.ssm_ngroups
    ks = jax.random.split(key, 6)
    std = D ** -0.5
    conv_ch = d_inner + 2 * G * N
    p = {
        "ln": L.init_rms_norm(D),
        # split in_proj so each segment gets its natural sharding
        "w_z": jax.random.normal(ks[0], (D, d_inner), L.PARAM_DTYPE) * std,
        "w_x": jax.random.normal(ks[1], (D, d_inner), L.PARAM_DTYPE) * std,
        "w_bc": jax.random.normal(ks[2], (D, 2 * G * N), L.PARAM_DTYPE) * std,
        "w_dt": jax.random.normal(ks[3], (D, nheads), L.PARAM_DTYPE) * std,
        "dt_bias": jnp.log(jnp.expm1(                      # softplus^-1 grid
            jnp.linspace(1e-3, 0.1, nheads, dtype=L.PARAM_DTYPE))),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nheads, dtype=L.PARAM_DTYPE)),
        "D_skip": jnp.ones((nheads,), L.PARAM_DTYPE),
        "conv_w": jax.random.normal(ks[4], (cfg.ssm_conv, conv_ch),
                                    L.PARAM_DTYPE) * (cfg.ssm_conv ** -0.5),
        "conv_b": jnp.zeros((conv_ch,), L.PARAM_DTYPE),
        "out_norm": L.init_rms_norm(d_inner),
        "w_out": jax.random.normal(ks[5], (d_inner, D), L.PARAM_DTYPE)
                 * d_inner ** -0.5,
    }
    return p


def _block_axes(cfg: ArchConfig):
    return {
        "ln": L.axes_rms_norm(),
        "w_z": logical("embed", "conv_dim", name="ssm.w_z"),
        "w_x": logical("embed", "conv_dim", name="ssm.w_x"),
        "w_bc": logical("embed", None, name="ssm.w_bc"),
        "w_dt": logical("embed", "ssm_heads", name="ssm.w_dt"),
        "dt_bias": logical("ssm_heads", name="ssm.dt_bias"),
        "A_log": logical("ssm_heads", name="ssm.A_log"),
        "D_skip": logical("ssm_heads", name="ssm.D_skip"),
        "conv_w": logical(None, "conv_dim", name="ssm.conv_w"),
        "conv_b": logical("conv_dim", name="ssm.conv_b"),
        "out_norm": {"scale": logical("conv_dim", name="ssm.out_norm")},
        "w_out": logical("conv_dim", "embed", name="ssm.w_out"),
    }


def init_params(key: Array, cfg: ArchConfig, tp: int = 16):
    ke, ku, kl = jax.random.split(key, 3)
    lkeys = jax.random.split(kl, cfg.num_layers)
    layers_p = jax.vmap(lambda k: _init_block(k, cfg))(lkeys)
    p = {
        "embed": L.init_embedding(ke, cfg.vocab_padded(tp), cfg.d_model),
        "layers": layers_p,
        "final_norm": L.init_rms_norm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = L.init_unembed(ku, cfg.d_model, cfg.vocab_padded(tp))
    return p


def param_axes(cfg: ArchConfig):
    from .transformer import _stack_axes
    a = {
        "embed": L.axes_embedding(),
        "layers": _stack_axes(_block_axes(cfg)),
        "final_norm": L.axes_rms_norm(),
    }
    if not cfg.tie_embeddings:
        a["unembed"] = L.axes_unembed()
    return a


# ---------------------------------------------------------------------------
# chunked SSD forward
# ---------------------------------------------------------------------------

def _segsum(a: Array) -> Array:
    """a: (..., Q) log-decays -> (..., Q, Q) lower-tri cumulative sums:
    out[..., i, j] = sum_{k=j+1..i} a[k]  (i >= j), -inf above diagonal."""
    Q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]           # sum_(j+1..i)
    mask = jnp.tril(jnp.ones((Q, Q), bool), 0)
    return jnp.where(mask, diff, -jnp.inf)


def _ssd_chunked(x: Array, dt: Array, A: Array, B: Array, C: Array,
                 chunk: int, h0: Array | None = None):
    """Chunked SSD scan.

    x:  (Bt, S, H, P)   — value channels per head
    dt: (Bt, S, H)      — positive step sizes (softplus already applied)
    A:  (H,)            — positive decay rates (a_t = exp(-dt*A))
    B:  (Bt, S, G, N)   — input projections (G groups broadcast over H)
    C:  (Bt, S, G, N)   — output projections
    h0: optional initial state (Bt, H, P, N)
    Returns (y (Bt,S,H,P), h_last (Bt,H,P,N)).
    """
    Bt, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    if S % chunk:                       # pad: dt=0 => a=1, no contribution
        padn = chunk - S % chunk
        x = jnp.pad(x, ((0, 0), (0, padn), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, padn), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, padn), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, padn), (0, 0), (0, 0)))
        y, h_last = _ssd_chunked(x, dt, A, B, C, chunk, h0=h0)
        return y[:, :S], h_last
    nc = S // chunk
    rep = H // G

    def to_chunks(t):
        return t.reshape(Bt, nc, chunk, *t.shape[2:])

    xc, dtc = to_chunks(x), to_chunks(dt)
    Bc, Cc = to_chunks(B), to_chunks(C)
    # broadcast groups over heads
    Bh = jnp.repeat(Bc, rep, axis=3)                      # (Bt,nc,Q,H,N)
    Ch = jnp.repeat(Cc, rep, axis=3)

    la = (-dtc * A[None, None, None, :]).astype(jnp.float32)  # log decay (Bt,nc,Q,H)
    seg = _segsum(la.transpose(0, 1, 3, 2))               # (Bt,nc,H,Q,Q)
    decay_mask = jnp.exp(seg)

    cd = L.COMPUTE_DTYPE
    # intra-chunk (quadratic branch): Y = ((C B^T) ⊙ M) (dt·X)
    scores = jnp.einsum("bcqhn,bckhn->bchqk", Ch.astype(cd), Bh.astype(cd),
                        preferred_element_type=jnp.float32)
    scores = scores * decay_mask
    xdt = xc * dtc[..., None]
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", scores.astype(cd),
                         xdt.astype(cd), preferred_element_type=jnp.float32)

    # chunk summaries: state contribution of each chunk
    la_cum = jnp.cumsum(la, axis=2)                       # (Bt,nc,Q,H)
    la_tot = la_cum[:, :, -1]                             # (Bt,nc,H)
    # decay from position q to end of its chunk
    decay_to_end = jnp.exp(la_tot[:, :, None] - la_cum)   # (Bt,nc,Q,H)
    states = jnp.einsum("bcqhn,bcqhp->bchpn",
                        (Bh * (dtc * decay_to_end)[..., None]).astype(cd),
                        xc.astype(cd), preferred_element_type=jnp.float32)

    # inter-chunk scan over chunk states
    def scan_fn(h, xs):
        st, lt = xs                                       # (Bt,H,P,N), (Bt,H)
        h_new = h * jnp.exp(lt)[:, :, None, None] + st
        return h_new, h                                   # emit state *before* chunk

    h_init = (jnp.zeros((Bt, H, P, N), jnp.float32) if h0 is None
              else h0.astype(jnp.float32))
    h_last, h_prevs = jax.lax.scan(
        scan_fn, h_init,
        (states.transpose(1, 0, 2, 3, 4), la_tot.transpose(1, 0, 2)))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)            # (Bt,nc,H,P,N)

    # inter-chunk (linear branch): y += C · decayed incoming state
    decay_in = jnp.exp(la_cum)                            # decay 0..q
    y_inter = jnp.einsum("bcqhn,bchpn->bcqhp", Ch.astype(cd),
                         h_prevs.astype(cd),
                         preferred_element_type=jnp.float32)
    y_inter = y_inter * decay_in[..., None]

    y = (y_intra + y_inter).reshape(Bt, S, H, P)
    return y.astype(cd), h_last


def _causal_conv(xbc: Array, w: Array, b: Array | None) -> Array:
    """Depthwise causal conv along S.  xbc (Bt,S,C), w (K,C), b (C,) or
    None (no bias)."""
    K = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(xbc, dtype=jnp.float32)
    for i in range(K):                                    # K is 4: unrolled
        out = out + pad[:, i:i + xbc.shape[1]].astype(jnp.float32) \
            * w[K - 1 - i].astype(jnp.float32)
    if b is not None:
        out = out + b.astype(jnp.float32)
    return out.astype(xbc.dtype)


def _block_apply(lp, cfg: ArchConfig, x: Array, *, state=None,
                 conv_state=None):
    """Full-sequence SSD block: ``x + mixer(RMSNorm(x))``.  state: optional
    initial SSM carry."""
    h = L.rms_norm(x, lp["ln"]["scale"], cfg.norm_eps)
    out, h_last = mixer(lp, cfg, h, state=state)
    return hint_act(x + out), h_last


def mixer(lp, cfg: ArchConfig, h: Array, *, state=None):
    """The Mamba-2 mixer on normalised input ``h`` (B, S, D): in-projection,
    causal conv, chunked SSD, gated RMSNorm, out-projection.  Returns
    (out (B, S, D), last SSM state).  ``lp`` needs no ``ln``."""
    d_inner, nheads = _dims(cfg)
    G, N, P = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_headdim
    cd = L.COMPUTE_DTYPE
    z = hint(jnp.einsum("bsd,di->bsi", h.astype(cd), lp["w_z"].astype(cd)),
             "dp", None, "model")
    xin = hint(jnp.einsum("bsd,di->bsi", h.astype(cd), lp["w_x"].astype(cd)),
               "dp", None, "model")
    bc = hint(jnp.einsum("bsd,dg->bsg", h.astype(cd), lp["w_bc"].astype(cd)),
              "dp", None, None)
    dt_raw = hint(jnp.einsum("bsd,dh->bsh", h.astype(cd),
                             lp["w_dt"].astype(cd)), "dp", None, "model")

    conv_in = jnp.concatenate([xin, bc], axis=-1)         # (B,S,conv_ch)
    conv_out = _causal_conv(conv_in, lp["conv_w"], lp["conv_b"])
    conv_out = jax.nn.silu(conv_out.astype(jnp.float32)).astype(cd)
    xin = conv_out[..., :d_inner]
    B_ = conv_out[..., d_inner:d_inner + G * N]
    C_ = conv_out[..., d_inner + G * N:]

    Bt, S = h.shape[0], h.shape[1]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + lp["dt_bias"].astype(jnp.float32))
    A = jnp.exp(lp["A_log"].astype(jnp.float32))
    from ..sharding.annotate import hint_heads
    xh = hint_heads(xin.reshape(Bt, S, nheads, P))
    Bh = B_.reshape(Bt, S, G, N)
    Ch = C_.reshape(Bt, S, G, N)
    y, h_last = _ssd_chunked(xh, dt, A, Bh, Ch, min(cfg.ssm_chunk, S),
                             h0=state)
    y = y + xh.astype(jnp.float32).astype(cd) \
        * lp["D_skip"].astype(cd)[None, None, :, None]
    y = y.reshape(Bt, S, d_inner)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(cd)  # gated
    y = L.rms_norm(y, lp["out_norm"]["scale"], cfg.norm_eps)
    out = jnp.einsum("bsi,id->bsd", y, lp["w_out"].astype(cd))
    return out, h_last


def forward(params, cfg: ArchConfig, batch, *, tp: int = 16,
            collect_state: bool = False):
    x = hint_act(L.embed(params["embed"], batch["tokens"]))

    def body(carry, lp):
        h, = carry
        h2, st = _block_apply(lp, cfg, h)
        return (h2,), st if collect_state else None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (x,), states = jax.lax.scan(body_fn, (x,), params["layers"])
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x.astype(L.COMPUTE_DTYPE),
                            params["embed"]["table"].astype(L.COMPUTE_DTYPE))
    else:
        logits = L.unembed(params["unembed"], x)
    return logits, states


def loss_fn(params, cfg: ArchConfig, batch, *, tp: int = 16) -> Array:
    logits, _ = forward(params, cfg, batch, tp=tp)
    return L.cross_entropy_loss(logits[:, :-1], batch["labels"][:, 1:],
                                vocab_real=cfg.vocab_size)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch_size: int, cache_len: int,
               tp: int = 16):
    """SSM 'cache' = per-layer state + conv ring buffer (+pos).  cache_len
    is irrelevant (O(1) state) — that is the whole point for long_500k."""
    d_inner, nheads = _dims(cfg)
    G, N, P = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_headdim
    conv_ch = d_inner + 2 * G * N
    Lc = cfg.num_layers
    return {
        "ssm": jnp.zeros((Lc, batch_size, nheads, P, N), jnp.float32),
        "conv": jnp.zeros((Lc, batch_size, cfg.ssm_conv - 1, conv_ch),
                          L.COMPUTE_DTYPE),
        "pos": jnp.zeros((), jnp.int32),
    }


def cache_axes(cfg: ArchConfig, *, seq_shard: bool = False):
    return {
        "ssm": logical("layers", "batch", "ssm_heads", None, None,
                       name="cache.ssm"),
        "conv": logical("layers", "batch", None, "conv_dim",
                        name="cache.conv"),
        "pos": logical(name="cache.pos"),
    }


def prefill(params, cfg: ArchConfig, batch, *, tp: int = 16,
            cache_len: int | None = None):
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = L.embed(params["embed"], tokens)
    d_inner, nheads = _dims(cfg)
    G, N = cfg.ssm_ngroups, cfg.ssm_state
    conv_ch = d_inner + 2 * G * N

    def body(h, lp):
        # recompute conv tail for the cache: cheap closed form — the last
        # (K-1) conv inputs of this layer
        hn = L.rms_norm(h, lp["ln"]["scale"], cfg.norm_eps)
        cd = L.COMPUTE_DTYPE
        xin = jnp.einsum("bsd,di->bsi", hn.astype(cd), lp["w_x"].astype(cd))
        bc = jnp.einsum("bsd,dg->bsg", hn.astype(cd), lp["w_bc"].astype(cd))
        conv_tail = jnp.concatenate([xin, bc], -1)[:, -(cfg.ssm_conv - 1):]
        h2, st = _block_apply(lp, cfg, h)
        return h2, (st, conv_tail)

    x, (states, conv_tails) = jax.lax.scan(body, x, params["layers"])
    x = L.rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x.astype(L.COMPUTE_DTYPE),
                            params["embed"]["table"].astype(L.COMPUTE_DTYPE))
    else:
        logits = L.unembed(params["unembed"], x)
    cache = {"ssm": states, "conv": conv_tails,
             "pos": jnp.asarray(S, jnp.int32)}
    return logits[:, -1], cache


def decode_step(params, cfg: ArchConfig, cache, tokens: Array, *,
                tp: int = 16):
    """Single-token SSM recurrence."""
    d_inner, nheads = _dims(cfg)
    G, N, P = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_headdim
    cd = L.COMPUTE_DTYPE
    x = L.embed(params["embed"], tokens)                  # (B,1,D)

    def body(h, lc):
        lp, ssm, conv = lc                                # ssm (B,H,P,N)
        hn = L.rms_norm(h, lp["ln"]["scale"], cfg.norm_eps)
        z = jnp.einsum("bsd,di->bsi", hn.astype(cd), lp["w_z"].astype(cd))
        xin = jnp.einsum("bsd,di->bsi", hn.astype(cd), lp["w_x"].astype(cd))
        bc = jnp.einsum("bsd,dg->bsg", hn.astype(cd), lp["w_bc"].astype(cd))
        dt_raw = jnp.einsum("bsd,dh->bsh", hn.astype(cd), lp["w_dt"].astype(cd))
        cin = jnp.concatenate([xin, bc], -1)[:, 0]        # (B,C)
        # conv ring: full window = [conv_state, cin]; win[:, -1] is the
        # current token, which _causal_conv pairs with w[0] (w is stored
        # newest-first: tap j multiplies x_{t-j})
        win = jnp.concatenate([conv, cin[:, None]], axis=1)  # (B,K,C)
        w = lp["conv_w"].astype(jnp.float32)[::-1]        # oldest-first
        cout = (win.astype(jnp.float32) * w[None]).sum(1) \
            + lp["conv_b"].astype(jnp.float32)
        cout = jax.nn.silu(cout).astype(cd)
        xs = cout[:, :d_inner].reshape(-1, nheads, P)
        Bv = cout[:, d_inner:d_inner + G * N].reshape(-1, G, N)
        Cv = cout[:, d_inner + G * N:].reshape(-1, G, N)
        rep = nheads // G
        Bh = jnp.repeat(Bv, rep, 1)                       # (B,H,N)
        Ch = jnp.repeat(Cv, rep, 1)
        dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32)
                             + lp["dt_bias"].astype(jnp.float32))  # (B,H)
        A = jnp.exp(lp["A_log"].astype(jnp.float32))
        a = jnp.exp(-dt * A[None])                        # (B,H)
        upd = jnp.einsum("bhn,bhp->bhpn", Bh.astype(jnp.float32),
                         (xs.astype(jnp.float32) * dt[..., None]))
        ssm_new = ssm * a[..., None, None] + upd
        y = jnp.einsum("bhn,bhpn->bhp", Ch.astype(jnp.float32), ssm_new)
        y = y + xs.astype(jnp.float32) * lp["D_skip"].astype(jnp.float32)[None, :, None]
        y = y.reshape(-1, 1, d_inner).astype(cd)
        y = y * jax.nn.silu(z.astype(jnp.float32)).astype(cd)
        y = L.rms_norm(y, lp["out_norm"]["scale"], cfg.norm_eps)
        out = jnp.einsum("bsi,id->bsd", y, lp["w_out"].astype(cd))
        return h + out, (ssm_new, win[:, 1:])

    h, (ssm_s, conv_s) = jax.lax.scan(
        body, x, (params["layers"], cache["ssm"], cache["conv"]))
    h = L.rms_norm(h, params["final_norm"]["scale"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h.astype(cd),
                            params["embed"]["table"].astype(cd))
    else:
        logits = L.unembed(params["unembed"], h)
    new_cache = {"ssm": ssm_s, "conv": conv_s, "pos": cache["pos"] + 1}
    return logits[:, 0], new_cache
