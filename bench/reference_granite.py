"""Plain reference of the granite cell's pooled features, written from
the published description of Granite 4.0-H (granitemoehybrid).

It imports nothing of the program.  Float32 throughout, matrix products
at ``jax.default_matmul_precision("highest")``, one prompt at a time, no
kernels and no cache.  For a prompt of ``S`` tokens:

* ``h = 12 * E[tokens]`` (``embedding_multiplier``; E is the tied table);
* each layer ``i`` (``layer_types[i]``)::

      h += 0.22 * mixer(RMSNorm(h))
      x  = RMSNorm(h)
      h += 0.22 * (MoE(x) + shared(x))

  - Mamba-2: in-projection to z, x, B, C, dt; depthwise causal conv of
    width 4 with bias on (x, B, C), SiLU; ``dt = softplus(dt + dt_bias)``,
    ``A = -exp(A_log)``; the SSM recurrence, token by token, per head:
    ``s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T``, ``y_t = s_t C_t +
    D x_t`` (one group: B and C shared by every head); ``y * silu(z)``,
    RMSNorm, out-projection;
  - attention: GQA (8 KV heads for 32 query heads), no positional
    embedding, causal, softmax scale ``attention_multiplier`` = 1/128;
  - MoE: router logits over all 72 experts, the top 10, softmax over
    those 10; the experts held here (the first ``num_local_experts``)
    add ``gate * SwiGLU_e(x)`` for the tokens routed to them; the shared
    expert is a SwiGLU of width 1536;
* ``logits[:, :16] = RMSNorm(h) @ E[:16]^T / 16`` (``logits_scaling``);
  the features are ``tanh(0.3 * mean over the prompt's tokens)``.

Departures from a whole model, each as the program has them too: ten
layers of the forty, the experts of one chip of eight, and only the 16
logit columns the features read.  Attention is computed in blocks of 512
queries so that a 4096-token prompt fits.  Each prompt is right-padded to
the next of 512, 1024, 2048, 4096 tokens, so that the jitted layers
compile at most four shapes: both mixers are causal, so the padding
cannot reach a real position, and the mean reads real positions only.

``act_dtype`` rounds the activations entering every matrix product to a
lower precision (float8 for the control); weights are used as given.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

FEATS = 16
PAD_LENGTHS = (512, 1024, 2048, 4096)
QUERY_BLOCK = 512


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


class Reference:
    """Layer functions of one configuration (the configuration file's
    dict), jitted per padded length."""

    def __init__(self, cfg: dict, act_dtype=None):
        self.cfg = cfg
        self.act_dtype = act_dtype
        self.eps = cfg["rms_norm_eps"]
        self.r = cfg["residual_multiplier"]
        self._mamba = jax.jit(self._mamba_layer)
        self._attention = jax.jit(self._attention_layer)

    # -- helpers -------------------------------------------------------

    def _act(self, x):
        if self.act_dtype is None:
            return x
        return x.astype(self.act_dtype).astype(jnp.float32)

    def _mm(self, eq, x, w):
        with jax.default_matmul_precision("highest"):
            return jnp.einsum(eq, self._act(x), w.astype(jnp.float32))

    def _swiglu(self, p, x):
        g = self._mm("sd,df->sf", x, p["w_gate"])
        u = self._mm("sd,df->sf", x, p["w_up"])
        return self._mm("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"])

    def _f32(self, tree):
        return jax.tree.map(lambda a: a.astype(jnp.float32), tree)

    # -- mixers --------------------------------------------------------

    def _mamba_mixer(self, p, h):
        c = self.cfg
        S, D = h.shape
        H, P, N = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
        G = c["mamba_n_groups"]
        di = c["mamba_expand"] * D
        z = self._mm("sd,di->si", h, p["w_z"])
        xbc = jnp.concatenate([self._mm("sd,di->si", h, p["w_x"]),
                               self._mm("sd,dg->sg", h, p["w_bc"])], -1)
        dt = jax.nn.softplus(self._mm("sd,dh->sh", h, p["w_dt"])
                             + p["dt_bias"])
        # causal depthwise conv: tap j multiplies the input j steps back
        K = p["conv_w"].shape[0]
        padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
        conv = sum(p["conv_w"][j] * padded[K - 1 - j:K - 1 - j + S]
                   for j in range(K)) + p["conv_b"]
        conv = jax.nn.silu(conv)
        x = conv[:, :di].reshape(S, H, P)
        B = conv[:, di:di + G * N].reshape(S, G, N)
        C = conv[:, di + G * N:].reshape(S, G, N)
        B = jnp.repeat(B, H // G, axis=1)                 # (S, H, N)
        C = jnp.repeat(C, H // G, axis=1)
        A = -jnp.exp(p["A_log"])

        def step(s, inputs):
            x_t, B_t, C_t, dt_t = inputs                  # (H,P) (H,N) (H)
            s = (jnp.exp(dt_t * A)[:, None, None] * s
                 + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
            return s, jnp.einsum("hpn,hn->hp", s, C_t,
                                 precision="highest")

        _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                            (x, B, C, dt))
        y = y + p["D_skip"][None, :, None] * x
        y = y.reshape(S, di) * jax.nn.silu(z)
        y = rms_norm(y, p["out_norm"]["scale"], self.eps)
        return self._mm("si,id->sd", y, p["w_out"])

    def _attention_mixer(self, p, h):
        c = self.cfg
        S = h.shape[0]
        q = self._mm("sd,dhk->shk", h, p["wq"])
        k = self._mm("sd,dhk->shk", h, p["wk"])
        v = self._mm("sd,dhk->shk", h, p["wv"])
        rep = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        out = []
        for lo in range(0, S, QUERY_BLOCK):
            qb = q[lo:lo + QUERY_BLOCK]
            s = self._mm("qhk,thk->hqt", qb, k) * c["attention_multiplier"]
            pos = lo + jnp.arange(qb.shape[0])
            s = jnp.where(pos[None, :, None] >= jnp.arange(S)[None, None, :],
                          s, -jnp.inf)
            out.append(self._mm("hqt,thk->qhk", jax.nn.softmax(s, -1), v))
        o = jnp.concatenate(out, 0)
        return self._mm("shk,hkd->sd", o, p["wo"])

    # -- feed-forward --------------------------------------------------

    def _moe(self, p, x):
        c = self.cfg
        k, n = c["num_experts_per_tok"], c["num_local_experts"]
        logits = self._mm("sd,de->se", x, p["router"])
        top, idx = jax.lax.top_k(logits, k)
        gates = jax.nn.softmax(top, -1)                   # (S, k)
        y = jnp.zeros_like(x)
        for e in range(n):                                # held: 0 .. n-1
            g = jnp.sum(jnp.where(idx == e, gates, 0.0), -1)
            ye = self._swiglu({w: p[w][e] for w in ("w_gate", "w_up",
                                                     "w_down")}, x)
            y = y + g[:, None] * ye
        return y

    def _ffn(self, lp, h):
        x = rms_norm(h, lp["ln2"]["scale"], self.eps)
        return h + self.r * (self._moe(lp["moe"], x)
                             + self._swiglu(lp["shared"], x))

    def _mamba_layer(self, lp, h):
        lp = self._f32(lp)
        h = h + self.r * self._mamba_mixer(
            lp["mixer"], rms_norm(h, lp["ln1"]["scale"], self.eps))
        return self._ffn(lp, h)

    def _attention_layer(self, lp, h):
        lp = self._f32(lp)
        h = h + self.r * self._attention_mixer(
            lp["mixer"], rms_norm(h, lp["ln1"]["scale"], self.eps))
        return self._ffn(lp, h)

    # -- the whole backbone --------------------------------------------

    def features(self, prompts, table, layer_weights, final_scale):
        """(n, 16) float32 features of ``prompts`` (1-D token arrays).

        ``layer_weights(i)`` returns layer i's weights; it is called once
        per layer, and each layer runs over every prompt before the next
        one's weights are made.
        """
        c = self.cfg
        table = jnp.asarray(table)
        hs, lengths = [], []
        for toks in prompts:
            S = len(toks)
            L = next(p for p in PAD_LENGTHS if S <= p)
            ids = np.zeros(L, np.int32)
            ids[:S] = toks
            hs.append(table[ids].astype(jnp.float32)
                      * c["embedding_multiplier"])
            lengths.append(S)
        for i, kind in enumerate(c["layer_types"]):
            lp = layer_weights(i)
            fn = self._mamba if kind == "mamba" else self._attention
            hs = [fn(lp, h) for h in hs]
            del lp
        cols = table[:FEATS].astype(jnp.float32)
        out = []
        for h, S in zip(hs, lengths):
            hn = rms_norm(h[:S], final_scale.astype(jnp.float32), self.eps)
            logits = self._mm("sd,vd->sv", hn, cols) / c["logits_scaling"]
            out.append(jnp.tanh(0.3 * logits.mean(0)))
        return np.asarray(jnp.stack(out), np.float32)


def feature_error(served: np.ndarray, ref: np.ndarray) -> float:
    """The largest distance of one prompt's served features from the
    reference's, over the root mean square of the reference's (the
    scale of a prompt's feature vector)."""
    scale = np.sqrt(np.mean(np.sum(np.square(ref), 1)))
    return float(np.max(np.linalg.norm(served - ref, axis=1)) / scale)
