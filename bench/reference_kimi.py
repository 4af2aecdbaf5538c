"""Plain reference of the Kimi Linear cell's pooled features, written from
the published description of Kimi Linear (arXiv:2510.26692 and the
model's config.json).

It imports nothing of the program.  Float32 throughout, matrix products
at ``jax.default_matmul_precision("highest")``, one prompt at a time, one
layer at a time, no kernels, no chunks and no cache.  For a prompt of
``S`` tokens:

* ``h = E[tokens]`` (the input embedding, untied, no multiplier);
* each layer ``i`` (1-indexed ``i+1`` in ``kda_layers`` or
  ``full_attn_layers``)::

      h += mixer(RMSNorm(h))
      h += ffn(RMSNorm(h))

  - KDA, per head of 128: ``q, k, v = SiLU(conv4(W x))`` (depthwise,
    causal, no bias), q and k L2-normalised, q times ``128^-1/2``; log
    decay per key channel ``g = -exp(A_log[h]) softplus(W_f_up W_f_down
    x + dt_bias)``; ``beta = sigmoid(W_b x)``; the recurrence token by
    token from a zero float32 state::

        S' = Diag(exp(g_t)) S_{t-1};   u = beta_t (v_t - S'^T k_t)
        S_t = S' + k_t u^T;            o_t = S_t^T q_t

    then ``W_o (RMSNorm_head(o) * sigmoid(W_g_up W_g_down x + b_g))``;
  - MLA without positional embedding: ``q = W_q x`` (32 x (128 + 64)),
    ``[c, k_pe] = W_kva x``, ``c = RMSNorm(c)``, ``[k_nope, v] = W_kvb c``,
    ``k = [k_nope, k_pe]`` (``k_pe`` shared by every head), causal softmax
    at scale ``192^-1/2``, ``W_o``;
  - feed-forward: layer 1 a dense SwiGLU of width 9216; the others
    ``s = sigmoid(x W_r)`` over all 256 experts, the top 8 of ``s +
    e_score_correction_bias``, weights ``2.446 s / sum(s)`` over the 8;
    the experts held here (the first ``num_experts``) add ``w *
    SwiGLU_e(x)`` for the tokens routed to them; plus the shared SwiGLU
    expert of width 1024;
* ``logits[:, :16] = RMSNorm(h) @ lm_head[:, :16]``; the features are
  ``tanh(0.3 * mean over the prompt's tokens)``.

Departures from a whole model, each as the program has them too: eight
layers of the 27, the experts of one chip of eight, only the 16 logit
columns the features read, random weights, and the ranks and
parameterisations the configuration file lists under ``assumed`` (the
gates' rank 128, no conv bias).  Attention is computed in blocks of 512
queries so that a 4096-token prompt fits.  Each prompt is right-padded to
the next of 512, 1024, 2048, 4096 tokens, so that the jitted layers
compile at most four shapes: every mixer is causal, so the padding
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


def l2_norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


class Reference:
    """Layer functions of one configuration (the configuration file's
    dict), jitted per padded length."""

    def __init__(self, cfg: dict, act_dtype=None):
        self.cfg = cfg
        self.act_dtype = act_dtype
        self.eps = cfg["rms_norm_eps"]
        self._layer = jax.jit(self._layer_fn,
                              static_argnames=("kind", "dense"))

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

    # -- mixers --------------------------------------------------------

    def _kda_mixer(self, p, x):
        la = self.cfg["linear_attn_config"]
        H, d = la["num_heads"], la["head_dim"]
        S = x.shape[0]

        def conv_silu(w, taps):
            y = self._mm("sd,dc->sc", x, w)
            K = taps.shape[0]
            padded = jnp.pad(y, ((K - 1, 0), (0, 0)))
            # causal depthwise conv: tap j multiplies the input j back
            y = sum(taps[j] * padded[K - 1 - j:K - 1 - j + S]
                    for j in range(K))
            return jax.nn.silu(y).reshape(S, H, d)

        q = l2_norm(conv_silu(p["wq"], p["conv_q"])) * d ** -0.5
        k = l2_norm(conv_silu(p["wk"], p["conv_k"]))
        v = conv_silu(p["wv"], p["conv_v"])
        f = self._mm("sr,rc->sc", self._mm("sd,dr->sr", x, p["w_f_down"]),
                     p["w_f_up"]) + p["dt_bias"]
        g = -jnp.exp(p["A_log"])[None, :, None] \
            * jax.nn.softplus(f.reshape(S, H, d))
        beta = jax.nn.sigmoid(self._mm("sd,dh->sh", x, p["w_b"]))

        def step(s, inputs):
            q_t, k_t, v_t, g_t, b_t = inputs          # (H,d) ... (H,)
            s = jnp.exp(g_t)[:, :, None] * s
            kv = jnp.einsum("hkv,hk->hv", s, k_t, precision="highest")
            u = b_t[:, None] * (v_t - kv)
            s = s + k_t[:, :, None] * u[:, None, :]
            return s, jnp.einsum("hkv,hk->hv", s, q_t, precision="highest")

        _, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32),
                            (q, k, v, g, beta))
        o = rms_norm(o, p["o_norm"]["scale"], self.eps).reshape(S, H * d)
        gate = self._mm("sr,rc->sc", self._mm("sd,dr->sr", x, p["w_g_down"]),
                        p["w_g_up"]) + p["b_g"]
        return self._mm("sc,cd->sd", o * jax.nn.sigmoid(gate), p["wo"])

    def _mla_mixer(self, p, x):
        c = self.cfg
        S = x.shape[0]
        R, dn = c["kv_lora_rank"], c["qk_nope_head_dim"]
        scale = (dn + c["qk_rope_head_dim"]) ** -0.5
        q = self._mm("sd,dhk->shk", x, p["wq"])
        ckv = self._mm("sd,dc->sc", x, p["w_kva"])
        lat = rms_norm(ckv[:, :R], p["kv_norm"]["scale"], self.eps)
        kv = self._mm("sc,chk->shk", lat, p["w_kvb"])
        H = kv.shape[1]
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            ckv[:, None, R:], (S, H, ckv.shape[1] - R))], -1)
        v = kv[..., dn:]
        out = []
        for lo in range(0, S, QUERY_BLOCK):
            qb = q[lo:lo + QUERY_BLOCK]
            s = self._mm("qhk,thk->hqt", qb, k) * scale
            pos = lo + jnp.arange(qb.shape[0])
            s = jnp.where(pos[None, :, None] >= jnp.arange(S)[None, None, :],
                          s, -jnp.inf)
            out.append(self._mm("hqt,thk->qhk", jax.nn.softmax(s, -1), v))
        o = jnp.concatenate(out, 0)
        return self._mm("shk,hkd->sd", o, p["wo"])

    # -- feed-forward --------------------------------------------------

    def _moe(self, p, x):
        c = self.cfg
        k, n = c["num_experts_per_token"], c["num_experts"]
        s = jax.nn.sigmoid(self._mm("sd,de->se", x, p["router"]))
        _, idx = jax.lax.top_k(s + p["score_bias"], k)
        chosen = jnp.take_along_axis(s, idx, -1)              # (S, k)
        w = c["routed_scaling_factor"] * chosen / chosen.sum(-1, keepdims=True)
        y = jnp.zeros_like(x)
        for e in range(n):                                    # held: 0 .. n-1
            we = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
            ye = self._swiglu({m: p[m][e] for m in ("w_gate", "w_up",
                                                     "w_down")}, x)
            y = y + we[:, None] * ye
        return y

    def _layer_fn(self, lp, h, *, kind: str, dense: bool):
        lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        x = rms_norm(h, lp["ln1"]["scale"], self.eps)
        mixer = self._kda_mixer if kind == "kda" else self._mla_mixer
        h = h + mixer(lp["mixer"], x)
        x = rms_norm(h, lp["ln2"]["scale"], self.eps)
        if dense:
            return h + self._swiglu(lp["mlp"], x)
        return h + self._moe(lp["moe"], x) + self._swiglu(lp["shared"], x)

    # -- the whole backbone --------------------------------------------

    def features(self, prompts, table, layer_weights, final_scale, lm_head):
        """(n, 16) float32 features of ``prompts`` (1-D token arrays).

        ``layer_weights(i)`` returns layer i's weights; it is called once
        per layer, and each layer runs over every prompt before the next
        one's weights are made.  ``lm_head`` is (hidden, >= 16).
        """
        c = self.cfg
        la = c["linear_attn_config"]
        table = jnp.asarray(table)
        hs, lengths = [], []
        for toks in prompts:
            S = len(toks)
            L = next(p for p in PAD_LENGTHS if S <= p)
            ids = np.zeros(L, np.int32)
            ids[:S] = toks
            hs.append(table[ids].astype(jnp.float32))
            lengths.append(S)
        for i in range(c["num_hidden_layers"]):
            kind = "kda" if i + 1 in la["kda_layers"] else "mla"
            dense = i < c["first_k_dense_replace"]
            lp = layer_weights(i)
            hs = [self._layer(lp, h, kind=kind, dense=dense) for h in hs]
            del lp
        cols = jnp.asarray(lm_head)[:, :FEATS].astype(jnp.float32)
        out = []
        for h, S in zip(hs, lengths):
            hn = rms_norm(h[:S], final_scale.astype(jnp.float32), self.eps)
            logits = self._mm("sd,dv->sv", hn, cols)
            out.append(jnp.tanh(0.3 * logits.mean(0)))
        return np.asarray(jnp.stack(out), np.float32)


def feature_error(served: np.ndarray, ref: np.ndarray) -> float:
    """The largest distance of one prompt's served features from the
    reference's, over the root mean square of the reference's (the
    scale of a prompt's feature vector)."""
    scale = np.sqrt(np.mean(np.sum(np.square(ref), 1)))
    return float(np.max(np.linalg.norm(served - ref, axis=1)) / scale)
