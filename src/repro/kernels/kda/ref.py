"""Pure-jnp oracle for the KDA kernel: the gated delta rule with a decay
per key channel, token by token."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def kda_recurrent(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                  beta: jax.Array) -> jax.Array:
    """q, k, g: (B, S, H, dk); v: (B, S, H, dv); beta: (B, S, H).
    Returns o (B, S, H, dv) float32.  Per head, from a zero state::

        S' = Diag(exp(g_t)) S_{t-1}
        S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
        o_t = S_t^T q_t
    """
    f32 = jnp.float32
    B, _, H, dk = q.shape
    dv = v.shape[-1]

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs                 # (B,H,dk) ... (B,H)
        s = jnp.exp(g_t)[..., None] * s
        kv = jnp.einsum("bhkv,bhk->bhv", s, k_t, precision="highest")
        u = b_t[..., None] * (v_t - kv)
        s = s + k_t[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision="highest")

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0)
               for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, dv), f32), xs)
    return jnp.moveaxis(o, 0, 1)
