"""Public op wrapper for the ``kda_chunk`` kernel: chunk padding, the
chunks' cumulative log decay and the beta-scaled operands."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import SUB, kda_chunk

#: tokens per chunk of the served kernel
CHUNK = 64


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
        beta: jax.Array, *, chunk: int = CHUNK, head_block: int = 4,
        interpret: bool | None = None) -> jax.Array:
    """The KDA recurrence from a zero state (``ref.kda_recurrent``) in the
    ``kda_chunk`` kernel.

    q, k: (B, S, H, dk), the query already scaled; v: (B, S, H, dv); g:
    (B, S, H, dk) log decay (<= 0); beta: (B, S, H).  Returns o (B, S,
    H, dv) bfloat16.  Any S: the tail of the last chunk is padded with
    zero keys, values and decays, which change no state and no output
    before them.  ``head_block`` heads share a grid step (fewer if H is
    not a multiple of it).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    C = min(chunk, _round_up(S, SUB))
    Sp = _round_up(S, C)
    pad = lambda a: jnp.pad(a, ((0, 0), (0, Sp - S)) + ((0, 0),)
                            * (a.ndim - 2))
    beta = beta.astype(jnp.float32)[..., None]
    bf = jnp.bfloat16
    flat = lambda a: pad(a).reshape(B, Sp, -1)
    G = jnp.cumsum(pad(g.astype(jnp.float32)).reshape(B, Sp // C, C, H, dk),
                   axis=2)
    hb = max(d for d in range(1, min(head_block, H) + 1) if H % d == 0)
    o = kda_chunk(flat(q.astype(bf)), flat(k.astype(bf)),
                  flat((k * beta).astype(bf)), flat((v * beta).astype(bf)),
                  G.reshape(B, Sp, H * dk), heads=H, chunk=C, head_block=hb,
                  interpret=interpret)
    return o.reshape(B, Sp, H, dv)[:, :S]


__all__ = ["CHUNK", "kda"]
