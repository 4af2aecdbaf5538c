"""kimi-linear-48b-a3b-instruct (family ``kda_mla_moe``) at a tiny size on
the CPU: the ``kda_chunk`` kernel (interpret mode) against the token-by-
token recurrence, MLA's value width, the sigmoid router, the expert
layer's shares, each layer kind and the whole backbone against the plain
float32 reference on seeded weights, and the DWN head's classify path
through the continuous scheduler."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import granite_weights, kimi_weights  # noqa: E402
from bench.reference_kimi import Reference, feature_error, rms_norm  # noqa: E402,E501
from repro.configs import get_arch  # noqa: E402
from repro.kernels.kda import ops as kda_ops  # noqa: E402
from repro.kernels.kda.ref import kda_recurrent  # noqa: E402
from repro.models import api, kimi_linear  # noqa: E402
from repro.models import layers as L  # noqa: E402

#: the tests' tiny size: d_model 64, four layers [kda+dense, kda, mla,
#: kda], 8 experts (top-2), heads of 16
ARCH = dataclasses.replace(
    get_arch("kimi-linear-48b-a3b-instruct").reduced(), num_layers=4,
    layer_types=("kda", "kda", "mla", "kda"))
#: the reference's configuration keys at ARCH's widths, 4 of 8 experts
#: held
CFG = {"hidden_size": 64, "intermediate_size": 96,
       "moe_intermediate_size": 32, "num_shared_experts": 1,
       "num_attention_heads": 4, "kv_lora_rank": 16,
       "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
       "num_experts_per_token": 2, "num_experts": 4,
       "routed_scaling_factor": 2.446, "first_k_dense_replace": 1,
       "vocab_size": 251, "rms_norm_eps": 1e-5, "num_hidden_layers": 4,
       "kda_gate_rank": 8,
       "linear_attn_config": {"kda_layers": [1, 2, 4],
                              "full_attn_layers": [3], "head_dim": 16,
                              "num_heads": 4, "short_conv_kernel_size": 4},
       "published": {"num_experts": 8}, "head": {"classes": 5}}
SEED = 11


def _close(got, want, tol):
    """Relative distance of ``got`` (program, bf16) from ``want``."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.linalg.norm(got - want) / np.linalg.norm(want) < tol


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _normed_input(S=24, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, S, 64))
    return rms_norm(x, 1.0, 1e-5)


def _kda_operands(B, S, H, d, seed=0, a_max=16.0, shift=-2.0):
    """Normalised keys and queries, values, decays ``-A softplus(z +
    shift)`` with A up to ``a_max``, and betas; q, k, v rounded to bf16
    as the kernel takes them."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    q = jax.random.normal(ks[0], (B, S, H, d))
    k = jax.random.normal(ks[1], (B, S, H, d))
    q = bf(q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5)
    k = bf(k / jnp.linalg.norm(k, axis=-1, keepdims=True))
    v = bf(jax.random.normal(ks[2], (B, S, H, d)))
    A = jnp.exp(jax.random.uniform(ks[3], (H,), minval=0.0,
                                   maxval=np.log(a_max)))
    g = -A[:, None] * jax.nn.softplus(
        jax.random.normal(ks[4], (B, S, H, d)) + shift)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, S, H)))
    return q, k, v, g, beta


# The kernel rounds the operands of the products with the state, and
# its output, to bf16 (2^-8 relative): it reads 0.002-0.003 here.
KERNEL_TOL = 1e-2


@pytest.mark.parametrize("S,a_max,shift,chunk", [
    (24, 16.0, -2.0, 64),       # one short chunk
    (100, 16.0, -2.0, 32),      # not a multiple of the chunk
    (130, 1.01, -4.0, 64),      # weak decays: the state carries across
    (64, 16.0, 5.0, 16),        # A = 16, ~-85 a token: e^-1000 a chunk
])
def test_kda_chunk_matches_the_recurrence(S, a_max, shift, chunk):
    ops = _kda_operands(2, S, 2, 16, seed=S, a_max=a_max, shift=shift)
    got = np.asarray(kda_ops.kda(*ops, chunk=chunk, head_block=2,
                                 interpret=True), np.float32)
    want = np.asarray(kda_recurrent(*ops))
    assert np.isfinite(got).all()
    if shift > 0:
        G = np.cumsum(np.asarray(ops[3])[:, :chunk], axis=1)
        assert G.min() < -1000
    assert _close(got, want, KERNEL_TOL)


def test_kda_chunk_right_padding_reaches_no_real_position():
    """Two rows with the same 40 real tokens and different padding after
    them give bit-equal outputs at the real positions."""
    q, k, v, g, beta = _kda_operands(2, 64, 2, 16, seed=7)
    real = jnp.arange(64) < 40
    same = lambda a: jnp.where(real.reshape((1, 64) + (1,) * (a.ndim - 2)),
                               a[:1], a)
    q, k, v, g, beta = (same(a) for a in (q, k, v, g, beta))
    o = np.asarray(kda_ops.kda(q, k, v, g, beta, interpret=True),
                   np.float32)
    np.testing.assert_array_equal(o[0, :40], o[1, :40])
    assert (o[0, 40:] != o[1, 40:]).any()


def test_attention_takes_a_value_width_of_its_own():
    """MLA's shape: queries and keys of 24, values of 16, against a plain
    causal softmax."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (2, 40, 4, 24))
    k = jax.random.normal(ks[1], (2, 40, 4, 24))
    v = jax.random.normal(ks[2], (2, 40, 4, 16))
    layout = L.make_head_layout(4, 4, 1)
    got = L.attention_chunked(q, k, v, layout, causal=True, kv_chunk=16)
    assert got.shape == (2, 40, 4, 16)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 24 ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((40, 40), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    assert _close(got, want, 1e-2)


def test_sigmoid_router_selects_by_the_biased_score_and_weighs_by_the_score():
    """One token, four experts, top 2: the bias picks experts 2 and 3
    although 0 and 1 score higher; their weights are their own sigmoid
    scores renormalised, times the scaling factor."""
    D = 8
    x = jnp.ones((1, 1, D)) / np.sqrt(D)
    logits = np.array([2.0, 1.0, -1.0, -2.0], np.float32)
    router = jnp.asarray(np.outer(np.ones(D) / np.sqrt(D), logits),
                         jnp.float32)
    eye = jnp.eye(D)
    p = {"router": router, "score_bias": jnp.array([0.0, 0.0, 1.0, 1.0]),
         # expert e returns (e + 1) x, through a SwiGLU of width D
         "w_gate": jnp.stack([eye * 50.0] * 4),
         "w_up": jnp.stack([eye * (e + 1) for e in range(4)]),
         "w_down": jnp.stack([eye] * 4)}
    y, _, _ = L.moe_apply(p, x, top_k=2, first_expert=0, router="sigmoid",
                          routed_scaling=2.446)
    s = 1 / (1 + np.exp(-logits))
    w = 2.446 * s[2:] / s[2:].sum()
    h = np.asarray(x[0, 0]) * np.asarray(jax.nn.silu(x[0, 0] * 50.0))
    want = (w[0] * 3 + w[1] * 4) * h
    np.testing.assert_allclose(np.asarray(y[0, 0], np.float32), want,
                               rtol=2e-2)
    ref = Reference(dict(CFG, num_experts_per_token=2, num_experts=4))
    np.testing.assert_allclose(np.asarray(ref._moe(_f32(p), x[0]))[0], want,
                               rtol=1e-5)


@pytest.mark.parametrize("held", [1, 2, 4])
def test_sigmoid_expert_shares_sum_to_the_whole_layer(held):
    """Every chip's part of the sigmoid-routed expert layer, with the
    shared expert counted once, adds up to the uncut layer and to the
    reference's."""
    p = L.init_moe(jax.random.PRNGKey(0), 32, 24, 8)
    p["score_bias"] = jax.random.uniform(jax.random.PRNGKey(4), (8,),
                                         minval=-0.05, maxval=0.05)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    shared = L.init_swiglu(jax.random.PRNGKey(5), 32, 40)
    moe = jax.jit(L.moe_apply, static_argnames=("top_k", "router",
                                                "routed_scaling"))
    kw = dict(top_k=3, router="sigmoid", routed_scaling=2.446)
    whole, _, _ = moe(p, x, first_expert=0, **kw)
    part = lambda e0: dict(p, **{w: p[w][e0:e0 + held]
                                 for w in ("w_gate", "w_up", "w_down")})
    parts = sum(moe(part(e0), x, first_expert=e0, **kw)[0]
                .astype(jnp.float32) for e0 in range(0, 8, held))
    s = L.swiglu(shared, x).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(parts + s),
                               np.asarray(whole.astype(jnp.float32) + s),
                               rtol=2e-2, atol=2e-2)
    ref = Reference(dict(CFG, num_experts_per_token=3, num_experts=8))
    want = jax.jit(ref._moe)(_f32(p), x.reshape(-1, 32)).reshape(x.shape)
    assert _close(whole, want, LAYER_TOL)


# bf16 activations and weights rounded in the program, float32 at the
# highest matmul precision in the reference: a few parts in a thousand
# per layer.
LAYER_TOL = 0.02


@pytest.mark.parametrize("part,index", [("kda", 0), ("mla", 2),
                                        ("dense", 0), ("moe", 1)])
def test_layer_parts_match_reference(part, index):
    lp = kimi_weights.layer(CFG, SEED, index)
    h = _normed_input(seed=index)
    ref = Reference(CFG)
    if part == "kda":
        got = jax.jit(lambda p, x: kimi_linear.kda_mixer(p, ARCH, x))(
            lp["mixer"], h)
        want = jax.jit(ref._kda_mixer)(_f32(lp["mixer"]), h[0])
    elif part == "mla":
        layout = kimi_linear._mla_layout(ARCH, 1)
        got = jax.jit(lambda p, x: kimi_linear.mla_mixer(p, ARCH, x,
                                                         layout))(
            lp["mixer"], h)
        want = jax.jit(ref._mla_mixer)(_f32(lp["mixer"]), h[0])
    elif part == "dense":
        got = L.swiglu(lp["mlp"], h)
        want = ref._swiglu(_f32(lp["mlp"]), h[0])
    else:
        got = jax.jit(lambda p, x: L.moe_apply(
            p, x, top_k=2, first_expert=0, router="sigmoid",
            routed_scaling=2.446)[0] + L.swiglu(lp["shared"], x))(
            lp["moe"], h)
        want = ref._moe(_f32(lp["moe"]), h[0]) \
            + ref._swiglu(_f32(lp["shared"]), h[0])
    assert _close(got[0], want, LAYER_TOL)


def _head():
    from repro.core.model import FrozenDWN
    from repro.dwn import DWNArtifact, get_spec
    spec = get_spec("dwn-lm-head")
    th, mapping, tables = granite_weights.head(
        {"head": {"features": 16, "bits_per_feature": 64, "luts": 50,
                  "fan_in": 6}}, SEED)
    return DWNArtifact(spec, frozen=FrozenDWN(spec.dwn_config(), th,
                                              [mapping], [tables], None))


def _engine(**kwargs):
    from repro.serving import ServingEngine
    return ServingEngine(ARCH, params=kimi_weights.model(CFG, SEED),
                         dwn_head=_head(), min_bucket=16, max_bucket=32,
                         step_tokens=64, seed=SEED, **kwargs)


@pytest.fixture(scope="module")
def engine():
    return _engine()


#: the tiny configuration's feature limit.  At these widths one token
#: whose router scores sit within bf16 rounding of a tie takes another
#: expert in the program than in the reference, and moves its prompt's
#: features by up to ~0.1; the float8 control reads 0.5 and more.
FEATURE_TOL = 0.25


def test_backbone_features_match_reference(engine):
    """The served step's pooled features (the engine's weights are
    ``kimi_weights.model(CFG, SEED)``) against the reference's."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 251, n) for n in (24, 31)]
    tokens = np.zeros((2, 32), np.int32)
    for row, t in enumerate(prompts):
        tokens[row, :len(t)] = t
    got = engine._classify_step(tokens, np.array([24, 31], np.int32))[2]
    args = (prompts, kimi_weights.embedding(CFG, SEED),
            lambda i: kimi_weights.layer(CFG, SEED, i),
            kimi_weights.final_norm(CFG), kimi_weights.lm_head(CFG, SEED))
    want = Reference(CFG).features(*args)
    control = Reference(CFG, act_dtype=jnp.float8_e4m3fn).features(*args)
    assert feature_error(got, want) < FEATURE_TOL
    assert feature_error(control, want) > FEATURE_TOL


def test_padding_mask_is_exact():
    """Right-padded prompts with a token mask: the hidden states at real
    positions, from which the pooled features are read, are those
    without the mask."""
    params = kimi_weights.model(CFG, SEED)
    rng = np.random.default_rng(4)
    lengths = np.array([21, 32, 9], np.int32)
    tokens = np.zeros((3, 32), np.int32)
    for row, n in enumerate(lengths):
        tokens[row, :n] = rng.integers(1, 251, n)
    real = np.arange(32)[None] < lengths[:, None]
    h_all, _, _ = kimi_linear.hidden(params, ARCH, tokens, tp=1)
    h_real, _, fallbacks = kimi_linear.hidden(params, ARCH, tokens, tp=1,
                                              token_mask=jnp.asarray(real))
    assert int(fallbacks) == 0
    np.testing.assert_array_equal(np.asarray(h_real, np.float32)[real],
                                  np.asarray(h_all, np.float32)[real])


def test_batched_classify_equals_one_prompt_at_a_time(engine):
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 251, (1, n)).astype(np.int32)
               for n in rng.integers(3, 33, 8)]
    with engine.serve():
        batched = [r.future.result() for r in
                   [engine.submit_async(p) for p in prompts]]
    with engine.serve():
        alone = [engine.submit_async(p).future.result() for p in prompts]
    assert all(r.ok for r in batched + alone)
    for b, a in zip(batched, alone):
        np.testing.assert_array_equal(b.value[0], a.value[0])   # counts
        np.testing.assert_array_equal(b.value[1], a.value[1])   # pred
        np.testing.assert_allclose(b.value[2], a.value[2], rtol=1e-6,
                                   atol=1e-6)


@pytest.mark.parametrize("forced", [False, True])
def test_fallback_counter_counts_the_moe_layers(engine, monkeypatch,
                                                request, forced):
    """The step's count of MoE layer calls on the worst-case buffer: 0 on
    ordinary steps, every layer but the leading dense one on steps whose
    sized buffer is forced down to 8 rows."""
    from repro.serving import steplog
    assert api.held_moe_layers(ARCH) == ARCH.num_layers - 1
    if forced:
        # kimi_linear.hidden is jitted at module level: drop its traces
        # with the rule forced, and again after
        monkeypatch.setattr(L, "held_rows", lambda T, k, n, E: 8)
        jax.clear_caches()
        request.addfinalizer(jax.clear_caches)
        engine = _engine(verify=False)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 251, (1, n)).astype(np.int32)
               for n in rng.integers(24, 33, 4)]
    mark = steplog.mark()
    with engine.serve():
        results = [r.future.result() for r in
                   [engine.submit_async(p) for p in prompts]]
    recs = steplog.since(mark)
    assert all(r.ok for r in results)
    np.testing.assert_array_equal(
        recs.moe_fallbacks, api.held_moe_layers(ARCH) if forced else 0)
