"""granite-4.0-h-small (family ``ssm_moe``) at a tiny size on the CPU:
each layer kind and the whole backbone against the plain float32
reference on seeded weights, the expert layer's shares and its dropless
routing, and the DWN head's classify path through the continuous
scheduler."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import granite_weights  # noqa: E402
from bench.reference_granite import Reference, feature_error, rms_norm  # noqa: E402,E501
from repro.configs import get_arch  # noqa: E402
from repro.models import granite, mamba2  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.serving.continuous import (ContinuousScheduler,  # noqa: E402
                                      TokenBuckets)

#: the tests' tiny size: d_model 64, four layers [m, m, a, m], 8 experts
ARCH = dataclasses.replace(
    get_arch("granite-4.0-h-small").reduced(), num_layers=4,
    layer_types=("mamba", "mamba", "attention", "mamba"), num_experts=8)
#: the reference's configuration keys at ARCH's widths
CFG = {"hidden_size": 64, "intermediate_size": 32,
       "shared_intermediate_size": 48, "num_attention_heads": 4,
       "num_key_value_heads": 2, "num_experts_per_tok": 2,
       "vocab_size": 251, "mamba_expand": 2, "mamba_n_heads": 8,
       "mamba_d_head": 16, "mamba_d_state": 16, "mamba_n_groups": 1,
       "mamba_d_conv": 4, "mamba_chunk_size": 8,
       "attention_multiplier": 1 / 16, "embedding_multiplier": 12.0,
       "residual_multiplier": 0.22, "logits_scaling": 16.0,
       "rms_norm_eps": 1e-5, "num_hidden_layers": 4,
       "layer_types": ["mamba", "mamba", "attention", "mamba"],
       "num_local_experts": 8, "published": {"num_local_experts": 8},
       "head": {"classes": 5}}
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


# bf16 activations and weights rounded in the program, float32 at the
# highest matmul precision in the reference: a few parts in a thousand
# per layer.
LAYER_TOL = 0.02


@pytest.mark.parametrize("kind,index", [("mamba", 0), ("attention", 2)])
def test_mixer_matches_reference(kind, index):
    lp = granite_weights.layer(CFG, SEED, index)
    h = _normed_input()
    ref = Reference(CFG)
    if kind == "mamba":
        got = jax.jit(lambda p, x: mamba2.mixer(p, ARCH, x)[0])(
            lp["mixer"], h)
        want = jax.jit(ref._mamba_mixer)(_f32(lp["mixer"]), h[0])
    else:
        layout = granite._layout(ARCH, 1)
        got = jax.jit(lambda p, x: granite._attention(p, ARCH, x, layout))(
            lp["mixer"], h)
        want = jax.jit(ref._attention_mixer)(_f32(lp["mixer"]), h[0])
    assert _close(got[0], want, LAYER_TOL)


def test_moe_and_shared_expert_match_reference():
    lp = granite_weights.layer(CFG, SEED, 1)
    h = _normed_input(seed=1)

    def program(p, x):
        y, _, _ = L.moe_apply(p["moe"], x, top_k=2, first_expert=0)
        return y + L.swiglu(p["shared"], x)

    ref = Reference(CFG)
    got = jax.jit(program)(lp, h)
    want = jax.jit(lambda p, x: ref._moe(p["moe"], x)
                   + ref._swiglu(p["shared"], x))(_f32(lp), h[0])
    assert _close(got[0], want, LAYER_TOL)


#: the tiny configuration's feature limit (the float8 control reads
#: 0.03 and more at these widths, the program about 0.012)
FEATURE_TOL = 0.02


def test_backbone_features_match_reference(engine):
    """The served step's pooled features (the engine's weights are
    ``granite_weights.model(CFG, SEED)``) against the reference's."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 251, n) for n in (24, 31)]
    tokens = np.zeros((2, 32), np.int32)
    for row, t in enumerate(prompts):
        tokens[row, :len(t)] = t
    got = engine._classify_step(tokens, np.array([24, 31], np.int32))[2]
    want = Reference(CFG).features(
        prompts, granite_weights.embedding(CFG, SEED),
        lambda i: granite_weights.layer(CFG, SEED, i),
        granite_weights.final_norm(CFG))
    assert feature_error(got, want) < FEATURE_TOL


def _moe_params(E=8, seed=0, S=16):
    p = L.init_moe(jax.random.PRNGKey(seed), 32, 24, E)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, S, 32))
    return p, x


def _share(p, lo, hi):
    return dict(p, **{w: p[w][lo:hi] for w in ("w_gate", "w_up", "w_down")})


@pytest.mark.parametrize("held", [1, 2, 4])
def test_expert_shares_sum_to_the_whole_layer(held):
    """Every chip's part of the expert layer, with the shared expert
    (computed alike on every chip) counted once, adds up to the uncut
    layer."""
    p, x = _moe_params()
    shared = L.init_swiglu(jax.random.PRNGKey(5), 32, 40)
    moe = jax.jit(L.moe_apply, static_argnames=("top_k",))
    whole, _, _ = moe(p, x, top_k=3, first_expert=0)
    parts = sum(moe(_share(p, e0, e0 + held), x, top_k=3,
                    first_expert=e0)[0].astype(jnp.float32)
                for e0 in range(0, 8, held))
    s = L.swiglu(shared, x).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(parts + s),
                               np.asarray(whole.astype(jnp.float32) + s),
                               rtol=2e-2, atol=2e-2)
    ref = Reference({"num_experts_per_tok": 3, "num_local_experts": 8,
                     "rms_norm_eps": 1e-5, "residual_multiplier": 1.0})
    want = jax.jit(ref._moe)(_f32(p), x.reshape(-1, 32)).reshape(x.shape)
    assert _close(whole, want, LAYER_TOL)


@pytest.mark.parametrize("S,favoured,fallback", [(16, (0,), 0),
                                                  (512, (0, 1), 1)])
def test_no_token_dropped_under_skewed_routing(S, favoured, fallback):
    """Every token routed to expert 0 (and at S=512 to expert 1 too, so
    the 2,048 pairs overflow the sized buffer of 1,024 rows): the
    capacity path drops most of them, the held-experts path computes them
    all, on the worst-case buffer where they overflow, and counts that."""
    p, x = _moe_params(S=S)
    bias = jnp.zeros((32, 8)).at[:, favoured].set(50.0)
    p = dict(p, router=p["router"] + bias)
    x = jnp.abs(x) + 0.1                  # router logits favour expert 0
    ref = Reference({"num_experts_per_tok": 2, "num_local_experts": 2,
                     "rms_norm_eps": 1e-5, "residual_multiplier": 1.0})
    held = _share(p, 0, 2)
    want = jax.jit(ref._moe)(_f32(held), x.reshape(-1, 32)).reshape(x.shape)
    moe = jax.jit(L.moe_apply, static_argnames=("top_k", "capacity_factor",
                                                "first_expert"))
    got, _, fell_back = moe(held, x, top_k=2, first_expert=0)
    assert _close(got, want, LAYER_TOL)
    assert int(fell_back) == fallback
    if fallback:
        assert L.held_rows(2 * S, 2, 2, 8) < 2 * S * len(favoured)
    capped, _ = moe(p, x, top_k=2, capacity_factor=1.0)
    assert not _close(capped, want, 0.2)


@pytest.mark.parametrize("B,S,k,n,E", [(2, 512, 2, 2, 8), (1, 1024, 3, 4, 16),
                                       (4, 512, 4, 2, 32), (2, 16, 2, 2, 8)])
def test_sized_buffer_equals_the_worst_case(monkeypatch, B, S, k, n, E):
    """On random routing the pairs fit the sized buffer, and the layer
    gives what it gives on the worst-case buffer of ``T * min(k, n)``
    rows (at S=16 the sized buffer is the worst case)."""
    T = B * S
    sized = L.held_rows(T, k, n, E)
    assert sized < T * min(k, n) or S == 16
    p = L.init_moe(jax.random.PRNGKey(k), 32, 24, E)
    p = _share(p, 0, n)
    x = jax.random.normal(jax.random.PRNGKey(E), (B, S, 32))

    def run():
        return jax.jit(lambda p, x: L.moe_apply(p, x, top_k=k,
                                                first_expert=0))(p, x)
    got, _, fell_back = run()
    monkeypatch.setattr(L, "held_rows", lambda T, k, n, E: T * min(k, n))
    want, _, worst_fell_back = run()
    assert int(fell_back) == int(worst_fell_back) == 0
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_padding_mask_is_exact():
    """Right-padded prompts with a token mask: the hidden states and the
    pooled features at real positions are those without the mask, and
    the MoE gives 0 at every padding position."""
    from repro.workloads.lm_head import pool_features
    params = granite_weights.model(CFG, SEED)
    rng = np.random.default_rng(4)
    lengths = np.array([21, 32, 9], np.int32)
    tokens = np.zeros((3, 32), np.int32)
    for row, n in enumerate(lengths):
        tokens[row, :n] = rng.integers(1, 251, n)
    real = np.arange(32)[None] < lengths[:, None]
    h_all, _, _ = granite.hidden(params, ARCH, tokens, tp=1)
    h_real, _, fallbacks = granite.hidden(params, ARCH, tokens, tp=1,
                                          token_mask=jnp.asarray(real))
    assert int(fallbacks) == 0
    np.testing.assert_array_equal(np.asarray(h_real, np.float32)[real],
                                  np.asarray(h_all, np.float32)[real])
    pooled = [pool_features(granite.logit_columns(
        params, ARCH, tokens, 16, tp=1, token_mask=m)[0], lengths)
        for m in (None, jnp.asarray(real))]
    np.testing.assert_array_equal(np.asarray(pooled[1]),
                                  np.asarray(pooled[0]))
    lp = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 32, 64))
    y_all, _, _ = L.moe_apply(lp["moe"], x, top_k=2, first_expert=0)
    y_real, _, _ = L.moe_apply(lp["moe"], x, top_k=2, first_expert=0,
                               token_mask=jnp.asarray(real))
    y_all, y_real = (np.asarray(y, np.float32) for y in (y_all, y_real))
    assert (y_real[~real] == 0).all() and (y_all[~real] != 0).any()
    np.testing.assert_array_equal(y_real[real], y_all[real])


def _engine(**kwargs):
    from repro.core.model import FrozenDWN
    from repro.dwn import DWNArtifact, get_spec
    from repro.serving import ServingEngine
    spec = get_spec("dwn-lm-head")
    th, mapping, tables = granite_weights.head(
        {"head": {"features": 16, "bits_per_feature": 64, "luts": 50,
                  "fan_in": 6}}, SEED)
    art = DWNArtifact(spec, frozen=FrozenDWN(spec.dwn_config(), th,
                                             [mapping], [tables], None))
    return ServingEngine(ARCH, params=granite_weights.model(CFG, SEED),
                         dwn_head=art, min_bucket=16, max_bucket=32,
                         step_tokens=64, seed=SEED, **kwargs)


@pytest.fixture(scope="module")
def engine():
    return _engine()


def test_pooled_features_do_not_change_with_the_padding_bucket(engine):
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 251, 7).astype(np.int32)
    feats = []
    for batch, length in engine.head_buckets.shapes:
        tokens = np.zeros((batch, length), np.int32)
        tokens[0, :7] = prompt
        lengths = np.zeros(batch, np.int32)
        lengths[0] = 7
        feats.append(engine._classify_step(tokens, lengths)[2][0])
    for f in feats[1:]:
        np.testing.assert_allclose(f, feats[0], rtol=1e-6, atol=1e-6)


def test_batched_classify_equals_one_prompt_at_a_time(engine):
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 251, (1, n)).astype(np.int32)
               for n in rng.integers(3, 33, 10)]
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
    steps = engine.report()["steps"]
    assert 0 < steps["token_occupancy_pct"] <= 100


def test_token_buckets_fill_behind_the_first_request():
    """The first queued prompt sets the step's length; shorter ones fill
    the rows behind it, a longer one waits, and a request of several
    prompts is split across steps."""
    seen = []

    def step(tokens, lengths):
        seen.append((tokens.shape, lengths.tolist()))
        return (np.arange(tokens.shape[0]),)

    sched = ContinuousScheduler(step, lambda outs: outs,
                                batching=TokenBuckets((8, 16, 32), 64))
    reqs = [sched.submit(np.ones((1, 12), np.int32)),
            sched.submit(np.ones((1, 30), np.int32)),
            sched.submit(np.ones((5, 16), np.int32)),
            sched.submit(np.ones((1, 3), np.int32))]
    while sched.step_once():
        pass
    assert seen == [((4, 16), [12, 16, 16, 16]),
                    ((2, 32), [30, 16]),
                    ((4, 16), [16, 3, 0, 0])]
    assert [r.future.result().value[0].tolist() for r in reqs] == \
        [[0], [0], [1, 2, 3, 1, 0], [1]]
    with pytest.raises(ValueError, match="does not fit"):
        TokenBuckets((8, 16), 64).shape_for(17)


def test_classify_needs_the_continuous_loop(engine):
    with pytest.raises(ValueError, match="continuous loop"):
        engine.submit({"tokens": np.ones((1, 4), np.int32),
                       "classify": True})


@pytest.mark.parametrize("forced", [False, True])
def test_fallback_counter_rides_the_step_answer(engine, monkeypatch, request,
                                                forced):
    """The step's count of MoE layer calls on the worst-case buffer comes
    back in its one copy and lands in the ring: 0 on ordinary steps, every
    layer on steps whose sized buffer is forced down to 8 rows.  Results
    keep their (counts, pred, features) shape."""
    from repro.serving import steplog
    if forced:
        # granite.hidden is jitted at module level: drop its traces with
        # the rule forced, and again after, so no other test reuses them
        monkeypatch.setattr(L, "held_rows", lambda T, k, n, E: 8)
        jax.clear_caches()
        request.addfinalizer(jax.clear_caches)
        engine = _engine(verify=False)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 251, (1, n)).astype(np.int32)
               for n in rng.integers(8, 33, 6)]
    mark = steplog.mark()
    with engine.serve():
        results = [r.future.result() for r in
                   [engine.submit_async(p) for p in prompts]]
    recs = steplog.since(mark)
    assert all(r.ok for r in results)
    for r in results:
        assert [v.shape for v in r.value] == [(1, 5), (1,), (1, 16)]
    assert len(recs) and (recs.d2h_copies == 1).all()
    layers = ARCH.num_layers if forced else 0
    np.testing.assert_array_equal(recs.moe_fallbacks, layers)
    steps = engine.report()["steps"]
    assert steps["moe_fallback_pct"] == (100.0 if forced else 0.0)
