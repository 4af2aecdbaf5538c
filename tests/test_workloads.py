"""Workload registry tests.

Covers: registry resolution (unknown-name errors that list the known
names), synthetic-fallback determinism, byte-exact JSC parity between
the registry loader and the legacy ``data.jsc.load_jsc`` path,
spec/sweep-point fingerprint stability (the default workload is omitted
from serialized dicts so pre-registry cache keys survive), the MNIST
end-to-end smoke (train -> freeze/pack -> serve bit-exact vs the packed
oracle -> cosim verify), and the LM-head workload + the engine's
``dwn_head`` path (one engine serving LM decode and a packed DWN head).
"""

import numpy as np
import pytest

from repro.data.jsc import load_jsc
from repro.dwn import DWNArtifact, DWNSpec, resolve_spec
from repro.workloads import (Workload, get_workload, list_workloads,
                             load_workload, register_workload)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_lists_builtin_workloads():
    names = list_workloads()
    assert {"jsc", "mnist", "lm-head"} <= set(names)


def test_unknown_workload_error_lists_known_names():
    with pytest.raises(KeyError, match="unknown workload 'cifar'.*jsc"):
        get_workload("cifar")
    with pytest.raises(KeyError, match="mnist"):
        load_workload("nope", 32, 16)


def test_workload_schema_matches_presets():
    for name in ("jsc", "mnist", "lm-head"):
        wl = get_workload(name)
        for tier, cfg in wl.presets.items():
            assert cfg.num_features == wl.num_features, (name, tier)
            assert cfg.num_classes == wl.num_classes, (name, tier)


def test_reregistering_name_is_an_error():
    wl = get_workload("jsc")
    with pytest.raises(AssertionError, match="already registered"):
        register_workload(Workload(
            name="jsc", num_features=wl.num_features,
            num_classes=wl.num_classes, loader=wl.loader,
            presets=wl.presets))


def test_jsc_parity_registry_vs_legacy_loader_byte_exact():
    old = load_jsc(256, 64, seed=3)
    new = load_workload("jsc", 256, 64, seed=3)
    for field in ("x_train", "y_train", "x_test", "y_test"):
        a, b = getattr(old, field), getattr(new, field)
        assert a.dtype == b.dtype
        assert np.array_equal(a, b), field


def test_workload_caps_clamp_split_sizes():
    wl = get_workload("lm-head")
    assert wl.cap_train is not None and wl.cap_test is not None
    # a request over the cap must come back clamped, not error
    d = wl.load(wl.cap_train + 999, wl.cap_test + 999, seed=0)
    assert d.x_train.shape[0] == wl.cap_train
    assert d.x_test.shape[0] == wl.cap_test


# ---------------------------------------------------------------------------
# MNIST synthetic fallback
# ---------------------------------------------------------------------------

def test_mnist_synthetic_deterministic(monkeypatch):
    monkeypatch.delenv("REPRO_MNIST_DOWNLOAD", raising=False)
    monkeypatch.setenv("REPRO_MNIST", "/nonexistent/mnist.npz")
    a = load_workload("mnist", 128, 32, seed=7)
    b = load_workload("mnist", 128, 32, seed=7)
    for field in ("x_train", "y_train", "x_test", "y_test"):
        assert np.array_equal(getattr(a, field), getattr(b, field)), field
    c = load_workload("mnist", 128, 32, seed=8)
    assert not np.array_equal(a.x_train, c.x_train)


def test_mnist_schema_and_input_contract(monkeypatch):
    monkeypatch.setenv("REPRO_MNIST", "/nonexistent/mnist.npz")
    d = load_workload("mnist", 128, 32, seed=0)
    assert d.x_train.shape == (128, 196) and d.x_test.shape == (32, 196)
    assert d.x_train.dtype == np.float32
    assert d.y_train.dtype == np.int32
    # thermometer input contract: features normalized into [-1, 1)
    assert d.x_train.min() >= -1.0 and d.x_train.max() < 1.0
    assert set(np.unique(d.y_train)) <= set(range(10))


def test_mnist_real_npz_roundtrip(tmp_path, monkeypatch):
    # a tiny fake "real" npz in the Keras layout exercises the non-
    # synthetic path: 28x28 uint8 images pooled down to the 196 schema
    rng = np.random.default_rng(0)
    np.savez(tmp_path / "mnist.npz",
             x_train=rng.integers(0, 256, (64, 28, 28), dtype=np.uint8),
             y_train=rng.integers(0, 10, 64, dtype=np.int64),
             x_test=rng.integers(0, 256, (32, 28, 28), dtype=np.uint8),
             y_test=rng.integers(0, 10, 32, dtype=np.int64))
    monkeypatch.setenv("REPRO_MNIST", str(tmp_path / "mnist.npz"))
    d = load_workload("mnist", 48, 16, seed=1)
    assert d.x_train.shape == (48, 196) and d.x_test.shape == (16, 196)
    assert d.x_train.min() >= -1.0 and d.x_train.max() < 1.0


# ---------------------------------------------------------------------------
# spec / sweep integration: fingerprints stay stable, presets validate
# ---------------------------------------------------------------------------

def test_jsc_spec_dict_has_no_workload_key():
    # pre-registry fingerprints, sweep-cache keys, and checkpoints hash
    # the spec dict: the default workload must not appear in it
    d = DWNSpec(preset="sm-50").to_dict()
    assert "workload" not in d and "backbone" not in d
    d2 = DWNSpec(preset="mnist-sm", bits=8, workload="mnist").to_dict()
    assert d2["workload"] == "mnist"
    assert DWNSpec.from_dict(d2).workload == "mnist"


def test_spec_rejects_preset_workload_mismatch():
    with pytest.raises(ValueError, match="workload 'mnist'.*mnist-sm"):
        DWNSpec(preset="sm-50", workload="mnist")
    with pytest.raises(ValueError, match="workload 'jsc'"):
        DWNSpec(preset="mnist-sm")


def test_spec_rejects_unknown_workload():
    with pytest.raises(ValueError, match="unknown workload"):
        DWNSpec(preset="sm-50", workload="cifar")


def test_mnist_spec_presets_registered():
    for tier in ("sm", "md", "lg"):
        spec = resolve_spec(f"dwn-mnist-{tier}")
        assert spec.workload == "mnist"
        cfg = spec.dwn_config()
        assert cfg.num_features == 196 and cfg.num_classes == 10
        assert cfg.lut_counts[-1] % 10 == 0
        arch = spec.arch_config()
        assert arch.d_model == 196 and arch.vocab_size == 10


def test_sweep_point_workload_label_and_dict_stability():
    from repro.sweep.grid import SweepPoint
    jsc = SweepPoint("sm-50", "TEN")
    assert "workload" not in jsc.to_dict()
    mn = SweepPoint("mnist-sm", "TEN", bits=8, workload="mnist")
    assert mn.to_dict()["workload"] == "mnist"
    assert mn.label.startswith("mnist:")
    assert SweepPoint.from_dict(mn.to_dict()) == mn


def test_mnist_grids_registered():
    from repro.sweep.grid import load_grid
    tiny = load_grid("mnist-tiny")
    assert all(p.workload == "mnist" for p in tiny)
    assert any(p.variant == "PEN" for p in tiny)
    full = load_grid("mnist")
    assert {p.preset for p in full} == {"mnist-sm", "mnist-md", "mnist-lg"}


# ---------------------------------------------------------------------------
# MNIST end-to-end smoke: train -> pack -> serve bit-exact -> cosim
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mnist_artifact():
    import jax.numpy as jnp  # noqa: F401 (jax init before data)
    data = load_workload("mnist", 512, 96, seed=0)
    spec = resolve_spec("dwn-mnist-sm")
    art = DWNArtifact(spec).train(data, epochs=1, batch=128, seed=0)
    art.freeze().pack()
    return art, data


def test_mnist_end_to_end_serve_bit_exact(mnist_artifact):
    import jax.numpy as jnp
    from repro.core.model import apply_hard_packed
    from repro.serving import ServingEngine

    art, data = mnist_artifact
    assert art.stage == "packed"
    engine = ServingEngine(art, max_bucket=32, min_bucket=8,
                           n_train=256, seed=0)
    engine.warmup(32)
    engine.submit(engine.make_request(32, seed=1))
    done = engine.drain()
    rep = engine.report()
    assert all(rep["bit_exact_vs_oracle"].values())
    assert sum(r.size for r in done) == 32
    # the engine's own data comes from the registry (mnist geometry)
    assert engine.data.x_test.shape[1] == 196
    # direct packed-oracle agreement on real split vectors
    counts = np.asarray(apply_hard_packed(art.frozen,
                                          jnp.asarray(data.x_test[:32])))
    assert counts.shape == (32, 10)


def test_mnist_end_to_end_cosim_verify(mnist_artifact):
    art, data = mnist_artifact
    rep = art.verify_rtl(data.x_test[:24], backend="python")
    assert rep.counts_checked and rep.n_vectors == 24
    # the default-vector path resolves the spec's own workload
    rep2 = art.verify_rtl(n=8, backend="python")
    assert rep2.n_vectors == 8


def test_mnist_hw_report_encoder_share(mnist_artifact):
    art, _ = mnist_artifact
    rep = art.hw_report()
    assert rep.total_luts > 0
    assert rep.luts.get("encoder", 0) == 0        # TEN: encoding off-chip
    import dataclasses
    pen = dataclasses.replace(art.spec, variant="PEN", input_bits=8)
    pen_art = DWNArtifact(pen).adopt(art.params, art.buffers).freeze()
    pen_rep = pen_art.hw_report()
    assert pen_rep.luts["encoder"] > 0            # PEN pays it on-chip


# ---------------------------------------------------------------------------
# LM-head workload + the engine's dwn_head path
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_lm_head_workload_deterministic_and_trainable():
    data = load_workload("lm-head", 96, 32, seed=0)
    again = load_workload("lm-head", 96, 32, seed=0)
    assert np.array_equal(data.x_train, again.x_train)
    assert data.x_train.shape == (96, 16)
    assert data.x_train.min() >= -1.0 and data.x_train.max() < 1.0
    assert set(np.unique(data.y_train)) <= set(range(5))


@pytest.mark.slow
def test_one_engine_serves_lm_decode_and_dwn_head():
    from repro.serving import ServingEngine

    data = load_workload("lm-head", 96, 32, seed=0)
    spec = resolve_spec("dwn-lm-head")
    art = DWNArtifact(spec).train(data, epochs=1, batch=32, seed=0)
    art.freeze().pack()

    engine = ServingEngine("qwen3-8b", reduced=True, prompt_len=8, gen=2,
                           seed=0, dwn_head=art, max_bucket=8,
                           step_tokens=32)
    assert engine.head_bit_exact is True          # startup oracle gate
    engine.submit(engine.make_request(2, seed=0))                 # LM
    done = engine.drain()
    assert done[0].result["tokens"].shape == (2, 2)
    with engine.serve():                                          # head
        head = engine.submit_async(
            engine.make_request(4, seed=1, classify=True)).future.result()
    counts, pred, feats = head.value
    assert pred.shape == (4,)
    assert counts.shape == (4, 5)
    assert feats.shape == (4, 16)
    rep = engine.report()
    assert rep["dwn_head"]["bit_exact_vs_oracle"] is True
    assert rep["dwn_head"]["served"] == 4


def test_dwn_engine_rejects_dwn_head():
    from repro.serving import ServingEngine
    with pytest.raises(AssertionError, match="LM engine"):
        ServingEngine("dwn-jsc-sm", dwn_head="dwn-lm-head")
