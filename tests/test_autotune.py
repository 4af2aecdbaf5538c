"""Fused-kernel autotuner: deterministic winner selection under a stubbed
clock, cache persistence + invalidation on kernel-source changes, and
cold-start fallback when the cache is absent or corrupt."""

import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import autotune
from repro.kernels.autotune import (AutotuneCache, FusedConfig,
                                    candidate_configs, tune_fused)
from repro.kernels.fused import ops as f_ops
from repro.kernels.fused.ref import fused_dwn_packed_ref


# tiny model: F*T = 32 (one packed word), bucket 8
F, T, M, N, C, BUCKET = 4, 8, 10, 3, 5, 8
SPEC_FP = "cafef00dcafef00d"


class FakeTimer:
    """Deterministic clock: call i advances by deltas[i] seconds.

    ``time_step`` with iters=1 brackets each candidate's timed run with
    two calls, so the measured time is exactly the delta consumed between
    them — the test scripts the race outcome.
    """

    def __init__(self, deltas):
        self._deltas = list(deltas)
        self._t = 0.0
        self.calls = 0

    def __call__(self):
        now = self._t
        if self.calls < len(self._deltas):
            self._t += self._deltas[self.calls]
        else:
            self._t += 1.0
        self.calls += 1
        return now


@pytest.fixture
def model():
    key = jax.random.PRNGKey(0)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    x = jax.random.uniform(k1, (BUCKET, F), minval=-1, maxval=1)
    th = jnp.sort(jax.random.uniform(k2, (F, T), minval=-1, maxval=1), 1)
    mapping = jax.random.randint(k3, (M, N), 0, F * T)
    tables = jax.random.randint(k4, (M, 2 ** N), 0, 2)
    return x, th, mapping, tables


CANDS = [FusedConfig(block_b=16), FusedConfig(block_b=8)]

# per candidate (iters=1): t0, timed run, t1 -> measured = delta at t0's
# index; scripted so 8 rows per step (5us) beat 16 (50us)
DELTAS = [50e-6, 1e-6, 5e-6, 1e-6]


def _tune(model, cache, timer, **kw):
    x, th, mapping, tables = model
    return tune_fused(th, [mapping], [tables], C, x,
                      spec_fingerprint=SPEC_FP, cache=cache,
                      candidates=CANDS, iters=1, timer=timer,
                      interpret=True, **kw)


def test_tuner_deterministic_under_stubbed_clock(tmp_path, model):
    """Same scripted timings -> same winner, twice over."""
    winners = []
    for run in range(2):
        cache = AutotuneCache(tmp_path / f"cache{run}.json")
        winners.append(_tune(model, cache, FakeTimer(DELTAS)))
    assert winners[0] == winners[1] == CANDS[1]


def test_cache_hit_skips_timing(tmp_path, model):
    cache = AutotuneCache(tmp_path / "cache.json")
    first = _tune(model, cache, FakeTimer(DELTAS))
    assert first == CANDS[1]
    # second tune: fresh cache object on the same file, stub clock must
    # never tick — the persisted winner is served without re-timing
    timer = FakeTimer(DELTAS)
    again = _tune(model, AutotuneCache(cache.path), timer)
    assert again == first
    assert timer.calls == 0
    # force=True re-times even on a hit
    forced = _tune(model, AutotuneCache(cache.path), FakeTimer(DELTAS),
                   force=True)
    assert forced == first


def test_cache_invalidated_on_kernel_source_change(tmp_path, model,
                                                   monkeypatch):
    cache = AutotuneCache(tmp_path / "cache.json")
    _tune(model, cache, FakeTimer(DELTAS))
    # simulate a kernel edit: the source fingerprint changes, so the
    # stored entry no longer matches and get() must miss
    monkeypatch.setattr(autotune, "kernel_fingerprint",
                        lambda: "0badc0de0badc0de")
    assert AutotuneCache(cache.path).get(SPEC_FP, BUCKET) is None
    timer = FakeTimer(DELTAS)
    retuned = _tune(model, AutotuneCache(cache.path), timer)
    assert timer.calls > 0          # re-timed, not served stale
    assert retuned == CANDS[1]


def test_cold_start_absent_and_corrupt_cache(tmp_path, model):
    # absent file: miss, tune still succeeds and writes the file
    cache = AutotuneCache(tmp_path / "nope.json")
    assert cache.get(SPEC_FP, BUCKET) is None
    cfg = _tune(model, cache, FakeTimer(DELTAS))
    assert cfg == CANDS[1]
    assert cache.path.exists()
    # corrupt file: miss (never an exception), tune overwrites cleanly
    bad = tmp_path / "corrupt.json"
    bad.write_text("{not json")
    cache = AutotuneCache(bad)
    assert cache.get(SPEC_FP, BUCKET) is None
    cfg = _tune(model, cache, FakeTimer(DELTAS))
    assert cfg == CANDS[1]
    assert json.loads(bad.read_text())["entries"]


def test_failing_candidate_raises(tmp_path, model, monkeypatch):
    """A candidate that fails to build raises: the tuner never hides a
    refused kernel behind a default."""
    def boom(*a, **kw):
        raise RuntimeError("no kernel for you")
    monkeypatch.setattr(f_ops, "make_forward_packed", boom)
    cache = AutotuneCache(tmp_path / "cache.json")
    with pytest.raises(RuntimeError, match="no kernel for you"):
        _tune(model, cache, FakeTimer(DELTAS))
    assert not cache.path.exists()      # nothing persisted for a non-race


def test_cache_entry_records_timings_and_roundtrips(tmp_path, model):
    cache = AutotuneCache(tmp_path / "cache.json")
    _tune(model, cache, FakeTimer(DELTAS))
    raw = json.loads(cache.path.read_text())["entries"]
    (key, entry), = raw.items()
    assert key == autotune.cache_key(SPEC_FP, BUCKET)
    assert entry["code"] == autotune.kernel_fingerprint()
    assert entry["timings_us"][CANDS[1].label] == pytest.approx(5.0)
    assert entry["timings_us"][CANDS[0].label] == pytest.approx(50.0)
    assert FusedConfig.from_dict(entry["config"]) == CANDS[1]


def test_candidate_configs_rows_per_bucket():
    assert [c.block_b for c in candidate_configs(64)] == [64, 32]
    # tiny buckets don't split below themselves; big ones cap the rows
    assert {c.block_b for c in candidate_configs(8)} == {8}
    assert {c.block_b for c in candidate_configs(4096)} == \
        {autotune.MAX_BLOCK_B}


def test_tuned_configs_stay_bit_exact(model):
    """Every candidate the tuner can pick produces oracle-identical
    (counts, argmax) — tuning is a pure perf decision."""
    x, th, mapping, tables = model
    ref_counts, ref_idx = fused_dwn_packed_ref(x, th, [mapping], [tables], C)
    for cfg in [None] + list(candidate_configs(BUCKET)):
        counts, idx = f_ops.forward_packed(x, th, mapping, tables, C,
                                           interpret=True, config=cfg)
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(ref_counts), err_msg=str(cfg))
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx),
                                      err_msg=str(cfg))
