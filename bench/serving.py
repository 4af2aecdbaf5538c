"""What the serving drivers share: the engine as a user builds it, the
seeded request rows, and the check of served answers against the plain
reference."""

from __future__ import annotations

import time

import numpy as np

from bench import model_init, reference
from bench.harness import Compared


def build_engine(cell, seed: int):
    """A ``ServingEngine`` over the cell's DWN, built the way the serve
    CLI builds it (backend from the spec, startup cross-check on), with
    weights made here from ``seed``.  Returns (engine, weights)."""
    from repro.core.model import FrozenDWN
    from repro.dwn import DWNArtifact, get_spec
    from repro.serving import ServingEngine
    cfg, mix = cell.config, cell.mix
    spec = get_spec(cfg["spec_preset"])
    check_spec(cfg, spec.dwn_config())
    weights = model_init.frozen_weights(cfg, seed)
    th, mapping, tables = weights
    art = DWNArtifact(spec, frozen=FrozenDWN(spec.dwn_config(), th,
                                             [mapping], [tables], None))
    # a one-chip cell stays on one chip on a host with more
    eng = ServingEngine(art, max_bucket=mix["max_bucket"],
                        min_bucket=mix["min_bucket"],
                        n_train=cfg["n_train"], verify=True,
                        data_parallel=cell.chips > 1)
    if not all(eng.bit_exact.values()):
        raise RuntimeError(f"startup cross-check failed: {eng.bit_exact}")
    for bucket in eng.scheduler.buckets:
        eng.warmup(bucket)
    return eng, weights


def check_spec(cfg: dict, dcfg) -> None:
    """The program's preset has the configuration file's sizes."""
    got = {"features": dcfg.num_features,
           "bits_per_feature": dcfg.bits_per_feature,
           "luts": dcfg.lut_counts[-1], "fan_in": dcfg.fan_in,
           "classes": dcfg.num_classes, "layers": len(dcfg.lut_counts),
           "placement": dcfg.encoding}
    want = {k: cfg[k] for k in got}
    if got != want:
        raise ValueError(f"preset {cfg['spec_preset']!r} is {got}, the "
                         f"configuration file says {want}")


def compare_answers(weights, cfg: dict, rows, answers, *, dtype=np.float32,
                    label: str = "") -> list[Compared]:
    """Rows whose served counts or prediction differ from the reference.

    ``rows`` and ``answers`` are matching lists: the request's (b, F)
    features and its served (counts, pred).  The comparison is exact.
    """
    th, mapping, tables = weights
    if not rows:
        return [Compared(f"answers_checked{label}", 0, -1)]
    x = np.concatenate(rows)
    counts = np.concatenate([np.asarray(a[0]) for a in answers])
    pred = np.concatenate([np.asarray(a[1]) for a in answers])
    ref_c, ref_p = reference.infer(x, th, mapping, tables, cfg["classes"],
                                   dtype=dtype)
    off = (np.any(counts.astype(np.int64) != ref_c, axis=1)
           | (pred.astype(np.int64) != ref_p))
    return [Compared(f"rows_off{label}", int(off.sum()), 0)]


#: how long past the window's close an answer may still come
GRACE_S = 60.0


def wait(req, t_end: float):
    """The request's ``ServeResult``, waiting up to a minute past the
    window's close; None if it never comes."""
    import concurrent.futures
    left = max(1.0, t_end + GRACE_S - time.perf_counter())
    try:
        return req.future.result(timeout=left)
    except concurrent.futures.TimeoutError:
        return None


class Reservoir:
    """A uniform sample of ``k`` of the items offered, drawn from
    ``seed`` (reservoir sampling), so that a run keeps only the answers
    it will check."""

    def __init__(self, k: int, seed: int):
        self.k, self.n, self.items = k, 0, []
        self._rng = np.random.default_rng([seed, 9])

    def offer(self, item) -> None:
        self.n += 1
        if len(self.items) < self.k:
            self.items.append(item)
            return
        j = int(self._rng.integers(self.n))
        if j < self.k:
            self.items[j] = item
