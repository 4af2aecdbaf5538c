"""Persistent per-(arch, bucket, device) autotuner for the fused kernels.

The fused DWN kernel has a real shape knob — how many sample rows one
grid step processes — and the best choice depends on the model's size
and the batch bucket.  Instead of hardcoding,
this module times the candidate configs on probe rows and persists the
winner in a JSON cache, keyed exactly like the sweep result cache
(``repro.sweep.cache``): a content fingerprint of the thing being tuned
(the ``DWNSpec`` fingerprint), the batch bucket, the device kind, and a
source fingerprint of the kernel modules — editing the kernels
invalidates stale configs instead of silently serving them.

Cache location: ``$REPRO_AUTOTUNE_CACHE`` if set, else
``~/.cache/repro/autotune/fused_configs.json`` (next to where the sweep
compile cache lives by convention).  A corrupt or absent cache file is a
miss, never an error.  A candidate that fails to compile or run is an
error: the tuner raises rather than serve something else under the
kernel's name.

The timing loop is deliberately tiny and injectable (``timer=``) so the
tuner is deterministic under a stubbed clock in tests.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class FusedConfig:
    """One point in the fused-kernel tuning space.

    Attributes:
      block_b: sample rows ``kernel.fused_dwn_batch_major`` processes per
        grid step.  Its first-layer LUT tile is fixed
        (``fused.ops.BLOCK_M``).
    """

    block_b: int = 256

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "FusedConfig":
        return cls(block_b=d["block_b"])

    @property
    def label(self) -> str:
        return f"b{self.block_b}"


#: what an untuned model serves with.
DEFAULT_CONFIG = FusedConfig()

#: most sample rows one grid step may hold: a (rows, LUT tile) block has
#: several live int32/f32 temporaries in VMEM.
MAX_BLOCK_B = 512


# ---------------------------------------------------------------------------
# fingerprints and keys
# ---------------------------------------------------------------------------

_FP: str | None = None


def kernel_fingerprint() -> str:
    """Source hash of the modules whose edits change kernel numbers.

    Same invalidation scheme as ``repro.sweep.cache._code_fingerprint``:
    cached configs were tuned against those kernels, so editing them must
    invalidate, not silently serve, stale block shapes.
    """
    global _FP
    if _FP is not None:
        return _FP
    from .fused import kernel as m1, ops as m2
    from ..core import bitpack as m3
    h = hashlib.sha256()
    for mod in (m1, m2, m3):
        try:
            with open(mod.__file__, "rb") as fh:
                h.update(fh.read())
        except OSError:
            h.update(mod.__name__.encode())
    _FP = h.hexdigest()[:16]
    return _FP


def device_kind() -> str:
    """The device the timings are valid on, as JAX names it (e.g.
    ``TPU v5 lite``): tunings transfer neither between chip generations
    nor to the CPU's interpret-mode emulation."""
    return jax.devices()[0].device_kind


def cache_key(spec_fingerprint: str, bucket: int,
              device: str | None = None) -> str:
    return f"{spec_fingerprint}:{bucket}:{device or device_kind()}"


def default_cache_path() -> Path:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return Path(env)
    return Path.home() / ".cache" / "repro" / "autotune" / \
        "fused_configs.json"


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

class AutotuneCache:
    """JSON-file cache of winning :class:`FusedConfig` per cache key.

    One flat file (atomic-rename writes) holding every tuned entry::

        {"entries": {"<spec_fp>:<bucket>:<device>": {
            "code": "<kernel fingerprint at tune time>",
            "config": {"block_b": ...},
            "timings_us": {"b256": 412.3, "b128": 455.0}}}}

    ``get`` misses (returns None) when the file is absent/corrupt, the
    stored ``code`` no longer matches :func:`kernel_fingerprint`, or the
    stored config does not parse — the caller then re-tunes.
    """

    def __init__(self, path: str | Path | None = None):
        self.path = Path(path) if path is not None else default_cache_path()
        self._entries: dict | None = None

    def _load(self) -> dict:
        if self._entries is None:
            try:
                with open(self.path) as fh:
                    data = json.load(fh)
                self._entries = dict(data.get("entries", {}))
            except (OSError, json.JSONDecodeError, AttributeError):
                self._entries = {}
        return self._entries

    def get(self, spec_fingerprint: str, bucket: int,
            device: str | None = None) -> FusedConfig | None:
        entry = self._load().get(cache_key(spec_fingerprint, bucket, device))
        if not entry or entry.get("code") != kernel_fingerprint():
            return None
        try:
            return FusedConfig.from_dict(entry["config"])
        except (KeyError, TypeError):
            return None

    def put(self, spec_fingerprint: str, bucket: int, config: FusedConfig,
            timings_us: dict[str, float] | None = None,
            device: str | None = None) -> None:
        entries = self._load()
        entries[cache_key(spec_fingerprint, bucket, device)] = {
            "code": kernel_fingerprint(),
            "config": config.to_dict(),
            "timings_us": {k: round(v, 1)
                           for k, v in (timings_us or {}).items()},
        }
        self.path.parent.mkdir(parents=True, exist_ok=True)
        tmp = self.path.with_suffix(".tmp")
        with open(tmp, "w") as fh:
            json.dump({"entries": entries}, fh, indent=1, sort_keys=True)
        tmp.replace(self.path)


# ---------------------------------------------------------------------------
# timing + tuning
# ---------------------------------------------------------------------------

def time_step(fn, x, *, iters: int = 3, timer=time.perf_counter,
              min_time_s: float = 0.0, max_iters: int = 50) -> float:
    """Best-of-``iters`` seconds of ``fn(x)`` after one untimed warmup.

    The warmup call absorbs the compile, so the measurement sees
    steady-state serving — the same protocol as
    ``serving.backends.time_backend_step`` (which delegates here).

    ``min_time_s > 0`` keeps sampling past ``iters`` (up to
    ``max_iters``) until that much measured time has accumulated:
    microsecond-scale steps get tens of reps — without it, scheduler
    jitter at small buckets swamps the real spread between candidates —
    while millisecond-scale steps stop at ``iters``.
    """
    jax.block_until_ready(fn(x))
    best, total, n = float("inf"), 0.0, 0
    while n < max(1, iters) or (total < min_time_s and n < max_iters):
        t0 = timer()
        jax.block_until_ready(fn(x))
        dt = timer() - t0
        best = min(best, dt)
        total += dt
        n += 1
    return best


def candidate_configs(bucket: int) -> list[FusedConfig]:
    """The rows-per-step sweep for one batch bucket.

    Rows at the bucket and at half of it, both capped at
    :data:`MAX_BLOCK_B` (the VMEM bound that ``tests/test_tpu_compile.py``
    checks) — kept deliberately small so startup tuning stays cheap; the
    cache amortizes it to zero on later runs.
    """
    rows = sorted({min(bucket, MAX_BLOCK_B),
                   min(max(bucket // 2, 8), MAX_BLOCK_B)}, reverse=True)
    return [FusedConfig(block_b=r) for r in rows]


def tune_fused(thresholds, mappings, tables, num_classes: int, x_probe, *,
               spec_fingerprint: str, input_frac_bits: int | None = None,
               cache: AutotuneCache | None = None,
               candidates: list[FusedConfig] | None = None,
               iters: int = 2, timer=time.perf_counter,
               min_time_s: float = 0.0,
               interpret: bool | None = None,
               force: bool = False) -> FusedConfig:
    """Pick (and persist) the fastest fused config for one bucket.

    Args:
      thresholds/mappings/tables/num_classes: the packed model operands,
        exactly as ``serving.backends.DWNModelBundle`` stages them.
      x_probe: (bucket, F) probe rows; the bucket is its leading dim.
      spec_fingerprint: ``DWNSpec.fingerprint()`` of the served model —
        the cache identity.
      input_frac_bits: PEN input quantization (None = TEN), applied
        before the kernel exactly like the serving backend does.
      cache: config cache (None = default path); hits skip timing.
      candidates: explicit sweep list (default
        :func:`candidate_configs`).
      iters / timer / min_time_s: timing knobs, injectable for
        deterministic tests (see :func:`time_step`).
      force: re-tune even on a cache hit.

    Returns the winning config (cached or freshly timed).  A candidate
    that fails to build or run raises: a kernel the device refuses is a
    fault to report, not a candidate to drop.
    """
    from .fused import ops as fused_ops
    from ..core.thermometer import quantize_fixed_point

    bucket = int(x_probe.shape[0])
    cache = cache if cache is not None else AutotuneCache()
    if not force:
        hit = cache.get(spec_fingerprint, bucket)
        if hit is not None:
            return hit
    cands = candidates if candidates is not None \
        else candidate_configs(bucket)
    x = jnp.asarray(x_probe)
    if input_frac_bits is not None:
        x = quantize_fixed_point(x, input_frac_bits)
    timings: dict[str, float] = {}
    best_cfg, best_t = None, float("inf")
    for cfg in cands:
        fwd = fused_ops.make_forward_packed(
            thresholds, mappings, tables, num_classes,
            interpret=interpret, config=cfg)
        t = time_step(fwd, x, iters=iters, timer=timer,
                      min_time_s=min_time_s)
        timings[cfg.label] = t * 1e6
        if t < best_t:
            best_cfg, best_t = cfg, t
    cache.put(spec_fingerprint, bucket, best_cfg, timings)
    return best_cfg


__all__ = [
    "AutotuneCache", "DEFAULT_CONFIG", "FusedConfig",
    "cache_key", "candidate_configs", "default_cache_path", "device_kind",
    "kernel_fingerprint", "time_step", "tune_fused",
]
