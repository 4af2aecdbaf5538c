"""Pluggable DWN datapath backends.

A *backend* is one implementation of the serving datapath
``features -> (class counts, argmax)`` over a frozen DWN.  All backends
share the same hardware semantics (paper §IV); they differ in how the
bits move:

    fused-packed   one Pallas ``pallas_call``: thermometer levels ->
                   LUT layer(s) -> class counts + argmax, no bit tensor
                   in HBM (``kernels/fused/kernel.py``)
    packed-xla     the same packed uint32 word format, but expressed as
                   plain XLA ops via ``core.bitpack`` /
                   ``apply_hard_packed`` — no ``pallas_call``, so it runs
                   anywhere XLA does and is the data-parallel reference
    float-oracle   ``apply_hard``: every bit a float32.  Slow, but the
                   bit-exactness oracle every other backend is checked
                   against at engine startup.

``BoundBackend`` binds a backend to one model and owns the
per-(arch, batch-bucket) compile cache: each bucket size gets exactly one
``jax.jit`` entry, and the number of XLA traces actually taken is counted
so the scheduler's no-recompile guarantee is testable.  Every jitted step
returns its answer packed into one 32-bit buffer (:func:`pack_answer`),
whose device layout is the host's linear order, so that the engine
fetches each step's answer in a single device-to-host transfer;
``BoundBackend.unpack`` restores counts and predictions on the host.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..configs.base import ArchConfig
from ..core.classifier import predict
from ..core.model import DWNConfig, FrozenDWN, apply_hard, apply_hard_packed
from ..core.thermometer import quantize_fixed_point
from ..kernels import autotune
from ..kernels.fused import ops as fused_ops

Array = jax.Array


# ---------------------------------------------------------------------------
# model bundle
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DWNModelBundle:
    """A frozen DWN plus its device-resident operand arrays.

    Built once per served arch; every backend reads from the same bundle so
    cross-backend comparisons are comparisons of *datapaths*, not weights.
    """

    cfg: ArchConfig
    dcfg: DWNConfig
    frozen: FrozenDWN
    thresholds: Array                 # (F, T)
    mappings: list                    # per layer (m, n) int32
    tables: list                      # per layer (m, 2^n) int32
    #: bucket -> tuned fused-kernel config (``autotune_model`` fills it;
    #: empty = every bucket serves on the default blocks).  Configs are
    #: resolved at trace time, so tune *before* the first step compiles.
    tuned_configs: dict = dataclasses.field(default_factory=dict)

    @property
    def num_classes(self) -> int:
        return self.dcfg.num_classes

    @property
    def arch_name(self) -> str:
        return self.cfg.name


def build_dwn_model(cfg: ArchConfig, x_train: np.ndarray,
                    seed: int = 0) -> DWNModelBundle:
    """Deprecated shim: init + freeze an arch's DWN and stage operands.

    The canonical construction path is the ``repro.dwn`` lifecycle::

        spec = DWNSpec.from_arch(cfg)            # or a spec preset
        bundle = (DWNArtifact(spec).fit(x_train, seed=seed)
                  .freeze().pack().serving_model())

    This shim delegates there (bit-identical output — same init PRNG,
    same freeze) and warns.
    """
    import warnings
    warnings.warn(
        "serving.backends.build_dwn_model is deprecated; construct a "
        "repro.dwn.DWNSpec and use DWNArtifact(spec).fit(...).freeze()"
        ".pack().serving_model() instead", DeprecationWarning,
        stacklevel=2)
    from ..dwn import DWNArtifact, DWNSpec
    art = DWNArtifact(DWNSpec.from_arch(cfg)).fit(x_train, seed=seed)
    return art.freeze().pack().serving_model(cfg=cfg)


# ---------------------------------------------------------------------------
# backend protocol + registry
# ---------------------------------------------------------------------------

class Backend:
    """One DWN serving datapath.  Subclass + :func:`register_backend`.

    ``make_step(model)`` returns ``fn(x) -> (counts, pred)`` for a feature
    batch ``x (B, F)``, both of 32-bit dtypes; the callable must be pure
    and jit-able (it is wrapped in ``jax.jit`` — and, data-parallel, in
    ``shard_map`` — by :class:`BoundBackend`).
    """

    name: str = "?"
    is_oracle: bool = False

    def make_step(self, model: DWNModelBundle) -> Callable:
        raise NotImplementedError


_REGISTRY: dict[str, Backend] = {}


def register_backend(cls):
    """Class decorator: register a Backend subclass under ``cls.name``."""
    assert cls.name not in _REGISTRY, cls.name
    _REGISTRY[cls.name] = cls()
    return cls


def get_backend(name: str) -> Backend:
    if name not in _REGISTRY:
        raise KeyError(f"unknown serving backend {name!r}; "
                       f"registered: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def available_backends() -> list[str]:
    return sorted(_REGISTRY)


@register_backend
class FusedPackedBackend(Backend):
    """Fused Pallas kernel, bits VMEM-resident end-to-end.

    The kernel's rows and LUTs per grid step come from the model's
    ``tuned_configs`` (per batch bucket, filled by
    :func:`autotune_model`); buckets without a tuned entry serve on
    ``autotune.DEFAULT_CONFIG``'s blocks.  The config is resolved at
    trace time — ``BoundBackend`` jits once per bucket, so each bucket's
    trace closes over its own config.
    """

    name = "fused-packed"

    def make_step(self, model: DWNModelBundle) -> Callable:
        fwd_cache: dict = {}

        def fwd_for(config):
            if config not in fwd_cache:
                # fn() below runs inside a jit trace; without this guard
                # omnistaging would stage the one-time operand prep into
                # whichever bucket traces first and leak its tracers into
                # the memoized closure
                with jax.ensure_compile_time_eval():
                    fwd_cache[config] = fused_ops.make_forward_packed(
                        model.thresholds, model.mappings, model.tables,
                        model.num_classes, config=config)
            return fwd_cache[config]

        # PEN models quantize inputs to the (1, n) grid before the
        # comparator bank (apply_hard semantics); the fused kernel sees
        # already-quantized rows so it stays bit-exact vs the oracle.
        frac = model.frozen.input_frac_bits

        def fn(x: Array):
            # x.shape[0] is static at trace time: per-bucket jit entries
            # each bind their bucket's tuned config here
            fwd = fwd_for(model.tuned_configs.get(x.shape[0]))
            if frac is not None:
                x = quantize_fixed_point(x, frac)
            counts, pred = fwd(x)
            return counts.astype(jnp.float32), pred
        return fn


@register_backend
class PackedXLABackend(Backend):
    """Packed uint32 words through plain XLA ops (no pallas_call)."""

    name = "packed-xla"

    def make_step(self, model: DWNModelBundle) -> Callable:
        frozen = model.frozen

        def fn(x: Array):
            counts = apply_hard_packed(frozen, x)
            return counts, predict(counts)
        return fn


@register_backend
class FloatOracleBackend(Backend):
    """``apply_hard``: the float bit-exactness oracle."""

    name = "float-oracle"
    is_oracle = True

    def make_step(self, model: DWNModelBundle) -> Callable:
        frozen = model.frozen

        def fn(x: Array):
            counts = apply_hard(frozen, x)
            return counts, predict(counts)
        return fn


# ---------------------------------------------------------------------------
# the packed answer: one 32-bit buffer per step
# ---------------------------------------------------------------------------

def pack_answer(counts: Array, pred: Array, *extra: Array) -> Array:
    """``counts (B, C)`` and ``pred (B,)`` as one ``int32[(C+1)·B]``.

    Class-major blocks ``[counts[:, 0], ..., counts[:, C-1], pred]``: a
    1-D array, so the chip holds it in the host's linear order and its
    copy needs no un-tiling or transpose.  Every array is bitcast, not
    converted, so :func:`unpack_answer` restores them exactly.  Each
    ``extra`` (B, k) 32-bit array follows as k further blocks.
    """
    arrays = (counts, pred) + extra
    for a in arrays:
        if jnp.dtype(a.dtype).itemsize != 4:
            raise TypeError(f"a packed answer holds 32-bit arrays, got "
                            f"{a.dtype}")
    blocks = [lax.bitcast_convert_type(a, jnp.int32) for a in arrays]
    blocks = [b.T if b.ndim == 2 else b[None] for b in blocks]
    return jnp.concatenate(blocks, axis=0).reshape(-1)


def unpack_answer(buf: np.ndarray, blocks: int, classes: int,
                  counts_dtype, pred_dtype, extra=()):
    """Invert :func:`pack_answer` on the host: ``(counts (B, C), pred
    (B,), *extra)`` from ``blocks`` packed answers laid end to end (one
    per data-parallel shard, each over its own rows, in row order).
    ``extra`` gives each extra array's (width, dtype)."""
    a = buf.reshape(blocks, classes + 1 + sum(w for w, _ in extra), -1)
    counts = a[:, :classes].view(counts_dtype).transpose(0, 2, 1)
    out = [counts.reshape(-1, classes),
           a[:, classes].view(pred_dtype).reshape(-1)]
    row = classes + 1
    for width, dtype in extra:
        block = a[:, row:row + width].view(dtype).transpose(0, 2, 1)
        out.append(block.reshape(-1, width))
        row += width
    return tuple(out)


# ---------------------------------------------------------------------------
# bound backend: per-(arch, bucket) compile cache
# ---------------------------------------------------------------------------

class BoundBackend:
    """A backend bound to one model, with a per-bucket compile cache.

    ``step_for(bucket)`` returns the jitted step for that batch-bucket,
    compiling at most once per bucket: ``x (B, F) -> int32[(C+1)·B]``,
    the step's answer packed by :func:`pack_answer`; ``unpack`` turns
    the host copy of that buffer back into ``(counts, pred)``, and
    calling the bound backend does both.  ``wrap(fn, bucket)``
    (optional, supplied by the engine) may interpose ``shard_map`` for
    data-parallel buckets; it returns the wrapped step and the number of
    shards, each of which packs its own rows.  ``compiles`` maps bucket
    -> number of XLA traces taken, the observable the scheduler tests
    pin down.
    """

    def __init__(self, backend: Backend, model: DWNModelBundle, *,
                 wrap: Callable | None = None):
        self.backend = backend
        self.model = model
        self._fn = backend.make_step(model)
        self._wrap = wrap
        self._jitted: dict[int, Callable] = {}
        #: bucket -> shards, and the (classes, counts dtype, pred dtype)
        #: of its answer (known once traced)
        self._shards: dict[int, int] = {}
        self._answer: dict[int, tuple] = {}
        self.compiles: dict[int, int] = {}

    @property
    def name(self) -> str:
        return self.backend.name

    @property
    def is_oracle(self) -> bool:
        return self.backend.is_oracle

    def step_for(self, bucket: int) -> Callable:
        if bucket not in self._jitted:
            self.compiles[bucket] = 0
            inner = self._fn

            def traced(x, _bucket=bucket):
                # the python body runs once per XLA trace: count them
                self.compiles[_bucket] += 1
                with jax.named_scope("dwn_forward"):
                    counts, pred = inner(x)
                self._answer[_bucket] = (counts.shape[-1], counts.dtype,
                                         pred.dtype)
                return pack_answer(counts, pred)

            fn, shards = traced, 1
            if self._wrap is not None:
                fn, shards = self._wrap(fn, bucket)
            self._shards[bucket] = shards
            self._jitted[bucket] = jax.jit(fn)
        return self._jitted[bucket]

    def unpack(self, buf: np.ndarray, bucket: int):
        """``(counts (B, C), pred (B,))`` in the backend's dtypes from the
        host copy of ``step_for(bucket)``'s answer."""
        return unpack_answer(buf, self._shards[bucket],
                             *self._answer[bucket])

    def __call__(self, x: Array):
        bucket = x.shape[0]
        return self.unpack(np.asarray(self.step_for(bucket)(x)), bucket)


# ---------------------------------------------------------------------------
# per-bucket step-time estimates (admission control's model of the device)
# ---------------------------------------------------------------------------

class StepTimeEstimator:
    """Per-bucket step wall-time estimates for SLO-aware admission.

    The continuous-batching loop needs to answer "how long until this
    request could complete?" *before* serving it.  The estimate has two
    sources, in order of freshness:

    * a **seed**: one ``time_backend_step`` probe of the served backend
      at ``max_bucket`` when the loop starts (step time is
      overhead-dominated at these model sizes, so one bucket's time is a
      usable prior for the whole ladder);
    * an **online EWMA** over the actual step times the loop observes
      (``update`` after every step), which quickly overrides the seed
      and tracks drift (thermal, contention, interpret-vs-compiled).

    ``estimate`` returns seconds or None when nothing is known for the
    bucket (or any larger one — a larger bucket's time upper-bounds a
    smaller one's here, so it stands in rather than admit blindly).
    """

    def __init__(self, *, alpha: float = 0.25):
        self.alpha = alpha
        self._seed: dict[int, float] = {}
        self._ewma: dict[int, float] = {}
        self.updates = 0

    def seed(self, bucket: int, seconds: float) -> None:
        """Install a calibration prior (ignored once EWMA data exists)."""
        self._seed[int(bucket)] = float(seconds)

    def update(self, bucket: int, seconds: float) -> None:
        """Fold one observed step time into the bucket's EWMA."""
        b = int(bucket)
        prev = self._ewma.get(b)
        self._ewma[b] = (seconds if prev is None
                         else prev + self.alpha * (seconds - prev))
        self.updates += 1

    def estimate(self, bucket: int) -> float | None:
        """Best current estimate (s) for one step at ``bucket``, or None."""
        b = int(bucket)
        for table in (self._ewma, self._seed):
            if b in table:
                return table[b]
        # fall back to the nearest known larger bucket (upper bound)
        for table in (self._ewma, self._seed):
            larger = [v for k, v in table.items() if k > b]
            if larger:
                return min(larger)
        return None

    def snapshot(self) -> dict:
        """JSON-able {bucket: est_ms} view for reports."""
        buckets = sorted(set(self._seed) | set(self._ewma))
        return {int(b): round((self.estimate(b) or 0.0) * 1e3, 4)
                for b in buckets}


# ---------------------------------------------------------------------------
# timing a bound step, tuning the fused kernel
# ---------------------------------------------------------------------------

def time_backend_step(bound: "BoundBackend", x: Array, *,
                      iters: int = 3) -> float:
    """Best-of-``iters`` seconds of one bound step at x's bucket size:
    the jitted step with its packed answer, as the engine serves it.

    The first (untimed) call warms the (backend, bucket) compile cache,
    so the measurement sees steady-state serving, exactly like a running
    server would.  The timing loop itself is ``autotune.time_step`` —
    the same machinery the kernel autotuner sweeps candidates with —
    with a 1 ms accumulation floor so microsecond-scale steps (small
    models, small buckets) are timed over enough reps to beat scheduler
    jitter.
    """
    return autotune.time_step(bound.step_for(x.shape[0]), x, iters=iters,
                              min_time_s=1e-3)


def autotune_model(model: DWNModelBundle, buckets, x_probe, *,
                   spec_fingerprint: str,
                   cache: "autotune.AutotuneCache | None" = None,
                   iters: int = 5, timer=None,
                   force: bool = False) -> dict:
    """Fill ``model.tuned_configs`` with the fastest fused config per
    bucket (cache-hit first, timed sweep on miss).

    Must run before the first fused step compiles: ``BoundBackend`` jits
    one entry per bucket and each trace binds the config it sees then.

    Args:
      model: the served bundle; mutated in place.
      buckets: bucket ladder to tune (e.g. ``scheduler.buckets``).
      x_probe: (>= max(buckets), F) probe rows; each bucket tunes on its
        leading slice.
      spec_fingerprint: ``DWNSpec.fingerprint()`` — the cache identity.
      cache / iters / timer / force: passed to ``autotune.tune_fused``.

    Returns {bucket: FusedConfig} (also left on the model).
    """
    cache = cache if cache is not None else autotune.AutotuneCache()
    kwargs = {} if timer is None else {"timer": timer}
    for bucket in buckets:
        cfg = autotune.tune_fused(
            model.thresholds, model.mappings, model.tables,
            model.num_classes, jnp.asarray(x_probe[:bucket]),
            spec_fingerprint=spec_fingerprint,
            input_frac_bits=model.frozen.input_frac_bits,
            cache=cache, iters=iters, min_time_s=1e-3, force=force,
            **kwargs)
        model.tuned_configs[bucket] = cfg
    return dict(model.tuned_configs)


# ---------------------------------------------------------------------------
# startup cross-check
# ---------------------------------------------------------------------------

def verify_backends(model: DWNModelBundle,
                    backends: Sequence[BoundBackend],
                    x_probe: np.ndarray) -> dict[str, bool]:
    """Bit-exactness gate: every non-oracle backend vs the float oracle.

    Runs each backend on the same probe batch (through its bucket cache,
    so the compile is reused by serving) and compares counts *and*
    predictions exactly.  Raises ``RuntimeError`` on any divergence —
    refusing to serve a broken datapath — and returns {name: True} for
    the checked backends otherwise.
    """
    x = jnp.asarray(x_probe)
    oracle = get_backend("float-oracle")
    oracle_bound = next((b for b in backends if b.is_oracle),
                        BoundBackend(oracle, model))
    counts_ref, pred_ref = oracle_bound(x)
    results: dict[str, bool] = {}
    for b in backends:
        if b.is_oracle:
            continue
        counts, pred = b(x)
        ok = (np.array_equal(np.asarray(counts, np.float32),
                             np.asarray(counts_ref, np.float32))
              and np.array_equal(pred, pred_ref))
        results[b.name] = bool(ok)
        if not ok:
            raise RuntimeError(
                f"serving backend {b.name!r} diverged from the apply_hard "
                f"oracle on arch {model.arch_name!r}; refusing to serve a "
                f"broken datapath")
    return results


__all__ = [
    "Backend", "BoundBackend", "DWNModelBundle", "StepTimeEstimator",
    "autotune_model", "available_backends", "build_dwn_model",
    "get_backend", "pack_answer", "register_backend", "time_backend_step",
    "unpack_answer", "verify_backends",
]
