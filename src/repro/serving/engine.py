"""ServingEngine: one submit/drain API over both served families.

DWN archs (``family == "dwn"``) serve batched classification of their
spec's workload (``repro.workloads``: JSC, MNIST, ...) through one
datapath backend (``serving.backends``), chosen at construction, packed
into power-of-two buckets by the continuous-batching scheduler
(``serving.continuous``), and sharded data-parallel across the host mesh
with ``shard_map`` when a bucket divides the device count.  Every
non-oracle backend is cross-checked bit-exactly against the
``apply_hard`` float oracle at startup — the engine refuses to construct
a broken datapath.

LM archs serve the existing prefill + token-by-token decode loop (KV /
SSM / LRU caches) one request per step, through the FIFO queue of
``serving.scheduler``, with the same per-request queue/compute latency
accounting.  With ``dwn_head=`` an LM engine *also* serves a packed DWN
classification head on its own backbone's pooled features (``classify``
requests), so one process serves LM decode and DWN classification side
by side.  Classify requests go through the continuous-batching loop:
prompts are right-padded into one of a few (batch, length) step shapes
of ``step_tokens`` tokens (``continuous.TokenBuckets``), and one jitted
step runs the backbone, pools each prompt's features over its real
tokens, runs the head on the DWN serving backend and packs the answer
(counts, predictions and features) into one buffer.

DWN requests take one batching loop, ``ContinuousScheduler``, in either
of two modes that share the datapath and its compile/autotune caches:

* **sync facade** (``submit`` + ``drain``): the engine's own scheduler,
  never started; ``drain()`` runs its steps one at a time on the calling
  thread until the queue is empty, in admission order;
* **continuous batching** (``serve()`` / ``submit_async``): a dedicated
  scheduler thread keeps steps in flight while requests stream in,
  results complete out of order via per-request futures, deadlines are
  enforced by SLO-aware admission control, and a bounded queue exerts
  backpressure.

Usage (sync):
    engine = ServingEngine("dwn-jsc-sm", max_bucket=256)
    for xb in request_stream:
        engine.submit(xb)
    results = engine.drain()
    print(engine.report())

Usage (continuous):
    with engine.serve(slo=SLOConfig(max_queue_samples=2048)):
        futs = [engine.submit_async(xb, deadline_ms=50).future
                for xb in request_stream]
        results = [f.result() for f in futs]   # ServeResult: ok or shed
"""

from __future__ import annotations

import contextlib
import dataclasses
import sys
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_arch
from ..configs.base import ArchConfig
from ..models import api
from ..runtime.straggler import StragglerMonitor
from ..sharding.partition import Partitioner
from ..launch.mesh import make_data_mesh, make_host_mesh
from .backends import (BoundBackend, DWNModelBundle, StepTimeEstimator,
                       available_backends, get_backend, pack_answer,
                       time_backend_step, unpack_answer, verify_backends)
from . import steplog
from .continuous import (AsyncRequest, ContinuousScheduler, SLOConfig,
                         TokenBuckets)
from .scheduler import MicrobatchScheduler, Request, latency_stats


@dataclasses.dataclass(frozen=True)
class Launched:
    """A dispatched step: its packed answer, whose copy to the host is
    already issued, and how to unpack that copy."""

    answer: jax.Array
    unpack: Callable[[np.ndarray], tuple]

    def collect(self) -> tuple:
        """The step's second half: wait for the device, then for the
        answer's copy, and unpack it into per-row arrays."""
        with steplog.phase("device"):
            self.answer.block_until_ready()
        with steplog.phase("d2h"):
            return self.unpack(np.asarray(self.answer))


class ServingEngine:
    """Unified serving engine; family dispatch happens at construction.

    Args:
      arch: what to serve — an arch name or ``ArchConfig`` (``family``
        selects the path), a ``repro.dwn.DWNSpec`` (the engine builds
        the artifact lifecycle itself), or a ``repro.dwn.DWNArtifact``
        (served as-is; trained/frozen state is reused, missing stages
        are completed in place).
      backend: DWN datapath backend name, one of the registered
        backends; every bucket serves on it.  ``None`` resolves from the
        spec's validated ``datapath`` field (legacy archs bridge through
        ``DWNSpec.from_arch``, which keeps the old fused-packed
        fallback).
      max_bucket / min_bucket: the power-of-two batch-bucket ladder (LM
        engines with a ``dwn_head``: the ladder of classify prompt
        lengths).
      data_parallel: shard DWN buckets over the ("data",) host mesh with
        ``shard_map`` (buckets not divisible by the device count fall back
        to single-device execution for that bucket).
      verify: run the startup bit-exactness cross-check of every
        registered non-oracle backend against the float oracle.
      autotune: run the fused-kernel autotuner over the bucket ladder at
        startup (``backends.autotune_model``): each bucket serves the
        fastest (rows, LUTs)-per-step fused config, cache-hit from
        the persistent config cache (docs/autotune.md) or timed once on
        miss.
      reduced: LM archs: serve the tiny same-family variant.  DWN archs:
        kept for CLI symmetry (the model is never shrunk — the datapath
        is the thing being served; callers shrink the request volume).
      n_train: training rows (of the spec's workload) used to fit
        thermometer thresholds.
      prompt_len / gen / model_parallel: LM serving shape knobs.
      params: LM engines: the backbone's weights (a pytree in the
        family's layout); None = initialised from ``seed``.
      dwn_head: LM engines only — attach a packed DWN classification
        head on the backbone's pooled features (a ``DWNArtifact``, a
        checkpoint path, or a spec-preset name like ``"dwn-lm-head"``).
        ``classify`` requests then go through the continuous-batching
        loop (:meth:`serve` / :meth:`submit_async`) while LM decode
        keeps the sync queue: one engine, both request kinds, one
        process.
      step_tokens: tokens (batch x length) of each padded classify step.
    """

    def __init__(self, arch: str | ArchConfig, *,
                 backend: str | None = None,
                 max_bucket: int = 256, min_bucket: int = 8,
                 data_parallel: bool = True, verify: bool = True,
                 autotune: bool = False,
                 reduced: bool = False, n_train: int = 2000,
                 seed: int = 0, prompt_len: int = 32, gen: int = 16,
                 model_parallel: int = 1, params=None, dwn_head=None,
                 step_tokens: int = 8192):
        from ..dwn import DWNArtifact, DWNSpec, has_spec, get_spec
        self.artifact: "DWNArtifact | None" = None
        self.spec: "DWNSpec | None" = None
        if isinstance(arch, DWNArtifact):
            self.artifact, self.spec = arch, arch.spec
            cfg = self.spec.arch_config()
        elif isinstance(arch, DWNSpec):
            self.spec = arch
            cfg = arch.arch_config()
        else:
            cfg = get_arch(arch) if isinstance(arch, str) else arch
            if cfg.family == "dwn":
                # registered spec presets are the blessed route for the
                # old --arch strings; raw ArchConfigs bridge via from_arch
                self.spec = (get_spec(cfg.name) if has_spec(cfg.name)
                             else DWNSpec.from_arch(cfg))
        self.cfg = cfg
        self.seed = seed
        self.family = "dwn" if cfg.family == "dwn" else "lm"
        self.scheduler = MicrobatchScheduler(
            max_bucket=max_bucket, min_bucket=min(min_bucket, max_bucket))
        self.bit_exact: dict[str, bool] = {}
        self.tuned_configs: dict = {}
        self._drain_wall = 0.0
        self._lm_stats: list[tuple[float, float]] = []
        #: anomalous step times surface as counters in report(); fed by
        #: every DWN and classify step, sync or continuous
        self.straggler = StragglerMonitor()
        #: DWN engines: the sync facade's scheduler (``_init_dwn``)
        self._sync: ContinuousScheduler | None = None
        self._cont: ContinuousScheduler | None = None
        self.estimator: StepTimeEstimator | None = None
        #: slim async requests from finished serve() sessions + the last
        #: session's loop counters (report() merges the live session in)
        self._async_done: list[AsyncRequest] = []
        self._async_counters: dict = {}
        self.head_artifact = None
        self.head_bit_exact: bool | None = None
        #: MoE layer calls per classify step on the held-experts path
        self._moe_layers = 0
        #: report()'s "steps" block covers the steps recorded from here on
        self._step_mark = steplog.mark()
        if self.family == "dwn":
            assert dwn_head is None, \
                "dwn_head attaches to an LM engine (the head rides the " \
                "backbone); DWN archs already serve classification"
            self._init_dwn(cfg, backend, n_train, data_parallel, verify,
                           autotune)
        else:
            if reduced:
                self.cfg = cfg = cfg.reduced()
            self._init_lm(cfg, prompt_len, gen, model_parallel, params)
            if dwn_head is not None:
                self._init_dwn_head(dwn_head, verify, step_tokens)

    # ------------------------------------------------------------------
    # DWN classification path
    # ------------------------------------------------------------------

    def _init_dwn(self, cfg: ArchConfig, backend: str | None,
                  n_train: int, data_parallel: bool, verify: bool,
                  autotune: bool):
        from ..dwn import DWNArtifact
        from ..workloads import load_workload
        # the spec validated its datapath at construction; an override is
        # checked here, before any work
        backend = self.spec.datapath if backend is None else backend
        if backend not in available_backends():
            raise ValueError(f"unknown serving backend {backend!r}; "
                             f"registered: {available_backends()}")
        self.data = load_workload(self.spec.workload, n_train,
                                  max(self.scheduler.max_bucket, 512),
                                  seed=self.seed)
        # one construction path: the artifact lifecycle.  A caller-built
        # artifact is served as-is; a spec-only engine fits thresholds on
        # its own data split (exactly the pre-spec build_dwn_model init).
        art = self.artifact if self.artifact is not None \
            else DWNArtifact(self.spec)
        if art.stage == "spec":
            art.fit(self.data.x_train, seed=self.seed)
        if art.stage == "trained":
            art.freeze()
        art.pack()
        self.artifact = art
        self.model: DWNModelBundle = art.serving_model(cfg=cfg)
        self.mesh = make_data_mesh()
        self.n_data = self.mesh.shape["data"]
        self._part = Partitioner(self.mesh)
        self.data_parallel = bool(data_parallel) and self.n_data > 1
        wrap = self._shard_wrap if self.data_parallel else None
        self.backends = {name: BoundBackend(get_backend(name), self.model,
                                            wrap=wrap)
                         for name in available_backends()}
        probe = self.data.x_test[:self.scheduler.max_bucket]
        if autotune:
            # tune BEFORE anything compiles: BoundBackend jits one entry
            # per bucket and each trace binds the tuned config it sees.
            # The startup verification below then cross-checks the tuned
            # config, not the default one.
            from .backends import autotune_model
            self.tuned_configs = autotune_model(
                self.model, self.scheduler.buckets, probe,
                spec_fingerprint=self.spec.fingerprint())
        if verify:
            # probe at the largest bucket: the multi-block grid path that
            # serving actually uses is the one cross-checked, and the
            # probe's compile is the one the serve loop reuses
            self.bit_exact = verify_backends(
                self.model, list(self.backends.values()), probe)
        self.backend = self.backends[backend]
        #: the sync facade's scheduler: never started, drain() steps it on
        #: the caller's thread; no deadlines, so its order is admission
        #: order, and no bound on its queue, which drain() empties
        self._sync = ContinuousScheduler(
            self._dwn_launch, self._dwn_step,
            max_bucket=self.scheduler.max_bucket,
            min_bucket=self.scheduler.min_bucket,
            slo=SLOConfig(max_queue_samples=sys.maxsize),
            monitor=self.straggler)
        #: requests the sync facade resolved since the last drain()
        self._sync_done: list[AsyncRequest] = []

    def _shard_wrap(self, fn, bucket: int):
        """shard_map a backend step over the ("data",) mesh for one bucket;
        returns the step and its number of shards.

        Each shard packs the answer of its own rows into one contiguous
        block of the flat output, so no collective is added.  Buckets
        that don't divide the device count run unsharded, as one block
        (the ladder is powers of two, so with a power-of-two device count
        only buckets below the device count fall back).
        """
        if bucket % self.n_data != 0:
            return fn, 1
        spec_x = self._part.spec(("dwn_batch", None), name="dwn.serve.x")
        spec_answer = self._part.spec(("dwn_batch",),
                                      name="dwn.serve.answer")
        return jax.shard_map(fn, mesh=self.mesh, in_specs=(spec_x,),
                             out_specs=spec_answer,
                             check_vma=False), self.n_data

    def use_backend(self, name: str) -> None:
        """Pin the DWN datapath to the registered backend ``name``
        (compile caches are kept)."""
        assert self.family == "dwn"
        self.backend = self.backends[name]

    def warmup(self, size: int | None = None) -> None:
        """Compile + execute the active backend's bucket outside timing.

        Warms the bucket that ``size``-sample requests land in (default:
        the largest bucket) without touching the request queue or the
        latency accounting, so a serve loop's first timed request measures
        steady-state serving rather than the one-time XLA trace.  Ragged
        streams may still hit other ladder buckets inside timing — bounded
        by one compile per bucket.
        """
        if self.family == "lm":
            assert self.head_artifact is not None, \
                "warmup compiles served steps: DWN archs or a dwn_head"
            for batch, length in self.head_buckets.shapes:
                self._classify_step(np.zeros((batch, length), np.int32),
                                    np.full((batch,), length, np.int32))
            return
        if size is None:
            bucket = self.scheduler.max_bucket
        else:
            bucket = self.scheduler.bucket_for(
                min(size, self.scheduler.max_bucket))
        self._dwn_step(np.asarray(self.data.x_test[:bucket]))

    def _dwn_launch(self, x: np.ndarray) -> Launched:
        """The DWN step's first half on padded rows ``x``: copy in and
        dispatch, with the answer's copy back issued."""
        bucket = x.shape[0]
        with steplog.phase("h2d"):
            xd = jnp.asarray(x)
        with steplog.phase("dispatch"):
            backend = self.backend
            step = backend.step_for(bucket)
            before = backend.compiles[bucket]
            answer = step(xd)
            # the one copy of the step's packed answer, in flight as soon
            # as the forward ends
            answer.copy_to_host_async()
            steplog.add_compiles(backend.compiles[bucket] - before)
            steplog.add_d2h_copies(1)
        return Launched(answer, lambda buf: backend.unpack(buf, bucket))

    def _dwn_collect(self, launched: Launched):
        """The DWN step's second half: ``(counts, pred)`` per row."""
        return launched.collect()

    def _dwn_step(self, x):
        """One blocking DWN step on padded rows ``x``: ``(counts, pred)``.
        Given a step :meth:`_dwn_launch` already launched, only its second
        half: the continuous loop collects through here too, so every DWN
        answer the engine serves leaves through this method."""
        return self._dwn_collect(
            x if isinstance(x, Launched) else self._dwn_launch(x))

    # ------------------------------------------------------------------
    # LM prefill/decode path
    # ------------------------------------------------------------------

    def _init_lm(self, cfg: ArchConfig, prompt_len: int, gen: int,
                 model_parallel: int, params=None):
        self.prompt_len, self.gen = prompt_len, gen
        self.mesh = make_host_mesh(model_parallel)
        tp = self.mesh.shape["model"]
        part = Partitioner(self.mesh)
        aparams = api.abstract_params(cfg, tp)
        p_shard = part.tree_shardings(aparams, api.param_axes(cfg))
        prefill = api.make_prefill(cfg, tp, cache_len=prompt_len + gen)
        decode = api.make_decode_step(cfg, tp)
        self._jprefill = jax.jit(prefill, in_shardings=(p_shard, None))
        self._jdecode = jax.jit(decode, in_shardings=(p_shard, None, None),
                                donate_argnums=(1,))
        self.tp = tp
        if params is not None:
            self.params = jax.device_put(params, p_shard)
            return
        mod = api.module_for(cfg)
        key = jax.random.PRNGKey(self.seed)
        with self.mesh:
            self.params = jax.jit(lambda k: mod.init_params(k, cfg, tp),
                                  out_shardings=p_shard)(key)

    # ------------------------------------------------------------------
    # DWN head on the LM backbone (dwn_head=)
    # ------------------------------------------------------------------

    def _init_dwn_head(self, head, verify: bool, step_tokens: int) -> None:
        """Attach a packed DWN classification head on this engine's own
        backbone: pooled-feature extraction (``workloads.lm_head.
        pool_features`` over each prompt's real tokens — the pooling the
        head trained on) feeds the head on its spec's serving backend.
        ``classify`` requests then serve through the continuous loop.
        """
        from pathlib import Path

        from ..core.model import apply_hard
        from ..dwn import DWNArtifact, resolve_spec
        from ..workloads.lm_head import FEATS, pool_features
        if isinstance(head, DWNArtifact):
            art = head
        elif Path(str(head)).exists():
            from ..runtime.checkpoint import load_artifact
            art = load_artifact(head)
        else:
            art = DWNArtifact(resolve_spec(head))
        if art.stage == "spec":
            from ..workloads import load_workload
            data = load_workload(art.spec.workload, 512, 64, seed=self.seed)
            art.fit(data.x_train, seed=self.seed)
        if art.stage == "trained":
            art.freeze()
        art.pack()
        self.head_artifact = art
        self.head_buckets = TokenBuckets(self.scheduler.buckets, step_tokens)
        cfg, tp = self.cfg, self.tp
        head_fn = get_backend(art.spec.datapath).make_step(
            art.serving_model())
        self._classify_compiles = 0
        self._moe_layers = api.held_moe_layers(cfg)

        def traced_classify(params, tokens, lengths):
            # the python bodies run once per XLA trace: count them
            self._classify_compiles += 1
            # only real tokens route through the experts; the padding on
            # their right reaches no real position in a causal backbone
            real = jnp.arange(tokens.shape[1]) < lengths[:, None]
            cols, fallbacks = api.logit_columns_and_fallbacks(
                params, cfg, tokens, FEATS, tp=tp, token_mask=real)
            return pool_features(cols, lengths), fallbacks

        def classify_head(feats, fallbacks):
            self._classify_compiles += 1
            with jax.named_scope("dwn_head"):
                counts, pred = head_fn(feats)
            self._head_answer = (counts.shape[-1], counts.dtype, pred.dtype,
                                 ((FEATS, feats.dtype), (1, jnp.int32)))
            # the step's fallback count rides its one copy back, as one
            # more block (the count in every row)
            return pack_answer(counts, pred, feats, jnp.broadcast_to(
                fallbacks, (feats.shape[0], 1)))

        # Two programs a step: the backbone (module "jit_traced_classify",
        # as a profiler trace names it) takes every weight as an argument,
        # so its compiled form does not depend on them and the persistent
        # compile cache serves it to any later process; the head's tables
        # are constants of its own small program.
        self._jclassify = jax.jit(traced_classify)
        self._jhead = jax.jit(classify_head)
        if verify:
            # startup cross-check on the shortest step shape: the served
            # head must agree bit-exactly with the float oracle on this
            # backbone's real features
            batch, length = self.head_buckets.shapes[0]
            rng = np.random.default_rng(self.seed)
            toks = rng.integers(0, cfg.vocab_size, (batch, length))
            lens = rng.integers(1, length + 1, batch)
            counts, _, feats = self._classify_step(toks.astype(np.int32),
                                                   lens.astype(np.int32))
            oracle = np.asarray(apply_hard(art.frozen, jnp.asarray(feats)))
            self.head_bit_exact = bool(np.array_equal(counts, oracle))
            assert self.head_bit_exact, \
                "served DWN head disagrees with the apply_hard oracle"

    def _classify_launch(self, tokens: np.ndarray,
                         lengths: np.ndarray) -> Launched:
        """The classify step's first half on right-padded ``tokens (B, L)``
        with real ``lengths (B,)``: copy in and dispatch backbone ->
        pooled features -> DWN head, with the answer's one copy back
        issued.  Its collect strips the step's count of MoE layer calls
        on the worst-case buffer into the step's record."""
        with steplog.phase("h2d"):
            tokens_d, lengths_d = jnp.asarray(tokens), jnp.asarray(lengths)
        with steplog.phase("dispatch"):
            before = self._classify_compiles
            answer = self._jhead(
                *self._jclassify(self.params, tokens_d, lengths_d))
            answer.copy_to_host_async()
            steplog.add_compiles(self._classify_compiles - before)
            steplog.add_d2h_copies(1)
        head = self._head_answer

        def unpack(buf):
            counts, pred, feats, fallbacks = unpack_answer(buf, 1, *head)
            steplog.add_moe_fallbacks(int(fallbacks[0, 0]))
            return counts, pred, feats
        return Launched(answer, unpack)

    def _classify_step(self, tokens: np.ndarray, lengths: np.ndarray):
        """One blocking classify step: per-row ``(counts, pred,
        features)``."""
        return self._classify_launch(tokens, lengths).collect()

    def _lm_step(self, batch: dict) -> dict:
        cfg = self.cfg
        t0 = time.perf_counter()
        with self.mesh:
            logits, cache = self._jprefill(self.params, batch)
        logits.block_until_ready()
        t_prefill = time.perf_counter() - t0
        generated = []
        nxt = jnp.argmax(logits[:, :cfg.vocab_size],
                         -1)[:, None].astype(jnp.int32)
        t0 = time.perf_counter()
        for _ in range(self.gen):
            generated.append(np.asarray(nxt))
            with self.mesh:
                logits, cache = self._jdecode(self.params, cache,
                                              {"tokens": nxt})
            nxt = jnp.argmax(logits[:, :cfg.vocab_size],
                             -1)[:, None].astype(jnp.int32)
        t_decode = time.perf_counter() - t0
        tokens = np.concatenate(generated, 1)
        assert np.isfinite(np.asarray(logits, np.float32)).all()
        return {"tokens": tokens, "prefill_s": t_prefill,
                "decode_s_per_tok": t_decode / max(self.gen, 1)}

    # ------------------------------------------------------------------
    # unified submit / drain API
    # ------------------------------------------------------------------

    def make_request(self, size: int, seed: int = 0, *,
                     classify: bool = False) -> Any:
        """Synthesize one request payload.

        Args:
          size: samples (DWN: feature rows drawn from the test split) or
            sequences (LM: random token prompts of ``prompt_len``).
          seed: draw seed, so streams are reproducible.
          classify: LM engines with a ``dwn_head``: a request for the
            DWN head (tokens -> pooled features -> packed classify), to
            pass to :meth:`submit_async`.

        Returns the payload in the shape :meth:`submit` expects.
        """
        rng = np.random.default_rng(seed)
        if self.family == "dwn":
            sel = rng.integers(0, self.data.x_test.shape[0], size)
            return self.data.x_test[sel]
        if classify:
            assert self.head_artifact is not None, \
                "classify requests need dwn_head= at construction"
            return {"tokens": rng.integers(
                0, self.cfg.vocab_size,
                (size, self.prompt_len)).astype(np.int32),
                "classify": True}
        key = jax.random.PRNGKey(seed)
        batch = {"tokens": np.asarray(jax.random.randint(
            key, (size, self.prompt_len), 0, self.cfg.vocab_size))}
        if self.cfg.family == "encdec":
            batch["frames"] = jax.random.normal(
                key, (size, self.cfg.enc_frames, self.cfg.d_model),
                jnp.bfloat16) * 0.1
        if self.cfg.family == "vlm":
            batch["patches"] = jax.random.normal(
                key, (size, self.cfg.num_patches, self.cfg.d_model),
                jnp.bfloat16) * 0.02
        return batch

    def submit(self, payload: Any) -> Request:
        """Enqueue one request (admission order is service order).

        Args:
          payload: (size, F) feature array (DWN) or an LM batch dict with
            a (size, prompt_len) ``tokens`` entry.  Classify requests go
            through :meth:`submit_async`.

        Returns the queued :class:`Request` (latency fields filled in by
        the drain that serves it; ``queue_ms``/``compute_ms`` are
        milliseconds).  DWN rows are packed densely into steps, so a
        request may share a step with others or span several.
        """
        if self.family == "dwn":
            payload = np.asarray(payload)
            req = self._sync.submit(payload, payload.shape[0])
            req.future.add_done_callback(
                lambda _, r=req: self._sync_done.append(r))
            return req
        if payload.get("classify"):
            raise ValueError("classify requests are served by the "
                             "continuous loop: engine.serve() and "
                             "submit_async()")
        size = int(np.asarray(payload["tokens"]).shape[0])
        return self.scheduler.submit(payload, size)

    def drain(self) -> list[Request]:
        """Serve every queued request, one step at a time on this thread;
        returns them in completion order once all results are ready."""
        t0 = time.perf_counter()
        if self.family == "dwn":
            while self._sync.step_once():
                pass
            done, self._sync_done = self._sync_done, []
        else:
            done = self.scheduler.drain_serial(self._lm_step)
            self._lm_stats.extend((r.result["prefill_s"],
                                   r.result["decode_s_per_tok"])
                                  for r in done)
        self._drain_wall += time.perf_counter() - t0
        return done

    # ------------------------------------------------------------------
    # continuous-batching async API (DWN classification, and the DWN head)
    # ------------------------------------------------------------------

    def start_serving(self, *, slo: SLOConfig | None = None) -> None:
        """Start the continuous-batching loop (a dedicated thread).

        Requests then stream in through :meth:`submit_async` and complete
        out of order via their futures; batch formation happens at step
        boundaries over the same bucket ladder (compile + autotune caches
        shared with the sync facade).  Admission control's step-time
        estimates seed from one probe of the served backend at
        ``max_bucket``; every step refines them online.  An LM engine
        serves its ``dwn_head``'s classify requests here, in the
        (batch, length) step shapes of ``head_buckets``; deadlines are
        enforced at expiry and completion, with no admission estimate.
        """
        assert self._cont is None, "serving loop already running"
        if self.family == "lm":
            assert self.head_artifact is not None, \
                "an LM engine serves classify requests: pass dwn_head="
            self._cont = ContinuousScheduler(
                self._classify_launch, Launched.collect, slo=slo,
                monitor=self.straggler, batching=self.head_buckets)
            self._cont.start()
            return
        if self.estimator is None:
            self.estimator = StepTimeEstimator()
            probe = jnp.asarray(self.data.x_test[:self.scheduler.max_bucket])
            self.estimator.seed(
                self.scheduler.max_bucket,
                time_backend_step(self.backend, probe, iters=2))
        self._cont = ContinuousScheduler(
            self._dwn_launch, self._dwn_step,
            max_bucket=self.scheduler.max_bucket,
            min_bucket=self.scheduler.min_bucket, slo=slo,
            estimator=self.estimator, monitor=self.straggler)
        self._cont.start()

    def stop_serving(self, *, drain: bool = True) -> None:
        """Stop the loop; ``drain=True`` serves the queue first.  Loop
        counters survive in :meth:`report` (sessions accumulate)."""
        assert self._cont is not None, "serving loop not running"
        self._cont.stop(drain=drain)
        self._async_done.extend(self._cont.completed)
        self._async_counters = self._cont.counters()
        self._cont = None

    @contextlib.contextmanager
    def serve(self, *, slo: SLOConfig | None = None):
        """Context manager over one continuous-batching session::

            with engine.serve(slo=SLOConfig(deadline_default_ms=50)):
                req = engine.submit_async(xb, deadline_ms=20)
                res = req.future.result()      # ServeResult
        """
        self.start_serving(slo=slo)
        try:
            yield self
        finally:
            self.stop_serving()

    def submit_async(self, payload: Any, *,
                     deadline_ms: float | None = None, priority: int = 0,
                     timeout: float | None = None) -> AsyncRequest:
        """Admit one request into the continuous-batching loop.

        Requires :meth:`start_serving` / :meth:`serve`.  Returns the
        :class:`AsyncRequest`; its ``future`` resolves to a
        ``ServeResult`` — ``ok`` with ``value == (counts, pred)``, or
        typed shed when the deadline was unmeetable (admission), expired
        in queue, or missed at completion.  Raises ``QueueFull`` after
        ``timeout`` when backpressure applies.

        LM engines with a ``dwn_head`` take classify requests: an int
        (n, L) token array (or a dict with it under ``tokens``), n
        prompts of L tokens each; ``value == (counts, pred, features)``.
        """
        assert self._cont is not None, \
            "submit_async needs the serving loop: use engine.serve()"
        if self.family == "lm":
            if isinstance(payload, dict):
                payload = payload["tokens"]
            payload = np.asarray(payload)
            if payload.ndim != 2 or payload.dtype.kind not in "iu":
                raise ValueError(f"a classify request is an int (n, L) "
                                 f"token array, got {payload.dtype} "
                                 f"{payload.shape}")
            self.head_buckets.shape_for(payload.shape[1])
            payload = payload.astype(np.int32)
        payload = np.asarray(payload)
        return self._cont.submit(payload, payload.shape[0],
                                 deadline_ms=deadline_ms,
                                 priority=priority, timeout=timeout)

    # ------------------------------------------------------------------
    # reporting
    # ------------------------------------------------------------------

    def compile_counts(self) -> dict[str, dict[int, int]]:
        """Per-backend {bucket: XLA traces} (DWN; empty for LM)."""
        if self.family != "dwn":
            return {}
        return {name: dict(b.compiles)
                for name, b in self.backends.items() if b.compiles}

    def _steps_summary(self) -> dict:
        """``steplog``'s summary of the steps served in this process since
        the engine was built (as many of them as its ring still holds)."""
        n = min(steplog.mark() - self._step_mark, steplog.RING.capacity)
        return steplog.last(n).summary(self._moe_layers)

    def report(self) -> dict:
        """JSON-able serving report over everything served so far.

        Units: ``throughput_samples_per_s`` is samples (DWN) or sequences
        (LM) per wall-clock second (sync drains + async session wall);
        ``latency.{queue,compute,total}_ms`` are per-request millisecond
        percentiles (p50/p99/p999) over *served* requests — shed requests
        are excluded from latency and counted in ``shed``;
        ``queue_depth`` / ``shed`` / ``straggler`` cover both serving
        modes; LM ``prefill_s`` / ``decode_s_per_tok`` are seconds.  DWN
        ``steps`` summarises the per-step phase record (``steplog``): mean
        and p99 ms of each phase, occupancy in %, the share of steps
        launched while another was in flight (``overlap_pct``), compiles,
        and the count of steps it covers; with a MoE backbone under a DWN
        head, ``moe_fallbacks`` and ``moe_fallback_pct``, the MoE layer
        calls whose held experts ran on the worst-case buffer.
        """
        async_all = list(self._async_done)
        async_counters = dict(self._async_counters)
        if self._cont is not None:
            async_all += list(self._cont.completed)
            async_counters = self._cont.counters()
        async_ok = [r for r in async_all if r.shed is None]
        shed = [r for r in async_all if r.shed is not None]
        # the sync facade's queue: DWN rows in its scheduler, LM decode in
        # the FIFO; the sync scheduler has no deadlines, so sheds nothing
        sync = self._sync
        reqs: Sequence[Request] = (
            list(self.scheduler.completed)
            + (list(sync.completed) if sync is not None else []) + async_ok)
        served = sum(r.size for r in reqs)
        wall = self._drain_wall + async_counters.get("session_wall_s", 0.0)
        shed_by: dict[str, int] = {}
        for r in shed:
            shed_by[r.shed] = shed_by.get(r.shed, 0) + 1
        finished = len(reqs) + len(shed)
        out = {
            "arch": self.cfg.name,
            "family": self.cfg.family,
            "requests": len(reqs),
            "served": served,
            "throughput_samples_per_s":
                round(served / wall, 1) if wall else 0.0,
            "latency": latency_stats(list(reqs)),
            "queue_depth": {
                "pending": self.scheduler.pending
                + (sync.pending if sync is not None else 0)
                + (self._cont.pending if self._cont is not None else 0),
                "max_requests": max(
                    self.scheduler.max_pending,
                    sync.max_depth_requests if sync is not None else 0,
                    async_counters.get("queue_depth_max_requests", 0)),
            },
            "shed": {
                "requests": len(shed),
                "rate": round(len(shed) / finished, 4) if finished
                else 0.0,
                "by_reason": shed_by,
            },
            "straggler": {
                "window": len(self.straggler.times),
                "events": len(self.straggler.events),
                "last_z": round(self.straggler.events[-1].z, 2)
                if self.straggler.events else None,
            },
        }
        if async_counters:
            out["async"] = async_counters
            if self.estimator is not None:
                out["async"]["step_estimates_ms"] = \
                    self.estimator.snapshot()
        if self.family == "dwn":
            out.update({
                "mode": "dwn-classify",
                "datapath": self.backend.name,
                "backends": available_backends(),
                "bit_exact_vs_oracle": self.bit_exact,
                "buckets": list(self.scheduler.buckets),
                "compiles": self.compile_counts(),
                "data_parallel": self.data_parallel,
                "devices": self.n_data,
                "luts": self.cfg.dwn_luts,
                "bits_per_feature": self.cfg.dwn_bits,
                "spec": self.spec.to_dict(),
                "spec_fingerprint": self.spec.fingerprint(),
                "artifact_stage": self.artifact.stage,
                "steps": self._steps_summary(),
            })
            if self.tuned_configs:
                out["autotune"] = {int(b): cfg.to_dict()
                                   for b, cfg in self.tuned_configs.items()}
        else:
            out.update({
                "mode": "lm-generate",
                "prompt_len": self.prompt_len,
                "generated": self.gen,
                "model_parallel": self.tp,
            })
            if self._lm_stats:
                out["prefill_s"] = round(
                    float(np.mean([s[0] for s in self._lm_stats])), 3)
                out["decode_s_per_tok"] = round(
                    float(np.mean([s[1] for s in self._lm_stats])), 4)
            if self.head_artifact is not None:
                out["steps"] = self._steps_summary()
                out["dwn_head"] = {
                    "spec": self.head_artifact.spec.to_dict(),
                    "spec_fingerprint":
                        self.head_artifact.spec.fingerprint(),
                    "artifact_stage": self.head_artifact.stage,
                    "bit_exact_vs_oracle": self.head_bit_exact,
                    "served": sum(r.size for r in async_ok),
                }
        return out


__all__ = ["ServingEngine"]
