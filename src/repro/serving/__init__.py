"""Serving subsystem: pluggable DWN datapath backends, one
continuous-batching loop, and the engine that unifies DWN classification
with LM prefill/decode serving behind one submit/drain API.

Layering (each importable on its own):

    backends.py    datapath registry + per-(arch, bucket) compile cache
                   + startup bit-exactness cross-check vs the oracle
                   + per-bucket step-time estimates (StepTimeEstimator)
    scheduler.py   power-of-two bucket ladder, per-request queue/compute
                   latency accounting, the FIFO that LM decode drains
    continuous.py  the batching loop: dense packing into the ladder,
                   stepped by the sync drain() or by a scheduler thread
                   with two steps in flight, futures, SLO-aware
                   admission + deadline shedding, bounded-queue
                   backpressure
    steplog.py     phase spans of each served step on the profiler's
                   clock + a process-wide ring of per-step records
    engine.py      ServingEngine: sync submit/drain AND async
                   serve()/submit_async over either family, DWN batches
                   sharded data-parallel across the host mesh

``repro.launch.serve`` is a thin CLI over :class:`ServingEngine`;
``repro.launch.loadgen`` is the open-loop load generator that drives it
to saturation.
"""

from .backends import (Backend, BoundBackend, StepTimeEstimator,
                       available_backends, get_backend, register_backend,
                       build_dwn_model, verify_backends)
from .continuous import (AsyncRequest, ContinuousScheduler, QueueFull,
                         SLOConfig, ServeResult)
from .scheduler import MicrobatchScheduler, Request, power_of_two_buckets
from .engine import ServingEngine

__all__ = [
    "AsyncRequest", "Backend", "BoundBackend", "ContinuousScheduler",
    "MicrobatchScheduler", "QueueFull", "Request", "SLOConfig",
    "ServeResult", "ServingEngine", "StepTimeEstimator",
    "available_backends", "build_dwn_model", "get_backend",
    "power_of_two_buckets", "register_backend", "verify_backends",
]
