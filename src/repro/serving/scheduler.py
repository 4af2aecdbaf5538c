"""The power-of-two bucket ladder, requests and their latency
accounting, and the FIFO queue that LM decode is served from.

Serving traffic is ragged: requests carry anywhere from one sample to
thousands.  Compiling one XLA executable per observed batch size would
recompile constantly, and padding everything to one giant batch wastes
compute on small requests.  Every batched step is therefore padded up
to the smallest power-of-two bucket that holds it, so the set of shapes
XLA ever sees is the fixed ladder ``{min_bucket, 2*min_bucket, ...,
max_bucket}`` — bounding JIT recompiles to at most one per (backend,
bucket).  The batching itself (dense packing, oversize requests in
max-bucket chunks, one loop for the sync and the continuous mode) lives
in ``serving.continuous``.

Every request records wall-clock (``time.perf_counter`` — monotonic, the
correct timer for sub-ms latencies) for **queue** time (submit -> first
step's start) and **compute** time (first step's start -> results
ready) separately, so a serving report can distinguish "waiting behind
other traffic" from "the datapath is slow".

:class:`MicrobatchScheduler` holds the ladder an engine serves on and a
FIFO of opaque payloads served one request per step (``drain_serial``:
LM prefill/decode), in strict admission order.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable

import numpy as np


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (bucket math lives in this module)."""
    p = 1
    while p < n:
        p *= 2
    return p


def power_of_two_buckets(min_bucket: int, max_bucket: int) -> tuple[int, ...]:
    """The bucket ladder: powers of two in [min_bucket, max_bucket]."""
    assert min_bucket > 0 and max_bucket >= min_bucket
    assert min_bucket & (min_bucket - 1) == 0, min_bucket
    assert max_bucket & (max_bucket - 1) == 0, max_bucket
    out, b = [], min_bucket
    while b <= max_bucket:
        out.append(b)
        b *= 2
    return tuple(out)


def bucket_for(n: int, buckets: tuple[int, ...]) -> int:
    """Smallest ladder bucket holding n samples (n <= buckets[-1])."""
    assert 0 < n <= buckets[-1], (n, buckets)
    for b in buckets:
        if n <= b:
            return b
    raise AssertionError  # unreachable: ladder ends at max_bucket


@dataclasses.dataclass
class Request:
    """One serving request plus its latency accounting."""

    rid: int
    payload: Any                       # (size, F) features | LM batch dict
    size: int                          # samples (DWN) / sequences (LM)
    t_submit: float
    t_start: float = 0.0               # first step's start
    t_done: float = 0.0                # last result ready
    buckets: tuple = ()                # bucket(s) this request ran in
    result: Any = None

    @property
    def queue_ms(self) -> float:
        return (self.t_start - self.t_submit) * 1e3

    @property
    def compute_ms(self) -> float:
        return (self.t_done - self.t_start) * 1e3

    @property
    def total_ms(self) -> float:
        return (self.t_done - self.t_submit) * 1e3


class MicrobatchScheduler:
    """The power-of-two bucket ladder and an admission-order FIFO served
    one request per step.

    ``timer`` is injectable (default ``time.perf_counter``) so latency
    attribution is testable with a deterministic clock.
    """

    def __init__(self, *, max_bucket: int = 256, min_bucket: int = 8,
                 timer: Callable[[], float] = time.perf_counter):
        self.buckets = power_of_two_buckets(min_bucket, max_bucket)
        self.min_bucket = min_bucket
        self.max_bucket = max_bucket
        self._timer = timer
        self._queue: deque[Request] = deque()
        self._next_rid = 0
        #: high-water mark of queued requests (serving report observable)
        self.max_pending = 0
        #: accounting history: slim copies (payload/result dropped) so a
        #: long-lived server's latency stats don't pin every array served.
        #: Full requests — payloads and results included — are returned to
        #: the caller by the drain call that served them.
        self.completed: list[Request] = []

    # -- admission ----------------------------------------------------------

    def submit(self, payload: Any, size: int | None = None) -> Request:
        if size is None:
            size = int(np.asarray(payload).shape[0])
        req = Request(rid=self._next_rid, payload=payload, size=size,
                      t_submit=self._timer())
        self._next_rid += 1
        self._queue.append(req)
        self.max_pending = max(self.max_pending, len(self._queue))
        return req

    @property
    def pending(self) -> int:
        return len(self._queue)

    def bucket_for(self, n: int) -> int:
        """Smallest ladder bucket holding n samples (n <= max_bucket)."""
        return bucket_for(n, self.buckets)

    # -- draining -----------------------------------------------------------

    def drain_serial(self, step: Callable) -> list[Request]:
        """Serve queued requests one per step (LM prefill/decode path).

        ``step(payload)`` returns the request's result and blocks until
        ready; returns the requests in completion order.
        """
        done: list[Request] = []
        while self._queue:
            req = self._queue.popleft()
            req.t_start = self._timer()
            req.result = step(req.payload)
            req.t_done = self._timer()
            req.buckets = (req.size,)
            done.append(req)
        self.completed.extend(
            dataclasses.replace(r, payload=None, result=None) for r in done)
        return done


def percentiles(values, *, round_to: int = 3) -> dict:
    """{p50, p99, p999, mean} over a value sequence (shared schema between
    the per-backend rows and the load-harness curve levels)."""
    vals = np.asarray(list(values), np.float64)
    return {"p50": round(float(np.percentile(vals, 50)), round_to),
            "p99": round(float(np.percentile(vals, 99)), round_to),
            "p999": round(float(np.percentile(vals, 99.9)), round_to),
            "mean": round(float(vals.mean()), round_to)}


def latency_stats(requests: list[Request]) -> dict:
    """Queue/compute/total latency percentiles over completed requests."""
    if not requests:
        return {}
    return {kind: percentiles(getattr(r, kind) for r in requests)
            for kind in ("queue_ms", "compute_ms", "total_ms")}


__all__ = ["MicrobatchScheduler", "Request", "bucket_for", "latency_stats",
           "next_pow2", "percentiles", "power_of_two_buckets"]
