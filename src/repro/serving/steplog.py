"""Phase times of every served DWN step: profiler spans and an in-memory ring.

A served step runs in six phases, in this order:

==================  ======================================================
span                covers
==================  ======================================================
``serve.batch``     batch formation under the scheduler's lock, row
                    slicing, concatenation, padding to the bucket
``serve.h2d``       the host-to-device copy of the padded batch
``serve.dispatch``  the jitted call on the served backend (a compile
                    lands here) and issuing the copy of its answer
``serve.device``    waiting for the device (``block_until_ready``)
``serve.d2h``       the one device-to-host copy of the step's packed
                    answer (counts and predictions in one buffer),
                    issued at dispatch, and its unpacking on the host
``serve.resolve``   splitting the outputs per request, setting futures
==================  ======================================================

The first three launch the step and the last three collect it.  The
continuous loop may launch the next step between the two halves (two
steps in flight), so one step's phases need not be contiguous in time;
each phase span is, and each is timed from its own start to its own end
into the record of its own step (:class:`Step`, made current on the
thread around each piece of work).  A step's time is the sum of its
phases: the thread's time spent on that step, never on the other one in
flight.  Under overlap ``serve.device`` and ``serve.d2h`` are what is
left of the wait when the host comes to collect, not the device's time.

``serve.step`` runs from the step's first phase to its last, with the
stats ``step`` (the record's sequence number), ``rows``, ``bucket`` and
``requests``; two steps' ``serve.step`` spans overlap where the loop
held one over.  ``serve.wait`` marks the continuous loop waiting on an
empty queue, outside any step.  While a profiler runs, each span is a
``jax.profiler.TraceAnnotation`` and lands on the device trace's clock;
otherwise a span costs one ``is_enabled`` check.

The same code records each step into one process-wide :class:`Ring` of
preallocated arrays (:data:`CAPACITY` steps): the phase times from
``time.perf_counter_ns``, the real rows launched, the bucket, the
requests in the batch, the compiles its dispatch triggered,
``d2h_copies``, the device-to-host transfers the step issued (one),
for a step over token sequences (the DWN head's classify step),
``tokens`` (real prompt tokens) and ``bucket_tokens`` (batch x length of
the padded step), both 0 for a step over feature rows,
``overlapped``: 1 where the step was launched while its predecessor was
still uncollected, and ``moe_fallbacks``: the MoE layer calls of a
classify step whose held experts ran on the worst-case sorted buffer
(``models.layers.held_rows``), 0 for a DWN step.  ``last(n)`` reads the
newest ``n`` records; ``mark()`` / ``since(mark)`` read what came after
a point.  Recording is
always on and writes nothing to disk.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np
from jax.profiler import TraceAnnotation

#: the phases of a step, in order; span ``serve.<phase>``
PHASES = ("batch", "h2d", "dispatch", "device", "d2h", "resolve")
#: steps the process-wide ring holds (about 8.4 MB)
CAPACITY = 65536

_INDEX = {p: i for i, p in enumerate(PHASES)}
_enabled = TraceAnnotation.is_enabled


class _Local(threading.local):
    step = None          # the step current on this thread


_local = _Local()


@dataclasses.dataclass
class Records:
    """Recorded steps, oldest first."""
    phase_ns: np.ndarray      # (n, len(PHASES)) int64
    step_ns: np.ndarray       # (n,) int64, the whole step
    rows: np.ndarray          # real rows launched
    bucket: np.ndarray        # rows of the padded batch
    requests: np.ndarray      # request slices in the batch
    compiles: np.ndarray      # XLA traces the dispatch took
    d2h_copies: np.ndarray    # device-to-host transfers issued
    tokens: np.ndarray        # real tokens of a step over sequences
    bucket_tokens: np.ndarray  # its padded batch x length
    overlapped: np.ndarray    # 1: launched before its predecessor's collect
    moe_fallbacks: np.ndarray  # MoE layer calls on the worst-case buffer

    def __len__(self) -> int:
        return len(self.step_ns)

    def phase_ms(self, *phases: str) -> np.ndarray:
        """Per-step milliseconds of the named phases, summed."""
        return self.phase_ns[:, [_INDEX[p] for p in phases]].sum(1) / 1e6

    def occupancy_pct(self) -> float:
        """Real rows over padded bucket rows, in percent."""
        return float(self.rows.sum() / self.bucket.sum() * 100.0)

    def token_occupancy_pct(self) -> float | None:
        """Real tokens over padded bucket tokens, in percent; None where
        no step ran over token sequences."""
        total = self.bucket_tokens.sum()
        return float(self.tokens.sum() / total * 100.0) if total else None

    def overlap_pct(self) -> float:
        """Steps launched while their predecessor was still in flight, in
        percent."""
        return float(self.overlapped.mean() * 100.0)

    def moe_fallback_pct(self, moe_layers: int) -> float | None:
        """MoE layer calls on the worst-case buffer over all of them, in
        percent, for steps over token sequences that each run
        ``moe_layers`` such calls; None where there are none."""
        calls = int((self.bucket_tokens > 0).sum()) * moe_layers
        return (float(self.moe_fallbacks.sum() / calls * 100.0) if calls
                else None)

    def summary(self, moe_layers: int = 0) -> dict:
        """Mean and p99 milliseconds of the step and of each phase, the
        occupancy (and the token occupancy of steps over sequences), the
        share of steps launched while another was in flight, the compiles,
        the device-to-host copies and the count of steps covered; with
        ``moe_layers`` (MoE layer calls per step over sequences), the
        share of them that fell back to the worst-case buffer."""
        if not len(self):
            return {"count": 0}
        fallback = self.moe_fallback_pct(moe_layers)
        ms = {"step": self.step_ns / 1e6}
        ms.update((p, self.phase_ms(p)) for p in PHASES)
        return {
            "count": len(self),
            "ms": {k: {"mean": round(float(v.mean()), 4),
                       "p99": round(float(np.percentile(v, 99)), 4)}
                   for k, v in ms.items()},
            "occupancy_pct": round(self.occupancy_pct(), 3),
            "overlap_pct": round(self.overlap_pct(), 3),
            "compiles": int(self.compiles.sum()),
            "d2h_copies": int(self.d2h_copies.sum()),
        } | ({} if self.token_occupancy_pct() is None else
             {"token_occupancy_pct": round(self.token_occupancy_pct(), 3)}
             ) | ({} if fallback is None else
                  {"moe_fallbacks": int(self.moe_fallbacks.sum()),
                   "moe_fallback_pct": round(fallback, 3)})


class Ring:
    """Fixed-capacity record of steps; the oldest are overwritten."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        #: per step: the phases' ns, the step's ns, rows, bucket,
        #: requests, compiles, d2h_copies, tokens, bucket_tokens,
        #: overlapped, moe_fallbacks
        self._data = np.zeros((capacity, len(PHASES) + 10), np.int64)
        self._n = 0
        self._lock = threading.Lock()

    def record(self, phase_ns, step_ns: int, rows: int, bucket: int,
               requests: int, compiles: int, d2h_copies: int = 0,
               tokens: int = 0, bucket_tokens: int = 0,
               overlapped: int = 0, moe_fallbacks: int = 0) -> int:
        """Store one step; returns its sequence number."""
        with self._lock:
            seq = self._n
            self._data[seq % self.capacity] = (*phase_ns, step_ns, rows,
                                               bucket, requests, compiles,
                                               d2h_copies, tokens,
                                               bucket_tokens, overlapped,
                                               moe_fallbacks)
            self._n = seq + 1
        return seq

    def mark(self) -> int:
        """Steps recorded so far; a point for :meth:`since`."""
        return self._n

    def last(self, n: int) -> Records | None:
        """The newest ``n`` records, or None where the ring does not
        hold ``n`` (fewer recorded, or overwritten)."""
        with self._lock:
            return self._read(self._n - n)

    def since(self, mark: int) -> Records | None:
        """The records after ``mark``, or None where they are overwritten."""
        with self._lock:
            return self._read(mark)

    def _read(self, start: int) -> Records | None:
        if start > self._n or start < max(0, self._n - self.capacity):
            return None
        d = self._data[np.arange(start, self._n) % self.capacity]
        k = len(PHASES)
        return Records(d[:, :k], *d[:, k:].T)


#: the process-wide ring every served step records into
RING = Ring()


def last(n: int) -> Records | None:
    return RING.last(n)


def mark() -> int:
    return RING.mark()


def since(mark: int) -> Records | None:
    return RING.since(mark)


class span:
    """``with span(name):`` a profiler annotation while a profiler runs,
    nothing otherwise."""

    __slots__ = ("name", "_tm")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self._tm = (TraceAnnotation(self.name).__enter__() if _enabled()
                    else None)
        return self

    def __exit__(self, *exc):
        if self._tm is not None:
            self._tm.__exit__(*exc)


class phase(span):
    """One phase of the step current on this thread: the ``serve.<name>``
    span, and its time, from its own start to its own end, added to the
    step's record.  Outside a step (warm-up, a bare engine step) only the
    span."""

    __slots__ = ("_i", "_rec", "_t")

    def __init__(self, name: str):
        self.name = "serve." + name
        self._i = _INDEX[name]

    def __enter__(self):
        super().__enter__()
        self._rec = _local.step
        self._t = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if self._rec is not None:
            self._rec.phase_ns[self._i] += time.perf_counter_ns() - self._t
        super().__exit__(*exc)


class Step(span):
    """One served step: its record, and its ``serve.step`` span from
    construction to :meth:`close`.

    ``with rec:`` makes it the step current on this thread, which the
    :class:`phase` spans inside record into; the scheduler does so around
    each piece of work of the step, so that two steps in flight on one
    thread each keep their own phases.  The scheduler sets ``rows``,
    ``bucket``, ``requests``, ``overlapped`` and, for token sequences,
    ``tokens`` and ``bucket_tokens``; the step's collect adds its
    ``moe_fallbacks`` (:func:`add_moe_fallbacks`); :meth:`close` stores
    the record in the ring unless the step launched no rows or failed.
    Its step time is the sum of its phases: the thread's time spent on
    this step."""

    __slots__ = ("rows", "bucket", "requests", "compiles", "d2h_copies",
                 "tokens", "bucket_tokens", "overlapped", "moe_fallbacks",
                 "phase_ns", "_prev")

    def __init__(self):
        self.name = "serve.step"
        self.rows = self.bucket = self.requests = self.compiles = 0
        self.d2h_copies = self.tokens = self.bucket_tokens = 0
        self.overlapped = self.moe_fallbacks = 0
        self.phase_ns = [0] * len(PHASES)
        super().__enter__()

    def __enter__(self):
        self._prev, _local.step = _local.step, self
        return self

    def __exit__(self, *exc):
        _local.step = self._prev

    def close(self, ok: bool = True) -> None:
        """End the span; record the step if ``ok`` and it launched rows."""
        if ok and self.rows:
            seq = RING.record(self.phase_ns, sum(self.phase_ns), self.rows,
                              self.bucket, self.requests, self.compiles,
                              self.d2h_copies, self.tokens,
                              self.bucket_tokens, self.overlapped,
                              self.moe_fallbacks)
            if self._tm is not None:
                self._tm.set_metadata(step=seq, rows=self.rows,
                                      bucket=self.bucket,
                                      requests=self.requests)
        super().__exit__(None, None, None)


def add_compiles(n: int) -> None:
    """Count ``n`` XLA traces against the step current on this thread."""
    rec = _local.step
    if rec is not None:
        rec.compiles += n


def add_d2h_copies(n: int) -> None:
    """Count ``n`` device-to-host transfers against the step current on
    this thread."""
    rec = _local.step
    if rec is not None:
        rec.d2h_copies += n


def add_moe_fallbacks(n: int) -> None:
    """Count ``n`` MoE layer calls on the worst-case buffer against the
    step current on this thread."""
    rec = _local.step
    if rec is not None:
        rec.moe_fallbacks += n


__all__ = ["CAPACITY", "PHASES", "RING", "Records", "Ring", "Step",
           "add_compiles", "add_d2h_copies", "add_moe_fallbacks", "last",
           "mark", "phase", "since", "span"]
