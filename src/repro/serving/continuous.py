"""Continuous-batching request loop with SLO-aware admission control.

The one batching loop every served DWN and classify step goes through.
Started, a dedicated scheduler thread keeps device steps in flight while
new requests stream in from any number of submitting threads, and every
request completes **out of order** through its own future.  Never
started, it is the engine's synchronous ``submit``/``drain`` facade:
``drain()`` calls :meth:`ContinuousScheduler.step_once` on the caller's
thread until the queue is empty.

Design:

* **Batch formation at step boundaries.**  At each step the loop takes
  whatever is queued, ordered by (priority desc, deadline asc, admission
  order) — earliest-deadline-first within a priority class — coalesces
  up to ``max_bucket`` samples, and pads to the power-of-two bucket
  ladder (``scheduler.power_of_two_buckets``), so the per-(backend,
  bucket) compile cache and the autotuned kernel configs are shared by
  every step of that size.
* **Oversize chunking without clock restarts.**  A request larger than
  ``max_bucket`` is served in max-bucket chunks across consecutive
  steps; its queue time is attributed from the *original submit* to the
  *first* chunk's start, and its future resolves once after the last
  chunk.
* **SLO-aware admission.**  A request may declare a deadline.  Admission
  rejects (types the result as shed, never raises) work that provably
  cannot meet its deadline given the samples queued ahead of it and the
  per-bucket step-time estimates (``backends.StepTimeEstimator`` — seeded
  from one probe of the served backend, refined online from every step).
  Queued work whose deadline expires before it can launch is shed at the
  step boundary instead of being served late; work that still completes
  past its deadline (estimates are estimates) is returned **marked
  shed** — a deadline-constrained request is never returned late without
  the marking.
* **Backpressure.**  Queue depth is bounded in *samples*; ``submit``
  blocks up to ``timeout`` for space and then raises :class:`QueueFull`,
  so an open-loop producer feels the engine's capacity instead of
  growing an unbounded heap.

* **Two steps in flight.**  A step comes in two halves:
  ``launch(*args) -> handle`` copies the padded batch in and dispatches
  it, returning while the device works;
  ``collect(handle)`` waits for the device and the answer's copy back
  and returns the per-sample arrays.  After launching step N the loop
  looks at the queue: if it holds work, it forms and launches step N+1
  *before* collecting and resolving N, so that N+1's batch formation,
  copy in and dispatch run while the device computes N; if the queue is
  empty it collects N at once, so a lone request on an idle loop waits
  no longer than a serial step.  The depth is fixed at two.  Steps are
  collected in launch order, so a request's chunks are appended in
  order; a request resolves with the step that held its last rows.
  A request's ``t_start`` is the start of its first step as ``busy_s``
  counts it (below), so a step launched behind a held one starts when
  the held one completes, as in the serial loop.
  ``stop(drain=True)`` collects the held step before the loop ends, and
  an exception in launching N+1 still collects and resolves N.

The loop is model-agnostic: a *batching* decides which queued samples
a step takes and how they are padded, and the two halves run the padded
batch and return a tuple of per-sample result arrays.
:class:`RowBuckets` (the default) packs feature rows densely along axis
0 into the power-of-two ladder; :class:`TokenBuckets` right-pads token
sequences into one of a few (batch, length) shapes of equal token count
and passes the real lengths along.  ``step_once()`` runs one scheduling
decision plus one launch and collect synchronously, with no overlap —
the engine's sync ``drain()`` and the unit tests drive it without
threads, so ordering assertions are deterministic.

Each step is one ``steplog`` record: its phases, timed into that step's
own record even while the other step's phases run between them, and
``overlapped`` (1 where it was launched before its predecessor was
collected).  ``busy_s``, and the step time the estimator and the
straggler monitor see, count each step from the later of its launch and
the previous step's completion to its own completion, so two steps in
flight are never counted twice and ``busy_s / steps`` is the loop's time
per step.
"""

from __future__ import annotations

import bisect
import dataclasses
import math
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable

import numpy as np

from . import steplog
from .scheduler import Request, bucket_for, power_of_two_buckets

#: shed reasons (the typed result's ``shed`` field)
SHED_ADMISSION = "admission"      # provably unmeetable deadline at submit
SHED_EXPIRED = "expired"          # deadline passed while queued
SHED_LATE = "late"                # served, but results ready past deadline
SHED_SHUTDOWN = "shutdown"        # scheduler stopped without draining


class QueueFull(RuntimeError):
    """Backpressure: the bounded queue had no room within the timeout."""


@dataclasses.dataclass(frozen=True)
class ServeResult:
    """What an async request's future resolves to.

    ``ok`` means served on time (or no deadline declared).  ``shed`` is
    one of the SHED_* reasons otherwise; ``value`` still carries the
    results for ``SHED_LATE`` (the work was done, just late) and is None
    for requests that never ran.
    """

    ok: bool
    value: Any
    shed: str | None
    rid: int


@dataclasses.dataclass(frozen=True)
class SLOConfig:
    """Service-level objectives for the continuous-batching loop.

    Attributes:
      max_queue_samples: backpressure bound on queued (not yet launched)
        samples; ``submit`` blocks then raises :class:`QueueFull`.
      submit_timeout_s: default time ``submit`` waits for queue space
        when the caller passes ``timeout=None``.
      admission_slack: multiplier on the step-time estimates used by
        admission control.  < 1.0 is optimistic (sheds only work that is
        provably late even under a rosy estimate), > 1.0 sheds earlier.
      deadline_default_ms: deadline applied to requests that don't
        declare one (None = no implicit deadline).
    """

    max_queue_samples: int = 4096
    submit_timeout_s: float = 1.0
    admission_slack: float = 1.0
    deadline_default_ms: float | None = None


@dataclasses.dataclass
class AsyncRequest(Request):
    """A :class:`Request` plus async-serving state.

    ``future`` resolves to a :class:`ServeResult` — possibly before the
    request ever reaches the queue (admission shed).  ``deadline`` is an
    absolute ``timer()`` timestamp or None.
    """

    priority: int = 0
    deadline: float | None = None
    future: Future = dataclasses.field(default_factory=Future)
    shed: str | None = None
    #: samples already launched (oversize requests span several steps)
    offset: int = 0
    #: per-chunk result tuples, concatenated at completion
    parts: list = dataclasses.field(default_factory=list)

    @property
    def remaining(self) -> int:
        return self.size - self.offset

    def sort_key(self):
        # priority classes first, earliest deadline within a class,
        # admission order for deadline ties / no-deadline traffic
        return (-self.priority,
                self.deadline if self.deadline is not None else math.inf,
                self.rid)


class RowBuckets:
    """Feature rows, packed densely along axis 0 and padded to the
    power-of-two bucket ladder; ``launch(x)`` gets the (bucket, ...) array.

    Requests are taken in sort order and the one straddling the bucket
    boundary is split: its head rows fill this step, the rest stays
    queued (front of its priority class) for the next step.  Oversize
    requests fall out of the same rule as max-bucket chunks.
    """

    def __init__(self, buckets: tuple[int, ...]):
        self.buckets = buckets
        self.max_bucket = buckets[-1]

    def take(self, pending) -> list[tuple["AsyncRequest", int]]:
        """(request, samples) pairs for the next step, in order."""
        out, total = [], 0
        for r in pending:
            if total >= self.max_bucket:
                break
            n = min(r.size - r.offset, self.max_bucket - total)
            out.append((r, n))
            total += n
        return out

    def assemble(self, xs: list[np.ndarray]):
        """(step args, rows, bucket rows, tokens, bucket tokens)."""
        total = sum(x.shape[0] for x in xs)
        bucket = bucket_for(total, self.buckets)
        x = np.concatenate(xs, axis=0) if len(xs) > 1 else xs[0]
        if bucket > total:
            pad = np.zeros((bucket - total,) + x.shape[1:], x.dtype)
            x = np.concatenate([x, pad], axis=0)
        return (x,), total, bucket, 0, 0


class TokenBuckets:
    """Token sequences, right-padded into one of a fixed set of step
    shapes: for each length ``L`` of ``lengths``, ``step_tokens // L``
    sequences of ``L`` tokens.  ``launch(tokens, lengths)`` gets the padded
    int32 (batch, L) tokens and each row's real length (0 for a padding
    row).

    A request's payload is an int (n, L) array: n sequences of L tokens.
    The first queued request sets the step's length (the shortest ``L``
    that holds it); later ones no longer than that fill the remaining
    rows in order, splitting the last across steps.
    """

    def __init__(self, lengths: tuple[int, ...], step_tokens: int):
        if any(step_tokens < L or step_tokens % L for L in lengths):
            raise ValueError(f"step_tokens={step_tokens} must be a "
                             f"multiple of every length in {lengths}")
        self.lengths = tuple(sorted(lengths))
        self.step_tokens = step_tokens

    @property
    def shapes(self) -> tuple[tuple[int, int], ...]:
        """Every (batch, length) a step can have."""
        return tuple((self.step_tokens // L, L) for L in self.lengths)

    def shape_for(self, length: int) -> tuple[int, int]:
        """(batch, length) of the step that holds a sequence of
        ``length`` tokens."""
        if not 0 < length <= self.lengths[-1]:
            raise ValueError(f"a sequence of {length} tokens does not fit "
                             f"the longest step length {self.lengths[-1]}")
        L = next(L for L in self.lengths if length <= L)
        return self.step_tokens // L, L

    def take(self, pending) -> list[tuple["AsyncRequest", int]]:
        batch, L = self.shape_for(np.shape(pending[0].payload)[1])
        out, total = [], 0
        for r in pending:
            if total >= batch:
                break
            if np.shape(r.payload)[1] <= L:
                n = min(r.size - r.offset, batch - total)
                out.append((r, n))
                total += n
        return out

    def assemble(self, xs: list[np.ndarray]):
        batch, L = self.shape_for(max(x.shape[1] for x in xs))
        tokens = np.zeros((batch, L), np.int32)
        lengths = np.zeros((batch,), np.int32)
        row = 0
        for x in xs:
            tokens[row:row + x.shape[0], :x.shape[1]] = x
            lengths[row:row + x.shape[0]] = x.shape[1]
            row += x.shape[0]
        return ((tokens, lengths), row, batch, int(lengths.sum()),
                batch * L)


@dataclasses.dataclass
class _InFlight:
    """A launched step, not yet collected."""
    rec: steplog.Step
    batch: list            # (request, lo, hi) payload row slices
    bucket: int
    handle: Any            # what ``launch`` returned
    t_launch: float


class ContinuousScheduler:
    """The continuous-batching loop behind ``ServingEngine.serve()``.

    Args:
      launch: ``launch(*args) -> handle``, the first half of a step on a
        padded batch (``args`` as the batching assembles them): copy in
        and dispatch, returning without waiting for the device.
      collect: ``collect(handle) -> tuple[per-sample arrays]``, the second
        half: block until the results are ready and bring them to the
        host.
      max_bucket / min_bucket: the power-of-two bucket ladder (the
        engine's, so compiles are shared).
      batching: how a step takes and pads samples; None =
        :class:`RowBuckets` over the ladder.  Admission estimates assume
        the ladder.
      slo: :class:`SLOConfig`; None = defaults (large queue, no implicit
        deadlines).
      estimator: per-bucket step-time estimates for admission control
        (``backends.StepTimeEstimator`` or any object with
        ``estimate(bucket) -> float | None`` and ``update(bucket, s)``).
        None disables admission-time shedding (expiry and late marking
        still apply: those need no estimate).
      monitor: optional ``runtime.straggler.StragglerMonitor``; every
        step's time, as ``busy_s`` counts it, is reported to it (the
        engine's report shows its anomalies).
      timer: injectable clock (tests use a deterministic one).
    """

    def __init__(self, launch: Callable, collect: Callable,
                 *, max_bucket: int = 256,
                 min_bucket: int = 8, slo: SLOConfig | None = None,
                 estimator=None, monitor=None,
                 timer: Callable[[], float] = time.perf_counter,
                 batching=None):
        self.buckets = power_of_two_buckets(
            min(min_bucket, max_bucket), max_bucket)
        self.max_bucket = max_bucket
        self.batching = (batching if batching is not None
                         else RowBuckets(self.buckets))
        self.slo = slo if slo is not None else SLOConfig()
        self.estimator = estimator
        self.monitor = monitor
        self._launch_fn = launch
        self._collect_fn = collect
        self._timer = timer
        # RLock: _finish() takes the lock for the completed/shed counters
        # and is reached both from submit() (admission shed, lock held)
        # and from the scheduler thread (lock not held)
        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        #: kept sorted by sort_key at insert time (bisect.insort), so the
        #: step loop never re-sorts; partial takes stay at the front
        self._pending: list[AsyncRequest] = []
        self._queued_samples = 0
        self._deadline_pending = 0   # queued requests carrying a deadline
        self._next_rid = 0
        self._thread: threading.Thread | None = None
        self._stopping = False
        # -- observables -------------------------------------------------
        #: slim copies of every finished request (served or shed), the
        #: report()/latency_stats source; payload/result dropped
        self.completed: list[AsyncRequest] = []
        self.shed_counts: dict[str, int] = {}
        self.steps = 0
        #: sum of step times, each from the later of its launch and the
        #: previous step's completion to its own completion
        self.busy_s = 0.0
        self._t_collected = -math.inf  # the last step's completion
        self.session_wall_s = 0.0    # start() -> stop() wall, accumulated
        self.max_depth_samples = 0
        self.max_depth_requests = 0
        self._t_session = None

    # ------------------------------------------------------------------
    # submission (any thread)
    # ------------------------------------------------------------------

    def submit(self, payload: Any, size: int | None = None, *,
               deadline_ms: float | None = None, priority: int = 0,
               timeout: float | None = None) -> AsyncRequest:
        """Admit one request; returns it with ``future`` attached.

        Blocks up to ``timeout`` seconds (None = ``slo.submit_timeout_s``)
        when the bounded queue is full, then raises :class:`QueueFull`.
        A request whose deadline provably cannot be met is *not* queued:
        its future resolves immediately to a ``ServeResult`` with
        ``shed == SHED_ADMISSION``.
        """
        if size is None:
            size = int(np.asarray(payload).shape[0])
        timeout = self.slo.submit_timeout_s if timeout is None else timeout
        if deadline_ms is None:
            deadline_ms = self.slo.deadline_default_ms
        t_submit = self._timer()
        deadline = (t_submit + deadline_ms / 1e3
                    if deadline_ms is not None else None)
        with self._cond:
            if self._stopping:
                raise RuntimeError("scheduler is stopped")
            t_wait_end = t_submit + timeout
            while self._queued_samples + size > self.slo.max_queue_samples:
                left = t_wait_end - self._timer()
                if left <= 0 or self._stopping:
                    raise QueueFull(
                        f"queue full: {self._queued_samples} samples "
                        f"queued, bound {self.slo.max_queue_samples}, "
                        f"request of {size} timed out after {timeout}s")
                self._cond.wait(left)
            req = AsyncRequest(rid=self._next_rid, payload=payload,
                               size=size, t_submit=t_submit,
                               priority=priority, deadline=deadline)
            self._next_rid += 1
            if deadline is not None:
                est = self._admission_estimate_locked(req)
                if (est is not None and
                        self._timer() + est * self.slo.admission_slack
                        > deadline):
                    self._finish(req, shed=SHED_ADMISSION)
                    return req
            bisect.insort(self._pending, req, key=AsyncRequest.sort_key)
            self._queued_samples += size
            if deadline is not None:
                self._deadline_pending += 1
            self.max_depth_samples = max(self.max_depth_samples,
                                         self._queued_samples)
            self.max_depth_requests = max(self.max_depth_requests,
                                          len(self._pending))
            self._cond.notify_all()
        return req

    def _admission_estimate_locked(self, req: AsyncRequest) -> float | None:
        """Lower-bound seconds until ``req`` could complete, or None.

        The bound assumes perfect batching of everything scheduled ahead
        of the request (same-or-better sort key) into max-bucket steps,
        plus the request's own chunks — optimistic, so a shed on this
        estimate means the deadline was provably unmeetable.
        """
        if self.estimator is None:
            return None
        est_max = self.estimator.estimate(self.max_bucket)
        if est_max is None:
            return None
        idx = bisect.bisect_left(self._pending, req.sort_key(),
                                 key=AsyncRequest.sort_key)
        ahead = sum(r.size - r.offset for r in self._pending[:idx])
        wait = math.ceil(ahead / self.max_bucket) * est_max
        own = 0.0
        remaining = req.size
        while remaining > 0:
            chunk = min(remaining, self.max_bucket)
            b = bucket_for(chunk, self.buckets)
            own += self.estimator.estimate(b) or est_max
            remaining -= chunk
        return wait + own

    # ------------------------------------------------------------------
    # completion plumbing
    # ------------------------------------------------------------------

    def _finish(self, req: AsyncRequest, *, shed: str | None,
                value: Any = None) -> None:
        """Record + resolve one request (safe from any thread)."""
        req.shed = shed
        with self._lock:
            if shed is not None:
                self.shed_counts[shed] = self.shed_counts.get(shed, 0) + 1
            self.completed.append(dataclasses.replace(
                req, payload=None, result=None, parts=[]))
        req.future.set_result(ServeResult(ok=shed is None, value=value,
                                          shed=shed, rid=req.rid))

    # ------------------------------------------------------------------
    # the step loop (scheduler thread, or step_once: the engine's sync
    # drain() and the tests)
    # ------------------------------------------------------------------

    def _form_batch_locked(self, now: float):
        """One scheduling decision: (batch slices, expired requests).

        ``batch`` is a list of ``(request, lo, hi)`` payload row slices,
        as the batching takes them (:class:`RowBuckets` packs them
        **densely**, so that ragged sizes do not pad away ~half of each
        bucket).  Requests whose deadline can no longer be met even if
        launched immediately are pulled out as ``expired``.
        """
        expired: list[AsyncRequest] = []
        if self._deadline_pending:
            est_max = (self.estimator.estimate(self.max_bucket)
                       if self.estimator is not None else None)
            # any request whose deadline clears now + the max possible
            # floor cannot expire this step — skip its bucket math
            cutoff = now + (est_max or 0.0) * self.slo.admission_slack
            floors: dict[int, float] = {}
            keep: list[AsyncRequest] = []
            for r in self._pending:
                if r.deadline is not None and r.deadline < cutoff:
                    floor = 0.0
                    if est_max is not None:
                        b = bucket_for(min(r.size - r.offset,
                                           self.max_bucket), self.buckets)
                        floor = floors.get(b)
                        if floor is None:
                            floor = ((self.estimator.estimate(b) or est_max)
                                     * self.slo.admission_slack)
                            floors[b] = floor
                    if now + floor > r.deadline:
                        expired.append(r)
                        self._queued_samples -= r.size - r.offset
                        self._deadline_pending -= 1
                        continue
                keep.append(r)
            if expired:
                self._pending = keep
        batch: list[tuple[AsyncRequest, int, int]] = []
        for r, take in (self.batching.take(self._pending)
                        if self._pending else ()):
            batch.append((r, r.offset, r.offset + take))
            r.offset += take
            self._queued_samples -= take
        if batch:
            still: list[AsyncRequest] = []
            for r in self._pending:
                if r.offset < r.size:
                    still.append(r)
                else:
                    if r.deadline is not None:
                        self._deadline_pending -= 1
            self._pending = still
        if batch or expired:
            self._cond.notify_all()    # space freed: wake submitters
        return batch, expired

    def step_once(self, *, wait_s: float = 0.0) -> int:
        """Run one scheduling decision + one device step synchronously:
        launch, then collect at once.

        Returns the number of samples launched (0 if the queue was empty
        after waiting ``wait_s``).  The engine's sync ``drain()`` calls it
        until it returns 0; tests call it directly for deterministic
        ordering.  The step's phases are ``steplog`` spans and one ring
        record.
        """
        with self._cond:
            if not self._pending and wait_s > 0:
                with steplog.span("serve.wait"):
                    self._cond.wait(wait_s)
            if not self._pending:
                return 0
        step = self._launch()
        return self._collect(step) if step is not None else 0

    def _launch(self, *, overlapped: bool = False) -> _InFlight | None:
        """The step's first half: form the next batch, then ``launch`` it.
        None where the queue held nothing to launch."""
        rec = steplog.Step()
        try:
            with rec:
                with steplog.phase("batch"):
                    with self._cond:
                        batch, expired = self._form_batch_locked(
                            self._timer())
                    for r in expired:
                        self._finish(r, shed=SHED_EXPIRED)
                    if batch:
                        t_launch = self._timer()
                        xs = [np.asarray(r.payload)[lo:hi]
                              for r, lo, hi in batch]
                        args, total, bucket, tokens, bucket_tokens = \
                            self.batching.assemble(xs)
                        rec.rows, rec.bucket = total, bucket
                        rec.requests, rec.overlapped = (len(batch),
                                                        int(overlapped))
                        rec.tokens, rec.bucket_tokens = (tokens,
                                                         bucket_tokens)
                if not batch:
                    rec.close()
                    return None
                handle = self._launch_fn(*args)
        except BaseException:
            rec.close(ok=False)
            raise
        return _InFlight(rec, batch, bucket, handle, t_launch)

    def _collect(self, step: _InFlight) -> int:
        """The step's second half: ``collect`` its outputs and resolve its
        requests.  Returns the samples it served."""
        try:
            with step.rec:
                outs = self._collect_fn(step.handle)
                with steplog.phase("resolve"):
                    self._resolve(step.batch, step.bucket, outs,
                                  step.t_launch)
        except BaseException:
            step.rec.close(ok=False)
            raise
        step.rec.close()
        return step.rec.rows

    def _resolve(self, batch, bucket: int, outs, t_launch: float) -> None:
        """Count the step, then hand each request its rows of ``outs``
        and resolve the futures of those whose last rows these were."""
        t_done = self._timer()
        # from the later of its launch and the previous step's completion:
        # two steps in flight are never counted twice
        t_begin = max(t_launch, self._t_collected)
        step_s = t_done - t_begin
        self._t_collected = t_done
        self.steps += 1
        self.busy_s += step_s
        if self.estimator is not None:
            self.estimator.update(bucket, step_s)
        if self.monitor is not None:
            self.monitor.report(step_s)
        off = 0
        for r, lo, hi in batch:
            n = hi - lo
            if lo == 0:                  # the request's first step
                r.t_start = t_begin
            r.parts.append(tuple(np.asarray(o)[off:off + n] for o in outs))
            r.buckets = r.buckets + (bucket,)
            off += n
            # by the slice, not by r.offset: a step launched since may
            # already hold the request's next rows
            if hi == r.size:
                r.t_done = t_done
                if len(r.parts) == 1:
                    result = r.parts[0]
                else:
                    result = tuple(np.concatenate(parts, axis=0)
                                   for parts in zip(*r.parts))
                r.result = result
                late = r.deadline is not None and t_done > r.deadline
                self._finish(r, shed=SHED_LATE if late else None,
                             value=result)

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def _loop(self) -> None:
        held = None          # launched, not yet collected
        while True:
            with self._cond:
                if held is None and not self._pending:
                    if self._stopping:
                        return
                    with steplog.span("serve.wait"):
                        self._cond.wait(0.002)
                    continue
            # with a step held, launch the next before collecting it, so
            # that its copy in and dispatch overlap the held step's wait
            try:
                nxt = (self._launch(overlapped=held is not None)
                       if self._pending else None)
            finally:
                if held is not None:
                    self._collect(held)
            held = nxt
            if held is not None and not self._pending:
                self._collect(held)      # nothing queued behind it
                held = None

    def start(self) -> None:
        assert self._thread is None, "already started"
        self._stopping = False
        self._t_session = self._timer()
        self._thread = threading.Thread(target=self._loop,
                                        name="repro-serve-loop",
                                        daemon=True)
        self._thread.start()

    def stop(self, *, drain: bool = True) -> None:
        """Stop the loop.  ``drain=True`` serves everything queued first;
        ``drain=False`` sheds queued requests with ``SHED_SHUTDOWN``.
        Either way every launched step is collected and resolved."""
        assert self._thread is not None, "not started"
        if not drain:
            with self._cond:
                dropped, self._pending = self._pending, []
                self._queued_samples = 0
                self._deadline_pending = 0
                self._cond.notify_all()
            for r in dropped:
                self._finish(r, shed=SHED_SHUTDOWN)
        with self._cond:
            self._stopping = True
            self._cond.notify_all()
        self._thread.join()
        self._thread = None
        self.session_wall_s += self._timer() - self._t_session
        self._t_session = None

    # ------------------------------------------------------------------
    # observables
    # ------------------------------------------------------------------

    @property
    def pending_samples(self) -> int:
        return self._queued_samples

    @property
    def pending(self) -> int:
        return len(self._pending)

    def counters(self) -> dict:
        """JSON-able loop counters for ``ServingEngine.report()``."""
        served = [r for r in self.completed if r.shed is None]
        shed = len(self.completed) - len(served)
        return {
            "steps": self.steps,
            "busy_s": round(self.busy_s, 4),
            "session_wall_s": round(
                self.session_wall_s + (self._timer() - self._t_session
                                       if self._t_session is not None
                                       else 0.0), 4),
            "served_requests": len(served),
            "served_samples": sum(r.size for r in served),
            "shed_requests": shed,
            "shed_by_reason": dict(self.shed_counts),
            "shed_rate": round(shed / len(self.completed), 4)
            if self.completed else 0.0,
            "queue_depth_max_samples": self.max_depth_samples,
            "queue_depth_max_requests": self.max_depth_requests,
        }


__all__ = [
    "AsyncRequest", "ContinuousScheduler", "QueueFull", "RowBuckets",
    "SLOConfig", "ServeResult", "TokenBuckets", "SHED_ADMISSION", "SHED_EXPIRED", "SHED_LATE",
    "SHED_SHUTDOWN",
]
