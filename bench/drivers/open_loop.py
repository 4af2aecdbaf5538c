"""Open loop: requests arrive on a seeded Poisson schedule whether or not
earlier ones have been answered, through the continuous-batching engine.

A copy of the schedule and latency arithmetic of the repository's load
generator (``repro.launch.loadgen``) with two changes: a request that is
shed, rejected or never answered stays in the latency tail, ranked after
every answered one; and the offered rate is a number in the mix, never
measured by the run.  Every seed gets the same multiset of inter-arrival
gaps and request sizes (drawn once from ``schedule_seed``), in an order
and with request rows drawn from the run's seed, so seeds change the
order of the work and not its amount.

Latency runs from a request's intended arrival to its answer, so a
generator that falls behind does not hide the wait.

Mix keys: ``rate_samples_per_s``, ``size_lo`` / ``size_hi`` (rows per
request, uniform, inclusive), ``deadline_ms``, ``max_queue_samples``,
``max_bucket`` / ``min_bucket``, ``schedule_seed``, ``check_requests``
(answered requests compared with the reference, drawn from the seed).
"""

from __future__ import annotations

import sys
import time

import numpy as np

from bench import serving
from bench.harness import Compared, Window, percentile
from bench.jsc_data import JetModel


def schedule(mix: dict, seconds: float, seed: int, rate: float | None = None):
    """(arrival times in s, request sizes) of one window.

    The gaps and sizes are the same multiset for every seed: ``n``
    exponential gaps at the mix's request rate, scaled to fill the
    window exactly, each paired with a uniform size; the run's seed
    shuffles the pairs.
    """
    lo, hi = mix["size_lo"], mix["size_hi"]
    rate = mix["rate_samples_per_s"] if rate is None else rate
    rate_rps = rate / ((lo + hi) / 2.0)
    base = np.random.default_rng([mix["schedule_seed"],
                                  int(round(seconds * 1000))])
    n = int(round(rate_rps * seconds))
    gaps = base.exponential(1.0, n)
    gaps *= seconds * (1.0 - 1e-9) / gaps.sum()
    sizes = base.integers(lo, hi + 1, n)
    order = np.random.default_rng([seed, 4]).permutation(n)
    return np.cumsum(gaps[order]), sizes[order]


class Session:
    def __init__(self, cell, seed: int, seconds: float, span):
        from repro.serving.continuous import SLOConfig
        self.cell, self.seed, self.span = cell, seed, span
        self.engine, self.weights = serving.build_engine(cell, seed)
        self.plan(seconds)
        self.engine.start_serving(slo=SLOConfig(
            max_queue_samples=cell.mix["max_queue_samples"]))

    def plan(self, seconds: float, rate: float | None = None) -> None:
        """The window's arrivals and request rows (``rate`` in samples/s
        overrides the mix's, for the knee sweep)."""
        self.arrivals, self.sizes = schedule(self.cell.mix, seconds,
                                             self.seed, rate)
        rows = JetModel().features(np.random.default_rng([self.seed, 5]),
                                   int(self.sizes.sum()))
        self.payloads = np.split(rows, np.cumsum(self.sizes)[:-1])

    def window(self, seconds: float) -> Window:
        from repro.serving.continuous import QueueFull
        mix, eng = self.cell.mix, self.engine
        arrivals, sizes = self.arrivals, self.sizes
        cont = eng._cont
        # the scheduler thread and this generator share the interpreter
        # lock; a short switch interval keeps either from holding it for
        # whole step times (as the repository's load generator does)
        switch = sys.getswitchinterval()
        sys.setswitchinterval(0.0005)
        reqs, lag = [], []
        try:
            steps0, busy0 = cont.steps, cont.busy_s
            t0 = time.perf_counter()
            for a, payload in zip(arrivals, self.payloads):
                due = t0 + a
                dt = due - time.perf_counter()
                if dt > 0:
                    time.sleep(dt)
                lag.append(time.perf_counter() - due)
                try:
                    with self.span("bench.submit"):
                        reqs.append(eng.submit_async(
                            payload, deadline_ms=mix["deadline_ms"],
                            timeout=0))
                except QueueFull:
                    reqs.append(None)
            t_end = t0 + seconds
            results = [serving.wait(r, t_end) if r is not None else None
                       for r in reqs]
            steps, busy = cont.steps - steps0, cont.busy_s - busy0
        finally:
            sys.setswitchinterval(switch)
        t_last = time.perf_counter()
        lat, queue, unserved, self.answered = [], [], [], []
        self.missing = failed = 0
        for i, (r, res) in enumerate(zip(reqs, results)):
            self.missing += r is not None and res is None
            failed += res is None or not res.ok
            if res is None or res.value is None:
                unserved.append(i)
                continue
            # answered, on time or late
            self.answered.append((i, res.value))
            lat.append((r.t_done - (t0 + arrivals[i])) * 1e3)
            queue.append((r.t_start - r.t_submit) * 1e3)
        # shed, rejected and unanswered requests rank after every answered
        # one: as late as the last wait for them ended
        worst = max(lat, default=0.0)
        lat += [max(worst, (t_last - t0 - arrivals[i]) * 1e3)
                for i in unserved]
        return Window(
            metrics={"serve_p99_ms": percentile(lat, 99)},
            counters={"steps": steps, "busy_s": busy,
                      "loadgen_lag_p99_ms": percentile(lag, 99) * 1e3,
                      "queue_ms_p99": percentile(queue, 99)
                      if queue else None,
                      "served_samples": int(sum(
                          sizes[i] for i, _ in self.answered)),
                      "window_s": seconds,
                      "ok_share": 1.0 - failed / max(1, len(reqs)),
                      "queue_depth_max_samples": cont.max_depth_samples},
            attempted=len(reqs), failed=failed)

    def finish(self) -> None:
        self.engine.stop_serving()
        del self.engine

    def check(self):
        idx = np.random.default_rng([self.seed, 9]).permutation(
            len(self.answered))[:self.cell.mix["check_requests"]]
        picked = [self.answered[i] for i in np.sort(idx)]
        return [Compared("answers_missing", self.missing, 0)] + \
            serving.compare_answers(
                self.weights, self.cell.config,
                [self.payloads[i] for i, _ in picked],
                [v for _, v in picked])


def setup(cell, seed: int, seconds: float, span) -> Session:
    return Session(cell, seed, seconds, span)
