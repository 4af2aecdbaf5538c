"""Closed loop: a fixed number of requests in flight, each replaced as
soon as the oldest is answered, through the continuous-batching engine.

Mix keys: ``request_rows`` (rows per request), ``in_flight``,
``max_bucket`` / ``min_bucket`` (the engine's bucket ladder), ``payloads``
(distinct seeded request arrays, sent in turn), ``check_requests``
(answered requests compared with the reference: a uniform sample drawn
from the seed).
"""

from __future__ import annotations

import collections
import time

import numpy as np

from bench import serving
from bench.harness import Compared, Window
from bench.jsc_data import JetModel


class Session:
    def __init__(self, cell, seed: int, seconds: float, span):
        from repro.serving.continuous import SLOConfig
        self.cell, self.seed, self.span = cell, seed, span
        mix = cell.mix
        self.engine, self.weights = serving.build_engine(cell, seed)
        rng = np.random.default_rng([seed, 2])
        jets = JetModel()
        self.payloads = [jets.features(rng, mix["request_rows"])
                         for _ in range(mix["payloads"])]
        self.engine.start_serving(slo=SLOConfig(
            max_queue_samples=mix["in_flight"] * mix["request_rows"]))
        self.sample = serving.Reservoir(mix["check_requests"], seed)
        self._i = 0
        # one pass of the loop outside the window, so that every step of
        # it is compiled and warm before timing
        for req in [self._submit() for _ in range(mix["in_flight"])]:
            req[1].future.result()

    def _submit(self):
        k = self._i % len(self.payloads)
        self._i += 1
        with self.span("bench.submit"):
            return k, self.engine.submit_async(self.payloads[k])

    def window(self, seconds: float) -> Window:
        mix, cont = self.cell.mix, self.engine._cont
        steps0, busy0 = cont.steps, cont.busy_s
        t0 = time.perf_counter()
        t_end = t0 + seconds
        flight = collections.deque()
        samples = sent = failed = 0
        self.missing = 0
        while True:
            if time.perf_counter() < t_end:
                while len(flight) < mix["in_flight"]:
                    flight.append(self._submit())
                    sent += 1
            elif not flight:
                break
            k, req = flight.popleft()
            with self.span("bench.wait"):
                res = serving.wait(req, t_end)
            if res is None:
                self.missing += 1
                failed += 1
                continue
            if res.shed is not None:
                failed += 1
                continue
            if req.t_done <= t_end:
                samples += req.size
            self.sample.offer((k, res.value))
        return Window(
            metrics={"serve_samples_per_s": samples / seconds},
            counters={"steps": cont.steps - steps0,
                      "busy_s": cont.busy_s - busy0,
                      "served_samples": samples, "window_s": seconds,
                      "bucket_rows": mix["max_bucket"]},
            attempted=sent, failed=failed)

    def finish(self) -> None:
        self.engine.stop_serving()
        del self.engine

    def check(self):
        picked = self.sample.items
        return [Compared("answers_missing", self.missing, 0)] + \
            serving.compare_answers(
                self.weights, self.cell.config,
                [self.payloads[k] for k, _ in picked],
                [v for _, v in picked])


def setup(cell, seed: int, seconds: float, span) -> Session:
    return Session(cell, seed, seconds, span)
