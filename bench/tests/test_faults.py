"""A run with its timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run on the CPU at a small size, with one fault planted in the program:
an answer altered where it is produced, or half of a step's rows
answered with the other half's.  The cells serve a classifier:
no step carries state, no batch is averaged, and no cell exchanges data
between chips, so those faults do not apply.
"""

import json
import time

import pytest

from bench import harness


def cell(config, mix, **over):
    """A one-chip cell of a configuration file and a mix file, cut small:
    fewer fit rows, and the mix's keys overridden."""
    cfg, mx = (json.loads((harness.BENCH / d / f"{n}.json").read_text())
               for d, n in (("configs", config), ("mixes", mix)))
    return harness.Cell(f"{config}.{mix}", 1,
                        dict(cfg, n_fit=500, n_train=500), dict(mx, **over),
                        end_to_end=[], per_layer=[])


SMALL_OFFLINE = dict(request_rows=16, max_bucket=16, min_bucket=16,
                     payloads=2, check_requests=8)
SMALL_TRIGGER = dict(rate_samples_per_s=2000, deadline_ms=2000.0)
SERVING = [("dwn-jsc-sm", "offline", SMALL_OFFLINE),
           ("dwn-jsc-sm", "trigger", SMALL_TRIGGER)]


def run(c):
    return harness.run(c, seed=2**31 + 21, seconds=0.3, trace=False,
                       t_start=time.perf_counter())


@pytest.mark.parametrize("config,mix,over", SERVING)
def test_sound_small_runs_are_correct(no_chip_check, config, mix, over):
    out = run(cell(config, mix, **over))
    assert out["correct"], out["compared"]


def _count_bumped(counts, pred):
    counts = counts.copy()
    counts[0, 0] += 1
    return counts, pred


def _half_from_first(counts, pred):
    """The step's second half of rows answered with the first half's."""
    h = len(counts) // 2
    if h == 0:
        return counts, pred
    counts, pred = counts.copy(), pred.copy()
    counts[h:2 * h], pred[h:2 * h] = counts[:h], pred[:h]
    return counts, pred


@pytest.mark.parametrize("fault", [_count_bumped, _half_from_first],
                         ids=["count_bumped", "half_from_first"])
@pytest.mark.parametrize("config,mix,over", SERVING)
def test_answer_altered_where_produced(no_chip_check, monkeypatch, config,
                                       mix, over, fault):
    from repro.serving.engine import ServingEngine
    step = ServingEngine._dwn_step
    monkeypatch.setattr(ServingEngine, "_dwn_step",
                        lambda self, x: fault(*step(self, x)))
    out = run(cell(config, mix, **over))
    assert not out["correct"]
    assert out["compared"]["rows_off"]["value"] > 0
