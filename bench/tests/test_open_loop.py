"""The open-loop schedule: every seed offers the same work in its own
order, inside the window, at the mix's rate."""

import numpy as np

from bench.drivers.open_loop import schedule

MIX = {"rate_samples_per_s": 20000, "size_lo": 1, "size_hi": 64,
       "schedule_seed": 12}


def test_seeds_change_the_order_not_the_work():
    a_t, a_n = schedule(MIX, 2.0, 2**31 + 1)
    b_t, b_n = schedule(MIX, 2.0, 2**33 + 7)
    assert len(a_n) == len(b_n) == round(20000 / 32.5 * 2.0)
    assert np.array_equal(np.sort(a_n), np.sort(b_n))
    assert not np.array_equal(a_n, b_n)
    assert np.allclose(np.sort(np.diff(a_t, prepend=0)),
                       np.sort(np.diff(b_t, prepend=0)))
    for t in (a_t, b_t):
        assert np.all(np.diff(t) > 0) and 0 < t[-1] < 2.0


def test_rate_override_scales_the_count():
    _, n = schedule(MIX, 1.0, 5, rate=40000)
    assert len(n) == round(40000 / 32.5)
