"""Sweep smoke — the tiny encoding grid through the full sweep pipeline.

Runs ``repro.sweep`` end-to-end (accuracy + hardware + fused-kernel axes;
serving is measured by the on-chip benchmark in ``bench/``) on the 6-point
tiny grid and prints the result table.  Asserts the two sweep invariants
that the paper-tolerance tests also pin down: TEN rows within tolerance
and encoder LUTs monotone in the PEN input width.
"""

from .common import csv_row, Timer


def run():
    from repro.sweep import SweepSettings, run_grid
    from repro.sweep.artifacts import TABLE1_TEN_TOLERANCE

    settings = SweepSettings(n_train=1000, n_test=500, serve=False,
                             kernel_batch=64, kernel_iters=1)
    with Timer() as t:
        result = run_grid("tiny", settings, cache_dir=None)
    print(result.table())
    for r in result.points:
        csv_row(f"sweep/{r.point.label}", t.us / len(result.points),
                f"luts={r.total_luts};acc={r.accuracy};"
                f"kernel_us={r.kernel_us}")

    by = {r.point.label: r for r in result.points}
    for preset in ("sm-10", "sm-50"):
        ten = by[f"{preset}/TEN/T200/distributive"]
        err = abs(ten.total_luts - ten.paper_luts) / ten.paper_luts
        assert err <= TABLE1_TEN_TOLERANCE[preset], (preset, err)
        pen4 = by[f"{preset}/PEN@4b/T200/distributive"]
        pen9 = by[f"{preset}/PEN@9b/T200/distributive"]
        assert pen4.luts["encoder"] < pen9.luts["encoder"], preset
    return result


if __name__ == "__main__":
    run()
