"""Operation and byte counts, checked by hand at small sizes."""

import pytest

from bench import work

CFG = {"features": 2, "bits_per_feature": 3, "luts": 4, "fan_in": 2,
       "classes": 2}


def test_inference_counts_by_hand():
    # 2*3 compares + 4*2 wire reads + 4 table reads + 4 class adds
    assert work.infer_ops_per_sample(CFG) == 6 + 8 + 4 + 4
    # thresholds 6*4 B, wires 8*4 B, tables 4 LUTs * 4 entries / 8 bits
    assert work.model_bytes(CFG) == 24 + 32 + 2
    # per row: 2 features * 4 B in, 2 counts * 4 B + 1 prediction * 4 B
    assert work.infer_bytes_per_call(CFG, 10) == 10 * (8 + 8 + 4) + 58


def test_least_time_names_its_bound():
    pk = work.peaks("TPU v5 lite")
    t, bound = work.least_time_s(393e12, 1.0, pk)
    assert bound == "ops" and t == pytest.approx(1.0)
    t, bound = work.least_time_s(1.0, 819e9, pk)
    assert bound == "bytes" and t == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("cpu")
