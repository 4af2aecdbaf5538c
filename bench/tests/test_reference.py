"""The plain reference against the program's own oracle, and its
lower-precision control, at sizes the CPU holds."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, model_init, reference
from bench.jsc_data import JetModel


def config(name):
    return json.loads((harness.BENCH / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name,rows", [("dwn-jsc-sm", 3000),
                                       ("dwn-jsc-lg", 256)])
def test_infer_matches_apply_hard(name, rows):
    from repro.core.classifier import predict
    from repro.core.model import DWNConfig, FrozenDWN, apply_hard
    cfg = dict(config(name), n_fit=2000)
    th, mapping, tables = model_init.frozen_weights(cfg, 2**31 + 5)
    x = JetModel().features(np.random.default_rng(1), rows)
    frozen = FrozenDWN(DWNConfig(lut_counts=(cfg["luts"],)), th,
                       [mapping], [tables], None)
    counts = np.asarray(apply_hard(frozen, jnp.asarray(x)))
    got_c, got_p = reference.infer(x, th, mapping, tables, 5, block=1000)
    assert np.array_equal(counts, got_c)
    assert np.array_equal(np.asarray(predict(jnp.asarray(counts))), got_p)
    # the control: the same comparisons in bfloat16 flip some rows
    ctl_c, _ = reference.infer(x, th, mapping, tables, 5,
                               dtype=reference.bf16())
    assert (ctl_c != got_c).any(axis=1).sum() > 0

