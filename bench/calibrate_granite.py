"""The control reading of the granite cell's feature check.

    python3 bench/calibrate_granite.py --seeds 1,2,3

For each seed: the cell's seeded prompts and weights, ``check_requests``
prompts drawn as a run draws its sample, and the plain reference's
features computed twice, in float32 and with float8 (e4m3) activations
entering every matrix product, the nearest precision below the
configuration's bfloat16.  Prints one JSON line per seed with the
control's ``feature_err`` against the float32 reference: its smallest
over the seeds is the upper reading of the limit (the lower is the
largest ``feature_err`` the program reads in its runs).  Needs the chip.
"""

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CELL = "granite-h-small.classify"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    import jax.numpy as jnp
    from bench import granite_weights, harness
    from bench.drivers.classify import prompts
    from bench.reference_granite import Reference, feature_error
    harness.enable_compile_cache()
    harness.require_chips(1)
    cell = harness.resolve(harness.load_benchmark(), CELL)
    cfg, mix = cell.config, cell.mix
    for seed in (int(s) for s in args.seeds.split(",")):
        sent = prompts(mix, cfg["vocab_size"], seed)
        pick = np.random.default_rng([seed, 9]).choice(
            len(sent), mix["check_requests"], replace=False)
        chosen = [sent[k][0] for k in pick]
        inputs = (chosen, granite_weights.embedding(cfg, seed),
                  lambda i: granite_weights.layer(cfg, seed, i),
                  granite_weights.final_norm(cfg))
        ref = Reference(cfg).features(*inputs)
        ctl = Reference(cfg, act_dtype=jnp.float8_e4m3fn).features(*inputs)
        print(json.dumps({"seed": seed,
                          "lengths": [len(p) for p in chosen],
                          "control": {"feature_err":
                                      feature_error(ctl, ref)},
                          "feature_rms": float(np.sqrt(np.mean(
                              np.sum(ref ** 2, 1))))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
