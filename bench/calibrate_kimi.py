"""The readings that set the Kimi Linear cell's feature limit.

    python3 bench/calibrate_kimi.py --seeds 1,2,3

For each seed: the cell's seeded prompts and weights, ``check_requests``
prompts drawn as a run draws its sample, and the plain reference's
features computed twice, in float32 and with float8 (e4m3) activations
entering every matrix product, the nearest precision below the
configuration's bfloat16; and the program's features of the same prompts,
served through the engine's classify step one prompt at a time at the
cell's step shapes.  Prints one JSON line per seed with the program's and
the control's ``feature_err`` against the float32 reference: the largest
program reading over the seeds is the lower reading of the limit, the
smallest control reading the upper one.  Needs the chip.
"""

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CELL = "kimi-linear.classify"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    import jax.numpy as jnp
    from bench import harness, kimi_weights
    from bench.drivers.classify import head_artifact, prompts
    from bench.drivers.classify_kimi import program_arch
    from bench.reference_kimi import Reference, feature_error
    from repro.serving import ServingEngine
    from repro.workloads.lm_head import FEATS
    harness.enable_compile_cache()
    harness.require_chips(1)
    cell = harness.resolve(harness.load_benchmark(), CELL)
    cfg, mix = cell.config, cell.mix
    arch = program_arch(cfg)
    for seed in (int(s) for s in args.seeds.split(",")):
        sent = prompts(mix, cfg["vocab_size"], seed)
        pick = np.random.default_rng([seed, 9]).choice(
            len(sent), mix["check_requests"], replace=False)
        chosen = [sent[k][0] for k in pick]
        engine = ServingEngine(
            arch, params=kimi_weights.model(cfg, seed),
            dwn_head=head_artifact(cfg, seed)[0],
            min_bucket=mix["min_length"], max_bucket=mix["length_max"],
            step_tokens=mix["step_tokens"], verify=False, seed=seed)
        served = []
        for toks in chosen:
            batch, length = engine.head_buckets.shape_for(len(toks))
            tokens = np.zeros((batch, length), np.int32)
            tokens[0, :len(toks)] = toks
            lengths = np.zeros(batch, np.int32)
            lengths[0] = len(toks)
            served.append(engine._classify_step(tokens, lengths)[2][0])
        del engine
        inputs = (chosen, kimi_weights.embedding(cfg, seed),
                  lambda i: kimi_weights.layer(cfg, seed, i),
                  kimi_weights.final_norm(cfg),
                  kimi_weights.lm_head(cfg, seed))
        ref = Reference(cfg).features(*inputs)
        ctl = Reference(cfg, act_dtype=jnp.float8_e4m3fn).features(*inputs)
        print(json.dumps({
            "seed": seed, "lengths": [len(p) for p in chosen],
            "program": {"feature_err": feature_error(
                np.stack(served)[:, :FEATS], ref)},
            "control": {"feature_err": feature_error(ctl, ref)},
            "feature_rms": float(np.sqrt(np.mean(np.sum(ref ** 2, 1))))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
