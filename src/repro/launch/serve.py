"""Serving CLI: a thin argparse front-end over ``repro.serving``.

All serving logic lives in the subsystem — ``serving/backends.py``
(pluggable DWN datapaths + compile cache + oracle cross-check),
``serving/continuous.py`` (the batching loop: dense packing into
power-of-two batch buckets), ``serving/engine.py`` (unified submit/drain
engine, DWN buckets sharded data-parallel over the host mesh).  This module only
parses flags, synthesizes a request stream, and prints the JSON report.

LM archs: batches of prompts are prefilled once, then decoded
token-by-token with the per-arch cache (KV / SSM state / LRU state).

DWN archs (family="dwn", e.g. --arch dwn-jsc-lg): batches of JSC feature
vectors are classified through the selected datapath backend
(--backend fused-packed | packed-xla | float-oracle); every non-oracle
backend is checked bit-exactly against the ``apply_hard`` oracle before
timing starts.  --ragged draws mixed request sizes in [1, batch] so the
scheduler's dense packing and padding are exercised.  --continuous serves the
same stream through the continuous-batching async engine (scheduler
thread, out-of-order futures, optional --deadline-ms SLO) instead of the
sync submit/drain facade.

Usage:
    python -m repro.launch.serve --arch mamba2-1.3b --reduced \
        --batch 4 --prompt-len 32 --gen 16
    python -m repro.launch.serve --arch dwn-jsc-lg --reduced
    python -m repro.launch.serve --arch dwn-jsc-sm --reduced --ragged \
        --backend packed-xla
    python -m repro.launch.serve --reduced \
        --spec '{"preset": "sm-50", "variant": "PEN", "input_bits": 9}'

DWN ``--arch`` strings are deprecated shims: they resolve to registered
``repro.dwn.DWNSpec`` presets (``--spec`` constructs one inline).
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from ..configs import get_arch
from ..serving import ServingEngine, available_backends
from ..serving.scheduler import next_pow2


def dwn_serve(target, args) -> int:
    """DWN classification serving through the engine + scheduler.

    ``target`` is anything the engine accepts: a registered arch name /
    ArchConfig (legacy), a ``DWNSpec`` (from ``--spec``), or a packed
    ``DWNArtifact``.
    """
    import dataclasses
    import warnings

    from ..dwn import resolve_spec
    workload = getattr(args, "workload", None)
    if workload is None:
        if resolve_spec(target).workload == "jsc":
            warnings.warn(
                "serving a DWN without --workload falls back to the "
                "implicit JSC default; pass --workload jsc (or any "
                "registered workload) explicitly",
                DeprecationWarning, stacklevel=2)
    else:
        spec = resolve_spec(target)
        if workload != spec.workload:
            # validated override: the preset must exist for that workload
            target = dataclasses.replace(spec, workload=workload)
    # --reduced shrinks the request volume, not the model: the datapath
    # (T=200 encode, m LUTs) is the thing being served.
    n_train = 2000 if args.reduced else 20000
    requests = args.requests if args.requests else (8 if args.reduced else 64)
    batch = args.batch if args.batch else (256 if args.reduced else 4096)
    max_bucket = next_pow2(batch)

    engine = ServingEngine(
        target, backend=args.backend or None, max_bucket=max_bucket,
        min_bucket=min(8, max_bucket), n_train=n_train, seed=args.seed,
        data_parallel=not args.no_data_parallel)
    # compile the serve bucket before timing starts (ragged streams may
    # still compile smaller ladder buckets in-band, one per bucket)
    engine.warmup(batch)

    rng = np.random.default_rng(args.seed)
    payloads = []
    for _ in range(requests):
        size = int(rng.integers(1, batch + 1)) if args.ragged else batch
        payloads.append(engine.make_request(
            size, seed=int(rng.integers(2**31))))
    if args.continuous:
        # continuous-batching path: futures resolve out of order while
        # the scheduler thread keeps steps in flight; a deadline makes
        # admission control + shedding part of the run
        with engine.serve():
            pending = [engine.submit_async(
                p, deadline_ms=args.deadline_ms or None) for p in payloads]
            results = [r.future.result() for r in pending]
        done = [r for r in results if r.ok]
    else:
        for p in payloads:
            engine.submit(p)
        done = engine.drain()

    rep = engine.report()
    rep["batch"] = batch
    rep["ragged"] = bool(args.ragged)
    rep["continuous"] = bool(args.continuous)
    # headline keys keep their pre-refactor meaning: *datapath* (compute)
    # latency per microbatch step.  Queue wait — which grows with the
    # pre-submitted stream length — stays separate under "latency".
    lat = rep.get("latency", {}).get("compute_ms", {})
    rep["latency_ms_p50"] = lat.get("p50")
    rep["latency_ms_p99"] = lat.get("p99")
    if done:
        first = done[0].value if args.continuous else done[0].result
        rep["sample"] = np.asarray(first[1][:8]).tolist()
    print(json.dumps(rep))
    return 0


def lm_serve(cfg, args) -> int:
    """LM prefill + decode serving through the engine.

    With ``--dwn-head`` (a DWNArtifact checkpoint path or a spec preset
    name like ``dwn-lm-head``) the engine also serves DWN classification
    on its own backbone features: a ``classify`` batch through the
    continuous loop beside the LM batch's drain — one process, both
    request kinds.
    """
    B = args.batch or 4
    # classify steps: prompt lengths up to the next power of two, B of
    # them per step at the longest
    longest = next_pow2(max(args.prompt_len, 8))
    engine = ServingEngine(
        cfg, reduced=args.reduced, prompt_len=args.prompt_len, gen=args.gen,
        model_parallel=args.model_parallel, seed=args.seed,
        dwn_head=args.dwn_head or None, max_bucket=longest,
        step_tokens=B * longest)
    engine.submit(engine.make_request(B, seed=args.seed))
    done = engine.drain()
    if args.dwn_head:
        with engine.serve():
            head = engine.submit_async(engine.make_request(
                B, seed=args.seed + 1, classify=True)).future.result()
        assert head.ok and head.value[1].shape == (B,)

    rep = engine.report()
    tokens = done[0].result["tokens"]
    assert tokens.shape == (B, args.gen)
    rep["batch"] = B
    rep["sample"] = tokens[0, :8].tolist()
    if args.dwn_head:
        rep["head_sample"] = head.value[1][:8].tolist()
    print(json.dumps(rep))
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="",
                    help="registered arch name (LM or DWN); DWN aliases "
                         "are deprecated shims over DWNSpec presets")
    ap.add_argument("--spec", default="",
                    help="DWN only: a DWNSpec as JSON, e.g. "
                         '\'{"preset": "sm-50", "variant": "PEN", '
                         '"input_bits": 9}\' — the typed replacement for '
                         "--arch dwn-jsc-* strings")
    ap.add_argument("--workload", default=None,
                    help="DWN mode: registered workload to serve "
                         "(jsc | mnist | ...; default: the spec's own "
                         "workload — omitting it for a JSC spec warns, "
                         "the implicit default is deprecated)")
    ap.add_argument("--dwn-head", default="",
                    help="LM mode: attach a packed DWN classification "
                         "head (DWNArtifact checkpoint path or spec "
                         "preset name, e.g. dwn-lm-head) and serve "
                         "classify requests alongside LM decode in the "
                         "same engine")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=0,
                    help="request batch size (default: 4 for LM archs, "
                         "256/4096 reduced/full for DWN archs)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--requests", type=int, default=0,
                    help="DWN mode: number of requests to serve")
    ap.add_argument("--ragged", action="store_true",
                    help="DWN mode: draw request sizes uniformly in "
                         "[1, batch] instead of a fixed batch")
    ap.add_argument("--continuous", action="store_true",
                    help="DWN mode: serve through the continuous-batching "
                         "async engine (scheduler thread + per-request "
                         "futures) instead of the sync submit/drain "
                         "facade")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="DWN mode with --continuous: per-request SLO "
                         "deadline; requests that provably cannot meet it "
                         "are shed at admission (0 = no deadline)")
    ap.add_argument("--backend", default="",
                    choices=[""] + available_backends(),
                    help="DWN datapath backend, served at every bucket "
                         "(default: the arch's dwn_datapath, else "
                         "fused-packed)")
    ap.add_argument("--no-data-parallel", action="store_true",
                    help="DWN mode: disable shard_map data parallelism")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--greedy", action="store_true", default=True)
    args = ap.parse_args(argv)
    from .compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.spec:
        if args.arch:
            ap.error("--arch and --spec are mutually exclusive")
        from ..dwn import DWNSpec
        return dwn_serve(DWNSpec(**json.loads(args.spec)), args)
    if not args.arch:
        ap.error("one of --arch or --spec is required")
    cfg = get_arch(args.arch)
    if cfg.family == "dwn":
        import warnings
        warnings.warn(
            f"--arch {args.arch!r} is a legacy DWN alias; it now "
            f"delegates to the registered DWNSpec preset of the same "
            f"name (prefer --spec or repro.dwn.get_spec)",
            DeprecationWarning, stacklevel=2)
        return dwn_serve(cfg, args)
    return lm_serve(cfg, args)


if __name__ == "__main__":
    sys.exit(main())
