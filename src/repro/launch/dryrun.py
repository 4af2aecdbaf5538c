import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines: jax locks the device count on first init.
#   Do not set this anywhere else (tests/benches must see 1 device).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the production mesh, abstract params/optimizer
state/batch (ShapeDtypeStructs — no allocation), resolves NamedShardings
from the logical-axis rules, and runs

    jax.jit(step, in_shardings=..., out_shardings=..., donate...)\
        .lower(*specs).compile()

then records memory_analysis(), cost_analysis() and the collective bytes
parsed from the optimized HLO into results/dryrun/<cell>.json (the roofline
table and §Perf read these).

Usage:
    python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k [--multi-pod]
    python -m repro.launch.dryrun --all [--multi-pod] [--force]
    python -m repro.launch.dryrun --list
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

import jax

from ..configs import SHAPES, DWN_SHAPES, cell_supported, get_arch
from ..configs.registry import assigned_archs
from ..models import api
from ..roofline.analyze import analyze, model_flops
from ..sharding.partition import Partitioner
from .mesh import make_production_mesh

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _cell_id(arch: str, shape: str, multi_pod: bool) -> str:
    return f"{arch}__{shape}__{'pod2' if multi_pod else 'pod1'}"


def _opt_state_axes(params_axes):
    """AdamState(step, mu, nu): moments shard like params."""
    from ..optim.adam import AdamState
    from ..sharding.partition import logical
    return AdamState(logical(name="opt.step"), params_axes, params_axes)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               perf_variant: str = "baseline", extra: dict | None = None):
    """Lower+compile one cell; returns the result record.

    perf_variant (§Perf hillclimb knobs, comma-separated):
      * "logits_sharded": decode/prefill logits stay vocab-sharded on the
        model axis (sampling happens on sharded logits) instead of being
        all-gathered;
      * "serve_tp_only": serving weights are replicated over the DP axes
        (TP-only placement) — no per-layer FSDP all-gathers on the decode
        path (weights must fit HBM, which every assigned arch does in
        fp32/256 chips and bf16 would halve again);
      * "serve_bf16": serving weights in bf16 — halves the per-token
        weight-streaming bytes that bound batch-1 decode.
    """
    cfg = get_arch(arch)
    shape = {**SHAPES, **DWN_SHAPES}[shape_name]
    ok, reason = cell_supported(cfg, shape)
    if not ok:
        return {"cell": _cell_id(arch, shape_name, multi_pod),
                "skipped": True, "reason": reason}

    variants = set(perf_variant.split(",")) if perf_variant else set()
    import dataclasses as _dc2
    if "attn_tri" in variants:
        cfg = _dc2.replace(cfg, attn_impl="tri")
    if "scores_bf16" in variants:
        cfg = _dc2.replace(cfg, attn_scores_bf16=True)
    if "moe_ep" in variants:
        cfg = _dc2.replace(cfg, moe_ep=True)
    if "cf1" in variants:
        cfg = _dc2.replace(cfg, capacity_factor=1.0)
    mesh = make_production_mesh(multi_pod=multi_pod)
    tp = mesh.shape["model"]
    chips = mesh.size
    rules = {}
    if "serve_tp_only" in variants and shape.kind != "train":
        rules["embed"] = None          # replicate the FSDP dim for serving
    part = Partitioner(mesh, rules=rules)

    t0 = time.time()
    aparams = api.abstract_params(cfg, tp)
    if "serve_bf16" in variants and shape.kind != "train":
        import jax.numpy as jnp
        aparams = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype),
            aparams)
    p_axes = api.param_axes(cfg)
    p_shard = part.tree_shardings(aparams, p_axes)

    record = {
        "cell": _cell_id(arch, shape_name, multi_pod),
        "arch": arch, "shape": shape_name,
        "mesh": dict(zip(mesh.axis_names, [mesh.shape[a] for a in mesh.axis_names])),
        "chips": chips,
        "perf_variant": perf_variant,
        "params": cfg.num_params(),
        "active_params": cfg.num_active_params(),
    }

    import contextlib
    mesh_ctx = mesh  # `with mesh:` makes it ambient for sharding hints

    if shape.kind == "train":
        micro = shape_train_micro(cfg, shape)
        step_fn, opt = api.make_train_step(cfg, tp, num_micro=micro)
        aopt = jax.eval_shape(opt.init, aparams)
        o_shard = part.tree_shardings(
            aopt, _opt_state_axes(p_axes))
        import dataclasses as _dc
        shp = _dc.replace(shape, num_microbatches=micro)
        bspecs = api.batch_specs(cfg, shp, micro=True)
        b_axes = api.batch_axes(cfg, shp, micro=True)
        b_shard = part.tree_shardings(bspecs, b_axes)
        fn = jax.jit(step_fn,
                     in_shardings=(p_shard, o_shard, b_shard),
                     out_shardings=(p_shard, o_shard, None),
                     donate_argnums=(0, 1))
        with mesh:
            lowered = fn.lower(aparams, aopt, bspecs)
        record["num_microbatches"] = micro
    elif shape.kind == "prefill":
        prefill_fn = api.make_prefill(cfg, tp, cache_len=shape.seq_len)
        bspecs = api.batch_specs(cfg, shape)
        b_axes = api.batch_axes(cfg, shape)
        b_shard = part.tree_shardings(bspecs, b_axes)
        acache = api.abstract_cache(cfg, shape, tp)
        c_shard = part.tree_shardings(acache, api.cache_axes(cfg, shape))
        from jax.sharding import NamedSharding, PartitionSpec as P
        logit_shard = (NamedSharding(mesh, P(None, "model"))
                       if "logits_sharded" in variants else None)
        fn = jax.jit(prefill_fn, in_shardings=(p_shard, b_shard),
                     out_shardings=(logit_shard, c_shard))
        with mesh:
            lowered = fn.lower(aparams, bspecs)
    else:  # decode
        decode_fn = api.make_decode_step(cfg, tp)
        acache = api.abstract_cache(cfg, shape, tp)
        c_shard = part.tree_shardings(acache, api.cache_axes(cfg, shape))
        bspecs = api.batch_specs(cfg, shape)
        b_axes = api.batch_axes(cfg, shape)
        b_shard = part.tree_shardings(bspecs, b_axes)
        from jax.sharding import NamedSharding, PartitionSpec as P
        logit_shard = (NamedSharding(mesh, P(None, "model"))
                       if "logits_sharded" in variants else None)
        fn = jax.jit(decode_fn,
                     in_shardings=(p_shard, c_shard, b_shard),
                     out_shardings=(logit_shard, c_shard),
                     donate_argnums=(1,))
        with mesh:
            lowered = fn.lower(aparams, acache, bspecs)

    t_lower = time.time() - t0
    t0 = time.time()
    with mesh:
        compiled = lowered.compile()
    t_compile = time.time() - t0

    hlo_text = compiled.as_text()
    stats = analyze(compiled, chips, hlo_text=hlo_text)
    record.update(stats)
    # keep the optimized HLO for offline perf analysis (§Perf digs here)
    import gzip
    RESULTS.mkdir(parents=True, exist_ok=True)
    hlo_path = RESULTS / (record["cell"] +
                          (f"__{perf_variant}" if perf_variant != "baseline"
                           else "") + ".hlo.txt.gz")
    with gzip.open(hlo_path, "wt") as f:
        f.write(hlo_text)
    record["fallbacks"] = [dataclasses.asdict(f) for f in part.fallbacks]
    record["lower_s"] = round(t_lower, 1)
    record["compile_s"] = round(t_compile, 1)
    mf = model_flops(cfg, shape,
                     include_backward=shape.kind == "train")
    record["model_flops_total"] = mf
    hlo_total = stats["flops_per_chip"] * chips
    record["useful_flops_ratio"] = mf / hlo_total if hlo_total else 0.0
    if extra:
        record.update(extra)
    return record


def shape_train_micro(cfg, shape) -> int:
    return max(1, cfg.train_microbatches) if shape.kind == "train" else 1


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             force: bool = False, tag: str = "",
             perf_variant: str = "baseline") -> dict:
    RESULTS.mkdir(parents=True, exist_ok=True)
    cid = _cell_id(arch, shape_name, multi_pod) + (f"__{tag}" if tag else "")
    out = RESULTS / f"{cid}.json"
    if out.exists() and not force:
        rec = json.loads(out.read_text())
        print(f"[cached] {cid}: {rec.get('roofline', rec.get('reason', ''))}")
        return rec
    print(f"[lower ] {cid} ...", flush=True)
    try:
        rec = lower_cell(arch, shape_name, multi_pod=multi_pod,
                         perf_variant=perf_variant)
    except Exception as e:
        rec = {"cell": cid, "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
        out.write_text(json.dumps(rec, indent=2))
        print(f"[FAIL  ] {cid}: {rec['error']}", flush=True)
        return rec
    out.write_text(json.dumps(rec, indent=2, default=float))
    if rec.get("skipped"):
        print(f"[skip  ] {cid}: {rec['reason']}", flush=True)
    else:
        r = rec["roofline"]
        print(f"[ok    ] {cid}: bound={r['bound']} "
              f"c={r['compute_s']:.4f}s m={r['memory_s']:.4f}s "
              f"x={r['collective_s']:.4f}s "
              f"(lower {rec['lower_s']}s compile {rec['compile_s']}s)",
              flush=True)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--dwn", action="store_true",
                    help="sweep the paper's DWN archs x DWN shapes")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    help="perf-variant knobs, comma separated "
                         "(logits_sharded,serve_tp_only)")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)
    from .compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.list:
        for a in assigned_archs():
            for s in SHAPES:
                ok, why = cell_supported(get_arch(a), SHAPES[s])
                print(f"{a:24s} {s:12s} {'ok' if ok else 'SKIP: ' + why}")
        return 0

    if args.dwn:
        failures = 0
        for a in ("dwn-jsc-sm10", "dwn-jsc-sm50", "dwn-jsc-md360",
                  "dwn-jsc-lg2400", "dwn-jsc-lg2400-fused",
                  "dwn-jsc-md360-fused"):
            for s in DWN_SHAPES:
                if a.endswith("-fused") and s == "dwn_train_1m":
                    continue          # fused variant is a serving datapath
                rec = run_cell(a, s, multi_pod=args.multi_pod,
                               force=args.force)
                failures += 1 if "error" in rec else 0
        print(f"done; failures={failures}")
        return 1 if failures else 0

    if args.all:
        failures = 0
        for a in assigned_archs():
            for s in SHAPES:
                rec = run_cell(a, s, multi_pod=args.multi_pod,
                               force=args.force)
                failures += 1 if "error" in rec else 0
        print(f"done; failures={failures}")
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch and --shape (or --all/--list)"
    rec = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   force=args.force, tag=args.tag,
                   perf_variant=args.variant)
    return 1 if "error" in rec else 0


if __name__ == "__main__":
    sys.exit(main())
