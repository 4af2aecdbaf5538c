"""Training driver: distributed LM training and scan-compiled DWN training.

LM archs (FSDP+TP via pjit on the host mesh): composes the model zoo,
sharding rules, optimizer, token pipeline, checkpoint/restart supervisor
and straggler monitor.  On this CPU container it runs reduced configs
end-to-end (tests/examples); on a pod the same driver runs the full
configs (the dry-run proves every full (arch x shape) cell lowers and
compiles on the production meshes).

DWN archs (family="dwn", e.g. --arch dwn-jsc-md): the scan-compiled
trainer from ``repro.training`` — device-resident epochs with donated
optimizer state; multiple --seeds train as ONE vmapped program
(``train_dwn_batch``), data-parallel over the host mesh when it has
devices.  Prints a JSON summary (per-seed soft accuracy, epoch seconds,
steps/s).

Usage:
    python -m repro.launch.train --arch qwen3-8b --reduced --steps 50 \
        [--batch 8] [--seq 128] [--ckpt-dir /tmp/ckpt] [--model-parallel 2]
    python -m repro.launch.train --arch dwn-jsc-md --reduced \
        --epochs 4 --seeds 0,1,2,3 [--batch 128]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np

from ..configs import get_arch
from ..data.tokens import TokenStream
from ..models import api
from ..runtime.fault import Supervisor, PreemptionHandler
from ..runtime.straggler import StragglerMonitor
from ..sharding.partition import Partitioner
from .mesh import make_host_mesh


def build(cfg, mesh, *, lr: float, num_micro: int = 1):
    """Returns (init_fn, jitted step, shardings)."""
    tp = mesh.shape["model"]
    part = Partitioner(mesh)
    aparams = api.abstract_params(cfg, tp)
    p_axes = api.param_axes(cfg)
    p_shard = part.tree_shardings(aparams, p_axes)
    step_fn, opt = api.make_train_step(
        cfg, tp, num_micro=num_micro, opt=api.make_optimizer(lr))
    aopt = jax.eval_shape(opt.init, aparams)
    from ..optim.adam import AdamState
    from ..sharding.partition import logical
    o_axes = AdamState(logical(name="opt.step"), p_axes, p_axes)
    o_shard = part.tree_shardings(aopt, o_axes)

    jstep = jax.jit(step_fn,
                    in_shardings=(p_shard, o_shard, None),
                    out_shardings=(p_shard, o_shard, None),
                    donate_argnums=(0, 1))

    def init(seed: int = 0):
        key = jax.random.PRNGKey(seed)
        mod = api.module_for(cfg)
        with mesh:
            params = jax.jit(
                lambda k: mod.init_params(k, cfg, tp),
                out_shardings=p_shard)(key)
            opt_state = jax.jit(opt.init, out_shardings=o_shard)(params)
        return params, opt_state

    return init, jstep, (p_shard, o_shard)


def dwn_train(cfg, args) -> int:
    """Scan-compiled DWN training: one device program per epoch block,
    multi-seed runs vmapped into a single program.

    The arch string resolves to a typed ``repro.dwn.DWNSpec``; the spec's
    workload (or ``--workload``) picks the dataset through the registry
    (``repro.workloads``).  With ``--artifact-dir`` each trained model is
    carried through the full lifecycle (freeze → pack) and checkpointed
    as a ``DWNArtifact``.
    """
    import dataclasses
    import warnings

    from ..dwn import DWNArtifact, resolve_spec
    from ..training import ScanTrainer, train_dwn_batch
    from ..workloads import get_workload

    spec = resolve_spec(args.arch)
    workload = getattr(args, "workload", None)
    if workload is None:
        if spec.workload == "jsc":
            warnings.warn(
                "training a DWN without --workload falls back to the "
                "implicit JSC default; pass --workload jsc (or any "
                "registered workload) explicitly",
                DeprecationWarning, stacklevel=2)
    elif workload != spec.workload:
        # validated override: the preset must exist for that workload
        spec = dataclasses.replace(spec, workload=workload)
    dcfg = spec.dwn_config()
    wl = get_workload(spec.workload)
    n_train = 4000 if args.reduced else 20000
    data = wl.load(n_train, max(1000, n_train // 4), seed=args.seed)
    n_train = data.x_train.shape[0]              # workload caps may clamp
    seeds = [int(s) for s in str(args.seeds).split(",") if s != ""]
    batch = args.batch if args.batch > 0 else 128
    epochs = args.epochs

    rep = {"arch": cfg.name, "engine": "scan", "epochs": epochs,
           "batch": batch, "n_train": n_train, "seeds": seeds,
           "workload": spec.workload,
           "spec": spec.to_dict(), "spec_fingerprint": spec.fingerprint()}
    trained: list[tuple[int, object, object, float]] = []
    if len(seeds) == 1:
        trainer = ScanTrainer(dcfg, data, batch=batch, lr=args.lr,
                              seed=seeds[0])
        res = trainer.train(epochs, eval_every=args.eval_every,
                            verbose=not args.quiet)
        secs = [h["sec"] for h in res.history]
        trained.append((seeds[0], res.params, res.buffers,
                        res.soft_test_acc))
        rep.update({
            "soft_test_acc": [round(res.soft_test_acc, 4)],
            "epoch_s": round(float(np.median(secs)), 3) if secs else None,
            "steps_per_epoch": trainer.steps_per_epoch,
            "steps_per_s": round(
                trainer.steps_per_epoch / float(np.median(secs)), 1)
            if secs else None,
        })
    else:
        out = train_dwn_batch(dcfg, data, epochs=epochs, seeds=seeds,
                              batch=batch, lr=args.lr)
        spe = data.x_train.shape[0] // batch
        trained.extend((s, r.params, r.buffers, r.soft_test_acc)
                       for s, r in zip(seeds, out.results))
        rep.update({
            "soft_test_acc": [round(r.soft_test_acc, 4)
                              for r in out.results],
            "vmapped": True,
            "data_parallel": out.data_parallel,
            "wall_s": round(out.wall_s, 3),
            "epoch_s_per_model": round(
                out.wall_s / max(1, epochs) / len(seeds), 3),
            "steps_per_epoch": spe,
        })
    if args.artifact_dir:
        saved = []
        for seed, params, buffers, acc in trained:
            art = DWNArtifact(spec).adopt(params, buffers,
                                          note="launch.train")
            art.calibration.update(seed=seed, epochs=epochs,
                                   soft_test_acc=round(float(acc), 4))
            path = art.freeze().pack().save(
                f"{args.artifact_dir}/seed{seed}")
            saved.append({"seed": seed, "path": str(path),
                          "stage": art.stage})
        rep["artifacts"] = saved
    print(json.dumps(rep))
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--workload", default=None,
                    help="DWN mode: registered workload to train on "
                         "(jsc | mnist | lm-head | ...; default: the "
                         "spec's own workload — omitting it for a JSC "
                         "spec warns, the implicit default is deprecated)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=0,
                    help="batch size (default: 8 for LM archs, 128 for "
                         "DWN archs)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=None,
                    help="learning rate (default: 3e-4 for LM archs, "
                         "1e-3 for DWN archs, the paper protocol)")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--epochs", type=int, default=4,
                    help="DWN mode: training epochs")
    ap.add_argument("--seeds", default="0",
                    help="DWN mode: comma-separated seeds; more than one "
                         "trains all of them as one vmapped program")
    ap.add_argument("--eval-every", type=int, default=1,
                    help="DWN mode: eval cadence (0 = final only, whole "
                         "run as one device program)")
    ap.add_argument("--artifact-dir", default="",
                    help="DWN mode: checkpoint each trained model as a "
                         "DWNArtifact (freeze + pack + save) under "
                         "<dir>/seed<N>")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)
    from .compile_cache import enable_compile_cache
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if cfg.family == "dwn":
        if args.lr is None:
            args.lr = 1e-3       # DWN paper protocol
        return dwn_train(cfg, args)
    if args.lr is None:
        args.lr = 3e-4
    if args.batch <= 0:
        args.batch = 8
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh(args.model_parallel)
    init, jstep, (p_shard, o_shard) = build(cfg, mesh, lr=args.lr)

    stream = TokenStream(cfg.vocab_size, args.seq, args.batch,
                         seed=args.seed)

    def make_batch(raw):
        import jax.numpy as jnp
        b = {"tokens": jnp.asarray(raw["tokens"]),
             "labels": jnp.asarray(raw["labels"])}
        if cfg.family == "encdec":
            b["frames"] = jnp.zeros(
                (args.batch, cfg.enc_frames, cfg.d_model), jnp.bfloat16)
        if cfg.family == "vlm":
            b["patches"] = jnp.zeros(
                (args.batch, cfg.num_patches, cfg.d_model), jnp.bfloat16)
        return b

    monitor = StragglerMonitor()
    losses = []

    def step_once(handle):
        params, opt_state = handle.state
        stream.restore(handle.extra.get("data", {"step": handle.step}))
        raw = stream.next_batch()
        monitor.step_start()
        with mesh:
            params, opt_state, metrics = jstep(params, opt_state,
                                               make_batch(raw))
        loss = float(metrics["loss"])
        monitor.step_end()
        losses.append(loss)
        handle.state = (params, opt_state)
        handle.step += 1
        handle.extra["data"] = stream.state()
        if handle.step % args.log_every == 0:
            print(f"step {handle.step:5d} loss={loss:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f}", flush=True)
        return handle

    params, opt_state = init(args.seed)
    if args.ckpt_dir:
        sup = Supervisor(args.ckpt_dir, save_every=args.save_every,
                         preemption=PreemptionHandler(),
                         shardings=(p_shard, o_shard))
        handle = sup.run(step_once, init_state=(params, opt_state),
                         total_steps=args.steps)
    else:
        from ..runtime.fault import TrainHandle
        handle = TrainHandle((params, opt_state), 0, {})
        while handle.step < args.steps:
            handle = step_once(handle)

    print(json.dumps({
        "arch": cfg.name, "steps": handle.step,
        "first_loss": losses[0] if losses else None,
        "last_loss": float(np.mean(losses[-5:])) if losses else None,
        "straggler_events": len(monitor.events),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
