"""Vmapped multi-seed / multi-point DWN training.

``train_dwn_batch`` trains a whole stack of same-shape models — different
init seeds, or different sweep grid points whose configs agree on every
array shape (same preset and encoder resolution T; thresholds/placement
may differ, they are arrays) — in ONE compiled device program:

* every member's params / optimizer state / encoded dataset are stacked
  on a leading model axis;
* the single-model epoch block (``engine.build_epoch_block``) is ``vmap``-ed
  over that axis — one XLA program, one dispatch per epoch block, params
  and optimizer state donated;
* per-member minibatch permutations follow each member's own seed stream,
  so member ``i``'s trajectory matches a sequential ``train_dwn(seed=i)``
  run of the same model (within vmap fp tolerance);
* when the host mesh has multiple devices and the model axis divides the
  device count, the vmapped block is wrapped in ``shard_map`` over the
  ``("data",)`` mesh from ``launch.mesh.make_data_mesh()`` — the same
  machinery DWN serving shards batches with — so members train
  data-parallel with zero cross-device collectives.

This is what lets ``repro.sweep.pipeline`` train a grid slice in one
compiled call instead of N sequential python loops.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.model import DWNConfig, init_dwn
from ..data.jsc import JSCData
from ..launch.mesh import make_data_mesh
from .engine import build_epoch_block, encode_dataset, epoch_permutation

_BATCH_PROGRAMS: dict = {}


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _member(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _batch_program(cfg: DWNConfig, n: int, num_bits: int, batch: int,
                   lr: float, sched: str, n_models: int,
                   data_parallel: bool):
    """jit(vmap(block)) over the stacked model axis, optionally laid over
    the ("data",) mesh with shard_map.  Cached process-wide."""
    key = ("batch", cfg, n, num_bits, batch, lr, sched, n_models,
           data_parallel)
    if key in _BATCH_PROGRAMS:
        return _BATCH_PROGRAMS[key]

    block, opt, steps = build_epoch_block(cfg, n, batch, lr, sched)
    fn = jax.vmap(block, in_axes=(0, 0, 0, None, 0))
    mesh = None
    if data_parallel:
        mesh = make_data_mesh()
        ndev = mesh.shape["data"]
        if ndev > 1 and n_models % ndev == 0:
            fn = jax.shard_map(
                fn, mesh=mesh,
                in_specs=(P("data"), P("data"), P("data"), P(), P("data")),
                out_specs=(P("data"), P("data"), P("data")),
                check_vma=False)
        else:
            mesh = None
    prog = jax.jit(fn, donate_argnums=(0, 1))
    _BATCH_PROGRAMS[key] = (prog, opt, steps, mesh is not None)
    return _BATCH_PROGRAMS[key]


@dataclasses.dataclass
class BatchTrainOutcome:
    """Results of one vmapped training run.

    Attributes:
      results: per-member ``TrainResult`` (params/buffers unstacked).
      wall_s: wall-clock of the whole batched run (all members together).
      data_parallel: whether the run was laid over a multi-device mesh.
    """
    results: list
    wall_s: float
    data_parallel: bool


def train_dwn_batch(cfg: DWNConfig, data: JSCData, *, epochs: int,
                    seeds=(0,), models=None, batch: int = 128,
                    lr: float = 1e-3, sched: str = "steplr",
                    input_frac_bits: int | None = None,
                    data_parallel: bool = True,
                    eval_final: bool = True) -> BatchTrainOutcome:
    """Train ``len(seeds)`` same-shape DWNs in one compiled program.

    Args:
      cfg: the shared model config (shapes must agree across members).
      data: shared JSC splits.
      epochs / batch / lr / sched: paper-protocol knobs, shared.
      seeds: per-member seed — drives the member's init (when ``models``
        is None) and its minibatch permutation stream, exactly like a
        sequential ``train_dwn(seed=s)`` run.
      models: optional list of (params, buffers) warm starts, one per
        seed; buffers may differ per member (e.g. threshold placements),
        shapes may not.
      input_frac_bits: PEN quantization folded into the one-time encode.
      data_parallel: lay the model axis over the ("data",) mesh when the
        host has multiple devices and the axis divides them.
      eval_final: run the cached evaluator on every member after training.

    Returns a :class:`BatchTrainOutcome`; ``results[i]`` corresponds to
    ``seeds[i]``.
    """
    from ..core.training import TrainResult
    seeds = list(seeds)
    if models is None:
        models = [init_dwn(jax.random.PRNGKey(s), cfg, data.x_train)
                  for s in seeds]
    assert len(models) == len(seeds), "one (params, buffers) per seed"
    S = len(models)

    t0 = time.time()
    params = _stack([jax.tree.map(jnp.array, p) for p, _ in models])
    buffers = _stack([jax.tree.map(jnp.array, b) for _, b in models])
    bits = jnp.stack([
        encode_dataset(data.x_train, b["thresholds"],
                       input_frac_bits=input_frac_bits)
        for _, b in models])                                 # (S, N, C)
    y = jnp.asarray(data.y_train)
    n = data.x_train.shape[0]

    prog, opt, steps, used_dp = _batch_program(
        cfg, n, int(bits.shape[-1]), batch, lr, sched, S, data_parallel)
    opt_state = _stack([opt.init(_member(params, i)) for i in range(S)])

    if epochs > 0:
        perms = jnp.asarray(np.stack([
            np.stack([epoch_permutation(n, steps, batch, seed=s, epoch=e)
                      for e in range(epochs)])
            for s in seeds]))                                # (S, E, L)
        params, opt_state, losses = prog(params, opt_state, bits, y, perms)
        losses = np.asarray(losses)                          # (S, E, steps)
    else:
        losses = np.zeros((S, 0, steps), np.float32)
    wall = time.time() - t0

    results = []
    for i, s in enumerate(seeds):
        p_i = _member(params, i)
        b_i = _member(buffers, i)
        acc = float("nan")
        if eval_final:
            from ..core.training import eval_soft
            acc = eval_soft(p_i, b_i, cfg, data.x_test, data.y_test,
                            input_frac_bits)
        history = [{"epoch": e, "loss": float(np.mean(losses[i, e])),
                    "test_acc": acc if e == epochs - 1 else None,
                    "sec": wall / max(1, epochs) / S}
                   for e in range(epochs)]
        results.append(TrainResult(p_i, b_i, cfg, history, acc))
    return BatchTrainOutcome(results, wall, used_dp)


__all__ = ["train_dwn_batch", "BatchTrainOutcome"]
