"""Production meshes.

A function, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS *before* first init).

Topology (TPU v5e pods):
    single pod : (16, 16)    axes ("data", "model")   = 256 chips
    two pods   : (2, 16, 16) axes ("pod", "data", "model") = 512 chips

"pod" composes with "data" for DP/FSDP; collectives crossing "pod" are the
slow (inter-pod) links, so gradient reduction is hierarchical by
construction (reduce-scatter within pod, then cross-pod all-reduce over
shards).  "model" carries TP/EP and stays inside the pod's dense ICI.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the code places arrays with
    in/out shardings and ``shard_map`` and lets the compiler propagate the
    rest, which ``make_mesh``'s Explicit default refuses."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (tests / examples on CPU)."""
    n = len(jax.devices())
    mp = max(1, min(model_parallel, n))
    return make_mesh((n // mp, mp), ("data", "model"))


def make_data_mesh():
    """1-D ("data",) mesh over every host device.

    The DWN classify path is embarrassingly data-parallel (no weights to
    shard: the whole frozen model fits any single device), so serving
    shards only the batch axis; ``ServingEngine`` lays batch buckets over
    this mesh with ``shard_map``.
    """
    return make_mesh((len(jax.devices()),), ("data",))


# TPU v5e hardware constants for the roofline (per chip).
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW_PER_LINK = 50e9            # bytes/s/link
