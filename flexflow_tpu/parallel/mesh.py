"""Device mesh construction.

TPU-native replacement for the reference's Legion mapper
(src/mapper/mapper.cc slice_task, mapper.cc:381-485): instead of mapping
index-space task points to processors, we lay out a jax.sharding.Mesh
whose named axes carry the parallelism kinds, and GSPMD places shards.

Canonical axis names:
  "data"    -- batch/sample parallelism (reference: DP)
  "model"   -- tensor/parameter parallelism (reference: TP)
  "seq"     -- sequence/context parallelism (new capability)
  "expert"  -- expert parallelism for MoE
  "pipe"    -- pipeline stages
Unused axes have size 1 and are dropped.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"
PIPE_AXIS = "pipe"


def build_mesh(
    axis_sizes: Dict[str, int],
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a Mesh with the given named axis sizes.

    Uses mesh_utils.create_device_mesh when the product covers all
    devices so the mesh layout follows the physical ICI torus (collectives
    ride neighbor links) — a mesh it cannot lay out raises; a mesh over a
    subset of the devices is a plain reshape.
    """
    sizes = {k: v for k, v in axis_sizes.items() if v > 1}
    if not sizes:
        sizes = {DATA_AXIS: 1}
    if devices is None and jax.process_count() > 1:
        # multi-host job: one axis spans hosts over DCN, the rest stay
        # inside a host on ICI (parallel/distributed.py)
        from .distributed import multihost_mesh_arrays

        dev_array, names = multihost_mesh_arrays(sizes)
        return Mesh(dev_array, names)
    if devices is None:
        devices = jax.devices()
    total = int(np.prod(list(sizes.values())))
    if total > len(devices):
        raise ValueError(f"mesh needs {total} devices, have {len(devices)}")
    names = tuple(sizes)
    shape = tuple(sizes[n] for n in names)
    use = list(devices)[:total]
    if total == len(devices):
        from jax.experimental import mesh_utils

        return Mesh(mesh_utils.create_device_mesh(shape, devices=use), names)
    return Mesh(np.asarray(use).reshape(shape), names)


def data_parallel_mesh(num_devices: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    n = num_devices or len(devs)
    return build_mesh({DATA_AXIS: n}, devs[:n])


def serving_mesh(tp_degree: int, devices: Optional[Sequence] = None) -> Mesh:
    """The generation engine's mesh: a 1-D ``"model"`` axis over the
    first ``tp_degree`` devices (tensor-parallel decode shards KV heads
    on it). Unlike :func:`build_mesh`, a degree-1 mesh KEEPS the named
    axis — the engine's PartitionSpecs always reference ``"model"``, and
    a 1-device mesh must lower them as no-ops rather than KeyErrors (the
    bit-for-bit single-device path)."""
    if tp_degree < 1:
        raise ValueError(f"tp_degree must be >= 1, got {tp_degree}")
    if devices is None:
        devices = jax.devices()
    if tp_degree > len(devices):
        raise ValueError(
            f"serving mesh needs {tp_degree} devices, have {len(devices)}"
        )
    return Mesh(np.asarray(list(devices)[:tp_degree]), (MODEL_AXIS,))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))
