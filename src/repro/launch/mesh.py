"""Production meshes.

Functions (not module-level constants) so importing never touches jax device
state. The dry-run sets XLA_FLAGS --xla_force_host_platform_device_count=512
BEFORE any jax import (see dryrun.py); smoke tests and benches see 1 device.

Axes:
  single-pod:  (16, 16)      ("data", "model")      — 256 chips (one v5e pod)
  multi-pod:   (2, 16, 16)   ("pod", "data", "model") — 512 chips

The ``"pod"`` axis doubles as the CODISTILLATION axis: n=2 codistilling
models, one per pod, so the only traffic crossing the (slow) pod-to-pod links
is the prediction exchange — the paper's setup mapped onto TPU topology.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: shardings are propagated
    by the partitioner (pjit / ``shard_map`` semantics). ``jax.make_mesh``
    otherwise defaults to ``Explicit`` axes, under which closing over
    sharded values in ``shard_map`` and duplicate-axis specs are errors."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_codist_mesh(n_models: int = 2, data: int = 8, model: int = 16):
    """Single-pod codistillation mesh: the pod's chips are partitioned into
    n_models groups (the paper's '8 GPUs per model on one server' analogue)."""
    return auto_mesh((n_models, data, model), ("pod", "data", "model"))


def make_host_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Tiny mesh for CI-scale distributed tests (8 forced host devices)."""
    return auto_mesh(shape, axes)


def mesh_chips(mesh) -> int:
    return mesh.devices.size


def pod_index_of_device(mesh, device_id: int) -> int:
    """Which pod a flat device id belongs to (0 if no pod axis)."""
    if "pod" not in mesh.axis_names:
        return 0
    import numpy as np
    idx = np.argwhere(np.vectorize(lambda d: d.id)(mesh.devices) == device_id)
    return int(idx[0][mesh.axis_names.index("pod")]) if idx.size else 0
