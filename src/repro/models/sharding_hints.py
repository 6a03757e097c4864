"""Optional activation-sharding constraints (MaxText-style logical axes).

Model code is mesh-agnostic; the launcher opts in by calling
``set_activation_sharding(batch_axes, tp_axis)`` before tracing. When active,
``hint(x, kind)`` applies ``with_sharding_constraint`` to steer SPMD away from
pathological resharding (e.g. all-gathering the full fp32 logits tensor in the
lm-head backward). When inactive (unit tests, single device) it is a no-op.

Kinds: 'btd' (batch, seq, d_model), 'btv' (batch, seq, vocab->tp).
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()


def set_activation_sharding(batch_axes: Optional[Tuple[str, ...]],
                            tp_axis: Optional[str],
                            tp_size: int = 0, mesh=None) -> None:
    _state.batch_axes = batch_axes
    _state.tp_axis = tp_axis
    _state.tp_size = tp_size
    _state.mesh = mesh


def clear_activation_sharding() -> None:
    _state.batch_axes = None
    _state.tp_axis = None
    _state.tp_size = 0
    _state.mesh = None


def current_mesh():
    return getattr(_state, "mesh", None)


@contextmanager
def activation_sharding(batch_axes, tp_axis, tp_size: int = 0, mesh=None):
    set_activation_sharding(batch_axes, tp_axis, tp_size, mesh)
    try:
        yield
    finally:
        clear_activation_sharding()


def _active() -> bool:
    return getattr(_state, "batch_axes", None) is not None or \
        getattr(_state, "tp_axis", None) is not None


def unpartitioned() -> bool:
    """True while tracing a program that GSPMD will not partition: no
    activation sharding is active, and the process has one device or the
    trace is inside a ``shard_map`` manual over every axis of its mesh. Only
    there may a Mosaic kernel (which XLA cannot partition) be placed."""
    if _active():
        return False
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty:
        return mesh.are_all_axes_manual
    return jax.device_count() == 1


def tensor_parallel_active() -> bool:
    """True while tracing under an activation-sharding context with a tensor
    -parallel axis (the lm-head/vocab dimension may be sharded)."""
    return getattr(_state, "tp_axis", None) is not None


def hint(x: jax.Array, kind: str) -> jax.Array:
    if not _active():
        return x
    batch_axes = getattr(_state, "batch_axes", None)
    tp = getattr(_state, "tp_axis", None)
    tp_size = getattr(_state, "tp_size", 0) or 1
    b = batch_axes if batch_axes else None
    if kind == "btd":
        spec = P(b, None, None)
    elif kind == "btd_carry":
        # residual stream between scanned blocks: shard d_model over tp
        # (Megatron sequence-parallel analogue) so the per-layer activations
        # saved for the backward pass cost 1/tp of HBM. XLA re-gathers at the
        # next layer's first matmul and reduce-scatters after the last.
        d = x.shape[-1]
        spec = P(b, None, tp if (d % tp_size == 0 and d >= tp_size) else None)
    elif kind == "btv":
        spec = P(b, None, tp)
    elif kind == "wire":
        # codistillation exchange payload, stacked over the model/pod axis:
        # (n, B, ...) — pin the stacked axis to "pod" so the cross-pod
        # collective moves THIS (compressed) tensor, not the raw logits.
        spec = P("pod", b, *([None] * (x.ndim - 2)))
        if len(spec) != x.ndim:
            return x
        try:
            return jax.lax.with_sharding_constraint(x, spec)
        except Exception:
            return x
    elif kind == "scores":
        # attention scores (B, H, S, T): shard heads over tp when divisible;
        # otherwise fall back to sequence parallelism over the query axis —
        # avoids the partitioner's "involuntary full rematerialization" (a
        # replicated multi-GB gather) for GQA head counts like 56 on tp=16.
        h, s = x.shape[-3], x.shape[-2]
        if h % tp_size == 0 and h >= tp_size:
            spec = P(b, tp, None, None)
        elif s % tp_size == 0 and s >= tp_size:
            spec = P(b, None, tp, None)
        else:
            return x
    else:
        return x
    if len(spec) != x.ndim:
        # stacked codist models: leading axis is pod-sharded by the param/batch
        # shardings already; pad with None on the left
        spec = P(*([None] * (x.ndim - len(spec)) + list(spec)))
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x
