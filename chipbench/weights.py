"""Weights and keys made from the run's seed, on the device.

The program's state and the plain reference both take their weights from
``make_params``, so the two start from the same numbers without either
taking anything the other made.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

EMBED_STD = 0.02
NORM_STD = 0.1
BIAS_STD = 0.1


def seed_key(seed: int) -> jax.Array:
    """A key that differs for every seed up to 2**63 (``jax.random.key``
    alone keeps only the low 32 bits)."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def weights_key(seed: int) -> jax.Array:
    return jax.random.fold_in(seed_key(seed), 1)


def data_key(seed: int) -> jax.Array:
    return jax.random.fold_in(seed_key(seed), 2)


def make_params(layout: Dict[str, Tuple[Tuple[int, ...], str, int]],
                key: jax.Array, dtype=jnp.float32) -> Dict[str, jax.Array]:
    """One model's weights as ``path -> array``. Each leaf draws from its own
    key, folded from ``key`` by the leaf's place in the sorted layout."""
    out = {}
    for i, (path, (shape, kind, fan_in)) in enumerate(sorted(layout.items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if kind == "matrix":
            x = z / fan_in ** 0.5
        elif kind == "embed":
            x = z * EMBED_STD
        elif kind == "norm":
            x = 1.0 + z * NORM_STD
        elif kind == "bias":
            x = z * BIAS_STD
        else:
            raise ValueError(f"{path}: unknown kind {kind!r}")
        out[path] = x.astype(dtype)
    return out


def peer_keys(key: jax.Array, n: int) -> jax.Array:
    """Model i's key, as the program's stacked init splits it."""
    return jax.random.split(key, n)


def nest(flat: Dict[str, jax.Array]) -> Dict:
    """``{"a/b": x}`` -> ``{"a": {"b": x}}``."""
    out: Dict = {}
    for path, x in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return out


def flatten(tree) -> Dict[str, jax.Array]:
    """The inverse of ``nest`` for a tree of nested dicts."""
    return {"/".join(str(k.key) for k in path): x
            for path, x in jax.tree_util.tree_leaves_with_path(tree)}
