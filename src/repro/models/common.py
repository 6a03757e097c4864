"""Shared layer primitives: inits, norms, embeddings, RoPE, dtype policy.

Parameters are plain nested dicts of jnp arrays (no flax) — this keeps the
stacked-model codistillation transform (leading ``n`` axis over the ``"pod"``
mesh axis) and scan-over-layers stacking (leading ``L`` axis) trivial pytree
operations.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

PyTree = Any


# ----------------------------------------------------------------------------
# initializers
# ----------------------------------------------------------------------------

def dense_init(key: jax.Array, in_dim: int, out_shape: Tuple[int, ...],
               dtype=jnp.float32, scale: float = 1.0) -> jax.Array:
    """Truncated-normal fan-in init for a (in_dim, *out_shape) matrix."""
    std = scale / math.sqrt(in_dim)
    return (jax.random.truncated_normal(key, -2.0, 2.0, (in_dim, *out_shape))
            * std).astype(dtype)


def embed_init(key: jax.Array, vocab: int, dim: int, dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)


def zeros(shape, dtype=jnp.float32) -> jax.Array:
    return jnp.zeros(shape, dtype)


def ones(shape, dtype=jnp.float32) -> jax.Array:
    return jnp.ones(shape, dtype)


class KeyGen:
    """Splitting helper: kg = KeyGen(key); w = init(kg(), ...)."""

    def __init__(self, key: jax.Array):
        self._key = key

    def __call__(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub


# ----------------------------------------------------------------------------
# norms
# ----------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def init_rms_norm(d: int, dtype=jnp.float32) -> Dict[str, jax.Array]:
    return {"scale": ones((d,), dtype)}


def init_layer_norm(d: int, dtype=jnp.float32) -> Dict[str, jax.Array]:
    return {"scale": ones((d,), dtype), "bias": zeros((d,), dtype)}


def apply_norm(params: Dict[str, jax.Array], x: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    if "bias" in params:
        return layer_norm(x, params["scale"], params["bias"], eps)
    return rms_norm(x, params["scale"], eps)


# ----------------------------------------------------------------------------
# rotary position embeddings
# ----------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    """(head_dim/2,) inverse frequencies."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def _rope_pairs(x: jax.Array, positions: jax.Array, theta: float):
    """Each dim pair (2i, 2i+1) of x rotated by its angle, in fp32: the
    pairs' first elements and their second ones, (..., S, H, hd/2) each."""
    hd = x.shape[-1]
    inv = rope_frequencies(hd, theta)                     # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, hd/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    sin = sin[..., None, :]  # broadcast over heads: (..., S, 1, hd/2)
    cos = cos[..., None, :]
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    return x1 * cos - x2 * sin, x2 * cos + x1 * sin


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    y1, y2 = _rope_pairs(x, positions, theta)
    out = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def apply_rope_halves(x: jax.Array, positions: jax.Array,
                      theta: float = 10000.0) -> jax.Array:
    """``apply_rope`` with each head's dims reordered: the rotated pairs'
    first elements, then their second ones. The dot product of two vectors
    so ordered is that of ``apply_rope``'s, summed in another order; the
    order needs no interleave, which on the TPU is a relayout."""
    y1, y2 = _rope_pairs(x, positions, theta)
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ----------------------------------------------------------------------------
# embeddings / output head
# ----------------------------------------------------------------------------

def init_embedding(key: jax.Array, cfg: ModelConfig,
                   dtype=jnp.float32) -> Dict[str, jax.Array]:
    kg = KeyGen(key)
    p = {"tokens": embed_init(kg(), cfg.padded_vocab, cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(kg(), cfg.d_model, (cfg.padded_vocab,), dtype)
    return p


def embed_tokens(params: Dict[str, jax.Array], tokens: jax.Array,
                 dtype) -> jax.Array:
    return params["tokens"].astype(dtype)[tokens]


def lm_head(params: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    """Logits in the activation dtype (losses upcast per-shard to fp32 —
    keeping the (B,S,V) tensor in bf16 on TPU halves HBM and collective
    traffic for the dominant tensor of LM training)."""
    from repro.models.sharding_hints import hint
    if "head" in params:
        w = params["head"]
    else:
        w = params["tokens"].T
    logits = jnp.einsum("...d,dv->...v", x, w.astype(x.dtype))
    return hint(logits, "btv")


# ----------------------------------------------------------------------------
# activations
# ----------------------------------------------------------------------------

def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name in ("gelu", "geglu"):
        return jax.nn.gelu
    if name == "relu":
        return jax.nn.relu
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


def count_params(tree: PyTree) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(tree))
