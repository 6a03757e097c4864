"""Flash attention (online softmax) Pallas TPU kernel with GQA + causal +
sliding-window support.

The jnp reference materializes (B, H, S, T) scores — the prefill hot spot at
32k context. This kernel tiles (BQ x BK) score blocks through VMEM with the
canonical (m, l, acc) online-softmax state, so HBM traffic is O(S*hd) and the
working set is a few MXU-aligned tiles.

Grid: (B*H, S/BQ, T/BK), innermost = KV blocks (accumulators carry across).
GQA is handled in the BlockSpec index maps: query row b*H+h reads KV row
b*KV + h//group. Causal/window masking is in-tile via iota comparison.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, n_k: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (BQ, hd)
    k = k_ref[0].astype(jnp.float32)                  # (BK, hd)
    v = v_ref[0].astype(jnp.float32)                  # (BK, hd)
    s = q @ k.T                                       # (BQ, BK)

    if causal or window > 0:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + i * block_q
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block_k
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = mask & (cols <= rows)
        if window > 0:
            mask = mask & (rows - cols < window)
        s = jnp.where(mask, s, NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
    m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _fin():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """GQA flash attention. q (B,S,H,hd); k,v (B,T,KV,hd) -> (B,S,H,hd).

    S % block_q == 0, T % block_k == 0, H % KV == 0.
    """
    b, sq, h, hd = q.shape
    _, tk, kvh, _ = k.shape
    assert h % kvh == 0 and sq % block_q == 0 and tk % block_k == 0
    g = h // kvh
    scale = hd ** -0.5
    n_q, n_k = sq // block_q, tk // block_k

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, tk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, tk, hd)

    def kv_row(bh, i, j):
        return (bh // h) * kvh + (bh % h) // g

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda bh, i, j: (kv_row(bh, i, j), j, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda bh, i, j: (kv_row(bh, i, j), j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        name="flash_attention",
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
