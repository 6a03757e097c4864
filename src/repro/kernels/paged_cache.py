"""Paged KV-cache gather/scatter Pallas kernels for the serving fleet.

The continuous batcher (``repro.serve.fleet``) stores decode-time KV in a
shared block pool ``(num_blocks, block_size, KV, hd)`` instead of one dense
``(B, cap, ...)`` buffer per call: a request owns ``ceil(ctx/block_size)``
blocks named by a per-slot block table, so HBM holds only live context and
slots of wildly different lengths share one allocation. Block 0 is the
reserved NULL block — never allocated, all-zero — and every dead table entry
points at it, which keeps the BlockSpec index maps total.

Two kernels move data between the pool and the decode step:

  ``paged_gather``   (pool, table, n_live) -> (S, MB*BS, KV, hd)
      grid (S, MB); program (s, m) DMAs pool block ``table[s, m]`` into the
      slot's contiguous view, zeroing blocks past ``n_live[s]`` — decode
      reads only live blocks (dead entries all alias the one null block).
  ``paged_scatter``  (pool, new, write_slot, write_off) -> pool
      grid (num_blocks,); the inverse block->writer map (computed host-side
      by the allocator: ``write_slot[b]`` = slot appending into block b this
      step, -1 = untouched) makes every output block written exactly once,
      so the update needs no atomics and no partially-covered outputs.

Quantized pools (``cache_dtype`` int8 / fp8) store one fp32 scale per
token row alongside the pool in a ``(num_blocks, block_size)`` array:
``paged_scatter_quant`` is the fused scatter variant that computes the
row's absmax scale and quantizes INSIDE the kernel (one pass, nothing
dequantized in HBM), and ``quantize_rows`` is the jnp row quantizer the
pool uses at prefill-insert time. Scale 0 (the null block, never written)
dequantizes to exactly 0, so the null-block invariant extends to scales.

All kernels use ``PrefetchScalarGridSpec``: the table / write maps are
scalar-prefetched so the index maps can compute DMA sources before the body
runs. Interpret mode on CPU, Mosaic on TPU (``auto_interpret``), with jnp
oracles (``*_ref``) pinned against the kernels in tests/test_kernels.py and
tests/test_paged_attention.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ----------------------------------------------------------------------------
# gather: pool blocks -> per-slot contiguous KV
# ----------------------------------------------------------------------------

def _gather_kernel(table_ref, nlive_ref, pool_ref, out_ref):
    s, m = pl.program_id(0), pl.program_id(1)
    live = m < nlive_ref[s]
    blk = pool_ref[0]
    out_ref[0, 0] = jnp.where(live, blk, jnp.zeros_like(blk))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_gather(pool: jax.Array, table: jax.Array, n_live: jax.Array,
                 interpret: Optional[bool] = None) -> jax.Array:
    """pool (NB, BS, KV, hd); table (S, MB) int32; n_live (S,) int32 live
    blocks per slot. Returns (S, MB*BS, KV, hd): slot s's context at
    positions [0, n_live[s]*BS), zeros beyond."""
    if interpret is None:
        from repro.kernels.ops import auto_interpret
        interpret = auto_interpret()
    nb, bs, kv, hd = pool.shape
    s, mb = table.shape
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s, mb),
            in_specs=[pl.BlockSpec((1, bs, kv, hd),
                                   lambda si, mi, t, nl: (t[si, mi], 0, 0, 0))],
            out_specs=pl.BlockSpec((1, 1, bs, kv, hd),
                                   lambda si, mi, t, nl: (si, mi, 0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((s, mb, bs, kv, hd), pool.dtype),
        name="paged_cache_gather",
        interpret=interpret,
    )(table.astype(jnp.int32), n_live.astype(jnp.int32), pool)
    return out.reshape(s, mb * bs, kv, hd)


def paged_gather_ref(pool: jax.Array, table: jax.Array,
                     n_live: jax.Array) -> jax.Array:
    """jnp oracle for ``paged_gather``."""
    s, mb = table.shape
    _, bs, kv, hd = pool.shape
    g = pool[table]                                     # (S, MB, BS, KV, hd)
    live = jnp.arange(mb)[None, :] < n_live[:, None]    # (S, MB)
    g = jnp.where(live[..., None, None, None], g, 0.0)
    return g.reshape(s, mb * bs, kv, hd)


# ----------------------------------------------------------------------------
# scatter: one new KV row per appending slot -> its (block, offset)
# ----------------------------------------------------------------------------

def _scatter_kernel(wslot_ref, woff_ref, new_ref, pool_ref, out_ref, *,
                    block_size: int):
    b = pl.program_id(0)
    w = wslot_ref[b]
    off = woff_ref[b]
    src = new_ref[pl.ds(jnp.maximum(w, 0), 1), :, :]      # (1, KV, hd)
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_size, 1, 1), 0)
    mask = (rows == off) & (w >= 0)
    out_ref[0] = jnp.where(mask, src, pool_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_scatter(pool: jax.Array, new: jax.Array, write_slot: jax.Array,
                  write_off: jax.Array,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Append one KV row per active slot into its owned block.

    pool (NB, BS, KV, hd); new (S, KV, hd); write_slot (NB,) int32 = the
    slot appending into block b this step (-1: block untouched); write_off
    (NB,) int32 = row within the block. The block->writer inversion is the
    allocator's (slots own disjoint blocks, so at most one writer per block)
    and makes each output block written exactly once.
    """
    if interpret is None:
        from repro.kernels.ops import auto_interpret
        interpret = auto_interpret()
    nb, bs, kv, hd = pool.shape
    s = new.shape[0]
    return pl.pallas_call(
        functools.partial(_scatter_kernel, block_size=bs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((s, kv, hd), lambda b, ws, wo: (0, 0, 0)),
                pl.BlockSpec((1, bs, kv, hd), lambda b, ws, wo: (b, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bs, kv, hd),
                                   lambda b, ws, wo: (b, 0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        name="paged_cache_scatter",
        interpret=interpret,
    )(write_slot.astype(jnp.int32), write_off.astype(jnp.int32),
      new.astype(pool.dtype), pool)


def paged_scatter_ref(pool: jax.Array, new: jax.Array, write_slot: jax.Array,
                      write_off: jax.Array) -> jax.Array:
    """jnp oracle for ``paged_scatter``."""
    nb, bs, _, _ = pool.shape
    rows = jnp.arange(bs)[None, :]
    mask = (write_slot >= 0)[:, None] & (rows == write_off[:, None])  # (NB,BS)
    src = new.astype(pool.dtype)[jnp.clip(write_slot, 0)]             # (NB,KV,hd)
    return jnp.where(mask[..., None, None], src[:, None], pool)


# ----------------------------------------------------------------------------
# quantized pools: per-row fp32 scales, fused quantize-at-scatter
# ----------------------------------------------------------------------------

# absmax of the representable range per quantized cache dtype
_QMAX = {"int8": 127.0, "float8_e4m3fn": 448.0}


def is_quantized_dtype(dtype) -> bool:
    """True for the quantized KV-pool dtypes (int8 / fp8)."""
    return jnp.dtype(dtype).name in _QMAX


def quantized_dtype_names():
    return tuple(sorted(_QMAX))


def _quantize(x: jax.Array, inv_scale: jax.Array, dtype) -> jax.Array:
    """fp32 -> quantized storage given the reciprocal row scale (already
    broadcast against x). int8 rounds-to-even then clips; fp8 is a plain
    dtype conversion (values are in range by construction of the scale)."""
    y = x.astype(jnp.float32) * inv_scale
    if jnp.dtype(dtype).name == "int8":
        return jnp.clip(jnp.round(y), -127.0, 127.0).astype(jnp.int8)
    return y.astype(dtype)


def quantize_rows(x: jax.Array, dtype):
    """Quantize ``x (..., KV, hd)`` with one fp32 absmax scale per leading
    index (a "row" = one stored token position across all KV heads).
    Returns ``(q, scales)`` with ``scales = x.shape[:-2]``; all-zero rows
    get scale 0 (and dequantize to exactly 0)."""
    qmax = _QMAX[jnp.dtype(dtype).name]
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(-2, -1))
    scales = absmax / qmax
    inv = jnp.where(scales > 0, 1.0 / jnp.maximum(scales, 1e-30), 0.0)
    return _quantize(x, inv[..., None, None], dtype), scales


def _scatter_quant_kernel(wslot_ref, woff_ref, new_ref, pool_ref, sc_ref,
                          out_ref, osc_ref, *, block_size: int, qmax: float,
                          out_dtype):
    b = pl.program_id(0)
    w = wslot_ref[b]
    off = woff_ref[b]
    src = new_ref[pl.ds(jnp.maximum(w, 0), 1), :, :]      # (1, KV, hd)
    src = src.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(src))
    scale = absmax / qmax
    inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    qrow = _quantize(src, inv, out_dtype)
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_size, 1, 1), 0)
    mask = (rows == off) & (w >= 0)
    out_ref[0] = jnp.where(mask, qrow, pool_ref[0])
    rows2 = jax.lax.broadcasted_iota(jnp.int32, (1, 1, block_size), 2)
    mask2 = (rows2 == off) & (w >= 0)
    osc_ref[...] = jnp.where(mask2, scale, sc_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_scatter_quant(pool: jax.Array, scales: jax.Array, new: jax.Array,
                        write_slot: jax.Array, write_off: jax.Array,
                        interpret: Optional[bool] = None):
    """``paged_scatter`` fused with row quantization: the appended fp32 KV
    row is absmax-scaled and stored quantized, its scale written into the
    ``(NB, BS)`` per-row scale array. Returns ``(pool, scales)``.
    Same writer-map contract as ``paged_scatter``. The scales travel as
    ``(NB, 1, BS)`` so each block's row of scales is a whole (1, BS) tile
    (a ``(1, BS)`` block of ``(NB, BS)`` breaks the TPU tiling rule)."""
    if interpret is None:
        from repro.kernels.ops import auto_interpret
        interpret = auto_interpret()
    nb, bs, kv, hd = pool.shape
    s = new.shape[0]
    qmax = _QMAX[jnp.dtype(pool.dtype).name]
    out, out_scales = pl.pallas_call(
        functools.partial(_scatter_quant_kernel, block_size=bs, qmax=qmax,
                          out_dtype=pool.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((s, kv, hd), lambda b, ws, wo: (0, 0, 0)),
                pl.BlockSpec((1, bs, kv, hd), lambda b, ws, wo: (b, 0, 0, 0)),
                pl.BlockSpec((1, 1, bs), lambda b, ws, wo: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bs, kv, hd), lambda b, ws, wo: (b, 0, 0, 0)),
                pl.BlockSpec((1, 1, bs), lambda b, ws, wo: (b, 0, 0)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((nb, 1, bs), jnp.float32)],
        name="paged_cache_scatter_quant",
        interpret=interpret,
    )(write_slot.astype(jnp.int32), write_off.astype(jnp.int32),
      new.astype(jnp.float32), pool,
      scales.astype(jnp.float32).reshape(nb, 1, bs))
    return out, out_scales.reshape(nb, bs)


def paged_scatter_quant_ref(pool: jax.Array, scales: jax.Array,
                            new: jax.Array, write_slot: jax.Array,
                            write_off: jax.Array):
    """jnp oracle for ``paged_scatter_quant``."""
    nb, bs, _, _ = pool.shape
    rows = jnp.arange(bs)[None, :]
    mask = (write_slot >= 0)[:, None] & (rows == write_off[:, None])  # (NB,BS)
    src = new[jnp.clip(write_slot, 0)]                                # (NB,KV,hd)
    qrow, sc = quantize_rows(src, pool.dtype)                         # (NB,), ...
    out = jnp.where(mask[..., None, None], qrow[:, None], pool)
    return out, jnp.where(mask, sc[:, None], scales.astype(jnp.float32))
