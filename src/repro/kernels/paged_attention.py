"""Fused paged-attention decode Pallas kernel: block tables straight into
flash-attention-style streaming softmax.

The jnp decode path in ``repro.serve.fleet.model_exec`` makes two full
passes over every slot's context: ``paged_gather`` materializes a dense
``(S, MB*BS, KVh, hd)`` copy of the pool, then the scores/softmax read it
all again. This kernel consumes the block table directly, so that gather
temporary never exists and each live KV block is read exactly once:

  grid (S, MB), KV blocks innermost. Program (s, m) DMAs pool block
  ``table[s, m]`` for every KV head (scalar-prefetched, like
  ``paged_cache`` — dead entries alias the all-zero null block 0) and
  folds it into the canonical online-softmax state (running max ``m``,
  denominator ``l``, accumulator ``acc`` — the same machinery as
  ``kernels/flash_attention``), carried in VMEM scratch across the
  innermost grid steps. Blocks at or past
  ``n_live[s]`` are skipped entirely (``pl.when``), positions past the
  slot's own length are masked to ``NEG`` in-tile (per-slot vector
  positions: every slot decodes at its OWN absolute position), and GQA maps
  the ``G = H // KVh`` query heads of group ``k`` onto KV head ``k`` as the
  batch axis of head-major batched dots.

Quantized pools (int8 / fp8, see ``paged_cache.quantize_rows``) carry one
fp32 scale per stored token row alongside the pool; the kernel dequantizes
inside the inner loop (row ``r``'s K scale multiplies its score, its V
scale its softmax weight — the same products as dequantizing the tile), so
quantization shrinks HBM traffic without a dequantized copy ever hitting
HBM.

Interpret mode on CPU, Mosaic on TPU (``auto_interpret``), with the jnp
oracle ``paged_attention_decode_ref`` pinned against the kernel in
tests/test_paged_attention.py (<=1e-4 at fp32 cache dtype; see
docs/serving.md for the quantized tolerances).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _decode_kernel(table_ref, len_ref, nlive_ref, q_ref, k_ref, v_ref,
                   o_ref, m_ref, l_ref, acc_ref, *,
                   scale: float, block_size: int, n_m: int):
    _decode_body(None, None, table_ref, len_ref, nlive_ref, q_ref, k_ref,
                 v_ref, o_ref, m_ref, l_ref, acc_ref, scale=scale,
                 block_size=block_size, n_m=n_m)


def _decode_kernel_quant(table_ref, len_ref, nlive_ref, q_ref, k_ref, v_ref,
                         ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref, *,
                         scale: float, block_size: int, n_m: int):
    _decode_body(ks_ref, vs_ref, table_ref, len_ref, nlive_ref, q_ref, k_ref,
                 v_ref, o_ref, m_ref, l_ref, acc_ref, scale=scale,
                 block_size=block_size, n_m=n_m)


def _decode_body(ks_ref, vs_ref, table_ref, len_ref, nlive_ref, q_ref, k_ref,
                 v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, block_size: int, n_m: int):
    si, mi = pl.program_id(0), pl.program_id(1)

    @pl.when(mi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(mi < nlive_ref[si])
    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale          # (KVh, G, hd)
        # (BS, KVh, hd) block -> head-major (KVh, BS, hd) for the batched dots
        k = jnp.swapaxes(k_ref[0].astype(jnp.float32), 0, 1)
        v = jnp.swapaxes(v_ref[0].astype(jnp.float32), 0, 1)
        s = jnp.einsum("kgd,ktd->kgt", q, k,
                       preferred_element_type=jnp.float32)  # (KVh, G, BS)
        if ks_ref is not None:
            # dequant in-loop: a per-row scale on K scales that row's score,
            # on V the row's softmax weight — (1, BS) lane vectors
            s = s * ks_ref[0]
        pos = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
               + mi * block_size)
        s = jnp.where(pos <= len_ref[si], s, NEG)
        m_prev = m_ref[...]                                # (KVh, G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = p * vs_ref[0] if vs_ref is not None else p
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "kgt,ktd->kgd", pv, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(mi == n_m - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_decode(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, table: jax.Array,
                           lengths: jax.Array,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """One-token decode for every slot, straight off the block pool.

    q (S, H, hd): the new token's (rope'd) query per slot; k_pool / v_pool
    (NB, BS, KVh, hd): the pools AFTER this step's scatter (the new token's
    KV is in its block); table (S, MB) int32; lengths (S,) int32 = each
    slot's pre-step context length == the new token's absolute position
    (valid keys are positions <= lengths[s]); k_scale / v_scale (NB, BS)
    fp32 per-row dequant scales for quantized pools (both or neither).
    Returns (S, H, hd) attention outputs in q's dtype.

    Each program DMAs one whole pool block, every KV head at once: a
    one-head ``(1, BS, 1, hd)`` block would break the TPU tiling rule
    (the last two block dims must be multiples of (8, 128) or whole).
    """
    if interpret is None:
        from repro.kernels.ops import auto_interpret
        interpret = auto_interpret()
    s, h, hd = q.shape
    nb, bs, kvh, _ = k_pool.shape
    mb = table.shape[1]
    assert h % kvh == 0, (h, kvh)
    g = h // kvh
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), "pass both scales or neither"
    n_live = (lengths.astype(jnp.int32) + bs) // bs   # blocks incl. new token

    pool_spec = pl.BlockSpec((1, bs, kvh, hd),
                             lambda si, mi, t, le, nl: (t[si, mi], 0, 0, 0))
    slot_spec = pl.BlockSpec((1, kvh, g, hd),
                             lambda si, mi, t, le, nl: (si, 0, 0, 0))
    in_specs = [slot_spec, pool_spec, pool_spec]
    operands = [q.reshape(s, kvh, g, hd), k_pool, v_pool]
    if quantized:
        # (NB, 1, BS): the row scales of one block as a (1, BS) lane vector
        scale_spec = pl.BlockSpec(
            (1, 1, bs), lambda si, mi, t, le, nl: (t[si, mi], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale.astype(jnp.float32).reshape(nb, 1, bs),
                     v_scale.astype(jnp.float32).reshape(nb, 1, bs)]
        kernel = _decode_kernel_quant
    else:
        kernel = _decode_kernel
    out = pl.pallas_call(
        functools.partial(kernel, scale=hd ** -0.5, block_size=bs, n_m=mb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s, mb),
            in_specs=in_specs,
            out_specs=slot_spec,
            scratch_shapes=[
                pltpu.VMEM((kvh, g, 1), jnp.float32),
                pltpu.VMEM((kvh, g, 1), jnp.float32),
                pltpu.VMEM((kvh, g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s, kvh, g, hd), q.dtype),
        name="paged_attention",
        interpret=interpret,
    )(table.astype(jnp.int32), lengths.astype(jnp.int32), n_live,
      *operands)
    return out.reshape(s, h, hd)


def paged_attention_decode_ref(q: jax.Array, k_pool: jax.Array,
                               v_pool: jax.Array, table: jax.Array,
                               lengths: jax.Array,
                               k_scale: Optional[jax.Array] = None,
                               v_scale: Optional[jax.Array] = None
                               ) -> jax.Array:
    """jnp oracle: gather the dense context, dense fp32 masked softmax."""
    from repro.kernels.paged_cache import paged_gather_ref
    s, h, hd = q.shape
    _, bs, kvh, _ = k_pool.shape
    g = h // kvh
    n_live = (lengths.astype(jnp.int32) + bs) // bs
    k = paged_gather_ref(k_pool.astype(jnp.float32), table, n_live)
    v = paged_gather_ref(v_pool.astype(jnp.float32), table, n_live)
    if k_scale is not None:
        ks = paged_gather_ref(k_scale[..., None, None].astype(jnp.float32),
                              table, n_live)          # (S, MB*BS, 1, 1)
        vs = paged_gather_ref(v_scale[..., None, None].astype(jnp.float32),
                              table, n_live)
        k, v = k * ks, v * vs
    qf = q.reshape(s, kvh, g, hd).astype(jnp.float32)
    scores = jnp.einsum("skgd,stkd->skgt", qf, k) * hd ** -0.5
    pos = jnp.arange(k.shape[1])
    valid = pos[None, :] <= lengths[:, None]          # (S, T)
    scores = jnp.where(valid[:, None, None, :], scores, NEG)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("skgt,stkd->skgd", w, v)
    return out.reshape(s, h, hd).astype(q.dtype)
