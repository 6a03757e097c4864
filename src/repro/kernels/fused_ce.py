"""Fused cross-entropy Pallas TPU kernels (forward AND backward).

The (T, V) logits tensor is the dominant HBM object of LM training with large
vocabularies (Qwen: 152k). The jnp path materializes exp/normalizer
intermediates at full width; these kernels stream vocab TILES through VMEM,
maintaining online per-token accumulators — one pass over the logits per
direction, no (T, V) fp32 temporary, MXU-free (pure VPU reduction).

Forward kernel
  * ``_ce_parts_kernel``  — NLL *and* the label-smoothing term
    ``logZ - mean_v(x)`` plus the ``logZ`` residual, so the custom-VJP wrapper
    in ``ops.py`` can compose arbitrary smoothing outside the kernel and the
    backward never recomputes the normalizer. The forward-only
    ``fused_cross_entropy`` keeps its NLL.

Backward kernel
  * ``_ce_grad_kernel``   — ``dL/dx = (g_nll + g_smooth) * softmax(x)
    - g_nll * onehot(label) - g_smooth * 1/V`` recomputed tile-by-tile from
    the saved per-token ``logZ`` residual (softmax = exp(x - logZ)); the only
    (T, V) write is the gradient itself, emitted in the logits dtype.

Grid: (T/block_t, V/block_v) with the vocab axis INNERMOST so the per-row
scratch carries across vocab steps ("arbitrary" dimension semantics).
Padded vocab columns (callers pad with -1e30) never win the max, never match
a label, and are excluded from the smoothing mean via ``v_real``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def tok_spec(block_t):
    """BlockSpec for a per-token operand on a (n_t, n_v) grid.

    Per-token operands travel as (T, 1) columns: Mosaic refuses 1-D (T,)
    blocks (XLA's 1-D tiling does not match the kernel's), while a (block_t,
    1) block obeys the TPU tiling rule (last dim equal to the array's) and
    broadcasts against (block_t, block_v) tiles without a relayout."""
    return pl.BlockSpec((block_t, 1), lambda i, j: (i, 0))


def col(x: jax.Array) -> jax.Array:
    """(T,) -> (T, 1) per-token column for ``tok_spec`` operands."""
    return x.reshape(-1, 1)


def tok_scratch(block_t: int):
    """fp32 VMEM scratch for one per-token accumulator column."""
    return pltpu.VMEM((block_t, 1), jnp.float32)


def tok_out(t: int, n: int):
    """``n`` per-token fp32 (T, 1) kernel outputs."""
    return [jax.ShapeDtypeStruct((t, 1), jnp.float32)] * n


def tile_spec(block_t, block_v):
    """BlockSpec for a (T, V) operand tiled over the (n_t, n_v) grid."""
    return pl.BlockSpec((block_t, block_v), lambda i, j: (i, j))


def ce_accumulate(x, labels, j, m_ref, s_ref, t_ref, x_ref, *,
                  block_v: int, v_real: int):
    """One vocab tile of the streaming CE state: online (max, sumexp) plus
    the true-logit and real-column logit-sum accumulators. Shared between the
    CE kernel and the combined CE+distill kernel."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(x, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    s_ref[...] = s_ref[...] * alpha + jnp.sum(jnp.exp(x - m_new), axis=-1,
                                              keepdims=True)
    m_ref[...] = m_new
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) + j * block_v
    hit = cols == labels
    t_ref[...] = t_ref[...] + jnp.sum(jnp.where(hit, x, 0.0), axis=-1,
                                      keepdims=True)
    # sum of REAL logits only (padded cols hold -1e30, excluded by v_real)
    x_ref[...] = x_ref[...] + jnp.sum(jnp.where(cols < v_real, x, 0.0),
                                      axis=-1, keepdims=True)


def ce_grad_term(x, labels, logz, gn, gs, j, *, block_v: int, v_real: int):
    """(dL/dx tile, softmax tile) for g_nll*nll + g_smooth*smooth, from the
    saved logZ residual: (gn+gs)*softmax - gn*onehot - gs*valid/V. The
    per-token operands are (block_t, 1) columns."""
    p = jnp.exp(x - logz)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) + j * block_v
    onehot = (cols == labels).astype(jnp.float32)
    valid = (cols < v_real).astype(jnp.float32)
    return (gn + gs) * p - gn * onehot - gs * (valid / v_real), p


# ----------------------------------------------------------------------------
# forward with label-smoothing parts + logZ residual (custom-VJP entry)
# ----------------------------------------------------------------------------

def _ce_parts_kernel(labels_ref, logits_ref, nll_ref, smooth_ref, logz_ref,
                     m_ref, s_ref, t_ref, x_ref, *,
                     block_v: int, n_v: int, v_real: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)
        t_ref[...] = jnp.zeros_like(t_ref)
        x_ref[...] = jnp.zeros_like(x_ref)

    x = logits_ref[...].astype(jnp.float32)
    ce_accumulate(x, labels_ref[...], j, m_ref, s_ref, t_ref, x_ref,
                  block_v=block_v, v_real=v_real)

    @pl.when(j == n_v - 1)
    def _fin():
        logz = m_ref[...] + jnp.log(s_ref[...])
        logz_ref[...] = logz
        nll_ref[...] = logz - t_ref[...]
        smooth_ref[...] = logz - x_ref[...] / v_real


@functools.partial(jax.jit, static_argnames=("block_t", "block_v", "v_real",
                                             "interpret"))
def fused_cross_entropy_parts(logits: jax.Array, labels: jax.Array,
                              block_t: int = 256, block_v: int = 512,
                              v_real: int = 0, interpret: bool = False):
    """Per-token (nll, smooth, logZ). logits (T, V), labels (T,) -> 3x (T,).

    ``nll = logZ - x[label]``; ``smooth = logZ - mean_{v<v_real}(x)`` (the
    label-smoothing term); ``logZ`` is the residual the backward kernel uses
    to rebuild softmax without a second max pass. ``v_real`` (default: V)
    excludes padded vocab columns from the smoothing mean.
    """
    t, v = logits.shape
    v_real = v_real or v
    assert t % block_t == 0 and v % block_v == 0, (t, v, block_t, block_v)
    n_t, n_v = t // block_t, v // block_v
    kernel = functools.partial(_ce_parts_kernel, block_v=block_v, n_v=n_v,
                               v_real=v_real)
    outs = pl.pallas_call(
        kernel,
        grid=(n_t, n_v),
        in_specs=[tok_spec(block_t), tile_spec(block_t, block_v)],
        out_specs=[tok_spec(block_t) for _ in range(3)],
        out_shape=tok_out(t, 3),
        scratch_shapes=[tok_scratch(block_t) for _ in range(4)],
        interpret=interpret,
    )(col(labels), logits)
    return tuple(o[:, 0] for o in outs)


def fused_cross_entropy(logits: jax.Array, labels: jax.Array,
                        block_t: int = 256, block_v: int = 512,
                        interpret: bool = False) -> jax.Array:
    """Per-token CE. logits (T, V), labels (T,) int32 -> (T,) fp32.

    T % block_t == 0 and V % block_v == 0 (callers pad; configs already pad
    vocab to a multiple of 256).
    """
    return fused_cross_entropy_parts(logits, labels, block_t=block_t,
                                     block_v=block_v,
                                     interpret=interpret)[0]


# ----------------------------------------------------------------------------
# backward: dL/dx from the saved logZ residual, one streaming pass
# ----------------------------------------------------------------------------

def _ce_grad_kernel(labels_ref, logz_ref, gn_ref, gs_ref, logits_ref, dx_ref,
                    *, block_v: int, v_real: int):
    x = logits_ref[...].astype(jnp.float32)
    dx, _ = ce_grad_term(x, labels_ref[...], logz_ref[...], gn_ref[...],
                         gs_ref[...], pl.program_id(1), block_v=block_v,
                         v_real=v_real)
    dx_ref[...] = dx.astype(dx_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "block_v", "v_real",
                                             "interpret"))
def fused_cross_entropy_grad(logits: jax.Array, labels: jax.Array,
                             logz: jax.Array, g_nll: jax.Array,
                             g_smooth: jax.Array, block_t: int = 256,
                             block_v: int = 512, v_real: int = 0,
                             interpret: bool = False) -> jax.Array:
    """dlogits for ``g_nll * nll + g_smooth * smooth`` (per token).

    Each (block_t, block_v) logits tile is read once; the gradient tile is the
    only (T, V) write, in the logits dtype. No cross-tile carry (every tile's
    gradient depends only on the (T,) residuals).
    """
    t, v = logits.shape
    v_real = v_real or v
    assert t % block_t == 0 and v % block_v == 0, (t, v, block_t, block_v)
    kernel = functools.partial(_ce_grad_kernel, block_v=block_v, v_real=v_real)
    return pl.pallas_call(
        kernel,
        grid=(t // block_t, v // block_v),
        in_specs=[tok_spec(block_t)] * 4 + [tile_spec(block_t, block_v)],
        out_specs=tile_spec(block_t, block_v),
        out_shape=jax.ShapeDtypeStruct((t, v), logits.dtype),
        interpret=interpret,
    )(col(labels), col(logz), col(g_nll), col(g_smooth), logits)
