"""Jit'd public wrappers around the Pallas kernels, including the
``jax.custom_vjp`` fused-loss entry points used by training.

On CPU (this container) the kernels execute in ``interpret=True`` mode for
validation; on TPU they compile to Mosaic. ``auto_interpret()`` picks per
backend so model code can call these unconditionally. Shapes are padded to
block multiples here so callers never worry about alignment.

Differentiable entry points (drop-ins for the jnp losses in
``core.codistillation``, dispatched there by the ``fused_losses`` flag):

  * ``fused_cross_entropy_loss``  — masked/smoothed mean CE; forward streams
    vocab tiles once, backward rebuilds softmax from the saved per-token
    ``logZ`` residual (CE gradient = softmax - smoothed-onehot);
  * ``fused_distill_mean``        — masked mean D(y, y') for mse / kl;
    MSE gradient = 2(a-b)/V, KL gradient from the five-accumulator residuals;
  * ``fused_ce_distill``          — COMBINED task CE + distill: the hot-path
    kernel that reads each (T, V) logits tile exactly once per model and
    emits both losses (and both gradients on the way back).

``fused_causal_attention`` is the differentiable causal attention core
(``kernels/causal_attention.py``) that ``models.attention`` routes training
and prefill through on the TPU.

The custom-VJP boundary sits at the per-token level: masking, label-smoothing
mixing and the mean-reduction stay in plain (T,)-sized differentiable jnp, so
no (T, V) fp32 temporary exists outside the kernels in either direction.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import causal_attention
from repro.kernels.combined_loss import (
    fused_ce_distill_grad,
    fused_ce_distill_parts,
)
from repro.kernels.distill_loss import (
    fused_distill_kl_grad,
    fused_distill_kl_parts,
    fused_distill_loss,
    fused_distill_mse_grad,
)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_ce import (
    NEG,
    fused_cross_entropy,
    fused_cross_entropy_grad,
    fused_cross_entropy_parts,
)


def auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def fused_losses_default() -> bool:
    """Default for the ``fused_losses`` runtime flag: on for TPU (Mosaic),
    off elsewhere — CPU callers opt in explicitly and run interpret-mode."""
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int, value=0.0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def cross_entropy_tokens(logits: jax.Array, labels: jax.Array,
                         block_t: int = 256, block_v: int = 512,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Per-token CE over the trailing vocab dim; any leading shape."""
    interpret = auto_interpret() if interpret is None else interpret
    lead = logits.shape[:-1]
    v = logits.shape[-1]
    t = int(jnp.prod(jnp.array(lead))) if lead else 1
    lg = logits.reshape(t, v)
    lb = labels.reshape(t)
    tp = (-t) % block_t
    lg = _pad_to(lg, 0, block_t)
    lg = _pad_to(lg, 1, block_v, value=NEG)
    lb = jnp.pad(lb, (0, tp))
    # padded vocab cols get -1e30 (never win max / never the label)
    out = fused_cross_entropy(lg, lb, block_t=block_t,
                              block_v=min(block_v, lg.shape[1]),
                              interpret=interpret)
    return out[:t].reshape(lead)


def distill_loss_tokens(logits: jax.Array, target_logits: jax.Array,
                        mode: str = "mse", block_t: int = 256,
                        block_v: int = 512,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Per-token distillation loss over the trailing vocab dim."""
    interpret = auto_interpret() if interpret is None else interpret
    lead = logits.shape[:-1]
    v = logits.shape[-1]
    t = int(jnp.prod(jnp.array(lead))) if lead else 1
    a = logits.reshape(t, v)
    b = target_logits.reshape(t, v)
    a = _pad_to(_pad_to(a, 0, block_t), 1, block_v,
                value=0.0 if mode == "mse" else NEG)
    b = _pad_to(_pad_to(b, 0, block_t), 1, block_v,
                value=0.0 if mode == "mse" else NEG)
    out = fused_distill_loss(a, b, mode=mode, block_t=block_t,
                             block_v=min(block_v, a.shape[1]),
                             interpret=interpret)
    if mode == "mse" and a.shape[1] != v:
        out = out * (a.shape[1] / v)  # undo the padded-vocab mean denominator
    return out[:t].reshape(lead)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
              window: int = 0, block_q: int = 128, block_k: int = 128,
              interpret: Optional[bool] = None) -> jax.Array:
    """GQA flash attention with automatic seq padding."""
    interpret = auto_interpret() if interpret is None else interpret
    sq, tk = q.shape[1], k.shape[1]
    bq = min(block_q, max(16, sq))
    bk = min(block_k, max(16, tk))
    if not causal:
        # padded keys would receive softmax mass without a causal mask
        assert tk % bk == 0, "non-causal attention needs T % block_k == 0"
    qp = _pad_to(q, 1, bq)
    kp = _pad_to(k, 1, bk)
    vp = _pad_to(v, 1, bk)
    # causal mask makes padded keys unreachable from real queries (padded key
    # positions >= sq > any real query row); padded query rows are sliced off.
    out = flash_attention(qp, kp, vp, causal=causal, window=window,
                          block_q=bq, block_k=bk, interpret=interpret)
    return out[:, :sq]


def fused_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Causal GQA attention core through the fused kernels
    (``kernels/causal_attention.py``), differentiable.

    q: (B, S, H, hd); k, v: (B, S, KV, hd) with H % KV == 0 and
    ``causal_attention.supports(S, hd)``. Returns softmax(q k^T / sqrt(hd))
    v, causal, as (B, S, H, hd) in q's dtype; the kernels scale q in fp32
    before its cast back. The kernels work sequence-minor, the layout the
    projections already have on the TPU, so the transposes here move no
    data there. S is padded to the kernels' multiple: the causal mask makes
    padded keys unreachable from real queries, and padded query rows are
    sliced off (their zero cotangents give the real rows nothing).
    """
    interpret = auto_interpret() if interpret is None else interpret
    b, s, h, hd = q.shape
    sp = _round_up(s, causal_attention.SEQ_MULTIPLE)

    def seq_minor(x):                  # (B, S, N, hd) -> (B, N*hd, Sp)
        return jnp.swapaxes(_pad_to(x, 1, sp).reshape(b, sp, -1), 1, 2)

    o = causal_attention.causal_attention(seq_minor(q), seq_minor(k),
                                          seq_minor(v), hd, bool(interpret))
    return jnp.swapaxes(o, 1, 2).reshape(b, sp, h, hd)[:, :s]


# ----------------------------------------------------------------------------
# custom-VJP fused losses
# ----------------------------------------------------------------------------
# The spec tuple (mode?, block_t, block_v, v_real, interpret) is the hashable
# nondiff argument; padded (T, V) arrays are the differentiable primals. Every
# per-token output is sliced/composed/reduced OUTSIDE the custom_vjp, in
# (T,)-sized jnp, so jax handles those cotangents and the kernels only ever
# see full-tile work.

def _int_zero(x: jax.Array):
    """Zero cotangent for an integer primal (labels)."""
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ce_parts_p(spec, logits, labels):
    bt, bv, v_real, interp = spec
    nll, smooth, _ = fused_cross_entropy_parts(
        logits, labels, block_t=bt, block_v=bv, v_real=v_real,
        interpret=interp)
    return nll, smooth


def _ce_parts_fwd(spec, logits, labels):
    bt, bv, v_real, interp = spec
    nll, smooth, logz = fused_cross_entropy_parts(
        logits, labels, block_t=bt, block_v=bv, v_real=v_real,
        interpret=interp)
    return (nll, smooth), (logits, labels, logz)


def _ce_parts_bwd(spec, res, g):
    bt, bv, v_real, interp = spec
    logits, labels, logz = res
    g_nll, g_smooth = g
    dx = fused_cross_entropy_grad(logits, labels, logz, g_nll, g_smooth,
                                  block_t=bt, block_v=bv, v_real=v_real,
                                  interpret=interp)
    return dx, _int_zero(labels)


_ce_parts_p.defvjp(_ce_parts_fwd, _ce_parts_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _distill_tokens_p(spec, logits, target):
    mode, bt, bv, v_real, interp = spec
    return fused_distill_loss(logits, target, mode=mode, block_t=bt,
                              block_v=bv, v_total=v_real, interpret=interp)


def _distill_tokens_fwd(spec, logits, target):
    mode, bt, bv, v_real, interp = spec
    if mode == "mse":
        loss = fused_distill_loss(logits, target, mode="mse", block_t=bt,
                                  block_v=bv, v_total=v_real,
                                  interpret=interp)
        return loss, (logits, target, ())
    loss, logzs, logzt, e = fused_distill_kl_parts(
        logits, target, block_t=bt, block_v=bv, interpret=interp)
    return loss, (logits, target, (logzs, logzt, e))


def _distill_tokens_bwd(spec, res, g):
    mode, bt, bv, v_real, interp = spec
    logits, target, extra = res
    if mode == "mse":
        da, db = fused_distill_mse_grad(logits, target, g, block_t=bt,
                                        block_v=bv, v_total=v_real,
                                        interpret=interp)
    else:
        logzs, logzt, e = extra
        da, db = fused_distill_kl_grad(logits, target, logzs, logzt, e, g,
                                       block_t=bt, block_v=bv,
                                       interpret=interp)
    return da, db


_distill_tokens_p.defvjp(_distill_tokens_fwd, _distill_tokens_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ce_distill_tokens_p(spec, logits, target, labels):
    mode, bt, bv, v_real, interp = spec
    (nll, smooth, dist), _ = fused_ce_distill_parts(
        logits, target, labels, mode=mode, block_t=bt, block_v=bv,
        v_real=v_real, interpret=interp)
    return nll, smooth, dist


def _ce_distill_tokens_fwd(spec, logits, target, labels):
    mode, bt, bv, v_real, interp = spec
    (nll, smooth, dist), residuals = fused_ce_distill_parts(
        logits, target, labels, mode=mode, block_t=bt, block_v=bv,
        v_real=v_real, interpret=interp)
    return (nll, smooth, dist), (logits, target, labels, residuals)


def _ce_distill_tokens_bwd(spec, res, g):
    mode, bt, bv, v_real, interp = spec
    logits, target, labels, residuals = res
    g_nll, g_smooth, g_dist = g
    # kl residuals: (logzs, logzt, e); mse: (logzs,) — grad kernels take the
    # tuple as leading (T,)-vector operands
    ds, dt = fused_ce_distill_grad(logits, target, labels, tuple(residuals),
                                   g_nll, g_smooth, g_dist, mode=mode,
                                   block_t=bt, block_v=bv, v_real=v_real,
                                   interpret=interp)
    return ds, dt, _int_zero(labels)


_ce_distill_tokens_p.defvjp(_ce_distill_tokens_fwd, _ce_distill_tokens_bwd)


# ----------------------------------------------------------------------------
# public fused-loss entry points (scalar, masked, drop-in for core losses)
# ----------------------------------------------------------------------------

def _masked_mean(per_tok: jax.Array, mask) -> jax.Array:
    """Exactly the jnp losses' masked mean: ``sum(loss * mask) / sum(mask)``
    with the ORIGINAL (unbroadcast) mask in the denominator — bit-for-bit the
    reference semantics for any mask broadcastable to the token shape."""
    if mask is not None:
        m_flat, m_raw = mask
        return (jnp.sum(per_tok * m_flat)
                / jnp.maximum(jnp.sum(m_raw.astype(jnp.float32)), 1.0))
    return jnp.mean(per_tok)


def _flat_mask(mask: Optional[jax.Array], lead: Tuple[int, ...], t: int):
    """(broadcast-flattened mask, original mask) or None."""
    if mask is None:
        return None
    return (jnp.broadcast_to(mask, lead).reshape(t).astype(jnp.float32),
            mask)


def _flatten_pad(logits: jax.Array, block_t: int, block_v: int,
                 pad_value: float) -> Tuple[jax.Array, int, int, int, int]:
    """(T, V)-flatten and block-pad; returns (padded, t, v, bt, bv)."""
    v = logits.shape[-1]
    t = 1
    for d in logits.shape[:-1]:
        t *= d
    bt = min(block_t, _round_up(max(t, 1), 8))
    bv = min(block_v, _round_up(v, 128))
    lg = _pad_to(_pad_to(logits.reshape(t, v), 0, bt), 1, bv, value=pad_value)
    return lg, t, v, bt, bv


def _flat_labels(labels: jax.Array, t: int, t_padded: int) -> jax.Array:
    lb = labels.reshape(t).astype(jnp.int32)
    return jnp.pad(lb, (0, t_padded - t))


def fused_cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                             label_smoothing: jax.Array | float = 0.0,
                             mask: Optional[jax.Array] = None,
                             block_t: int = 256, block_v: int = 512,
                             interpret: Optional[bool] = None) -> jax.Array:
    """Differentiable drop-in for ``codistillation.cross_entropy``.

    logits: (..., V) float; labels: (...) int; mask: (...) broadcastable.
    """
    interpret = auto_interpret() if interpret is None else interpret
    lg, t, v, bt, bv = _flatten_pad(logits, block_t, block_v, NEG)
    lb = _flat_labels(labels, t, lg.shape[0])
    spec = (bt, bv, v, bool(interpret))
    nll, smooth = _ce_parts_p(spec, lg, lb)
    ls = jnp.asarray(label_smoothing, jnp.float32)
    per_tok = (1.0 - ls) * nll[:t] + ls * smooth[:t]
    return _masked_mean(per_tok, _flat_mask(mask, logits.shape[:-1], t))


def fused_distill_mean(logits: jax.Array, target_logits: jax.Array,
                       mode: str = "mse", mask: Optional[jax.Array] = None,
                       block_t: int = 256, block_v: int = 512,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Differentiable drop-in for ``distill_mse`` / ``distill_kl``."""
    assert mode in ("mse", "kl"), mode
    interpret = auto_interpret() if interpret is None else interpret
    # mse pads with 0.0 (exact in every dtype => zero diff on padded cols);
    # kl needs the -1e30 sentinel so padded cols carry no softmax mass
    pad = 0.0 if mode == "mse" else NEG
    a, t, v, bt, bv = _flatten_pad(logits, block_t, block_v, pad)
    b, *_ = _flatten_pad(target_logits, block_t, block_v, pad)
    spec = (mode, bt, bv, v, bool(interpret))
    per_tok = _distill_tokens_p(spec, a, b)[:t]
    return _masked_mean(per_tok, _flat_mask(mask, logits.shape[:-1], t))


def fused_ce_distill(logits: jax.Array, target_logits: jax.Array,
                     labels: jax.Array,
                     mode: str = "mse",
                     label_smoothing: jax.Array | float = 0.0,
                     mask: Optional[jax.Array] = None,
                     block_t: int = 256, block_v: int = 512,
                     interpret: Optional[bool] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """(task CE, distill) scalars, reading each logits tile exactly once.

    The codistillation hot path: equivalent to
    ``(cross_entropy(logits, labels, ls, mask),
       distill_pair(mode, logits, target_logits, mask))``
    but one HBM sweep of the student logits instead of two.
    """
    assert mode in ("mse", "kl"), mode
    interpret = auto_interpret() if interpret is None else interpret
    lg, t, v, bt, bv = _flatten_pad(logits, block_t, block_v, NEG)
    tg, *_ = _flatten_pad(target_logits, block_t, block_v, NEG)
    lb = _flat_labels(labels, t, lg.shape[0])
    spec = (mode, bt, bv, v, bool(interpret))
    nll, smooth, dist = _ce_distill_tokens_p(spec, lg, tg, lb)
    ls = jnp.asarray(label_smoothing, jnp.float32)
    per_tok = (1.0 - ls) * nll[:t] + ls * smooth[:t]
    m = _flat_mask(mask, logits.shape[:-1], t)
    return _masked_mean(per_tok, m), _masked_mean(dist[:t], m)
