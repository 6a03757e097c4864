"""Combined CE + distillation Pallas kernel: one read of each logits tile.

The codistillation hot path (Algorithm 1, prediction mode) evaluates BOTH the
task cross-entropy and the distillation loss D(y, y') on the same student
logits every step. Run as two separate kernels that is two full HBM sweeps of
the (T, V) logits — at Qwen-scale vocab (152k) the logits are the dominant
HBM object, so the second sweep roughly doubles the loss cost. This kernel
fuses them: each (block_t, block_v) student tile and target tile is read
EXACTLY ONCE and all per-token outputs stream out of VMEM accumulators:

  nll     = logZ_s - x[label]                (task CE)
  smooth  = logZ_s - mean_v(x)               (label-smoothing term)
  dist    = mse: mean_v (s - t)^2            (paper A.3)
            kl:  KL(softmax(t) || softmax(s))  (Anil-style)

For ``kl`` the student-side online logsumexp is shared between the CE and the
KL — the five-accumulator KL form degenerates to just three extra registers
(m_t, s_t, U) on top of the CE accumulators.

The matching backward kernels emit (dstudent, dtarget) in one pass from the
saved (T,)-sized residuals (logZ_s and, for kl, logZ_t and E = E_p[lt - ls]):

  dstudent = (g_nll + g_smooth) softmax(s) - g_nll onehot - g_smooth / V
             + g_dist * (mse: 2(s-t)/V | kl: softmax(s) - softmax(t))
  dtarget  = g_dist * (mse: -2(s-t)/V  | kl: softmax(t)((t-s) - E))

Padded vocab columns must hold -1e30 in BOTH operands (never win a max, zero
MSE diff, zero softmax mass); ``v_real`` excludes them from the /V means.
``ops.py`` wraps these in the ``fused_ce_distill`` custom-VJP entry point.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_ce import NEG, col, tok_out, tok_scratch
from repro.kernels.fused_ce import ce_accumulate as _ce_accumulate
from repro.kernels.fused_ce import ce_grad_term as _ce_grad_term
from repro.kernels.fused_ce import tile_spec as _tile_spec
from repro.kernels.fused_ce import tok_spec as _tok_spec


def _combined_mse_kernel(labels_ref, s_logits_ref, t_logits_ref,
                         nll_ref, smooth_ref, dist_ref, logzs_ref,
                         m_ref, s_ref, tr_ref, xs_ref, acc_ref, *,
                         block_v: int, n_v: int, v_real: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        for r in (s_ref, tr_ref, xs_ref, acc_ref):
            r[...] = jnp.zeros_like(r)

    x = s_logits_ref[...].astype(jnp.float32)
    t = t_logits_ref[...].astype(jnp.float32)
    _ce_accumulate(x, labels_ref[...], j, m_ref, s_ref, tr_ref, xs_ref,
                   block_v=block_v, v_real=v_real)
    # padded cols hold the -1e30 sentinel whose bf16<->f32 round trip is not
    # exact — mask them out rather than relying on a zero diff
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) + j * block_v
    d = jnp.where(cols < v_real, x - t, 0.0)
    acc_ref[...] = acc_ref[...] + jnp.sum(d * d, axis=-1, keepdims=True)

    @pl.when(j == n_v - 1)
    def _fin():
        logz = m_ref[...] + jnp.log(s_ref[...])
        logzs_ref[...] = logz
        nll_ref[...] = logz - tr_ref[...]
        smooth_ref[...] = logz - xs_ref[...] / v_real
        dist_ref[...] = acc_ref[...] / v_real


def _combined_kl_kernel(labels_ref, s_logits_ref, t_logits_ref,
                        nll_ref, smooth_ref, dist_ref, logzs_ref, logzt_ref,
                        e_ref, m_ref, s_ref, tr_ref, xs_ref, mt_ref, st_ref,
                        u_ref, *, block_v: int, n_v: int, v_real: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        mt_ref[...] = jnp.full_like(mt_ref, NEG)
        for r in (s_ref, tr_ref, xs_ref, st_ref, u_ref):
            r[...] = jnp.zeros_like(r)

    x = s_logits_ref[...].astype(jnp.float32)
    lt = t_logits_ref[...].astype(jnp.float32)
    # student-side accumulators serve the CE *and* the KL's logZ_s
    _ce_accumulate(x, labels_ref[...], j, m_ref, s_ref, tr_ref, xs_ref,
                   block_v=block_v, v_real=v_real)
    # target-side online logsumexp + rescaled cross term
    mt_prev = mt_ref[...]
    mt_new = jnp.maximum(mt_prev, jnp.max(lt, axis=-1, keepdims=True))
    alpha_t = jnp.exp(mt_prev - mt_new)
    w = jnp.exp(lt - mt_new)
    st_ref[...] = st_ref[...] * alpha_t + jnp.sum(w, axis=-1, keepdims=True)
    u_ref[...] = u_ref[...] * alpha_t + jnp.sum(w * (lt - x), axis=-1,
                                                keepdims=True)
    mt_ref[...] = mt_new

    @pl.when(j == n_v - 1)
    def _fin():
        logzs = m_ref[...] + jnp.log(s_ref[...])
        logzt = mt_ref[...] + jnp.log(st_ref[...])
        e = u_ref[...] / st_ref[...]
        logzs_ref[...] = logzs
        logzt_ref[...] = logzt
        e_ref[...] = e
        nll_ref[...] = logzs - tr_ref[...]
        smooth_ref[...] = logzs - xs_ref[...] / v_real
        dist_ref[...] = e - logzt + logzs


@functools.partial(jax.jit, static_argnames=("mode", "block_t", "block_v",
                                             "v_real", "interpret"))
def fused_ce_distill_parts(logits: jax.Array, target_logits: jax.Array,
                           labels: jax.Array, mode: str = "mse",
                           block_t: int = 256, block_v: int = 512,
                           v_real: int = 0, interpret: bool = False):
    """One-sweep CE + distill forward. (T, V) x2, (T,) labels.

    Returns per-token ``(nll, smooth, dist)`` plus residuals: ``(logzs,)``
    for mse, ``(logzs, logzt, e)`` for kl.
    """
    t, v = logits.shape
    assert logits.shape == target_logits.shape
    v_real = v_real or v
    assert t % block_t == 0 and v % block_v == 0, (t, v, block_t, block_v)
    n_t, n_v = t // block_t, v // block_v
    if mode == "mse":
        kernel = functools.partial(_combined_mse_kernel, block_v=block_v,
                                   n_v=n_v, v_real=v_real)
        n_out, n_scratch = 4, 5
    elif mode == "kl":
        kernel = functools.partial(_combined_kl_kernel, block_v=block_v,
                                   n_v=n_v, v_real=v_real)
        n_out, n_scratch = 6, 7
    else:
        raise ValueError(mode)
    outs = pl.pallas_call(
        kernel,
        grid=(n_t, n_v),
        in_specs=[_tok_spec(block_t), _tile_spec(block_t, block_v),
                  _tile_spec(block_t, block_v)],
        out_specs=[_tok_spec(block_t) for _ in range(n_out)],
        out_shape=tok_out(t, n_out),
        scratch_shapes=[tok_scratch(block_t) for _ in range(n_scratch)],
        interpret=interpret,
    )(col(labels), logits, target_logits)
    outs = tuple(o[:, 0] for o in outs)
    return outs[:3], outs[3:]


# ----------------------------------------------------------------------------
# backward: (dstudent, dtarget) in one fused pass
# ----------------------------------------------------------------------------

def _combined_mse_grad_kernel(labels_ref, logzs_ref, gn_ref, gs_ref, gd_ref,
                              s_logits_ref, t_logits_ref, ds_ref, dt_ref, *,
                              block_v: int, v_real: int):
    j = pl.program_id(1)
    x = s_logits_ref[...].astype(jnp.float32)
    t = t_logits_ref[...].astype(jnp.float32)
    ce, _ = _ce_grad_term(x, labels_ref[...], logzs_ref[...], gn_ref[...],
                          gs_ref[...], j, block_v=block_v, v_real=v_real)
    # same cols<v_real guard as the forward: the pad sentinel's dtype
    # round-trip makes x-t nonzero (or inf for narrow dtypes) on padded cols
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) + j * block_v
    d = jnp.where(cols < v_real, x - t, 0.0)
    dd = gd_ref[...] * (2.0 / v_real) * d
    ds_ref[...] = (ce + dd).astype(ds_ref.dtype)
    dt_ref[...] = (-dd).astype(dt_ref.dtype)


def _combined_kl_grad_kernel(labels_ref, logzs_ref, logzt_ref, e_ref, gn_ref,
                             gs_ref, gd_ref, s_logits_ref, t_logits_ref,
                             ds_ref, dt_ref, *, block_v: int, v_real: int):
    j = pl.program_id(1)
    x = s_logits_ref[...].astype(jnp.float32)
    lt = t_logits_ref[...].astype(jnp.float32)
    ce, q = _ce_grad_term(x, labels_ref[...], logzs_ref[...], gn_ref[...],
                          gs_ref[...], j, block_v=block_v, v_real=v_real)
    p = jnp.exp(lt - logzt_ref[...])
    gd = gd_ref[...]
    ds_ref[...] = (ce + gd * (q - p)).astype(ds_ref.dtype)
    dt_ref[...] = (gd * p * ((lt - x) - e_ref[...])).astype(dt_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mode", "block_t", "block_v",
                                             "v_real", "interpret"))
def fused_ce_distill_grad(logits: jax.Array, target_logits: jax.Array,
                          labels: jax.Array, residuals, g_nll: jax.Array,
                          g_smooth: jax.Array, g_dist: jax.Array,
                          mode: str = "mse", block_t: int = 256,
                          block_v: int = 512, v_real: int = 0,
                          interpret: bool = False):
    """(dlogits, dtarget) for the combined loss, one read of each tile."""
    t, v = logits.shape
    v_real = v_real or v
    assert t % block_t == 0 and v % block_v == 0, (t, v, block_t, block_v)
    if mode == "mse":
        kernel = functools.partial(_combined_mse_grad_kernel, block_v=block_v,
                                   v_real=v_real)
    elif mode == "kl":
        kernel = functools.partial(_combined_kl_grad_kernel, block_v=block_v,
                                   v_real=v_real)
    else:
        raise ValueError(mode)
    tok_ins = [_tok_spec(block_t)] * (1 + len(residuals) + 3)
    return pl.pallas_call(
        kernel,
        grid=(t // block_t, v // block_v),
        in_specs=tok_ins + [_tile_spec(block_t, block_v),
                            _tile_spec(block_t, block_v)],
        out_specs=[_tile_spec(block_t, block_v),
                   _tile_spec(block_t, block_v)],
        out_shape=[jax.ShapeDtypeStruct((t, v), logits.dtype),
                   jax.ShapeDtypeStruct((t, v), target_logits.dtype)],
        interpret=interpret,
    )(*map(col, (labels, *residuals, g_nll, g_smooth, g_dist)), logits,
      target_logits)
