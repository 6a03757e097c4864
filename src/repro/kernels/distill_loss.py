"""Fused codistillation-loss Pallas TPU kernels (the paper's D(y, y')).

Computes the per-token distillation loss between two logit tensors without
materializing any (T, V) fp32 temporary: vocab tiles stream through VMEM and
per-row accumulators carry across the innermost grid dimension.

Modes:
  * ``mse`` — mean over vocab of (a - b)^2, the paper's loss (A.3:
    "mean squared error between the logits of the two models");
  * ``kl``  — KL(softmax(target) || softmax(logits)) via a streaming
    five-accumulator form (online logsumexp for BOTH operands plus the
    max-rescaled cross term), Anil/Zhang et al.'s loss.

Both read each logit tile exactly once. The residual variants additionally
emit the per-token normalizers so the matching BACKWARD kernels
(``fused_distill_mse_grad`` / ``fused_distill_kl_grad``) can rebuild both
softmaxes in a single second pass:

  mse:  dA =  g * 2 (a - b) / V,            dB = -dA        (no residuals)
  kl:   dLs = g * (softmax(ls) - softmax(lt))
        dLt = g * softmax(lt) * ((lt - ls) - E[lt - ls])
        from residuals (logZ_t, logZ_s, E = U/S_t).

These are the kernels that make every-step prediction exchange affordable at
LM vocabulary sizes; ``ops.py`` wraps them in ``jax.custom_vjp`` entry points.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_ce import col, tok_out, tok_scratch
from repro.kernels.fused_ce import tile_spec as _tile_spec
from repro.kernels.fused_ce import tok_spec as _tok_spec

NEG = -1e30


def _mse_kernel(a_ref, b_ref, out_ref, acc_ref, *, n_v: int, v_total: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    d = a - b
    acc_ref[...] = acc_ref[...] + jnp.sum(d * d, axis=-1, keepdims=True)

    @pl.when(j == n_v - 1)
    def _fin():
        out_ref[...] = acc_ref[...] / v_total


def _kl_accumulate(s_logits_ref, t_logits_ref, mt_ref, st_ref, ms_ref, ss_ref,
                   u_ref):
    """One vocab tile of the streaming five-accumulator KL form."""
    lt = t_logits_ref[...].astype(jnp.float32)
    ls = s_logits_ref[...].astype(jnp.float32)

    # target-side online logsumexp + rescaled cross term U = sum e^{lt-Mt}(lt-ls)
    mt_prev = mt_ref[...]
    mt_new = jnp.maximum(mt_prev, jnp.max(lt, axis=-1, keepdims=True))
    alpha_t = jnp.exp(mt_prev - mt_new)
    w = jnp.exp(lt - mt_new)
    st_ref[...] = st_ref[...] * alpha_t + jnp.sum(w, axis=-1, keepdims=True)
    u_ref[...] = u_ref[...] * alpha_t + jnp.sum(w * (lt - ls), axis=-1,
                                                keepdims=True)
    mt_ref[...] = mt_new

    # student-side online logsumexp
    ms_prev = ms_ref[...]
    ms_new = jnp.maximum(ms_prev, jnp.max(ls, axis=-1, keepdims=True))
    ss_ref[...] = ss_ref[...] * jnp.exp(ms_prev - ms_new) + jnp.sum(
        jnp.exp(ls - ms_new), axis=-1, keepdims=True)
    ms_ref[...] = ms_new


def _kl_init(mt_ref, st_ref, ms_ref, ss_ref, u_ref):
    mt_ref[...] = jnp.full_like(mt_ref, NEG)
    ms_ref[...] = jnp.full_like(ms_ref, NEG)
    st_ref[...] = jnp.zeros_like(st_ref)
    ss_ref[...] = jnp.zeros_like(ss_ref)
    u_ref[...] = jnp.zeros_like(u_ref)


def _kl_kernel(s_logits_ref, t_logits_ref, out_ref,
               mt_ref, st_ref, ms_ref, ss_ref, u_ref, *, n_v: int):
    """KL(softmax(t) || softmax(s)) streamed over vocab tiles."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _kl_init(mt_ref, st_ref, ms_ref, ss_ref, u_ref)

    _kl_accumulate(s_logits_ref, t_logits_ref, mt_ref, st_ref, ms_ref, ss_ref,
                   u_ref)

    @pl.when(j == n_v - 1)
    def _fin():
        log_zt = mt_ref[...] + jnp.log(st_ref[...])
        log_zs = ms_ref[...] + jnp.log(ss_ref[...])
        out_ref[...] = u_ref[...] / st_ref[...] - log_zt + log_zs


def _kl_parts_kernel(s_logits_ref, t_logits_ref, out_ref, logzs_ref,
                     logzt_ref, e_ref, mt_ref, st_ref, ms_ref, ss_ref, u_ref,
                     *, n_v: int):
    """KL forward that also emits the (logZ_s, logZ_t, E) residuals."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _kl_init(mt_ref, st_ref, ms_ref, ss_ref, u_ref)

    _kl_accumulate(s_logits_ref, t_logits_ref, mt_ref, st_ref, ms_ref, ss_ref,
                   u_ref)

    @pl.when(j == n_v - 1)
    def _fin():
        log_zt = mt_ref[...] + jnp.log(st_ref[...])
        log_zs = ms_ref[...] + jnp.log(ss_ref[...])
        e = u_ref[...] / st_ref[...]
        out_ref[...] = e - log_zt + log_zs
        logzs_ref[...] = log_zs
        logzt_ref[...] = log_zt
        e_ref[...] = e


@functools.partial(jax.jit,
                   static_argnames=("mode", "block_t", "block_v", "v_total",
                                    "interpret"))
def fused_distill_loss(logits: jax.Array, target_logits: jax.Array,
                       mode: str = "mse", block_t: int = 256,
                       block_v: int = 512, v_total: int = 0,
                       interpret: bool = False) -> jax.Array:
    """Per-token distillation loss. (T, V) x2 -> (T,) fp32.

    ``v_total`` overrides the MSE mean denominator (default: padded V) so
    callers that pad the vocab with equal values in both operands get the
    unpadded mean directly.
    """
    t, v = logits.shape
    assert logits.shape == target_logits.shape
    assert t % block_t == 0 and v % block_v == 0, (t, v, block_t, block_v)
    n_t, n_v = t // block_t, v // block_v
    if mode == "mse":
        kernel = functools.partial(_mse_kernel, n_v=n_v, v_total=v_total or v)
        scratch = [tok_scratch(block_t)]
    elif mode == "kl":
        kernel = functools.partial(_kl_kernel, n_v=n_v)
        scratch = [tok_scratch(block_t) for _ in range(5)]
    else:
        raise ValueError(mode)
    return pl.pallas_call(
        kernel,
        grid=(n_t, n_v),
        in_specs=[_tile_spec(block_t, block_v), _tile_spec(block_t, block_v)],
        out_specs=_tok_spec(block_t),
        out_shape=tok_out(t, 1)[0],
        scratch_shapes=scratch,
        interpret=interpret,
    )(logits, target_logits)[:, 0]


@functools.partial(jax.jit, static_argnames=("block_t", "block_v",
                                             "interpret"))
def fused_distill_kl_parts(logits: jax.Array, target_logits: jax.Array,
                           block_t: int = 256, block_v: int = 512,
                           interpret: bool = False):
    """KL forward returning (loss, logZ_s, logZ_t, E) — all (T,) fp32."""
    t, v = logits.shape
    assert logits.shape == target_logits.shape
    assert t % block_t == 0 and v % block_v == 0, (t, v, block_t, block_v)
    n_t, n_v = t // block_t, v // block_v
    kernel = functools.partial(_kl_parts_kernel, n_v=n_v)
    outs = pl.pallas_call(
        kernel,
        grid=(n_t, n_v),
        in_specs=[_tile_spec(block_t, block_v), _tile_spec(block_t, block_v)],
        out_specs=[_tok_spec(block_t) for _ in range(4)],
        out_shape=tok_out(t, 4),
        scratch_shapes=[tok_scratch(block_t) for _ in range(5)],
        interpret=interpret,
    )(logits, target_logits)
    return tuple(o[:, 0] for o in outs)


# ----------------------------------------------------------------------------
# backward kernels (single pass, no cross-tile carry)
# ----------------------------------------------------------------------------

def _mse_grad_kernel(a_ref, b_ref, g_ref, da_ref, db_ref, *, v_total: int):
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    da = g_ref[...] * (2.0 / v_total) * (a - b)
    da_ref[...] = da.astype(da_ref.dtype)
    db_ref[...] = (-da).astype(db_ref.dtype)


def _kl_grad_kernel(s_ref, t_ref, logzs_ref, logzt_ref, e_ref, g_ref,
                    ds_ref, dt_ref):
    ls = s_ref[...].astype(jnp.float32)
    lt = t_ref[...].astype(jnp.float32)
    q = jnp.exp(ls - logzs_ref[...])                 # softmax(student)
    p = jnp.exp(lt - logzt_ref[...])                 # softmax(target)
    g = g_ref[...]
    ds_ref[...] = (g * (q - p)).astype(ds_ref.dtype)
    dt_ref[...] = (g * p * ((lt - ls) - e_ref[...])).astype(dt_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "block_v", "v_total",
                                             "interpret"))
def fused_distill_mse_grad(logits: jax.Array, target_logits: jax.Array,
                           g: jax.Array, block_t: int = 256,
                           block_v: int = 512, v_total: int = 0,
                           interpret: bool = False):
    """(dlogits, dtarget) for per-token grads ``g``. dB = -dA = -g*2(a-b)/V."""
    t, v = logits.shape
    assert t % block_t == 0 and v % block_v == 0, (t, v, block_t, block_v)
    kernel = functools.partial(_mse_grad_kernel, v_total=v_total or v)
    return pl.pallas_call(
        kernel,
        grid=(t // block_t, v // block_v),
        in_specs=[_tile_spec(block_t, block_v), _tile_spec(block_t, block_v),
                  _tok_spec(block_t)],
        out_specs=[_tile_spec(block_t, block_v),
                   _tile_spec(block_t, block_v)],
        out_shape=[jax.ShapeDtypeStruct((t, v), logits.dtype),
                   jax.ShapeDtypeStruct((t, v), target_logits.dtype)],
        interpret=interpret,
    )(logits, target_logits, col(g))


@functools.partial(jax.jit, static_argnames=("block_t", "block_v",
                                             "interpret"))
def fused_distill_kl_grad(logits: jax.Array, target_logits: jax.Array,
                          logzs: jax.Array, logzt: jax.Array, e: jax.Array,
                          g: jax.Array, block_t: int = 256,
                          block_v: int = 512, interpret: bool = False):
    """(dlogits, dtarget) from the saved five-accumulator residuals.

    Both softmaxes are rebuilt tile-by-tile from (logZ_s, logZ_t); the
    target-side gradient uses E = E_{softmax(t)}[lt - ls] saved forward.
    """
    t, v = logits.shape
    assert t % block_t == 0 and v % block_v == 0, (t, v, block_t, block_v)
    return pl.pallas_call(
        _kl_grad_kernel,
        grid=(t // block_t, v // block_v),
        in_specs=[_tile_spec(block_t, block_v), _tile_spec(block_t, block_v),
                  _tok_spec(block_t), _tok_spec(block_t), _tok_spec(block_t),
                  _tok_spec(block_t)],
        out_specs=[_tile_spec(block_t, block_v),
                   _tile_spec(block_t, block_v)],
        out_shape=[jax.ShapeDtypeStruct((t, v), logits.dtype),
                   jax.ShapeDtypeStruct((t, v), target_logits.dtype)],
        interpret=interpret,
    )(logits, target_logits, col(logzs), col(logzt), col(e), col(g))
