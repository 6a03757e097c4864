"""Fused causal attention core for training and prefill, forward and backward.

The kernels take q, k, v and give the output sequence-minor: (B, H*hd, S),
each head's hd rows over the sequence along the lanes. That is the layout
XLA gives the projections' outputs on the TPU, so the transposes around the
call move no data, and the head dim never has to fill the 128 lanes (hd 64
is a block of 64 rows). Grouped-query heads read their shared K/V rows
through the index maps.

Forward, grid (B, H, S/bq): the head's K and V stay in VMEM for the whole
sequence, and each query block loops over the key blocks up to its diagonal
(the blocks above it are never visited; only those crossing it are masked).
The query block is transposed once, so each product streams bq rows through
a (hd, bk) weight tile. q is scaled by hd^-0.5 in fp32 and cast back; the
scores and the online softmax are fp32; P is cast to the inputs' dtype for
PV, which accumulates in fp32. It saves each query's logsumexp, (B, H, 1, S)
fp32. The output and the logsumexp carry the checkpoint name ``RESIDUAL``,
so a remat policy can keep them and not rerun the forward.

Backward, one kernel, grid (B, H, S/bk): its first key block transposes the
head's Q and dO into VMEM and forms rowsum(dO * O) per query; each key block
then loops over the query blocks from its diagonal down with the keys down
the sublanes, rebuilds P^T from the logsumexp, and accumulates dK and dV for
its block and dQ for the whole sequence in a VMEM scratch, written after the
last key block. Its five products per block pair take bf16 operands and
accumulate in fp32. With grouped K/V heads it gives dK and dV per query head
in fp32, summed over each group after the call.

Both are named for the profiler: ``causal_attention_fwd`` and
``causal_attention_bwd``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCKS = (512, 256, 128)        # candidate query / key tiles, largest first
SEQ_MULTIPLE = BLOCKS[-1]       # the sequence is padded to a multiple of this
MAX_SEQ = 8192                  # a head's K, V (forward) and Q, O, dO, dQ
                                # (backward) stay in VMEM for the sequence
MASK = -0.7 * float(jnp.finfo(jnp.float32).max)
VMEM_LIMIT = 64 * 2 ** 20
RESIDUAL = "causal_attention_residual"   # the output and logsumexp the
                                         # backward takes
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def supports(seq: int, hd: int) -> bool:
    """Whether the kernels take a head of ``hd`` rows over ``seq`` padded:
    whole bf16 sublane tiles, and the sequence fits VMEM."""
    return hd % 16 == 0 and -(-seq // SEQ_MULTIPLE) * SEQ_MULTIPLE <= MAX_SEQ


def block_size(seq: int) -> int:
    """The query and key tile for a padded sequence length: the largest
    candidate that divides it. Each loop step has a fixed cost that larger
    tiles amortize; at 512 tokens this beats skipping the blocks above the
    diagonal with smaller ones (PERF.md, PR 14)."""
    if seq % SEQ_MULTIPLE:
        raise ValueError(f"sequence {seq} is not a multiple of "
                         f"{SEQ_MULTIPLE}")
    return next(b for b in BLOCKS if seq % b == 0)


def _scaled(q: jax.Array) -> jax.Array:
    """q * hd^-0.5 for a (hd, n) block, multiplied in fp32, cast back."""
    return (q.astype(jnp.float32) * q.shape[0] ** -0.5).astype(q.dtype)


def _dot(a, b, dims, interpret: bool):
    """a . b with fp32 accumulation. XLA's CPU backend has no bf16 x bf16 ->
    fp32 product for some operand layouts, so the interpreter multiplies the
    operands' fp32 values, which bf16 converts to exactly."""
    if interpret:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _row(col: jax.Array) -> jax.Array:
    """(n, 1) -> (1, n), through a transpose of a lane-wide broadcast."""
    return jnp.broadcast_to(col, (col.shape[0], 128)).T[:1]


# ----------------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, bq: int, bk: int,
                interpret: bool):
    iq = pl.program_id(2)
    # queries down the sublanes for the products: each streams bq rows
    # through a (hd, bk) weight tile
    qt = _scaled(q_ref[0]).T                                # (bq, hd)
    qpos = iq * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    keys = lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    def body(j, carry, masked):
        m, l, acc = carry
        start = pl.multiple_of(j * bk, bk)
        s = _dot(qt, k_ref[0, :, pl.ds(start, bk)], _NN, interpret)
        if masked:
            s = jnp.where(keys + start <= qpos, s, MASK)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, :, pl.ds(start, bk)]                   # (hd, bk)
        acc = alpha * acc + _dot(p.astype(v.dtype), v, _NT, interpret)
        return m_new, l, acc

    carry = (jnp.full((bq, 1), -jnp.inf, jnp.float32),
             jnp.zeros((bq, 1), jnp.float32),
             jnp.zeros(qt.shape, jnp.float32))
    # key blocks wholly at or before the block's first query need no mask;
    # the loop ends at the block holding its last query
    n_full = (iq * bq + 1) // bk
    n_kv = ((iq + 1) * bq - 1) // bk + 1
    carry = lax.fori_loop(0, n_full, functools.partial(body, masked=False),
                          carry)
    m, l, acc = lax.fori_loop(n_full, n_kv,
                              functools.partial(body, masked=True), carry)
    o_ref[0] = (acc / l).T.astype(o_ref.dtype)
    lse_ref[0, 0] = _row(m + jnp.log(l))


def _fwd(q, k, v, *, hd: int, interpret: bool):
    b, w, s = q.shape
    heads, rep = w // hd, w // k.shape[1]
    bq = bk = block_size(s)
    kv_spec = pl.BlockSpec((1, hd, s), lambda b, h, i: (b, h // rep, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk, interpret=interpret),
        grid=(b, heads, s // bq),
        in_specs=[pl.BlockSpec((1, hd, bq), lambda b, h, i: (b, h, i)),
                  kv_spec, kv_spec],
        out_specs=[pl.BlockSpec((1, hd, bq), lambda b, h, i: (b, h, i)),
                   pl.BlockSpec((1, 1, 1, bq), lambda b, h, i: (b, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, heads, 1, s), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="causal_attention_fwd",
        interpret=interpret,
    )(q, k, v)


# ----------------------------------------------------------------------------
# backward
# ----------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dk_ref,
                dv_ref, qt_s, dot_s, di_s, dqt_acc, *, bq: int, bk: int,
                interpret: bool):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        # the head's Q (scaled) and dO with queries down the sublanes, and
        # rowsum(dO * O) per query as a lane-dense row, for every key block
        qt_s[...] = _scaled(q_ref[0]).T
        dot_s[...] = do_ref[0].T
        di_s[...] = jnp.sum(o_ref[0].astype(jnp.float32)
                            * do_ref[0].astype(jnp.float32), axis=0,
                            keepdims=True)
        dqt_acc[...] = jnp.zeros_like(dqt_acc)

    # keys down the sublanes: each product streams bk rows
    kt = k_ref[0].T                                         # (bk, hd)
    vt = v_ref[0].T
    keys = j * bk + lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    qpos = lax.broadcasted_iota(jnp.int32, (bk, bq), 1)

    def body(i, carry, masked):
        dkt, dvt = carry
        start = pl.multiple_of(i * bq, bq)
        blk = pl.ds(start, bq)
        qt = qt_s[blk, :]                                   # (bq, hd)
        st = _dot(kt, qt, _NT, interpret)                   # (bk, bq)
        if masked:
            st = jnp.where(keys <= qpos + start, st, MASK)
        pt = jnp.exp(st - lse_ref[0, 0, :, blk])
        dot = dot_s[blk, :]                                # (bq, hd)
        dvt = dvt + _dot(pt.astype(dot.dtype), dot, _NN, interpret)
        dpt = _dot(vt, dot, _NT, interpret)                 # (bk, bq)
        dst = (pt * (dpt - di_s[:, blk])).astype(qt.dtype)
        dkt = dkt + _dot(dst, qt, _NN, interpret)           # (bk, hd)
        dqt_acc[blk, :] += _dot(dst, kt, _TN, interpret)    # (bq, hd)
        return dkt, dvt

    zero = jnp.zeros(kt.shape, jnp.float32)
    # from the first query block holding a query at or after this key
    # block; those wholly at or after its last key need no mask
    first = (j * bk) // bq
    i_full = ((j + 1) * bk - 1 + bq - 1) // bq
    carry = lax.fori_loop(first, i_full, functools.partial(body, masked=True),
                          (zero, zero))
    dkt, dvt = lax.fori_loop(i_full, q_ref.shape[2] // bq,
                             functools.partial(body, masked=False), carry)
    dk_ref[0] = dkt.T.astype(dk_ref.dtype)
    dv_ref[0] = dvt.T.astype(dv_ref.dtype)

    @pl.when(j == pl.num_programs(2) - 1)
    def _fin():
        # the scores took q scaled: dQ carries the scale once more
        hd = dqt_acc.shape[1]
        dq_ref[0] = (dqt_acc[...] * hd ** -0.5).T.astype(dq_ref.dtype)


def _bwd(q, k, v, o, do, lse, *, hd: int, interpret: bool):
    b, w, s = q.shape
    heads, rep = w // hd, w // k.shape[1]
    bq = bk = block_size(s)
    # grouped K/V heads: dK, dV per query head in fp32, summed below
    dkv_dtype = k.dtype if rep == 1 else jnp.float32
    seq_spec = pl.BlockSpec((1, hd, s), lambda b, h, j: (b, h, 0))
    kv_spec = pl.BlockSpec((1, hd, bk), lambda b, h, j: (b, h // rep, j))
    out_spec = pl.BlockSpec((1, hd, bk), lambda b, h, j: (b, h, j))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, bq=bq, bk=bk, interpret=interpret),
        grid=(b, heads, s // bk),
        in_specs=[seq_spec, kv_spec, kv_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, 1, 1, s), lambda b, h, j: (b, h, 0, 0))],
        out_specs=[seq_spec, out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(q.shape, dkv_dtype),
                   jax.ShapeDtypeStruct(q.shape, dkv_dtype)],
        scratch_shapes=[pltpu.VMEM((s, hd), q.dtype),
                        pltpu.VMEM((s, hd), q.dtype),
                        pltpu.VMEM((1, s), jnp.float32),
                        pltpu.VMEM((s, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="causal_attention_bwd",
        interpret=interpret,
    )(q, k, v, o, do, lse)
    if rep > 1:
        def group_sum(x):
            return (x.reshape(b, heads // rep, rep, hd, s).sum(axis=2)
                    .reshape(k.shape).astype(k.dtype))
        dk, dv = group_sum(dk), group_sum(dv)
    return dq, dk, dv


# ----------------------------------------------------------------------------
# the differentiable entry
# ----------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, hd: int,
                     interpret: bool) -> jax.Array:
    """softmax(q^T k / sqrt(hd)) v^T per head, causal, sequence-minor.

    q: (B, H*hd, S); k, v: (B, KV*hd, S) with H % KV == 0; S a multiple of
    ``SEQ_MULTIPLE``. Returns (B, H*hd, S) in q's dtype."""
    return _fwd(q, k, v, hd=hd, interpret=interpret)[0]


def _vjp_fwd(q, k, v, hd, interpret):
    # named, so a remat policy may keep them and not rerun the forward
    o, lse = (checkpoint_name(x, RESIDUAL)
              for x in _fwd(q, k, v, hd=hd, interpret=interpret))
    return o, (q, k, v, o, lse)


def _vjp_bwd(hd, interpret, res, do):
    q, k, v, o, lse = res
    return _bwd(q, k, v, o, do, lse, hd=hd, interpret=interpret)


causal_attention.defvjp(_vjp_fwd, _vjp_bwd)
