"""Batched decode over the paged KV pool: one jitted step for all slots.

Mirrors ``LM.decode``'s scan-over-layers, but attention sublayers read/write
the shared block pool through the ``repro.kernels.paged_cache`` kernels
instead of a dense per-call cache, and every slot carries its OWN absolute
position (= its current context length) — the ragged substrate continuous
batching needs. Recurrent sublayers (mamba / rwkv) reuse the model's
``_sublayer_decode`` unchanged (their state is position-free).

Two attention paths, numerically pinned against each other:

* ``fused_attention=False`` — the jnp oracle: ``paged_gather`` a dense
  ``(S, MB*BS, KVh, hd)`` context, dense fp32 masked softmax. Same
  projections, same fp32 softmax as the dense engine path — masked (dead /
  padded) slots contribute exactly 0 after ``exp(NEG - max)`` underflow, so
  per-slot logits match single-request ``Engine.generate`` decode and
  greedy streams are token-identical (the fleet-vs-engine parity pinned in
  tests/test_fleet.py).
* ``fused_attention=True`` (the default) — the
  ``repro.kernels.paged_attention`` streaming-softmax kernel consumes the
  block table directly: the gather temporary never exists and each live KV
  block is read exactly once (Mosaic on TPU, interpret on CPU — the usual
  ``auto_interpret`` convention). Logits parity vs the oracle is <=1e-4 at
  fp32 cache dtype (tests/test_paged_attention.py).

Quantized pools (``cache_dtype`` int8/fp8) append through the fused
``paged_scatter_quant`` (quantize-at-scatter) and dequantize per-row inside
whichever attention path runs.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import paged_attention_decode
from repro.kernels.paged_cache import (paged_gather, paged_scatter,
                                       paged_scatter_quant)
from repro.models import attention as attn
from repro.models.common import apply_norm, embed_tokens, lm_head
from repro.models.ffn import ffn_forward
from repro.models.moe import moe_forward
from repro.models.transformer import _n_scan, _sub_kinds, _sublayer_decode

PyTree = Any


def _paged_attention_decode(p: Dict, x: jax.Array, kv: Dict[str, jax.Array],
                            table: jax.Array, lengths: jax.Array,
                            write_slot: jax.Array, write_off: jax.Array,
                            cfg, fused: bool
                            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode for every slot against its paged context.

    x (S,1,d); kv {"k","v"[,"k_scale","v_scale"]}: (NB,BS,KVh,hd) pools for
    THIS layer (plus (NB,BS) fp32 row scales when quantized); table (S,MB);
    lengths (S,) = each slot's context length == the new token's absolute
    position; write_slot/write_off (NB,) from ``PagedCachePool.write_maps``
    (inactive slots appear in no map entry, so they never touch the pool).
    """
    quantized = "k_scale" in kv
    bs = kv["k"].shape[1]
    positions = lengths[:, None]                       # (S,1) per-slot pos
    q, k_new, v_new = attn._project_qkv(p, x, cfg)
    q = attn.apply_rope(q, positions, cfg.rope_theta)
    k_new = attn.apply_rope(k_new, positions, cfg.rope_theta)

    if quantized:
        k_pool, k_sc = paged_scatter_quant(kv["k"], kv["k_scale"],
                                           k_new[:, 0], write_slot, write_off)
        v_pool, v_sc = paged_scatter_quant(kv["v"], kv["v_scale"],
                                           v_new[:, 0], write_slot, write_off)
        kv_out = {"k": k_pool, "v": v_pool,
                  "k_scale": k_sc, "v_scale": v_sc}
    else:
        k_pool = paged_scatter(kv["k"], k_new[:, 0], write_slot, write_off)
        v_pool = paged_scatter(kv["v"], v_new[:, 0], write_slot, write_off)
        k_sc = v_sc = None
        kv_out = {"k": k_pool, "v": v_pool}

    if fused:
        o = paged_attention_decode(q[:, 0], k_pool, v_pool, table, lengths,
                                   k_scale=k_sc, v_scale=v_sc)  # (S, H, hd)
        out = attn._out_proj(p, o[:, None].astype(x.dtype))
        return out, kv_out

    n_live = (lengths + bs) // bs                      # blocks incl. new token
    k = paged_gather(k_pool, table, n_live)            # (S, MB*BS, KVh, hd)
    v = paged_gather(v_pool, table, n_live)
    if quantized:
        ks = paged_gather(k_sc[..., None, None], table, n_live)  # (S,T,1,1)
        vs = paged_gather(v_sc[..., None, None], table, n_live)
        k = (k.astype(jnp.float32) * ks).astype(x.dtype)
        v = (v.astype(jnp.float32) * vs).astype(x.dtype)

    scores = attn._gqa_scores(q, k)                    # (S, H, 1, MB*BS)
    slot_pos = jnp.arange(k.shape[1])
    valid = (slot_pos[None, :] <= lengths[:, None])[:, None, None, :]
    scores = jnp.where(valid, scores, attn.NEG_INF)
    w = attn._softmax(scores).astype(x.dtype)
    out = attn._out_proj(p, attn._gqa_combine(w, v))
    return out, kv_out


def _attn_sublayer(p: Dict, x: jax.Array, kv, table, lengths, write_slot,
                   write_off, cfg, ffn_kind: str, fused: bool):
    h = apply_norm(p["norm1"], x, cfg.norm_eps)
    h, kv = _paged_attention_decode(p["mix"], h, kv, table, lengths,
                                    write_slot, write_off, cfg, fused)
    x = x + h
    h2 = apply_norm(p["norm2"], x, cfg.norm_eps)
    if ffn_kind == "moe":
        h2, _ = moe_forward(p["ffn"], h2, cfg, capacity_factor=0.0)
    else:
        h2 = ffn_forward(p["ffn"], h2, cfg)
    return x + h2, kv


def build_decode_step(model, fused_attention: Optional[bool] = None):
    """Compile-once batched decode: (params, kv, states, table, lengths,
    write_slot, write_off, tokens) -> (logits (S,V), kv, states).

    ``fused_attention`` None/True (the default) runs the
    ``kernels.paged_attention`` streaming-softmax kernel; False pins the
    jnp gather+dense-softmax oracle. All operands have step-invariant
    shapes, so the returned jit compiles exactly once per fleet engine and
    every scheduler tick reuses it. The ``kv`` argument is donated.
    """
    cfg = model.cfg
    kinds = _sub_kinds(cfg)
    fused = True if fused_attention is None else bool(fused_attention)

    def step(params, kv, states, table, lengths, write_slot, write_off,
             tokens):
        dtype = cfg.activation_dtype
        x = embed_tokens(params["embed"], tokens, dtype)   # (S,1,d)
        if "embed_norm" in params:
            x = apply_norm(params["embed_norm"], x, cfg.norm_eps)

        def body(carry, xs):
            h = carry
            lp, kv_l, st_l = xs
            kv_out, st_out = {}, {}
            for i, (m, f) in enumerate(kinds):
                name = f"sub{i}"
                if m == "attn":
                    h, kv_out[name] = _attn_sublayer(
                        lp[name], h, kv_l[name], table, lengths,
                        write_slot, write_off, cfg, f, fused)
                else:
                    h, st_out[name] = _sublayer_decode(
                        lp[name], h, st_l[name], cfg, m, f,
                        jnp.zeros((), jnp.int32))
            return h, (kv_out, st_out)

        x, (kv, states) = jax.lax.scan(body, x, (params["layers"], kv,
                                                 states))
        x = apply_norm(params["final_norm"], x, cfg.norm_eps)
        logits = lm_head(params["embed"], x)               # (S,1,V)
        return logits[:, -1], kv, states

    _n_scan(cfg)           # called for effect: validates the scan layout early
    # the KV pools (argument 1) are donated: every caller replaces its pools
    # with the returned ones, so the step updates them in place in HBM
    return jax.jit(step, donate_argnums=1)


def _paged_attention_verify(p: Dict, x: jax.Array, kv: Dict[str, jax.Array],
                            table: jax.Array, lengths: jax.Array,
                            write_slots: jax.Array, write_offs: jax.Array,
                            cfg, fused: bool
                            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """k-token speculative verify for every slot in one forward.

    x (S,k,d) — the k draft inputs per slot at positions
    ``lengths[s] + j``; write_slots/write_offs (k,NB) from
    ``PagedCachePool.write_maps_k`` (one scatter per draft position, k is
    static so the loop unrolls inside the jit). The fused path expands each
    slot into k pseudo-slots sharing its block table — the decode kernel's
    inclusive ``pos <= length`` mask then gives exact causal semantics:
    pseudo-slot (s, j) attends positions ``0..lengths[s]+j``, i.e. the full
    prior context plus drafts ``<= j``. Bitwise, each row reproduces what a
    plain one-token decode at that position would compute, which is what
    makes accept/reject resampling exact at temperature 0.
    """
    quantized = "k_scale" in kv
    bs = kv["k"].shape[1]
    S, kq, _ = x.shape
    positions = lengths[:, None] + jnp.arange(kq)[None, :]      # (S,k)
    q, k_new, v_new = attn._project_qkv(p, x, cfg)
    q = attn.apply_rope(q, positions, cfg.rope_theta)
    k_new = attn.apply_rope(k_new, positions, cfg.rope_theta)

    k_pool, v_pool = kv["k"], kv["v"]
    k_sc, v_sc = kv.get("k_scale"), kv.get("v_scale")
    for j in range(kq):
        if quantized:
            k_pool, k_sc = paged_scatter_quant(k_pool, k_sc, k_new[:, j],
                                               write_slots[j], write_offs[j])
            v_pool, v_sc = paged_scatter_quant(v_pool, v_sc, v_new[:, j],
                                               write_slots[j], write_offs[j])
        else:
            k_pool = paged_scatter(k_pool, k_new[:, j],
                                   write_slots[j], write_offs[j])
            v_pool = paged_scatter(v_pool, v_new[:, j],
                                   write_slots[j], write_offs[j])
    kv_out = ({"k": k_pool, "v": v_pool, "k_scale": k_sc, "v_scale": v_sc}
              if quantized else {"k": k_pool, "v": v_pool})

    if fused:
        qf = q.reshape(S * kq, *q.shape[2:])                    # (S*k, H, hd)
        table_x = jnp.repeat(table, kq, axis=0)                 # (S*k, MB)
        len_x = positions.reshape(-1)                           # (S*k,)
        o = paged_attention_decode(qf, k_pool, v_pool, table_x, len_x,
                                   k_scale=k_sc, v_scale=v_sc)
        o = o.reshape(S, kq, *o.shape[1:])                      # (S,k,H,hd)
        return attn._out_proj(p, o.astype(x.dtype)), kv_out

    last = positions[:, -1]                            # deepest draft position
    n_live = jnp.minimum((last + bs) // bs, table.shape[1])
    k = paged_gather(k_pool, table, n_live)            # (S, MB*BS, KVh, hd)
    v = paged_gather(v_pool, table, n_live)
    if quantized:
        ks = paged_gather(k_sc[..., None, None], table, n_live)
        vs = paged_gather(v_sc[..., None, None], table, n_live)
        k = (k.astype(jnp.float32) * ks).astype(x.dtype)
        v = (v.astype(jnp.float32) * vs).astype(x.dtype)

    scores = attn._gqa_scores(q, k)                    # (S, H, k, MB*BS)
    slot_pos = jnp.arange(k.shape[1])
    valid = (slot_pos[None, None, :] <=
             positions[:, :, None])[:, None, :, :]     # (S,1,k,T) causal
    scores = jnp.where(valid, scores, attn.NEG_INF)
    w = attn._softmax(scores).astype(x.dtype)
    out = attn._out_proj(p, attn._gqa_combine(w, v))
    return out, kv_out


def _attn_verify_sublayer(p: Dict, x, kv, table, lengths, write_slots,
                          write_offs, cfg, ffn_kind: str, fused: bool):
    h = apply_norm(p["norm1"], x, cfg.norm_eps)
    h, kv = _paged_attention_verify(p["mix"], h, kv, table, lengths,
                                    write_slots, write_offs, cfg, fused)
    x = x + h
    h2 = apply_norm(p["norm2"], x, cfg.norm_eps)
    if ffn_kind == "moe":
        h2, _ = moe_forward(p["ffn"], h2, cfg, capacity_factor=0.0)
    else:
        h2 = ffn_forward(p["ffn"], h2, cfg)
    return x + h2, kv


def build_verify_step(model, k: int, fused_attention: Optional[bool] = None):
    """Compile-once k-token speculative verify: (params, kv, states, table,
    lengths, write_slots (k,NB), write_offs (k,NB), tokens (S,k)) ->
    (logits (S,k,V), kv, states).

    ``logits[s, j]`` is the target's distribution for position
    ``lengths[s]+j+1`` given the prompt plus draft tokens ``<= j`` — the
    greedy argmax over it is exactly the token plain decode would emit
    there, so the caller can accept the matching draft prefix and resample
    the first divergence bit-identically. Attention-only models only:
    recurrent sublayer state (mamba/rwkv) cannot be rolled back when a
    draft is rejected, so those architectures raise here.
    """
    cfg = model.cfg
    kinds = _sub_kinds(cfg)
    if any(m != "attn" for m, _ in kinds):
        raise ValueError(
            "speculative verify requires attention-only models (recurrent "
            f"sublayer state has no rollback); got kinds={[m for m, _ in kinds]}")
    fused = True if fused_attention is None else bool(fused_attention)

    def step(params, kv, states, table, lengths, write_slots, write_offs,
             tokens):
        dtype = cfg.activation_dtype
        x = embed_tokens(params["embed"], tokens, dtype)   # (S,k,d)
        if "embed_norm" in params:
            x = apply_norm(params["embed_norm"], x, cfg.norm_eps)

        def body(carry, xs):
            h = carry
            lp, kv_l, st_l = xs
            kv_out = {}
            for i, (m, f) in enumerate(kinds):
                name = f"sub{i}"
                h, kv_out[name] = _attn_verify_sublayer(
                    lp[name], h, kv_l[name], table, lengths,
                    write_slots, write_offs, cfg, f, fused)
            return h, (kv_out, st_l)

        x, (kv, states) = jax.lax.scan(body, x, (params["layers"], kv,
                                                 states))
        x = apply_norm(params["final_norm"], x, cfg.norm_eps)
        logits = lm_head(params["embed"], x)               # (S,k,V)
        return logits, kv, states

    _n_scan(cfg)           # called for effect: validates the scan layout early
    # the KV pools (argument 1) are donated: every caller replaces its pools
    # with the returned ones, so the step updates them in place in HBM
    return jax.jit(step, donate_argnums=1)
