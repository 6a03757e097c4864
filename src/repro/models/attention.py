"""GQA attention: training forward, prefill (cache emit) and decode (cache read).

Supports: grouped-query heads (num_kv_heads <= num_heads), optional QKV bias
(Qwen), RoPE, causal and sliding-window masks, cross-attention (enc-dec), and
ring-buffer windowed KV caches for long-context decode (the sub-quadratic dense
variant used by ``long_500k``).

Keys are stored in the cache ALREADY rotated (standard practice) so ring-buffer
eviction never needs absolute positions at read time.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import causal_attention, ops
from repro.models import sharding_hints as hints
from repro.models.common import (KeyGen, apply_rope, apply_rope_halves,
                                  dense_init, zeros)

NEG_INF = -1e30


# ----------------------------------------------------------------------------
# params
# ----------------------------------------------------------------------------

def init_attention(key: jax.Array, cfg: ModelConfig,
                   dtype=jnp.float32) -> Dict[str, jax.Array]:
    kg = KeyGen(key)
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    p = {
        "wq": dense_init(kg(), d, (h, hd), dtype),
        "wk": dense_init(kg(), d, (kv, hd), dtype),
        "wv": dense_init(kg(), d, (kv, hd), dtype),
        "wo": dense_init(kg(), h * hd, (d,), dtype, scale=1.0 / max(1, cfg.num_layers) ** 0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros((h, hd), dtype)
        p["bk"] = zeros((kv, hd), dtype)
        p["bv"] = zeros((kv, hd), dtype)
    return p


def _project_qkv(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    return q, k, v


def _out_proj(p: Dict[str, jax.Array], o: jax.Array) -> jax.Array:
    b, s, h, hd = o.shape
    return jnp.einsum("bse,ed->bsd", o.reshape(b, s, h * hd),
                      p["wo"].astype(o.dtype))


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B,S,H,hd), k: (B,T,KV,hd) -> scores (B,H,S,T) with head grouping."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k)
    return scores.reshape(b, kvh * g, s, k.shape[1]) * (hd ** -0.5)


def _gqa_combine(w: jax.Array, v: jax.Array) -> jax.Array:
    """w: (B,H,S,T), v: (B,T,KV,hd) -> (B,S,H,hd)."""
    b, h, s, t = w.shape
    kvh = v.shape[2]
    g = h // kvh
    wg = w.reshape(b, kvh, g, s, t)
    o = jnp.einsum("bkgst,btkd->bskgd", wg, v)
    return o.reshape(b, s, h, v.shape[-1])


def _softmax(scores: jax.Array) -> jax.Array:
    return jax.nn.softmax(scores.astype(jnp.float32), axis=-1)


# ----------------------------------------------------------------------------
# training / prefill forward
# ----------------------------------------------------------------------------

def _takes_fused_core(cfg: ModelConfig, causal: bool, seq: int) -> bool:
    """Whether the S x S core (scores, mask, softmax, PV) runs as the fused
    causal kernels (``ops.fused_causal_attention``): on the TPU, for a
    causal unwindowed mask over whole query groups at head widths and
    lengths the kernels take, in a program that GSPMD does not partition.
    Everything else keeps the dense core below."""
    return (jax.default_backend() == "tpu" and causal
            and cfg.sliding_window == 0
            and cfg.num_heads % cfg.num_kv_heads == 0
            and causal_attention.supports(seq, cfg.resolved_head_dim)
            and hints.unpartitioned())


def _dense_core(q: jax.Array, k: jax.Array, v: jax.Array, cfg: ModelConfig,
                causal: bool) -> jax.Array:
    """The S x S core in plain XLA: (B,H,S,S) bf16 scores, masked, softmaxed
    in fp32 and cast back for the PV product. -> (B,S,H,hd)."""
    s = q.shape[1]
    scores = hints.hint(_gqa_scores(q, k), "scores")  # (B,H,S,S)
    if causal:
        i = jnp.arange(s)[:, None]
        j = jnp.arange(s)[None, :]
        mask = j <= i
        if cfg.sliding_window > 0:
            mask = mask & (i - j < cfg.sliding_window)
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    w = _softmax(scores).astype(q.dtype)
    return _gqa_combine(w, v)


def attention_forward(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig,
                      positions: Optional[jax.Array] = None,
                      causal: bool = True,
                      return_cache: bool = False):
    """Full-sequence attention. x: (B,S,d). Returns (out, cache|None).

    cache = {"k": roped keys (B,S,KV,hd), "v": values} for prefill handoff.
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)[None, :]
    q, k, v = _project_qkv(p, x, cfg)
    if _takes_fused_core(cfg, causal, s):
        # q and k in RoPE's halves order: the scores are the same sums
        o = ops.fused_causal_attention(
            apply_rope_halves(q, positions, cfg.rope_theta),
            apply_rope_halves(k, positions, cfg.rope_theta), v)
        if return_cache:
            k = apply_rope(k, positions, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        o = _dense_core(q, k, v, cfg, causal)
    cache = {"k": k, "v": v} if return_cache else None
    return _out_proj(p, o), cache


def cross_attention_forward(p: Dict[str, jax.Array], x: jax.Array,
                            memory: jax.Array, cfg: ModelConfig):
    """Decoder-to-encoder attention (no RoPE on memory, no mask)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", memory, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", memory, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    w = _softmax(_gqa_scores(q, k)).astype(x.dtype)
    return _out_proj(p, _gqa_combine(w, v))


# ----------------------------------------------------------------------------
# KV cache (decode)
# ----------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype) -> Dict[str, jax.Array]:
    """Windowed ring buffer when sliding_window>0, else a full-length buffer."""
    length = min(max_len, cfg.sliding_window) if cfg.sliding_window > 0 else max_len
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, length, kv, hd), dtype),
        "v": jnp.zeros((batch, length, kv, hd), dtype),
    }


def prefill_into_cache(cache: Dict[str, jax.Array],
                       new: Dict[str, jax.Array], cfg: ModelConfig):
    """Copy prefill keys/values into the (possibly windowed) cache buffer."""
    s = new["k"].shape[1]
    cap = cache["k"].shape[1]
    if s >= cap:
        # keep the trailing window, rolled so position p lands at slot p % cap —
        # decode writes use (pos % cap) and must overwrite the oldest slot.
        shift = s % cap
        return {"k": jnp.roll(new["k"][:, s - cap:], shift, axis=1),
                "v": jnp.roll(new["v"][:, s - cap:], shift, axis=1)}
    k = jax.lax.dynamic_update_slice(cache["k"], new["k"], (0, 0, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], new["v"], (0, 0, 0, 0))
    return {"k": k, "v": v}


def attention_decode(p: Dict[str, jax.Array], x: jax.Array,
                     cache: Dict[str, jax.Array], pos: jax.Array,
                     cfg: ModelConfig):
    """One-token decode. x: (B,1,d); pos: () int32 absolute position, or a
    (B,) int32 vector of PER-ROW positions (ragged continuous batching: each
    cache row advances on its own clock; full-length caches only).

    Returns (out (B,1,d), new_cache). With a windowed cache the write index is
    pos % window (ring buffer) and reads mask out unwritten / evicted slots.
    """
    b = x.shape[0]
    cap = cache["k"].shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    vector_pos = pos.ndim == 1
    positions = pos[:, None] if vector_pos else jnp.full((b, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(p, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k_new = apply_rope(k_new, positions, cfg.rope_theta)

    slot = jnp.arange(cap)
    if vector_pos:
        assert cfg.sliding_window <= 0, \
            "per-row positions require a full-length (non-ring) cache"
        rows = jnp.arange(b)
        k = cache["k"].at[rows, pos].set(k_new[:, 0].astype(cache["k"].dtype))
        v = cache["v"].at[rows, pos].set(v_new[:, 0].astype(cache["v"].dtype))
        valid = (slot[None, :] <= pos[:, None])[:, None, None, :]  # (B,1,1,cap)
    else:
        write_idx = (pos % cap) if cfg.sliding_window > 0 else pos
        k = jax.lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype), (0, write_idx, 0, 0))
        v = jax.lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype), (0, write_idx, 0, 0))
        if cfg.sliding_window > 0:
            # slot holds absolute position: the largest written pos congruent mod cap
            age = (write_idx - slot) % cap           # 0 == just written
            abs_pos = pos - age
            valid = (abs_pos >= 0) & (age < jnp.minimum(cap, pos + 1))
        else:
            valid = slot <= pos
        valid = valid[None, None, None, :]

    scores = _gqa_scores(q, k)  # (B,H,1,cap)
    scores = jnp.where(valid, scores, NEG_INF)
    w = _softmax(scores).astype(x.dtype)
    out = _out_proj(p, _gqa_combine(w, v))
    return out, {"k": k, "v": v}


def cross_attention_decode(p: Dict[str, jax.Array], x: jax.Array,
                           mem_cache: Dict[str, jax.Array], cfg: ModelConfig):
    """Decode-time cross attention against precomputed encoder K/V."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    w = _softmax(_gqa_scores(q, mem_cache["k"].astype(x.dtype)))
    return _out_proj(p, _gqa_combine(w.astype(x.dtype),
                                     mem_cache["v"].astype(x.dtype)))


def encoder_kv(p: Dict[str, jax.Array], memory: jax.Array, cfg: ModelConfig):
    k = jnp.einsum("bsd,dhk->bshk", memory, p["wk"].astype(memory.dtype))
    v = jnp.einsum("bsd,dhk->bshk", memory, p["wv"].astype(memory.dtype))
    if "bk" in p:
        k = k + p["bk"].astype(memory.dtype)
        v = v + p["bv"].astype(memory.dtype)
    return {"k": k, "v": v}
