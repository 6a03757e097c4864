"""Decoder-only LM covering the dense / moe / hybrid / ssm / vlm families.

Layers are *scanned*: per-layer params are stacked along a leading axis and the
forward runs ``jax.lax.scan`` over them, keeping HLO size O(1) in depth (a
95-layer model lowers as fast as a 2-layer one — essential for the 512-device
dry-runs). Hybrid (Jamba) models scan over *blocks* of ``attn_layer_period``
sub-layers so the scanned pytree stays homogeneous.

API (pure functions bundled by ``LM``):
    init(key) -> params
    forward(params, batch, remat=False) -> (logits, aux)        # train
    prefill(params, batch) -> (logits, cache)                   # emit cache
    init_cache(batch, cap, dtype) -> cache
    decode(params, cache, tokens, pos) -> (logits, cache)       # one token
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import rwkv as rk
from repro.models.common import (KeyGen, apply_norm, embed_tokens,
                                 init_embedding, init_rms_norm, lm_head)
from repro.models.ffn import ffn_forward, init_ffn
from repro.models.moe import init_moe, moe_forward

PyTree = Any


# ----------------------------------------------------------------------------
# sub-layer templates
# ----------------------------------------------------------------------------

def _sub_kinds(cfg: ModelConfig) -> list[Tuple[str, str]]:
    """(mixer, ffn) kind per scanned sub-layer within one scan step."""
    if cfg.family == "ssm":
        return [("rwkv", "rwkv")]
    period = cfg.attn_layer_period or 1
    kinds = []
    for i in range(period):
        mixer = cfg.layer_kind(i)
        ffn = "moe" if cfg.is_moe_layer(i) else "dense"
        kinds.append((mixer, ffn))
    return kinds


def _n_scan(cfg: ModelConfig) -> int:
    period = len(_sub_kinds(cfg))
    assert cfg.num_layers % period == 0, (cfg.num_layers, period)
    return cfg.num_layers // period


def _init_sublayer(key: jax.Array, cfg: ModelConfig, mixer: str,
                   ffn: str, dtype) -> Dict:
    kg = KeyGen(key)
    d = cfg.d_model
    p: Dict = {"norm1": init_rms_norm(d, dtype)}
    if mixer == "attn":
        p["mix"] = attn.init_attention(kg(), cfg, dtype)
    elif mixer == "ssm":
        p["mix"] = mb.init_mamba(kg(), cfg, dtype)
    elif mixer == "rwkv":
        p["mix"] = rk.init_time_mix(kg(), cfg, dtype)
    if ffn == "rwkv":
        p["norm2"] = init_rms_norm(d, dtype)
        p["ffn"] = rk.init_channel_mix(kg(), cfg, dtype)
    elif ffn == "moe":
        p["norm2"] = init_rms_norm(d, dtype)
        p["ffn"] = init_moe(kg(), cfg, dtype)
    else:
        p["norm2"] = init_rms_norm(d, dtype)
        p["ffn"] = init_ffn(kg(), cfg, dtype=dtype)
    return p


def _init_scan_step(key: jax.Array, cfg: ModelConfig, dtype) -> Dict:
    kinds = _sub_kinds(cfg)
    kg = KeyGen(key)
    return {f"sub{i}": _init_sublayer(kg(), cfg, m, f, dtype)
            for i, (m, f) in enumerate(kinds)}


# ----------------------------------------------------------------------------
# forward bodies (one scan step = one block of sub-layers)
# ----------------------------------------------------------------------------

def _sublayer_fwd(p: Dict, x: jax.Array, cfg: ModelConfig, mixer: str,
                  ffn: str, positions: Optional[jax.Array]):
    # the named scopes (the mixer's kind, "mlp") tag the device ops of each
    # half of the layer, forward, recompute and backward, for the profiler
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope(mixer):
        h = apply_norm(p["norm1"], x, cfg.norm_eps)
        if mixer == "attn":
            h, _ = attn.attention_forward(p["mix"], h, cfg, positions)
        elif mixer == "ssm":
            h = mb.mamba_forward(p["mix"], h, cfg)
        else:  # rwkv time mix
            h, _ = rk.time_mix_forward(p["mix"], h, cfg)
    x = x + h
    with jax.named_scope("mlp"):
        h = apply_norm(p["norm2"], x, cfg.norm_eps)
        if ffn == "moe":
            h, aux = moe_forward(p["ffn"], h, cfg)
        elif ffn == "rwkv":
            h, _ = rk.channel_mix_forward(p["ffn"], h, cfg)
        else:
            h = ffn_forward(p["ffn"], h, cfg)
    return x + h, aux


def _scan_forward(layers: PyTree, x: jax.Array, cfg: ModelConfig,
                  positions: Optional[jax.Array], remat: bool):
    kinds = _sub_kinds(cfg)

    from repro.models.sharding_hints import hint

    def body(carry, lp):
        h = carry
        aux = jnp.zeros((), jnp.float32)
        for i, (m, f) in enumerate(kinds):
            h, a = _sublayer_fwd(lp[f"sub{i}"], h, cfg, m, f, positions)
            aux = aux + a
        return hint(h, "btd"), aux

    from repro.models.runtime_flags import scan_unroll
    if remat:
        body = jax.checkpoint(body)
    with jax.named_scope("layers"):
        x, auxs = jax.lax.scan(body, x, layers, unroll=scan_unroll())
        return x, jnp.sum(auxs)


# ----------------------------------------------------------------------------
# caches (decode state) per sub-layer kind
# ----------------------------------------------------------------------------

def _init_sub_cache(cfg: ModelConfig, mixer: str, batch: int, cap: int, dtype):
    if mixer == "attn":
        return attn.init_kv_cache(cfg, batch, cap, dtype)
    if mixer == "ssm":
        return mb.init_mamba_state(cfg, batch, dtype)
    # rwkv: wkv state + token-shift carries for both mixes
    h, hd, _ = rk._dims(cfg)
    d = cfg.d_model
    return {
        "s": jnp.zeros((batch, h, hd, hd), jnp.float32),
        "shift_tm": jnp.zeros((batch, 1, d), dtype),
        "shift_cm": jnp.zeros((batch, 1, d), dtype),
    }


def _sublayer_prefill(p: Dict, x: jax.Array, cfg: ModelConfig, mixer: str,
                      ffn: str, positions, cap: int, dtype):
    """Forward + emit decode cache for this sub-layer."""
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(p["norm1"], x, cfg.norm_eps)
    if mixer == "attn":
        h, kv = attn.attention_forward(p["mix"], h, cfg, positions,
                                       return_cache=True)
        cache = attn.prefill_into_cache(
            attn.init_kv_cache(cfg, x.shape[0], cap, dtype),
            {"k": kv["k"].astype(dtype), "v": kv["v"].astype(dtype)}, cfg)
    elif mixer == "ssm":
        h, cache = mb.mamba_prefill(p["mix"], h, cfg)
    else:
        h, (shift_tm, s_fin) = rk.time_mix_forward(p["mix"], h, cfg)
        cache = {"s": s_fin, "shift_tm": shift_tm.astype(dtype)}
    x = x + h
    h2 = apply_norm(p["norm2"], x, cfg.norm_eps)
    if ffn == "moe":
        h2, aux = moe_forward(p["ffn"], h2, cfg, capacity_factor=0.0)
    elif ffn == "rwkv":
        h2, shift_cm = rk.channel_mix_forward(p["ffn"], h2, cfg)
        cache["shift_cm"] = shift_cm.astype(dtype)
    else:
        h2 = ffn_forward(p["ffn"], h2, cfg)
    return x + h2, cache, aux


def _sublayer_decode(p: Dict, x: jax.Array, cache, cfg: ModelConfig,
                     mixer: str, ffn: str, pos):
    h = apply_norm(p["norm1"], x, cfg.norm_eps)
    if mixer == "attn":
        h, cache = attn.attention_decode(p["mix"], h, cache, pos, cfg)
    elif mixer == "ssm":
        h, cache = mb.mamba_decode(p["mix"], h, cache, cfg)
    else:
        h, (shift_tm, s_fin) = rk.time_mix_forward(
            p["mix"], h, cfg, shift_prev=cache["shift_tm"].astype(h.dtype),
            s0=cache["s"])
        cache = dict(cache, s=s_fin, shift_tm=shift_tm.astype(cache["shift_tm"].dtype))
    x = x + h
    h2 = apply_norm(p["norm2"], x, cfg.norm_eps)
    if ffn == "moe":
        h2, _ = moe_forward(p["ffn"], h2, cfg, capacity_factor=0.0)
    elif ffn == "rwkv":
        h2, shift_cm = rk.channel_mix_forward(
            p["ffn"], h2, cfg, shift_prev=cache["shift_cm"].astype(h2.dtype))
        cache = dict(cache, shift_cm=shift_cm.astype(cache["shift_cm"].dtype))
    else:
        h2 = ffn_forward(p["ffn"], h2, cfg)
    return x + h2, cache


# ----------------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------------

@dataclass(frozen=True)
class LM:
    cfg: ModelConfig

    # -- init ---------------------------------------------------------------
    def init(self, key: jax.Array) -> PyTree:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.param_dtype)
        kg = KeyGen(key)
        n_scan = _n_scan(cfg)
        layer_keys = jax.random.split(kg(), n_scan)
        layers = jax.vmap(lambda k: _init_scan_step(k, cfg, dtype))(layer_keys)
        params: Dict = {
            "embed": init_embedding(kg(), cfg, dtype),
            "final_norm": init_rms_norm(cfg.d_model, dtype),
            "layers": layers,
        }
        if cfg.family == "ssm":
            params["embed_norm"] = init_rms_norm(cfg.d_model, dtype)
        return params

    # -- shared embedding path ----------------------------------------------
    def _embed(self, params: PyTree, batch: Dict) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        dtype = cfg.activation_dtype
        with jax.named_scope("embed"):
            x = embed_tokens(params["embed"], batch["tokens"], dtype)
            if cfg.num_patches and "patches" in batch:
                # VLM: precomputed patch embeddings prefix (stub frontend)
                x = jnp.concatenate([batch["patches"].astype(dtype), x],
                                    axis=1)
            if "embed_norm" in params:
                x = apply_norm(params["embed_norm"], x, cfg.norm_eps)
            from repro.models.sharding_hints import hint
            x = hint(x, "btd")
            positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
        return x, positions

    # -- train forward --------------------------------------------------------
    def forward(self, params: PyTree, batch: Dict,
                remat: bool = False) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        x, positions = self._embed(params, batch)
        x, aux = _scan_forward(params["layers"], x, cfg, positions, remat)
        with jax.named_scope("head"):
            x = apply_norm(params["final_norm"], x, cfg.norm_eps)
            if cfg.num_patches and "patches" in batch:
                x = x[:, batch["patches"].shape[1]:]  # logits only over text
            return lm_head(params["embed"], x), aux

    # -- serving --------------------------------------------------------------
    def init_cache(self, batch: int, cap: int, dtype=jnp.bfloat16) -> PyTree:
        cfg = self.cfg
        kinds = _sub_kinds(cfg)
        n_scan = _n_scan(cfg)

        def one(_):
            return {f"sub{i}": _init_sub_cache(cfg, m, batch, cap, dtype)
                    for i, (m, _f) in enumerate(kinds)}

        return jax.vmap(one)(jnp.arange(n_scan))

    def prefill(self, params: PyTree, batch: Dict, cap: int,
                cache_dtype=jnp.bfloat16) -> Tuple[jax.Array, PyTree]:
        cfg = self.cfg
        kinds = _sub_kinds(cfg)
        x, positions = self._embed(params, batch)
        # cap must cover the full prefix (VLM patches included) unless the
        # model uses windowed attention — otherwise the ring-buffer path
        # would silently evict live context.
        assert cfg.sliding_window > 0 or cap >= x.shape[1], \
            (cap, x.shape[1], "cache capacity smaller than prefill length")

        def body(carry, lp):
            h = carry
            caches = {}
            for i, (m, f) in enumerate(kinds):
                h, c, _ = _sublayer_prefill(lp[f"sub{i}"], h, cfg, m, f,
                                            positions, cap, cache_dtype)
                caches[f"sub{i}"] = c
            return h, caches

        from repro.models.runtime_flags import scan_unroll
        x, cache = jax.lax.scan(body, x, params["layers"],
                                unroll=scan_unroll())
        x = apply_norm(params["final_norm"], x, cfg.norm_eps)
        if cfg.num_patches and "patches" in batch:
            x = x[:, batch["patches"].shape[1]:]
        # only the last position's logits are needed to continue decoding
        return lm_head(params["embed"], x[:, -1:]), cache

    def decode(self, params: PyTree, cache: PyTree, tokens: jax.Array,
               pos: jax.Array) -> Tuple[jax.Array, PyTree]:
        """tokens: (B,1) int32; pos: () int32 absolute position, or (B,)
        int32 per-row positions (ragged decode — state-based mixers ignore
        it, attention scatters per row; see ``attention_decode``)."""
        cfg = self.cfg
        kinds = _sub_kinds(cfg)
        dtype = cfg.activation_dtype
        x = embed_tokens(params["embed"], tokens, dtype)
        if "embed_norm" in params:
            x = apply_norm(params["embed_norm"], x, cfg.norm_eps)

        def body(carry, xs):
            h = carry
            lp, c_in = xs
            c_out = {}
            for i, (m, f) in enumerate(kinds):
                h, c = _sublayer_decode(lp[f"sub{i}"], h, c_in[f"sub{i}"],
                                        cfg, m, f, pos)
                c_out[f"sub{i}"] = c
            return h, c_out

        from repro.models.runtime_flags import scan_unroll
        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache),
                                    unroll=scan_unroll())
        x = apply_norm(params["final_norm"], x, cfg.norm_eps)
        return lm_head(params["embed"], x), new_cache
