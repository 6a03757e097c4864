"""The fused causal attention core (``kernels/causal_attention.py`` through
``ops.fused_causal_attention``, interpret mode here) against the dense core,
and the route ``attention_forward`` takes for each kind of call."""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels import causal_attention, ops
from repro.models import attention as A
from repro.models import sharding_hints as hints
from repro.models.common import apply_rope, apply_rope_halves

PEERS, BATCH, D = 2, 1, 64
# both routes round bf16 intermediates, at different places (the dense core
# rounds its scores before the softmax, the kernel keeps them fp32): about
# 0.5% relative apart at these sizes
RTOL = 1e-2


def _cfg(h: int, kv: int, hd: int, **kw) -> ModelConfig:
    return ModelConfig(name="t", family="dense", num_layers=2, d_model=D,
                       num_heads=h, num_kv_heads=kv, d_ff=128, vocab_size=64,
                       head_dim=hd, qkv_bias=True, **kw)


def _rel(a, b, floor: float = 0.0) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), floor))


def _peers_params(cfg: ModelConfig) -> Dict:
    """Two peers' attention weights, biases drawn non-zero."""
    def one(key):
        p = A.init_attention(key, cfg)
        kb = jax.random.split(jax.random.fold_in(key, 1), 3)
        for name, k in zip(("bq", "bk", "bv"), kb):
            p[name] = 0.1 * jax.random.normal(k, p[name].shape)
        return p
    return jax.vmap(one)(jax.random.split(jax.random.key(0), PEERS))


def _fused_route(monkeypatch, on: bool) -> None:
    monkeypatch.setattr(A, "_takes_fused_core", lambda cfg, causal, seq: on)


@pytest.mark.parametrize("seq", [256, 200], ids=["s256", "s200_padded"])
@pytest.mark.parametrize("h,kv", [(2, 2), (4, 2)], ids=["mha", "gqa2"])
@pytest.mark.parametrize("hd", [64, 128])
def test_kernel_route_matches_dense_route(monkeypatch, hd, h, kv, seq):
    """Forward output and the q/k/v and weight gradients, through a peer
    ``vmap`` and ``jax.checkpoint`` as the scanned layers run them."""
    _compare_routes(monkeypatch, hd, h, kv, seq)


def test_kernel_route_matches_dense_route_over_many_blocks(monkeypatch):
    """640 rows: five query and five key tiles, so blocks below, on and
    above the diagonal all occur."""
    assert causal_attention.block_size(640) == 128
    _compare_routes(monkeypatch, 64, 4, 2, 640)


def _compare_routes(monkeypatch, hd, h, kv, seq):
    cfg = _cfg(h, kv, hd)
    params = _peers_params(cfg)
    kx, kc = jax.random.split(jax.random.key(1))
    x = jax.random.normal(kx, (PEERS, BATCH, seq, D)).astype(jnp.bfloat16)
    ct = jax.random.normal(kc, (PEERS, BATCH, seq, D))

    def layer_loss(p, x):
        out = jax.vmap(jax.checkpoint(
            lambda p, x: A.attention_forward(p, x, cfg)[0]))(p, x)
        return jnp.sum(out.astype(jnp.float32) * ct), out

    def run(on):
        _fused_route(monkeypatch, on)
        (_, out), grads = jax.value_and_grad(layer_loss, argnums=(0, 1),
                                             has_aux=True)(params, x)
        return out, grads

    out_k, (gp_k, gx_k) = run(True)
    out_d, (gp_d, gx_d) = run(False)
    assert _rel(out_k, out_d) < RTOL
    assert _rel(gx_k, gx_d) < RTOL
    # a leaf whose gradient is ~0 in exact arithmetic (bk: a shift shared by
    # every key leaves the softmax unchanged) is held to the others' scale
    floor = float(np.median([np.linalg.norm(np.asarray(g))
                             for g in jax.tree.leaves(gp_d)]))
    for name in gp_d:
        assert _rel(gp_k[name], gp_d[name], floor) < RTOL, name

    # the core alone: q, k, v gradients
    q, k, v = (jax.random.normal(kk, (PEERS, BATCH, seq, n, hd))
               .astype(jnp.bfloat16)
               for kk, n in zip(jax.random.split(jax.random.key(2), 3),
                                (h, kv, kv)))
    cq = jax.random.normal(jax.random.key(3), q.shape)

    def core_loss(core):
        def f(q, k, v):
            o = jax.vmap(jax.checkpoint(core))(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * cq)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    got = core_loss(ops.fused_causal_attention)
    want = core_loss(lambda q, k, v: A._dense_core(q, k, v, cfg, True))
    for name, a, b in zip("qkv", got, want):
        assert a.dtype == jnp.bfloat16 and a.shape == b.shape
        assert _rel(a, b) < RTOL, name


def test_rope_halves_is_rope_with_each_heads_dims_reordered():
    x = jax.random.normal(jax.random.key(5), (2, 200, 4, 64)).astype(
        jnp.bfloat16)
    pos = jnp.arange(200, dtype=jnp.int32)[None]
    order = np.concatenate([np.arange(0, 64, 2), np.arange(1, 64, 2)])
    np.testing.assert_array_equal(
        np.asarray(apply_rope_halves(x, pos, 1e6), np.float32),
        np.asarray(apply_rope(x, pos, 1e6), np.float32)[..., order])


def test_kernel_route_prefill_cache_is_the_dense_routes(monkeypatch):
    """Prefill hands the decode cache keys in RoPE's own order on either
    route, bit for bit."""
    cfg = _cfg(4, 2, 64)
    p = jax.tree.map(lambda a: a[0], _peers_params(cfg))
    x = jax.random.normal(jax.random.key(6), (1, 200, D)).astype(jnp.bfloat16)
    got = {}
    for on in (True, False):
        _fused_route(monkeypatch, on)
        got[on] = A.attention_forward(p, x, cfg, return_cache=True)
    (out_k, cache_k), (out_d, cache_d) = got[True], got[False]
    assert _rel(out_k, out_d) < RTOL
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cache_k[name], np.float32),
                                      np.asarray(cache_d[name], np.float32))


@pytest.mark.parametrize("seq,block", [(128, 128), (256, 256), (384, 128),
                                       (512, 512), (768, 256), (4096, 512)])
def test_block_is_the_largest_candidate_dividing_the_padded_length(seq,
                                                                   block):
    assert causal_attention.block_size(seq) == block


def test_block_rule_refuses_an_unpadded_length():
    with pytest.raises(ValueError):
        causal_attention.block_size(200)


# ----------------------------------------------------------------------------
# routing
# ----------------------------------------------------------------------------

def _takes_kernel(cfg: ModelConfig, causal: bool = True,
                  wrap=lambda f: f, seq: int = 256) -> bool:
    """Whether tracing ``attention_forward`` places the fused kernel."""
    p = A.init_attention(jax.random.key(0), cfg)
    x = jnp.zeros((1, seq, D), jnp.bfloat16)
    f = wrap(lambda p, x: A.attention_forward(p, x, cfg, causal=causal)[0])
    text = str(jax.make_jaxpr(f)(p, x))
    return "causal_attention_fwd" in text


def _one_device_mesh(axes):
    return Mesh(np.asarray(jax.devices()[:1]).reshape((1,) * len(axes)),
                axes)


def _in_shard_map(axes, manual):
    def wrap(f):
        return jax.shard_map(f, mesh=_one_device_mesh(axes),
                             in_specs=(P(), P()), out_specs=P(),
                             axis_names=set(manual), check_vma=False)
    return wrap


@pytest.mark.parametrize("case,want", [
    ("tpu", True),
    ("cpu", False),
    ("sliding_window", False),
    ("non_causal", False),
    ("activation_sharding", False),
    ("shard_map_all_manual", True),
    ("shard_map_partly_manual", False),
    ("longer_than_the_kernels_hold", False),
    ("head_dim_off_the_sublane_tile", False),
])
def test_route(monkeypatch, case, want):
    if case != "cpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _cfg(2, 2, 40 if case == "head_dim_off_the_sublane_tile" else 64,
               sliding_window=64 if case == "sliding_window" else 0)
    if case == "activation_sharding":
        with hints.activation_sharding(("data",), None):
            got = _takes_kernel(cfg)
    elif case == "shard_map_all_manual":
        got = _takes_kernel(cfg, wrap=_in_shard_map(("pod",), ("pod",)))
    elif case == "shard_map_partly_manual":
        got = _takes_kernel(cfg, wrap=_in_shard_map(("pod", "data"),
                                                    ("pod",)))
    elif case == "longer_than_the_kernels_hold":
        got = _takes_kernel(cfg, seq=causal_attention.MAX_SEQ + 1)
    else:
        got = _takes_kernel(cfg, causal=case != "non_causal")
    assert got is want


def _parent_attention_forward(p: Dict[str, jax.Array], x: jax.Array,
                              cfg: ModelConfig,
                              positions: Optional[jax.Array] = None,
                              causal: bool = True,
                              return_cache: bool = False):
    """``attention_forward`` as it was before the fused route, verbatim."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)[None, :]
    q, k, v = A._project_qkv(p, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    from repro.models.sharding_hints import hint
    scores = hint(A._gqa_scores(q, k), "scores")  # (B,H,S,S)
    if causal:
        i = jnp.arange(s)[:, None]
        j = jnp.arange(s)[None, :]
        mask = j <= i
        if cfg.sliding_window > 0:
            mask = mask & (i - j < cfg.sliding_window)
        scores = jnp.where(mask[None, None], scores, A.NEG_INF)
    w = A._softmax(scores).astype(x.dtype)
    out = A._out_proj(p, A._gqa_combine(w, v))
    cache = {"k": k, "v": v} if return_cache else None
    return out, cache


@pytest.mark.parametrize("kind", ["causal", "gqa", "window", "non_causal"])
def test_dense_route_on_cpu_is_bit_identical_to_the_parent(kind):
    assert jax.default_backend() == "cpu"
    cfg = _cfg(4, 2 if kind == "gqa" else 4, 64,
               sliding_window=48 if kind == "window" else 0)
    p = _peers_params(cfg)
    p = jax.tree.map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.key(4), (2, 200, D)).astype(jnp.bfloat16)
    causal = kind != "non_causal"
    assert not A._takes_fused_core(cfg, causal, 200)
    def run(f):
        return jax.jit(lambda p, x: f(p, x, cfg, causal=causal,
                                      return_cache=True))(p, x)

    out, cache = run(A.attention_forward)
    want_out, want_cache = run(_parent_attention_forward)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want_out, np.float32))
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cache[name], np.float32),
                                      np.asarray(want_cache[name],
                                                 np.float32))
