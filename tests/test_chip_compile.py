"""Compile the main-path kernels and the training step for a TPU v5e at
qwen1.5-0.5b's published widths, against a described (not attached)
``v5e:2x2`` topology.

Nothing runs: these compiles catch what interpret mode cannot — block
shapes Mosaic refuses, layouts XLA and Mosaic disagree on, and a step that
does not fit the chip's HBM. The topology is described inside a module
fixture (never at import), so only the worker that runs this file loads the
TPU compiler, and a machine that cannot describe it skips here.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import CodistConfig, TrainConfig, get_config

# qwen1.5-0.5b: d_model 1024, 16 heads (MHA), head_dim 64, vocab 151936
# padded to 152064; one peer's batch of 4 x 512 tokens
CFG = get_config("qwen1.5-0.5b")
T, V = 4 * 512, CFG.padded_vocab
H = KVH = CFG.num_heads
HD = CFG.resolved_head_dim
SLOTS, BS, NB, MB = 8, 16, 129, 8
HBM_BYTES = 15.75 * 2 ** 30     # what the compiler reports a v5e can hold


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles here go to no persistent cache: they could not be read back
    # without the chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ----------------------------------------------------------------------------
# fused losses, forward and backward
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("loss", ["ce", "distill_mse", "distill_kl",
                                  "ce_distill_mse", "ce_distill_kl"])
def test_fused_loss_value_and_grad_compiles(one_chip, loss):
    from repro.kernels import ops
    logits = _sds(one_chip, (T, V), jnp.bfloat16)
    labels = _sds(one_chip, (T,), jnp.int32)

    mode = loss.rsplit("_", 1)[-1]
    if loss == "ce":
        def f(a, b, lb):
            return ops.fused_cross_entropy_loss(a, lb, 0.1, interpret=False)
    elif loss.startswith("distill_"):
        def f(a, b, lb):
            return ops.fused_distill_mean(a, b, mode=mode, interpret=False)
    else:
        def f(a, b, lb):
            task, dist = ops.fused_ce_distill(a, b, lb, mode=mode,
                                              label_smoothing=0.1,
                                              interpret=False)
            return task + 0.5 * dist

    compiled = _compile(jax.value_and_grad(f, argnums=(0, 1)), logits,
                        logits, labels)
    assert _has_kernel(compiled)


def test_forward_only_cross_entropy_compiles(one_chip):
    from repro.kernels.fused_ce import fused_cross_entropy
    compiled = _compile(
        lambda a, lb: fused_cross_entropy(a, lb, interpret=False),
        _sds(one_chip, (T, V), jnp.bfloat16), _sds(one_chip, (T,), jnp.int32))
    assert _has_kernel(compiled)


# ----------------------------------------------------------------------------
# the fused causal attention core, forward and backward
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("heads,hd", [(H, HD), (20, 128)],
                         ids=["qwen1.5-0.5b", "qwen1.5-4b"])
def test_fused_causal_attention_value_and_grad_compiles(one_chip, heads, hd):
    """Two peers' 4 x 512 rows under remat, as the scanned layers run the
    core: the forward, its recompute and the backward kernel."""
    from repro.kernels import ops
    qkv = _sds(one_chip, (2, 4, 512, heads, hd), jnp.bfloat16)

    def f(q, k, v):
        core = jax.checkpoint(lambda q, k, v: ops.fused_causal_attention(
            q, k, v, interpret=False))
        return jnp.sum(jax.vmap(core)(q, k, v).astype(jnp.float32))

    text = _compile(jax.value_and_grad(f, argnums=(0, 1, 2)), qkv, qkv,
                    qkv).as_text()
    for kernel in ("fwd", "bwd"):
        assert f"%causal_attention_{kernel}" in text, kernel


# ----------------------------------------------------------------------------
# paged decode: attention over the block pool, and the appending scatters
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_paged_attention_decode_compiles(one_chip, pool_dtype):
    from repro.kernels.paged_attention import paged_attention_decode
    quant = pool_dtype == jnp.int8
    args = [_sds(one_chip, (SLOTS, H, HD), jnp.bfloat16),
            _sds(one_chip, (NB, BS, KVH, HD), pool_dtype),
            _sds(one_chip, (NB, BS, KVH, HD), pool_dtype),
            _sds(one_chip, (SLOTS, MB), jnp.int32),
            _sds(one_chip, (SLOTS,), jnp.int32)]
    if quant:
        args += [_sds(one_chip, (NB, BS), jnp.float32)] * 2

    def f(q, k, v, table, lengths, *scales):
        ks, vs = scales if quant else (None, None)
        return paged_attention_decode(q, k, v, table, lengths, k_scale=ks,
                                      v_scale=vs, interpret=False)

    assert _has_kernel(_compile(f, *args))


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_paged_scatter_compiles(one_chip, pool_dtype):
    from repro.kernels.paged_cache import paged_scatter, paged_scatter_quant
    pool = _sds(one_chip, (NB, BS, KVH, HD), pool_dtype)
    new = _sds(one_chip, (SLOTS, KVH, HD), jnp.bfloat16)
    wmap = _sds(one_chip, (NB,), jnp.int32)
    if pool_dtype == jnp.int8:
        compiled = _compile(
            lambda p, s, n, w, o: paged_scatter_quant(p, s, n, w, o,
                                                      interpret=False),
            pool, _sds(one_chip, (NB, BS), jnp.float32), new, wmap, wmap)
    else:
        compiled = _compile(
            lambda p, n, w, o: paged_scatter(p, n, w, o, interpret=False),
            pool, new, wmap, wmap)
    assert _has_kernel(compiled)


# ----------------------------------------------------------------------------
# the whole training step: 2-peer prediction exchange at full width
# ----------------------------------------------------------------------------

def test_full_width_codist_step_fits_one_chip(one_chip, monkeypatch):
    """24 layers, 464M parameters per peer, 2 peers, SGD with momentum,
    remat, fused losses and attention, state donated: the step must fit a
    v5e's HBM."""
    from repro.kernels import ops
    from repro.models import build_model
    from repro.optim import make_optimizer
    from repro.train.engine import PredictionExchange, build_train_step

    # the step asks the backend (the CPU here) whether to interpret the
    # kernels and whether attention takes the fused core; compile them as
    # the chip would
    monkeypatch.setattr(ops, "auto_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = build_model(CFG)
    tc = TrainConfig(optimizer="sgdm", remat=True, fused_losses=True,
                     total_steps=4, warmup_steps=0)
    codist = CodistConfig(n_models=2)
    strategy = PredictionExchange(codist)
    opt_init, _ = make_optimizer("sgdm")
    state = jax.eval_shape(lambda: strategy.init_state(
        model, tc, jax.random.key(0), opt_init))
    tokens = jax.ShapeDtypeStruct((2, 4, 512), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens,
             "mask": jax.ShapeDtypeStruct((2, 4, 512), jnp.float32)}
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: _sds(one_chip, s.shape, s.dtype), tree)
    bundle = build_train_step(model, tc, codist, strategy)
    compiled = bundle.jitted("on").lower(on_chip(state),
                                         on_chip(batch)).compile()
    assert _has_kernel(compiled)
    # one forward kernel per layer: remat keeps its named residuals and
    # does not rerun it; the backward kernel takes them
    text = compiled.as_text()
    assert len(re.findall(r"%causal_attention_fwd[.\d]* = ", text)) == 1
    assert len(re.findall(r"%causal_attention_bwd[.\d]* = ", text)) == 1
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.alias_size_in_bytes > 0.9 * mem.output_size_in_bytes
    assert used < HBM_BYTES, (used / 2 ** 30, mem)
