"""The training step's named scopes survive autodiff, remat and XLA's
optimizations: the compiled HLO's ``op_name`` metadata names every layer,
and every matrix product and kernel call lies under a top-level scope. The
profiler reads the same metadata, so a device trace can be cut by layer
(``attn``, ``mlp``, ...) and by pass (``transpose(`` marks the backward,
``rematted_computation`` the recompute)."""
from __future__ import annotations

import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

from repro.configs import CodistConfig, TrainConfig, get_reduced
from repro.models import build_model
from repro.optim import make_optimizer
from repro.train.engine import PredictionExchange, build_train_step

SCOPES = ("embed", "layers", "attn", "mlp", "head", "loss", "exchange",
          "optimizer", "metrics")
TOP = ("embed", "layers", "head", "loss", "exchange", "optimizer", "metrics")


def _elements(op_name: str):
    """Path elements with the transform wrappers (``jvp(..)``,
    ``transpose(..)``, ``vmap(..)``) stripped."""
    out = []
    for el in op_name.split("/"):
        while (m := re.fullmatch(r"[\w.\-]+\((.*)\)", el)):
            el = m.group(1)
        out.append(el)
    return out


@pytest.fixture(scope="module")
def hlo():
    cfg = replace(get_reduced("qwen1.5-0.5b"), num_layers=2, d_model=32,
                  d_ff=64, vocab_size=64, num_heads=2, num_kv_heads=2,
                  head_dim=16)
    model = build_model(cfg)
    tc = TrainConfig(optimizer="sgdm", remat=True, total_steps=4,
                     warmup_steps=0)
    codist = CodistConfig(n_models=2)
    strategy = PredictionExchange(codist)
    opt_init, _ = make_optimizer("sgdm")
    state = jax.eval_shape(lambda: strategy.init_state(
        model, tc, jax.random.key(0), opt_init))
    tokens = jax.ShapeDtypeStruct((2, 2, 16), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens,
             "mask": jax.ShapeDtypeStruct((2, 2, 16), jnp.float32)}
    bundle = build_train_step(model, tc, codist, strategy)
    return bundle.jitted("on").lower(state, batch).compile().as_text()


def test_op_names_hold_every_scope_and_pass(hlo):
    names = re.findall(r'op_name="([^"]*)"', hlo)
    elements = {el for n in names for el in _elements(n)}
    for scope in SCOPES:
        assert scope in elements, scope
    assert any("transpose(" in n for n in names)
    assert any("rematted_computation" in _elements(n) for n in names)
    # the backward of attention is under attn too, not only its forward
    assert any("transpose(" in n and "attn" in _elements(n) for n in names)


def test_every_product_and_kernel_call_is_under_a_top_level_scope(hlo):
    ops = re.findall(r"= \S+ (dot|convolution|custom-call)\(.*?"
                     r'op_name="([^"]*)"', hlo)
    assert any(kind == "dot" for kind, _ in ops)
    for kind, name in ops:
        assert set(_elements(name)) & set(TOP), (kind, name)
