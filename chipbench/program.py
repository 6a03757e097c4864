"""The system under test, built from a configuration and a traffic file.

This is the only module of the benchmark that imports the program
(``src/repro``). It builds the step the training loop drives,
``build_train_step(model, tc, codist, strategy)``, and the state it starts
from; the weights in that state come from ``weights.make_params``, in the
layout the configuration's plain reference declares, and the layout is
checked against the program's own ``model.init``.

The traffic file's ``strategy`` names what is built, on one chip:
``prediction`` (``PredictionExchange``, Algorithm 1: ``models`` stacked
peers, with the file's ``period``, ``distill``, ``alpha`` and
``compression``) or ``all_reduce`` (``AllReduce``, the paper's baseline:
one model, no exchange and no distill term). Any other strategy is refused.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from chipbench import manifest, weights


def model_config(cfg: Dict):
    """The program's ``ModelConfig`` for a configuration file, mapped by its
    ``model_type`` (``archs/<model_type>.py``)."""
    from repro.configs import get_config
    base = get_config(cfg["run"]["program_arch"])
    return manifest.arch(cfg).program_config(cfg, base)


class _SeededWeights:
    """Stands in for the model at ``init``: the strategy's own state builder
    calls ``init(key)`` once per model with the key it would give the model."""

    def __init__(self, layout, dtype):
        self.layout, self.dtype = layout, dtype

    def init(self, key):
        return weights.nest(weights.make_params(self.layout, key, self.dtype))


def leaf_norms(tree, stacked: bool) -> Dict[str, jax.Array]:
    """Per-leaf L2 norms: one per model (when ``stacked``) and per layer
    (for ``layers/...``), in float32."""
    out = {}
    for path, x in weights.flatten(tree).items():
        lead = int(stacked) + int(path.startswith("layers/"))
        x = x.astype(jnp.float32)
        out[path] = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(lead, x.ndim))))
    return out


@dataclasses.dataclass
class Program:
    bundle: Any                   # the StepBundle the window drives
    stacked: bool
    init_state: Callable          # weights key -> state, one jitted call
    initial_params: Callable      # weights key -> params, as the state holds

    def grad_norms(self, state) -> Dict[str, jax.Array]:
        """The optimizer's momentum after the first step is the first
        gradient as the optimizer got it (zero start, no weight decay)."""
        return self._grad_norms(state.opt.m)

    def change_norms(self, state, wkey) -> Dict[str, jax.Array]:
        return self._change_norms(state.params, wkey)

    def __post_init__(self):
        stacked, init = self.stacked, self.initial_params
        self._grad_norms = jax.jit(lambda m: leaf_norms(m, stacked))
        self._change_norms = jax.jit(lambda p, k: leaf_norms(
            jax.tree.map(lambda a, b: a - b, p, init(k)), stacked))


def build(cfg: Dict, traffic: Dict, layout: Dict) -> Program:
    from repro.configs import CodistConfig, TrainConfig
    from repro.models import build_model
    from repro.optim import make_optimizer
    from repro.train import STRATEGIES, build_train_step

    mcfg = model_config(cfg)
    model = build_model(mcfg)
    n = int(traffic["models"])
    name = traffic["strategy"]
    if name == "prediction":
        codist = CodistConfig(
            n_models=n, mode="predictions", period=int(traffic["period"]),
            distill_loss=traffic["distill"], alpha0=float(traffic["alpha"]),
            compression=traffic["compression"])
    elif name == "all_reduce":
        codist = CodistConfig(n_models=1)
    else:
        raise ValueError(f"strategy {name!r}: the harness builds "
                         "'prediction' and 'all_reduce' only, on one chip")
    if float(traffic["weight_decay"]) != 0.0:
        raise ValueError("the first gradient is read from the momentum, "
                         "which needs weight_decay 0")
    tc = TrainConfig(
        lr=float(traffic["lr"]), lr_schedule="constant", warmup_steps=0,
        total_steps=1 << 30, optimizer=traffic["optimizer"],
        momentum=float(traffic["momentum"]),
        weight_decay=float(traffic["weight_decay"]),
        remat=bool(cfg["run"]["remat"]),
        fused_losses=bool(cfg["run"]["fused_losses"]), seed=0)
    strategy = STRATEGIES[name](codist)
    bundle = build_train_step(model, tc, codist, strategy)
    stacked = bool(strategy.stacked)
    if stacked != (n > 1):
        raise ValueError(f"strategy {name!r} with {n} model(s)")

    shim = _SeededWeights(layout, jnp.dtype(mcfg.param_dtype))
    key0 = jax.random.key(0)
    want = jax.eval_shape(model.init, key0)
    got = jax.eval_shape(shim.init, key0)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"{cfg['name']}: the reference's weight layout is "
                         "not the program's")
    opt_init, _ = make_optimizer(tc.optimizer, momentum=tc.momentum,
                                 dtype=tc.opt_dtype)

    def init_state(wkey):
        return strategy.init_state(shim, tc, wkey, opt_init)

    def initial_params(wkey):
        if stacked:
            return jax.vmap(shim.init)(weights.peer_keys(wkey, n))
        return shim.init(wkey)

    return Program(bundle=bundle, stacked=stacked,
                   init_state=jax.jit(init_state),
                   initial_params=initial_params)
