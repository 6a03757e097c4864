"""Step-time microbenchmarks (CPU, tiny model): every exchange strategy
through the unified ``build_train_step`` engine, plus the kernels vs their
jnp references. Wall-clock on this container is NOT TPU-predictive —
roofline terms in the dry-run are — but relative step structure (distill
on/off, checkpoint n-forwards, pipelined replay, shard_map exchange) is.
Each strategy row's ``derived`` carries its Section-3 comm accounting:
``strategy.comm_bytes`` per exchange event."""
from __future__ import annotations

from typing import Dict, List

import jax

from repro.configs import CodistConfig, TrainConfig
from repro.data import make_lm_batch
from repro.optim import make_optimizer
from repro.train import (AllReduce, CheckpointExchange, PipelinedPredictions,
                         PredictionExchange, ShardMapCompressed,
                         build_train_step, stack_batches)

from benchmarks.common import lm_setup, timed


def _strategy_rows(model, task, quick: bool) -> List[Dict]:
    """ms/step + comm bytes for every strategy via the unified builder."""
    tc = TrainConfig(lr=1e-3, total_steps=100, optimizer="adamw")
    opt_init, _ = make_optimizer("adamw")
    n, b, s = 2, 8, 64
    batch = stack_batches([make_lm_batch(task, b, s, 0, None, seed=0)
                           for _ in range(n)])
    single = make_lm_batch(task, b, s, 0, None, seed=0)
    pred_cfg = CodistConfig(n_models=n)
    topk_cfg = CodistConfig(n_models=n, compression="topk", topk=16)
    ckpt_cfg = CodistConfig(n_models=n, mode="checkpoints")
    pipe_cfg = CodistConfig(n_models=n, pipelined=True)
    setups = [
        ("allreduce", AllReduce(), single, "on"),
        ("prediction", PredictionExchange(pred_cfg), batch, "on"),
        ("prediction_off", PredictionExchange(pred_cfg), batch, "off"),
        ("prediction_topk", PredictionExchange(topk_cfg), batch, "on"),
        ("checkpoint", CheckpointExchange(ckpt_cfg), batch, "on"),
        ("pipelined", PipelinedPredictions(pipe_cfg), batch, "on"),
    ]
    rows: List[Dict] = []
    if jax.device_count() >= n:
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((n,), ("pod",))
        setups.append(("shardmap", ShardMapCompressed(topk_cfg, mesh), batch,
                       "on"))
    else:
        # no silent skips: the shard_map strategy needs an n-device "pod"
        # axis (jax is already initialized, so host devices can't be forced
        # here); record the row with its comm accounting and zero timing
        st = PredictionExchange(topk_cfg).init_state(
            model, tc, jax.random.key(0), opt_init, batch)
        comm = PredictionExchange(topk_cfg).comm_bytes(model, st, batch)
        rows.append({"name": "throughput/strategy_shardmap",
                     "us_per_call": 0.0,
                     "derived": f"skipped_needs_{n}_devices,"
                                f"comm_bytes={comm:.0f}"})
    for name, strategy, bt, variant in setups:
        # build_train_step falls back to strategy.codist for the schedules
        bundle = build_train_step(model, tc, None, strategy)
        state = strategy.init_state(model, tc, jax.random.key(0), opt_init,
                                    bt)
        comm = strategy.comm_bytes(model, state, bt)
        # undonated: the timing loop calls the step on the same state
        fn = jax.jit(bundle.variants[variant])
        _, us = timed(lambda f=fn, st=state, bb=bt: f(st, bb), warmup=1,
                      iters=2 if quick else 5)
        rows.append({"name": f"throughput/strategy_{name}",
                     "us_per_call": us,
                     "derived": f"comm_bytes={comm:.0f}"})
    return rows


def run(quick: bool = False) -> List[Dict]:
    model, task = lm_setup()
    rows = _strategy_rows(model, task, quick)

    # kernels vs jnp references (interpret mode: correctness-path timing only)
    from repro.core import codistillation as cd
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref
    t, v = (256, 512) if quick else (512, 2048)
    lg = jax.random.normal(jax.random.key(0), (t, v))
    lb = jax.random.randint(jax.random.key(1), (t,), 0, v)
    tgt = jax.random.normal(jax.random.key(2), (t, v))
    _, us_k = timed(lambda: kops.cross_entropy_tokens(lg, lb, interpret=True),
                    iters=2)
    _, us_r = timed(lambda: kref.cross_entropy_ref(lg, lb), iters=2)
    rows.append({"name": "throughput/fused_ce_interp_vs_ref",
                 "us_per_call": us_k, "derived": f"{us_k / us_r:.1f}x_ref"})
    # both paper loss variants: mse (A.3) and kl (Anil-style)
    for mode in ("mse", "kl"):
        _, us_k = timed(lambda m=mode: kops.distill_loss_tokens(
            lg, tgt, mode=m, interpret=True), iters=2)
        ref_fn = kref.distill_mse_ref if mode == "mse" else kref.distill_kl_ref
        _, us_r = timed(lambda f=ref_fn: f(lg, tgt), iters=2)
        rows.append({"name": f"throughput/fused_distill_{mode}_interp_vs_ref",
                     "us_per_call": us_k,
                     "derived": f"{us_k / us_r:.1f}x_ref"})

    # GRADIENT timings: jax.grad through the custom-VJP kernels vs the jnp
    # losses (the training path the fused_losses flag switches)
    grad_pairs = {
        "ce": (
            jax.jit(jax.grad(lambda x: kops.fused_cross_entropy_loss(
                x, lb, 0.1, interpret=True))),
            jax.jit(jax.grad(lambda x: cd.cross_entropy(x, lb, 0.1,
                                                        fused=False))),
        ),
    }
    for mode in ("mse", "kl"):
        ref_loss = cd.distill_mse if mode == "mse" else cd.distill_kl
        grad_pairs[f"distill_{mode}"] = (
            jax.jit(jax.grad(lambda x, m=mode: kops.fused_distill_mean(
                x, tgt, m, interpret=True))),
            jax.jit(jax.grad(lambda x, f=ref_loss: f(x, tgt, fused=False))),
        )
    for name, (fused_g, ref_g) in grad_pairs.items():
        _, us_k = timed(lambda f=fused_g: f(lg), iters=2)
        _, us_r = timed(lambda f=ref_g: f(lg), iters=2)
        rows.append({"name": f"throughput/grad_{name}_fused_vs_jnp",
                     "us_per_call": us_k,
                     "derived": f"{us_k / us_r:.1f}x_ref"})
    return rows
