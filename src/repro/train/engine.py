"""Unified step engine: one `build_train_step` for every exchange mechanism.

The paper's contribution is a comparison of *synchronization mechanisms*
(Section 3): fully-synchronous all-reduce, prediction exchange, and Anil et
al.'s checkpoint exchange. Each mechanism used to live in its own step
factory, duplicating the schedule/optimizer/microbatch plumbing and drifting
apart (microbatching and the ``trainable`` mask only worked for some of
them). This module makes the mechanism a first-class pluggable object:

    strategy = resolve_strategy(codist)          # or an explicit instance
    bundle   = build_train_step(model, tc, codist, strategy, trainable)
    state    = strategy.init_state(model, tc, key, opt_init, example_batch)
    state, metrics, plan = bundle.apply(state, batch, k)

``build_train_step`` threads the shared pieces through **every** strategy
exactly once: LR / weight-decay / label-smoothing / alpha schedules evaluated
from ``state.step``, ``_grads_with_metrics`` microbatched gradient
accumulation, and the ``opt_update(..., trainable)`` optimizer call. A
strategy only supplies what genuinely differs:

  * ``plan(step)``        — host-side schedule: which compiled variant runs
                            and whether an exchange (communication) happens;
  * ``distill_targets``   — the distillation-target kwargs for
                            ``codist_loss`` (live logits / stale-replica
                            pairwise / previous-step logits);
  * ``loss``              — the traced loss (default template uses
                            ``distill_targets``; shard_map overrides it);
  * ``post_update``       — cross-step strategy state (stale replicas, the
                            pipelined peer buffer);
  * ``comm_bytes``        — Section-3 accounting: bytes crossing the slow
                            links per exchange event.

Concrete strategies:

  AllReduce             baseline: gradient sync every step (single model)
  PredictionExchange    Algorithm 1, coordinated sampling, logits exchange
  CheckpointExchange    Anil et al. (arXiv:1804.03235): distill against the
                        stale replica set, params exchanged every T steps
  PipelinedPredictions  beyond-paper: previous exchange's logits as targets,
                        removing the per-step sync point
  ShardMapCompressed    beyond-paper: explicit ``shard_map`` over the "pod"
                        axis so only the compressed wire crosses pods

The legacy step factories (``make_codist_step`` et al.) were removed after
every caller migrated here; this module is the only way to build steps.
"""
from __future__ import annotations

from dataclasses import replace
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import CodistConfig, TrainConfig
from repro.core import codistillation as cd
from repro.core import comm_model as cm
from repro.core import schedules as sched
from repro.core.exchange import StepPlan
from repro.optim import make_optimizer
from repro.train.state import (CodistState, TrainState, init_codist_state,
                               init_peer_state, init_train_state)

PyTree = Any


# ----------------------------------------------------------------------------
# schedule bundle (shared by every strategy)
# ----------------------------------------------------------------------------

class Schedules(NamedTuple):
    lr: Callable
    wd: Callable
    ls: Callable
    alpha: Callable


def make_schedules(tc: TrainConfig, codist: Optional[CodistConfig] = None):
    lr_fn = sched.make_lr_fn(tc.lr_schedule, tc.lr, tc.total_steps,
                             tc.warmup_steps, tc.step_milestones, tc.step_decay)
    if tc.weight_decay_schedule:
        values = tuple(tc.weight_decay_schedule)
        miles = tc.step_milestones[: len(values) - 1]
        wd_fn = lambda s: sched.scheduled_weight_decay(s, tc.total_steps,
                                                       values, miles)
    else:
        wd_fn = lambda s: sched.constant_weight_decay(s, tc.weight_decay)
    if tc.label_smoothing_decay:
        ls_fn = lambda s: sched.decayed_label_smoothing(s, tc.total_steps,
                                                        tc.label_smoothing)
    else:
        ls_fn = lambda s: jnp.asarray(tc.label_smoothing, jnp.float32)
    if codist is not None:
        alpha_fn = lambda s: sched.alpha_schedule(
            s, codist.alpha0, codist.alpha_growth, codist.steps_per_epoch,
            codist.burn_in_steps)
    else:
        alpha_fn = lambda s: jnp.zeros((), jnp.float32)
    return lr_fn, wd_fn, ls_fn, alpha_fn


# ----------------------------------------------------------------------------
# shared forward / gradient-accumulation helpers
# ----------------------------------------------------------------------------

def _task_forward(model, params: PyTree, batch: Dict, remat: bool):
    """Unified forward over LM / enc-dec / conv models."""
    if hasattr(model.cfg, "kind"):  # ConvConfig
        return model.forward(params, batch)
    return model.forward(params, batch, remat=remat)


def _stacked_forward(model, stacked_params: PyTree, batch_all: Dict,
                     remat: bool):
    """vmap over the model axis: batch_all arrays carry a leading n axis."""
    def one(params, batch):
        return _task_forward(model, params, batch, remat)
    return jax.vmap(one)(stacked_params, batch_all)


def _grads_metrics_aux(loss_fn, params: PyTree, batch: Dict, k: int,
                       accum_dtype=jnp.float32):
    """Gradients of ``loss_fn(params, batch) -> (loss, (metrics, aux))``.

    k>1 enables microbatched gradient accumulation: every batch leaf carries a
    leading (k, B/k, ...) axis and a lax.scan accumulates fp32 grads — the
    production memory lever for the biggest configs (per-layer activations
    saved for backward scale with B/k, not B). ``metrics`` are averaged over
    microbatches; ``aux`` (optional pytree, e.g. the pipelined peer logits) is
    STACKED with a leading k axis so per-example tensors survive accumulation.
    """
    if k <= 1:
        (_, (metrics, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return grads, metrics, aux

    m_shape, _ = jax.eval_shape(
        lambda p, b: loss_fn(p, b)[1], params,
        jax.tree.map(lambda x: x[0], batch))
    m0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), m_shape)
    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)

    def body(carry, mb):
        g_acc, m_acc = carry
        (_, (m, aux)), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
        g_acc = jax.tree.map(lambda a, gg: a + gg.astype(accum_dtype) / k,
                             g_acc, g)
        m_acc = jax.tree.map(lambda a, mm: a + mm / k, m_acc, m)
        return (g_acc, m_acc), aux

    (grads, metrics), aux = jax.lax.scan(body, (g0, m0), batch)
    return grads, metrics, aux


def _grads_with_metrics(loss_fn, params: PyTree, batch: Dict, k: int,
                        accum_dtype=jnp.float32):
    """Legacy aux-free spelling: ``loss_fn -> (loss, metrics)``."""
    def wrapped(p, b):
        total, metrics = loss_fn(p, b)
        return total, (metrics, None)
    grads, metrics, _ = _grads_metrics_aux(wrapped, params, batch, k,
                                           accum_dtype)
    return grads, metrics


def _param_bits(params: PyTree, n: int = 1) -> float:
    """Bits of one model's parameter vector (stacked trees carry n models)."""
    total = sum(x.size * jnp.dtype(x.dtype).itemsize * 8
                for x in jax.tree.leaves(params))
    return total / max(1, n)


def _plain_task_metrics(codist, logits_all, batch, ls, fused):
    """Stacked task-only loss (the prediction off-step / alpha=0 shape)."""
    task = jax.vmap(
        lambda lg, lb, m: cd.cross_entropy(lg, lb, ls, m, fused=fused)
    )(logits_all, batch["labels"],
      batch.get("mask", jnp.ones(batch["labels"].shape, jnp.float32)))
    total = jnp.mean(task)
    metrics = {"loss": total, "task_loss": total,
               "distill_loss": jnp.zeros(()),
               "task_loss_per_model": task,
               "distill_loss_per_model": jnp.zeros_like(task),
               "alpha": jnp.zeros(())}
    return total, metrics


# ----------------------------------------------------------------------------
# the strategy protocol
# ----------------------------------------------------------------------------

class ExchangeStrategy:
    """Pluggable Section-3 synchronization mechanism.

    Host-side API (loop / StepBundle): ``init_state``, ``ensure_state``,
    ``plan``, ``variant_for``, ``host_exchange``, ``comm_bytes``,
    ``make_eval``. Traced API (inside the compiled step): ``prepare``,
    ``distill_targets``, ``loss``, ``post_update``. The default ``loss``
    template covers every stacked-logits mechanism via ``distill_targets``;
    strategies with a structurally different loss (pipelined replay,
    shard_map) override it.
    """

    name = "base"
    variants: Tuple[str, ...] = ("on",)
    stacked = True  # CodistState with leading n axis (vs single TrainState)

    def __init__(self, codist: Optional[CodistConfig] = None):
        self.codist = codist

    # ---- host side ---------------------------------------------------------
    def init_state(self, model, tc: TrainConfig, key, opt_init,
                   example_batch: Optional[Dict] = None):
        return init_codist_state(model, key, self.codist.n_models, opt_init)

    def ensure_state(self, state, model, tc: TrainConfig,
                     example_batch: Optional[Dict] = None):
        """Repair strategy-specific state on a user-supplied ``state``."""
        return state

    def plan(self, step: int) -> StepPlan:
        raise NotImplementedError

    def variant_for(self, plan: StepPlan) -> str:
        return "on"

    def host_exchange(self, state):
        """Host-side exchange action (checkpoint mode refreshes the stale
        replicas); the default mechanisms exchange inside the compiled step."""
        return state

    def comm_bytes(self, model, state, batch_all: Dict,
                   microbatch: int = 0) -> float:
        """Bytes crossing the slow (cross-pod) links per exchange EVENT."""
        return 0.0

    def make_eval(self, model, tc: TrainConfig) -> Callable:
        return make_codist_eval_step(model, tc)

    # ---- traced (inside the compiled step) ---------------------------------
    def prepare(self, state, batch_all: Dict, k: int):
        """Scan operand for ``_grads_metrics_aux``: microbatch axis moves in
        front of the stacked model axis ((n, k, B/k, ...) -> (k, n, ...))."""
        if k > 1:
            return jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), batch_all)
        return batch_all

    def distill_targets(self, model, tc: TrainConfig, state, batch: Dict,
                        logits_all) -> Dict:
        """kwargs for ``codist_loss`` selecting the distillation targets."""
        return {}

    def loss(self, model, tc: TrainConfig, sch: Schedules, state, params,
             batch: Dict, variant: str):
        """Return ``(total, metrics, aux)`` for one (micro)batch."""
        logits_all, aux_all = _stacked_forward(model, params, batch, tc.remat)
        if variant == "on":
            total, metrics = cd.codist_loss(
                self.codist, logits_all, batch["labels"],
                sch.alpha(state.step), sch.ls(state.step), batch.get("mask"),
                fused=tc.fused_losses,
                **self.distill_targets(model, tc, state, batch, logits_all))
        else:
            total, metrics = _plain_task_metrics(
                self.codist, logits_all, batch, sch.ls(state.step),
                tc.fused_losses)
        total = total + jnp.mean(aux_all)
        metrics["aux_loss"] = jnp.mean(aux_all)
        metrics["accuracy"] = jnp.mean(jax.vmap(cd.accuracy)(
            logits_all, batch["labels"]))
        return total, metrics, None

    def post_update(self, state, params, opt, batch_all: Dict, aux, k: int):
        return CodistState(params, opt, state.step + 1, state.stale,
                           state.peer)


# ----------------------------------------------------------------------------
# concrete strategies
# ----------------------------------------------------------------------------

class AllReduce(ExchangeStrategy):
    """Standard data-parallel baseline: the gradient all-reduce crosses the
    pod links every step (C_AR = 2 * b_model bits/iter, Section 3)."""

    name = "all_reduce"
    stacked = False

    def init_state(self, model, tc, key, opt_init, example_batch=None):
        return init_train_state(model, key, opt_init)

    def plan(self, step: int) -> StepPlan:
        return StepPlan(distill=False, exchange=True)

    def comm_bytes(self, model, state, batch_all, microbatch=0) -> float:
        return 2.0 * _param_bits(state.params) / 8.0

    def make_eval(self, model, tc):
        return make_eval_step(model, tc)

    def loss(self, model, tc, sch, state, params, batch, variant):
        logits, aux = _task_forward(model, params, batch, tc.remat)
        task = cd.cross_entropy(logits, batch["labels"], sch.ls(state.step),
                                batch.get("mask"), fused=tc.fused_losses)
        metrics = {"loss": task + aux, "task_loss": task, "aux_loss": aux,
                   "accuracy": cd.accuracy(logits, batch["labels"],
                                           batch.get("mask"))}
        return task + aux, metrics, None

    def prepare(self, state, batch_all, k):
        # single-model batches already carry the (k, B/k, ...) layout
        return batch_all

    def post_update(self, state, params, opt, batch_all, aux, k):
        return TrainState(params, opt, state.step + 1)


class PredictionExchange(ExchangeStrategy):
    """Algorithm 1 with coordinated sampling: on exchange steps the stacked
    logits are the distillation targets (the cross-pod logits collective);
    off steps compile a separate variant that omits the distillation term —
    and hence the collective — entirely (Section 3's periodic exchange)."""

    name = "prediction"
    variants = ("on", "off")

    def plan(self, step: int) -> StepPlan:
        return StepPlan.for_step(replace(self.codist, mode="predictions"),
                                 step)

    def variant_for(self, plan: StepPlan) -> str:
        return "on" if plan.distill else "off"

    def comm_bytes(self, model, state, batch_all, microbatch=0) -> float:
        cfg = self.codist
        try:
            labels = batch_all["labels"]
            n = cfg.n_models
            mcfg = getattr(model, "cfg", None)
            if labels.ndim >= 3:  # LM: (n, [k,] B, S)
                seq = labels.shape[-1]
                samples = labels.size // (n * seq)
                b_pred = cm.prediction_bits_lm(mcfg, seq, 32, cfg.compression,
                                               cfg.topk, cfg.subsample)
            else:                 # classifier: (n, B)
                samples = labels.size // n
                b_pred = cm.prediction_bits_classifier(mcfg.num_classes)
            return (n - 1) * b_pred * samples / 8.0
        except (KeyError, AttributeError, TypeError):
            # model without Section-3 accounting metadata (e.g. a custom
            # cfg): report 0 rather than refuse to train
            return 0.0


class CheckpointExchange(PredictionExchange):
    """Anil et al.'s variant: every step each model draws its OWN batch and
    distills against the stale replicas' predictions on it (n-1 extra
    gradient-free forwards); every T steps the host refreshes ``state.stale``
    via ``refresh_stale`` (the cross-pod parameter all-gather)."""

    name = "checkpoint"
    variants = ("on",)

    def init_state(self, model, tc, key, opt_init, example_batch=None):
        return init_codist_state(model, key, self.codist.n_models, opt_init,
                                 with_stale=True)

    def ensure_state(self, state, model, tc, example_batch=None):
        if state.stale is None:  # user-supplied state without stale replicas
            return state._replace(stale=jax.tree.map(jnp.array, state.params))
        return state

    def plan(self, step: int) -> StepPlan:
        # distill EVERY step against the stale replicas (even during burn-in,
        # where alpha is 0); exchange every T per the config-driven schedule
        p = StepPlan.for_step(replace(self.codist, mode="checkpoints"), step)
        return StepPlan(True, p.exchange)

    def variant_for(self, plan: StepPlan) -> str:
        return "on"

    def host_exchange(self, state):
        return refresh_stale(state)

    def comm_bytes(self, model, state, batch_all, microbatch=0) -> float:
        n = self.codist.n_models
        return (n - 1) * _param_bits(state.params, n) / 8.0

    def distill_targets(self, model, tc, state, batch, logits_all):
        # peer_pairwise[i, j] = stale_j(x_i); gradient-free, recomputed per
        # microbatch so gradient accumulation stays exact
        def stale_on_batch(batch_i):
            return jax.vmap(
                lambda sp: _task_forward(model, sp, batch_i, tc.remat)[0]
            )(state.stale)
        peer_pairwise = jax.lax.stop_gradient(
            jax.vmap(stale_on_batch)(batch))     # (n_batch=i, n_model=j, ...)
        return {"peer_pairwise": peer_pairwise}


class PipelinedPredictions(ExchangeStrategy):
    """Beyond-paper: distill against the PREVIOUS exchange's peer logits,
    replaying the previous (coordinated) batch for the distill term. The
    logits collective of step k-1 overlaps with step k's compute, removing
    the sync point the paper flags for prediction exchange.

    ``state.peer = {"batch": prev batch_all, "logits": prev logits_all,
    "valid": bool}`` — with microbatching both carry the (n, k, B/k, ...)
    layout so the replay pairs microbatch m with its own stale logits.
    """

    name = "pipelined"

    def init_state(self, model, tc, key, opt_init, example_batch=None):
        state = init_codist_state(model, key, self.codist.n_models, opt_init)
        return self.ensure_state(state, model, tc, example_batch)

    def ensure_state(self, state, model, tc, example_batch=None):
        if state.peer is not None or example_batch is None:
            return state
        n = self.codist.n_models
        k = tc.microbatch

        def slice0(x):  # model 0 (and microbatch 0 when microbatched)
            return x[0][0] if k > 1 else x[0]
        logits_shape = jax.eval_shape(
            lambda p, b: _task_forward(model, p, b, False)[0],
            jax.tree.map(lambda x: x[0], state.params),
            jax.tree.map(slice0, example_batch)).shape
        lead = (n, k) if k > 1 else (n,)
        return state._replace(peer=init_peer_state(example_batch,
                                                   lead + logits_shape))

    def plan(self, step: int) -> StepPlan:
        # the (stale) logits collective overlaps every step
        return StepPlan(True, True)

    def comm_bytes(self, model, state, batch_all, microbatch=0) -> float:
        return PredictionExchange.comm_bytes(self, model, state, batch_all,
                                             microbatch)

    def prepare(self, state, batch_all, k):
        operand = {"batch": batch_all, "peer_batch": state.peer["batch"],
                   "peer_logits": state.peer["logits"]}
        if k > 1:
            operand = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), operand)
        return operand

    def loss(self, model, tc, sch, state, params, operand, variant):
        batch = operand["batch"]
        peer_batch = operand["peer_batch"]
        codist = self.codist
        logits_all, aux_all = _stacked_forward(model, params, batch, tc.remat)
        task = jax.vmap(
            lambda lg, lb, m: cd.cross_entropy(lg, lb, sch.ls(state.step), m,
                                               fused=tc.fused_losses)
        )(logits_all, batch["labels"],
          batch.get("mask", jnp.ones(batch["labels"].shape, jnp.float32)))
        # replay forward on the previous batch for the distillation term
        replay_logits, _ = _stacked_forward(model, params, peer_batch,
                                            tc.remat)
        _, dmetrics = cd.codist_loss(
            codist, replay_logits, peer_batch["labels"],
            sch.alpha(state.step), 0.0, peer_batch.get("mask"),
            peer_logits_all=operand["peer_logits"], fused=tc.fused_losses)
        dist = dmetrics["distill_loss_per_model"]
        alpha = sch.alpha(state.step) * state.peer["valid"].astype(jnp.float32)
        total = jnp.mean(task + alpha * dist) + jnp.mean(aux_all)
        metrics = {"loss": total, "task_loss": jnp.mean(task),
                   "distill_loss": jnp.mean(dist), "alpha": alpha,
                   "aux_loss": jnp.mean(aux_all),
                   "accuracy": jnp.mean(jax.vmap(cd.accuracy)(
                       logits_all, batch["labels"]))}
        return total, metrics, jax.lax.stop_gradient(logits_all)

    def post_update(self, state, params, opt, batch_all, aux, k):
        logits = aux
        if k > 1:  # scan stacked (k, n, B/k, ...) -> stored (n, k, B/k, ...)
            logits = jnp.swapaxes(logits, 0, 1)
        new_peer = {"batch": batch_all,
                    "logits": logits.astype(state.peer["logits"].dtype),
                    "valid": jnp.ones((), jnp.bool_)}
        return CodistState(params, opt, state.step + 1, state.stale, new_peer)


class AsyncPrediction(ExchangeStrategy):
    """Single-peer view of the prediction exchange for the async runtime.

    The synchronous ``PredictionExchange`` computes every model's forward in
    one vmapped step; in ``repro.runtime`` each peer runs on its OWN step
    clock, so a step sees only this peer's params and the distillation
    targets arrive from the host (``runtime.mailbox`` payloads posted by
    peers on their own clocks). The operand is::

        {"batch": <single-model batch>,
         "peer_wire":      compressed-wire pytree (``compress_targets``,
                           producer side), every leaf stacked (P, ...);
                           zero-filled slots for absent peers
         "peer_weight":    (P,)  1.0 accepted / 0.0 dropped-or-missing
         "peer_staleness": (P,)  receiver_step - sender_step}

    The traced loss is ``(task + alpha * dist + aux) / n_slots`` — exactly
    this peer's share of ``codist_loss``'s mean over n models (every other
    model's term is a constant w.r.t. this peer's params), so with fresh
    same-step targets the gradient, and hence the whole trajectory, matches
    the synchronous engine (pinned by ``tests/test_runtime.py``). The weight
    vector implements the staleness-bound drop policy: dropped peers
    contribute nothing, and when every payload is dropped the distillation
    term (and alpha) vanishes — the step degrades to plain task training
    instead of blocking, which is the fault-tolerance argument of Anil et
    al. (arXiv:1804.03235). Metrics report the UNSCALED task/distill terms
    plus the measured staleness of the targets actually used.
    """

    name = "async_prediction"
    variants = ("on", "off")
    stacked = False

    def __init__(self, codist: CodistConfig, n_slots: Optional[int] = None):
        super().__init__(codist)
        # the divisor of the codist mean AND 1 + number of target slots;
        # fixed at build time so elastic membership keeps shapes static
        self.n_slots = max(2, n_slots or codist.n_models)

    def init_state(self, model, tc, key, opt_init, example_batch=None):
        return init_train_state(model, key, opt_init)

    def plan(self, step: int) -> StepPlan:
        # standalone use mirrors the synchronous prediction schedule; the
        # AsyncScheduler drives variants directly from mailbox availability
        return StepPlan.for_step(replace(self.codist, mode="predictions"),
                                 step)

    def variant_for(self, plan: StepPlan) -> str:
        return "on" if plan.distill else "off"

    def make_eval(self, model, tc):
        return make_eval_step(model, tc)

    def comm_bytes(self, model, state, operand, microbatch=0) -> float:
        cfg = self.codist
        try:
            batch = operand["batch"] if "batch" in operand else operand
            labels = batch["labels"]
            seq = labels.shape[-1]
            samples = labels.size // seq
            b_pred = cm.prediction_bits_lm(model.cfg, seq, 32,
                                           cfg.compression, cfg.topk,
                                           cfg.subsample)
            return (self.n_slots - 1) * b_pred * samples / 8.0
        except (KeyError, AttributeError, TypeError):
            return 0.0

    def prepare(self, state, operand, k):
        if k <= 1:
            return operand
        # batch leaves already carry the (k, B/k, ...) layout (single model);
        # wire leaves arrive as (P, k, ...) and scalars-per-peer are tiled so
        # the gradient-accumulation scan can slice a k axis off every leaf
        return {"batch": operand["batch"],
                "peer_wire": jax.tree.map(
                    lambda x: jnp.swapaxes(x, 0, 1), operand["peer_wire"]),
                "peer_weight": jnp.broadcast_to(
                    operand["peer_weight"],
                    (k,) + operand["peer_weight"].shape),
                "peer_staleness": jnp.broadcast_to(
                    operand["peer_staleness"],
                    (k,) + operand["peer_staleness"].shape)}

    def loss(self, model, tc, sch, state, params, operand, variant):
        batch = operand["batch"] if "batch" in operand else operand
        logits, aux = _task_forward(model, params, batch, tc.remat)
        mask = batch.get("mask")
        task = cd.cross_entropy(logits, batch["labels"], sch.ls(state.step),
                                mask, fused=tc.fused_losses)
        acc = cd.accuracy(logits, batch["labels"], mask)
        n = self.n_slots
        if variant != "on":
            total = (task + aux) / n
            metrics = {"loss": total, "task_loss": task,
                       "distill_loss": jnp.zeros(()), "aux_loss": aux,
                       "alpha": jnp.zeros(()), "accuracy": acc,
                       "staleness": jnp.zeros(()),
                       "peer_weight": jnp.zeros(())}
            return total, metrics, None
        wires = operand["peer_wire"]  # host-provided constants: no gradient
        w = operand["peer_weight"].astype(jnp.float32)
        st = operand["peer_staleness"].astype(jnp.float32)
        ds = []
        for j in range(jax.tree.leaves(wires)[0].shape[0]):
            wire = jax.tree.map(lambda x: x[j], wires)
            ds.append(cd.distill_vs_compressed(self.codist, logits, wire,
                                               mask, fused=tc.fused_losses))
        d = jnp.stack(ds)
        wsum = jnp.sum(w)
        denom = jnp.maximum(wsum, 1.0)   # == n-1 with a full fresh mailbox
        dist = jnp.sum(w * d) / denom
        stale = jnp.sum(w * st) / denom
        alpha = sch.alpha(state.step) * (wsum > 0).astype(jnp.float32)
        total = (task + alpha * dist + aux) / n
        metrics = {"loss": total, "task_loss": task, "distill_loss": dist,
                   "aux_loss": aux, "alpha": alpha, "accuracy": acc,
                   "staleness": stale, "peer_weight": wsum}
        return total, metrics, None

    def post_update(self, state, params, opt, batch_all, aux, k):
        return TrainState(params, opt, state.step + 1)


class ShardMapCompressed(PredictionExchange):
    """Prediction exchange with an explicitly scheduled compressed wire.

    The pure-pjit prediction step lets XLA place the cross-pod exchange —
    fine for raw logits, but compiler-chosen placement defeats producer-side
    COMPRESSION (XLA may move the raw logits and compress afterwards). This
    strategy pins the schedule by construction: manual ``shard_map`` over
    ``"pod"`` (``"data"``/``"model"`` stay automatic, so FSDP/TP inside the
    pod is unchanged), each pod computes its model's forward + task loss +
    the compressed wire locally, and ``jax.lax.all_gather(wire, "pod")`` is
    the ONLY cross-pod communication. ``stop_gradient`` on the received wire
    keeps the backward pass pod-local. Off steps reuse the prediction
    strategy's collective-free variant.
    """

    name = "shardmap"
    variants = ("on", "off")

    def __init__(self, codist: CodistConfig, mesh):
        super().__init__(codist)
        self.mesh = mesh
        if "pod" not in mesh.axis_names:
            raise ValueError("ShardMapCompressed needs a mesh with a 'pod' "
                             f"axis; got {mesh.axis_names}")

    def loss(self, model, tc, sch, state, params, batch, variant):
        if variant == "off":
            return super().loss(model, tc, sch, state, params, batch, "off")
        from jax.sharding import PartitionSpec as P
        codist, mesh, n = self.codist, self.mesh, self.codist.n_models

        def lead_spec(tree):
            return jax.tree.map(
                lambda x: P(*(["pod"] + [None] * (x.ndim - 1))), tree)

        def per_pod(params_1, batch_1):
            p = jax.tree.map(lambda x: x[0], params_1)
            b = jax.tree.map(lambda x: x[0], batch_1)
            logits, aux = _task_forward(model, p, b, tc.remat)
            task = cd.cross_entropy(logits, b["labels"], sch.ls(state.step),
                                    b.get("mask"), fused=tc.fused_losses)
            # local compression, explicit cross-pod gather of the wire
            wire = cd.compress_targets(codist, jax.lax.stop_gradient(logits))
            wires_all = jax.tree.map(
                lambda x: jax.lax.all_gather(x, "pod"), wire)
            idx = jax.lax.axis_index("pod")
            dist = jnp.zeros((), jnp.float32)
            for j in range(n):
                wire_j = jax.tree.map(lambda x: x[j], wires_all)
                d = cd.distill_vs_compressed(codist, logits, wire_j,
                                             b.get("mask"),
                                             fused=tc.fused_losses)
                dist = dist + jnp.where(idx == j, 0.0, d)
            dist = dist / (n - 1)
            total = task + sch.alpha(state.step) * dist + aux
            out = jnp.stack([total, task, dist, aux])
            return out[None]  # (1, 4): pod-sharded metrics row

        per_pod_mapped = jax.shard_map(
            per_pod, mesh=mesh,
            in_specs=(lead_spec(params), lead_spec(batch)),
            out_specs=P("pod", None),
            check_vma=False, axis_names={"pod"})
        rows = per_pod_mapped(params, batch)         # (n, 4)
        total = jnp.mean(rows[:, 0])
        metrics = {"loss": total,
                   "task_loss": jnp.mean(rows[:, 1]),
                   "distill_loss": jnp.mean(rows[:, 2]),
                   "aux_loss": jnp.mean(rows[:, 3]),
                   "task_loss_per_model": rows[:, 1],
                   "distill_loss_per_model": rows[:, 2],
                   "alpha": sch.alpha(state.step)}
        return total, metrics, None


def resolve_strategy(codist: Optional[CodistConfig],
                     mesh=None) -> ExchangeStrategy:
    """CodistConfig -> strategy. ``mesh`` (with a "pod" axis) selects the
    explicit-collective compressed exchange; otherwise the config's
    ``pipelined`` / ``mode`` fields pick the mechanism, mirroring the old
    host-loop dispatch."""
    if codist is None:
        return AllReduce()
    if mesh is not None:
        return ShardMapCompressed(codist, mesh)
    if codist.pipelined:
        return PipelinedPredictions(codist)
    if codist.mode == "checkpoints":
        return CheckpointExchange(codist)
    return PredictionExchange(codist)


STRATEGIES = {cls.name: cls for cls in
              (AllReduce, PredictionExchange, CheckpointExchange,
               PipelinedPredictions, ShardMapCompressed, AsyncPrediction)}


# ----------------------------------------------------------------------------
# the unified builder
# ----------------------------------------------------------------------------

class StepBundle:
    """Compiled variants of one strategy plus the plan-driven dispatcher."""

    def __init__(self, strategy: ExchangeStrategy,
                 variants: Dict[str, Callable], eval_fn: Callable):
        self.strategy = strategy
        self.variants = variants     # raw (unjitted) step fns
        self.eval_fn = eval_fn       # raw eval fn
        self._jitted: Dict[str, Callable] = {}

    def jitted(self, variant: str = "on") -> Callable:
        """The compiled variant. It donates the state (argument 0): the
        caller must not read the state it passed in after the call."""
        if variant not in self._jitted:
            self._jitted[variant] = jax.jit(self.variants[variant],
                                            donate_argnums=0)
        return self._jitted[variant]

    def apply(self, state, batch_all: Dict, step_idx: int):
        """One host-loop iteration: plan -> (optional) host exchange ->
        compiled variant. Returns ``(state, metrics, plan)``."""
        plan = self.strategy.plan(step_idx)
        if plan.exchange:
            state = self.strategy.host_exchange(state)
        state, metrics = self.jitted(self.strategy.variant_for(plan))(
            state, batch_all)
        return state, metrics, plan


def build_train_step(model, tc: TrainConfig, codist: Optional[CodistConfig],
                     strategy: ExchangeStrategy,
                     trainable: Optional[PyTree] = None) -> StepBundle:
    """The single entry point: every strategy's step variants share ONE
    schedules/optimizer/microbatch/trainable path."""
    codist = codist if codist is not None else strategy.codist
    sch = Schedules(*make_schedules(tc, codist))
    _, opt_update = make_optimizer(tc.optimizer, momentum=tc.momentum,
                                   b1=tc.adam_b1, b2=tc.adam_b2,
                                   dtype=tc.opt_dtype)

    def make_variant(variant: str) -> Callable:
        def step(state, batch_all: Dict):
            operand = strategy.prepare(state, batch_all, tc.microbatch)

            def loss_fn(params, b):
                total, metrics, aux = strategy.loss(model, tc, sch, state,
                                                    params, b, variant)
                return total, (metrics, aux)

            grads, metrics, aux = _grads_metrics_aux(
                loss_fn, state.params, operand, tc.microbatch,
                jnp.dtype(tc.accum_dtype))
            params, opt = opt_update(state.params, grads, state.opt,
                                     sch.lr(state.step), sch.wd(state.step),
                                     trainable)
            metrics.update(lr=sch.lr(state.step), wd=sch.wd(state.step))
            new_state = strategy.post_update(state, params, opt, batch_all,
                                             aux, tc.microbatch)
            return new_state, metrics
        return step

    variants = {v: make_variant(v) for v in strategy.variants}
    return StepBundle(strategy, variants, strategy.make_eval(model, tc))


# ----------------------------------------------------------------------------
# host-side exchange ops & eval steps
# ----------------------------------------------------------------------------

@jax.jit
def refresh_stale(state: CodistState) -> CodistState:
    """The checkpoint exchange: stale <- current params (cross-pod all-gather
    in the sharded setting: params are pod-sharded, stale is pod-replicated)."""
    return state._replace(stale=jax.tree.map(jnp.array, state.params))


def make_eval_step(model, tc: Optional[TrainConfig] = None) -> Callable:
    fused = tc.fused_losses if tc is not None else None

    def eval_step(params: PyTree, batch: Dict) -> Dict:
        logits, _ = _task_forward(model, params, batch, False)
        return {
            "eval_loss": cd.cross_entropy(logits, batch["labels"],
                                          0.0, batch.get("mask"),
                                          fused=fused),
            "eval_accuracy": cd.accuracy(logits, batch["labels"],
                                         batch.get("mask")),
        }
    return eval_step


def make_codist_eval_step(model, tc: Optional[TrainConfig] = None) -> Callable:
    fused = tc.fused_losses if tc is not None else None

    def eval_step(stacked_params: PyTree, batch_all: Dict) -> Dict:
        logits_all, _ = _stacked_forward(model, stacked_params, batch_all,
                                         False)
        loss = jax.vmap(lambda lg, lb: cd.cross_entropy(lg, lb, fused=fused))(
            logits_all, batch_all["labels"])
        acc = jax.vmap(cd.accuracy)(logits_all, batch_all["labels"])
        return {"eval_loss": jnp.mean(loss), "eval_loss_per_model": loss,
                "eval_accuracy": jnp.mean(acc), "eval_accuracy_per_model": acc}
    return eval_step
