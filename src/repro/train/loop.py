"""Host training loop: metric logging, plan-driven variant dispatch, comm
event/byte accounting, eval, and the Fig.-7 parameter-distance probe.

The loop is strategy-agnostic: ``strategy.plan(k)`` picks the compiled
variant and decides when an exchange happens; the strategy's
``host_exchange`` performs any host-side communication (the checkpoint-mode
stale refresh); ``strategy.comm_bytes`` prices each exchange event for the
Section-3 accounting. No mechanism-specific branching lives here.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import CodistConfig, TrainConfig
from repro.core.codistillation import param_distance_from
from repro.train.engine import (ExchangeStrategy, AllReduce, build_train_step,
                                resolve_strategy)

PyTree = Any

# History JSONL schema: bump when the on-disk record shape changes in a way
# old readers would misparse. v1 = a header line {"schema_version": 1}
# followed by one record per line (files written before the header existed
# load as legacy v1 — their record shape is identical).
HISTORY_SCHEMA_VERSION = 1


@dataclass
class History:
    records: List[Dict[str, float]] = field(default_factory=list)
    # per step variant, the step index of each trace (StepBundle.traced_at)
    traced_at: Dict[str, List[Optional[int]]] = field(default_factory=dict)

    def log(self, step: int, metrics: Dict[str, Any], **extra):
        rec = {"step": step}
        for k, v in metrics.items():
            try:
                arr = jnp.asarray(v)
                if arr.ndim == 0:
                    rec[k] = float(arr)
                else:
                    for i, x in enumerate(arr.reshape(-1)):
                        rec[f"{k}_{i}"] = float(x)
            except Exception:
                pass
        rec.update(extra)
        self.records.append(rec)

    def last(self, key: str) -> float:
        for rec in reversed(self.records):
            if key in rec:
                return rec[key]
        raise KeyError(key)

    def series(self, key: str) -> List[float]:
        return [r[key] for r in self.records if key in r]

    def save(self, path: str) -> None:
        """Persist as JSONL: a ``{"schema_version": N}`` header line, then
        one record per line — async runs and benchmarks stream trajectories
        to disk instead of keeping them in memory."""
        import json
        import os
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            f.write(json.dumps({"schema_version": HISTORY_SCHEMA_VERSION})
                    + "\n")
            for rec in self.records:
                f.write(json.dumps(rec) + "\n")

    @classmethod
    def load(cls, path: str) -> "History":
        import json
        with open(path) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        if rows and "schema_version" in rows[0] and "step" not in rows[0]:
            version = rows[0]["schema_version"]
            if version != HISTORY_SCHEMA_VERSION:
                raise ValueError(
                    f"{path}: History schema_version {version} is not "
                    f"supported by this reader (expects "
                    f"{HISTORY_SCHEMA_VERSION}). Re-generate the JSONL with "
                    "this version of the repo, or load it with the matching "
                    "older version.")
            rows = rows[1:]
        # headerless files predate the schema header: legacy v1, same shape
        return cls(rows)


def train(model, tc: TrainConfig, batches: Callable[[int], Dict],
          strategy: ExchangeStrategy, codist: Optional[CodistConfig] = None,
          eval_batches: Optional[Callable[[int], Dict]] = None,
          eval_every: int = 0, log_every: int = 10,
          state=None, trainable: Optional[PyTree] = None,
          track_param_distance: bool = False,
          tracer=None, metrics=None, watch=None) -> tuple:
    """Generic strategy-driven loop. ``batches(step)`` returns the batch for
    that step (stacked with a leading n axis for codist strategies — it owns
    coordinated vs. independent sampling).

    A supplied ``state`` is donated to the compiled step: the caller must
    not read it after the call (pass a copy to keep it).

    ``tracer``/``metrics`` are optional ``repro.obs`` hooks on the step
    clock (one step renders as 1 ms): per-step spans with exchange markers
    and comm-byte counters. ``watch`` is an optional Watchtower on the same
    step clock, evaluated at each log point against the live
    ``train/task_loss`` gauge. ``None`` leaves the loop untouched."""
    from repro.optim import make_optimizer
    opt_init, _ = make_optimizer(tc.optimizer, momentum=tc.momentum,
                                 b1=tc.adam_b1, b2=tc.adam_b2,
                                 dtype=tc.opt_dtype)
    example = batches(0)
    if state is None:
        state = strategy.init_state(model, tc, jax.random.key(tc.seed),
                                    opt_init, example)
    else:
        state = strategy.ensure_state(state, model, tc, example)
    bundle = build_train_step(model, tc, codist, strategy, trainable)
    eval_fn = jax.jit(bundle.eval_fn)
    params0 = (jax.tree.map(jnp.array, state.params)
               if track_param_distance else None)
    bytes_per_event = strategy.comm_bytes(model, state, example, tc.microbatch)
    hist = History()
    comm_events = 0
    mreg = metrics                   # the obs registry; the loop's local
    del metrics                      # ``metrics`` name is the step's dict
    if tracer is not None:
        tracer.name_process(0, "train")
        tracer.name_thread(0, 0, strategy.__class__.__name__)
    for k in range(tc.total_steps):
        batch = example if k == 0 else batches(k)
        state, metrics, plan = bundle.apply(state, batch, k)
        if plan.exchange:
            comm_events += 1
        if tracer is not None:
            tracer.complete("step", k, k + 1, cat="train",
                            args={"step": k, "exchange": bool(plan.exchange)})
            if plan.exchange:
                tracer.instant("exchange", k, cat="train")
        if k % log_every == 0 or k == tc.total_steps - 1:
            extra = {"comm_events": comm_events,
                     "comm_bytes": comm_events * bytes_per_event}
            if track_param_distance:
                extra["param_distance"] = float(
                    param_distance_from(state.params, params0))
            if eval_every and eval_batches is not None and (
                    k % eval_every == 0 or k == tc.total_steps - 1):
                metrics = {**metrics, **eval_fn(state.params, eval_batches(k))}
            hist.log(k, metrics, **extra)
            if tracer is not None:
                tracer.counter("comm", k, {"events": comm_events,
                                           "bytes": extra["comm_bytes"]})
            if mreg is not None:
                # live loss stream for alert rules: scalar runs log
                # "task_loss", codist runs log one "task_loss_<i>" per
                # peer — average the peers into one gauge
                rec = hist.records[-1]
                losses = [v for name, v in sorted(rec.items())
                          if name == "task_loss"
                          or name.startswith("task_loss_")]
                if losses:
                    mreg.gauge("train/task_loss").set(
                        sum(losses) / len(losses))
            if watch is not None:
                watch.evaluate(k)
    hist.traced_at = bundle.traced_at
    if mreg is not None:
        mreg.counter("train/comm_events").inc(comm_events)
        mreg.counter("train/comm_bytes").inc(comm_events * bytes_per_event)
        mreg.gauge("train/steps").set(tc.total_steps)
        try:
            mreg.gauge("train/final_task_loss").set(hist.last("task_loss"))
        except KeyError:
            pass
    return state, hist


def train_allreduce(model, tc: TrainConfig, batches: Iterator[Dict],
                    eval_batches: Optional[Callable[[int], Dict]] = None,
                    eval_every: int = 0, log_every: int = 10,
                    state=None, trainable: Optional[PyTree] = None,
                    track_param_distance: bool = False,
                    tracer=None, metrics=None, watch=None) -> tuple:
    it = iter(batches)
    return train(model, tc, lambda k: next(it), AllReduce(),
                 eval_batches=eval_batches, eval_every=eval_every,
                 log_every=log_every, state=state, trainable=trainable,
                 track_param_distance=track_param_distance,
                 tracer=tracer, metrics=metrics, watch=watch)


def train_codist(model, codist: CodistConfig, tc: TrainConfig,
                 batches: Callable[[int], Dict],
                 eval_batches: Optional[Callable[[int], Dict]] = None,
                 eval_every: int = 0, log_every: int = 10,
                 state=None, trainable: Optional[PyTree] = None,
                 track_param_distance: bool = False,
                 strategy: Optional[ExchangeStrategy] = None,
                 tracer=None, metrics=None, watch=None) -> tuple:
    """Codistillation loop; the mechanism comes from ``strategy`` (explicit
    instance, e.g. ``ShardMapCompressed``) or ``resolve_strategy(codist)``."""
    strategy = strategy if strategy is not None else resolve_strategy(codist)
    return train(model, tc, batches, strategy, codist=codist,
                 eval_batches=eval_batches, eval_every=eval_every,
                 log_every=log_every, state=state, trainable=trainable,
                 track_param_distance=track_param_distance,
                 tracer=tracer, metrics=metrics, watch=watch)


def stack_batches(batch_list: List[Dict]) -> Dict:
    """[batch_i] -> stacked dict with leading n axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *batch_list)
