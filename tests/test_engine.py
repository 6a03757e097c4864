"""Unified step-engine tests: strategy parity, microbatch gradient
accumulation for every mechanism, the trainable mask, plan schedules, and
comm accounting — plus subprocess checks for the shard_map strategy (which
needs a multi-device "pod" axis)."""
import json
import os
import subprocess
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CodistConfig, TrainConfig, get_reduced
from repro.core.codistillation import model_slice
from repro.data import MarkovLM, make_lm_batch
from repro.models import build_model
from repro.optim import make_optimizer
from repro.train import (AllReduce, CheckpointExchange, PipelinedPredictions,
                         PredictionExchange, TrainState, build_train_step,
                         init_codist_state, resolve_strategy, stack_batches,
                         train, train_allreduce, train_codist)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def tiny_cfg():
    return replace(get_reduced("qwen1.5-0.5b"), num_layers=1, d_model=32,
                   d_ff=64, vocab_size=64, num_heads=2, num_kv_heads=2,
                   head_dim=16)


TASK = MarkovLM(vocab=64, seed=0)
N, B, S = 2, 8, 16


def coord_batches(n=N, b=B, s=S):
    def fn(step):
        return stack_batches([make_lm_batch(TASK, b, s, step, None, seed=0)
                              for _ in range(n)])
    return fn


def single_batches(b=B, s=S):
    return lambda step: make_lm_batch(TASK, b, s, step, None, seed=0)


def mb_batches(k, n=N, b=B, s=S):
    """Same data as coord_batches, reshaped to the (n, k, B/k, ...) layout."""
    base = coord_batches(n, b, s)

    def fn(step):
        return jax.tree.map(
            lambda x: x.reshape((n, k, b // k) + x.shape[2:]), base(step))
    return fn


# ----------------------------------------------------------------------------
# strategy parity: alpha=0 reduces every mechanism to independent training
# ----------------------------------------------------------------------------

class TestStrategyParity:
    """At alpha=0 the codist loss is mean_i task_i, so model i's gradient is
    (1/n) * d(task_i): with SGD-momentum, zero weight decay and the codist LR
    scaled by n, every codist strategy must reproduce the all-reduce
    trajectory of each model EXACTLY (AdamW would only match approximately —
    its normalizer absorbs the 1/n)."""

    STEPS = 6

    def _tc(self, lr_scale=1.0):
        return TrainConfig(lr=0.05 * lr_scale, lr_schedule="cosine",
                           warmup_steps=2, total_steps=self.STEPS,
                           weight_decay=0.0, optimizer="sgdm", seed=0)

    @pytest.fixture(scope="class")
    def reference(self):
        """Per-model all-reduce task-loss trajectories from a shared init."""
        model = build_model(tiny_cfg())
        opt_init, _ = make_optimizer("sgdm")
        stacked = init_codist_state(model, jax.random.key(0), N, opt_init)
        runs = []
        for i in range(N):
            st = TrainState(model_slice(stacked.params, i),
                            opt_init(model_slice(stacked.params, i)),
                            jnp.zeros((), jnp.int32))
            _, hist = train(model, self._tc(), single_batches(), AllReduce(),
                            state=st, log_every=1)
            runs.append(hist.series("task_loss"))
        return model, stacked, np.asarray(runs)  # (n, steps)

    def _run_codist(self, model, stacked, strategy_cls, **cfg_kw):
        codist = CodistConfig(n_models=N, alpha0=0.0, **cfg_kw)
        _, hist = train_codist(model, codist, self._tc(lr_scale=N),
                               coord_batches(),
                               state=jax.tree.map(jnp.copy, stacked),
                               log_every=1,
                               strategy=strategy_cls(codist))
        return hist

    def test_prediction_matches_allreduce(self, reference):
        model, stacked, ref = reference
        hist = self._run_codist(model, stacked, PredictionExchange)
        for i in range(N):
            got = hist.series(f"task_loss_per_model_{i}")
            np.testing.assert_allclose(got, ref[i], rtol=1e-4, atol=1e-5)

    def test_checkpoint_matches_allreduce(self, reference):
        model, stacked, ref = reference
        # stale is absent on the supplied state: ensure_state must repair it
        hist = self._run_codist(model, stacked, CheckpointExchange,
                                mode="checkpoints", period=2)
        for i in range(N):
            got = hist.series(f"task_loss_per_model_{i}")
            np.testing.assert_allclose(got, ref[i], rtol=1e-4, atol=1e-5)

    def test_pipelined_matches_allreduce(self, reference):
        model, stacked, ref = reference
        hist = self._run_codist(model, stacked, PipelinedPredictions,
                                pipelined=True)
        got = hist.series("task_loss")
        np.testing.assert_allclose(got, ref.mean(axis=0), rtol=1e-4,
                                   atol=1e-5)


# ----------------------------------------------------------------------------
# microbatch gradient accumulation: parity between microbatch=1 and =4
# (pins the fix: checkpoint/pipelined used to silently skip accumulation)
# ----------------------------------------------------------------------------

class TestMicrobatchParity:
    K = 4
    STEPS = 2  # two steps so the pipelined peer buffer is exercised

    @pytest.fixture(scope="class")
    def model(self):
        return build_model(tiny_cfg())

    def _tc(self, k):
        return TrainConfig(lr=1e-2, total_steps=self.STEPS, warmup_steps=0,
                           optimizer="sgdm", microbatch=k, seed=0)

    def _final_params(self, model, strategy_cls, cfg_kw, k):
        codist = CodistConfig(n_models=N, alpha0=1.0, **cfg_kw)
        batches = mb_batches(self.K) if k > 1 else coord_batches()
        strategy = strategy_cls(codist)
        tc = self._tc(k)
        opt_init, _ = make_optimizer("sgdm")
        state = strategy.init_state(model, tc, jax.random.key(0), opt_init,
                                    batches(0))
        bundle = build_train_step(model, tc, codist, strategy)
        for s in range(self.STEPS):
            state, _, _ = bundle.apply(state, batches(s), s)
        return state.params

    @pytest.mark.parametrize("strategy_cls,cfg_kw", [
        (PredictionExchange, {}),
        (CheckpointExchange, {"mode": "checkpoints"}),
        (PipelinedPredictions, {"pipelined": True}),
    ], ids=["prediction", "checkpoint", "pipelined"])
    def test_grad_parity(self, model, strategy_cls, cfg_kw):
        p1 = self._final_params(model, strategy_cls, cfg_kw, k=0)
        p4 = self._final_params(model, strategy_cls, cfg_kw, k=self.K)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    def test_allreduce_grad_parity(self, model):
        tc1, tc4 = self._tc(0), self._tc(self.K)
        opt_init, _ = make_optimizer("sgdm")
        b1 = single_batches()(0)
        b4 = jax.tree.map(
            lambda x: x.reshape((self.K, B // self.K) + x.shape[1:]), b1)
        s0 = AllReduce().init_state(model, tc1, jax.random.key(0), opt_init)
        st1, _ = build_train_step(model, tc1, None,
                                  AllReduce()).variants["on"](s0, b1)
        st4, _ = build_train_step(model, tc4, None,
                                  AllReduce()).variants["on"](s0, b4)
        for a, b in zip(jax.tree.leaves(st1.params),
                        jax.tree.leaves(st4.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


# ----------------------------------------------------------------------------
# trainable mask: frozen params stay frozen under EVERY strategy
# (pins the fix: the pipelined step used to drop the mask)
# ----------------------------------------------------------------------------

class TestTrainableMask:
    @pytest.mark.parametrize("cfg_kw", [
        {}, {"mode": "checkpoints"}, {"pipelined": True},
    ], ids=["prediction", "checkpoint", "pipelined"])
    def test_frozen_params_unchanged(self, cfg_kw):
        model = build_model(tiny_cfg())
        codist = CodistConfig(n_models=N, alpha0=1.0, **cfg_kw)
        tc = TrainConfig(lr=1e-2, total_steps=1, warmup_steps=0,
                         optimizer="sgdm", seed=0)
        strategy = resolve_strategy(codist)
        opt_init, _ = make_optimizer("sgdm")
        batch = coord_batches()(0)
        state = strategy.init_state(model, tc, jax.random.key(0), opt_init,
                                    batch)
        frozen = jax.tree.map(lambda p: jnp.zeros((), jnp.int32),
                              state.params)
        bundle = build_train_step(model, tc, codist, strategy,
                                  trainable=frozen)
        before = jax.tree.map(np.asarray, state.params)  # state is donated
        new_state, _, _ = bundle.apply(state, batch, 0)
        for a, b in zip(jax.tree.leaves(before),
                        jax.tree.leaves(new_state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------------------
# plan schedules + comm accounting
# ----------------------------------------------------------------------------

class TestPlansAndComm:
    def test_prediction_plan_period(self):
        s = PredictionExchange(CodistConfig(n_models=2, period=5))
        assert [s.plan(k).distill for k in range(10)] == \
            [True, False, False, False, False] * 2
        assert [s.variant_for(s.plan(k)) for k in range(3)] == \
            ["on", "off", "off"]

    def test_checkpoint_plan_distills_every_step(self):
        s = CheckpointExchange(CodistConfig(n_models=2, mode="checkpoints",
                                            period=5))
        plans = [s.plan(k) for k in range(10)]
        assert all(p.distill for p in plans)
        assert sum(p.exchange for p in plans) == 2

    def test_allreduce_plan_exchanges_every_step(self):
        s = AllReduce()
        assert all(s.plan(k).exchange for k in range(5))

    def test_comm_bytes_ordering(self):
        """Section-3 accounting through strategy.comm_bytes: small-vocab
        prediction exchange is cheaper per event than a parameter exchange,
        which is cheaper than the 2x-model all-reduce."""
        model = build_model(tiny_cfg())
        opt_init, _ = make_optimizer("sgdm")
        codist = CodistConfig(n_models=N)
        batch = coord_batches(b=2, s=8)(0)
        state = init_codist_state(model, jax.random.key(0), N, opt_init)
        pred = PredictionExchange(codist).comm_bytes(model, state, batch)
        ckpt = CheckpointExchange(
            replace(codist, mode="checkpoints")).comm_bytes(
                model, state, batch)
        ar_state = AllReduce().init_state(model, None, jax.random.key(0),
                                          opt_init)
        ar = AllReduce().comm_bytes(model, ar_state, batch)
        assert 0 < pred < ckpt < ar
        # prediction bits: (n-1) * B * S * padded_vocab * 32 / 8
        want = (N - 1) * 2 * 8 * model.cfg.padded_vocab * 32 / 8
        assert pred == pytest.approx(want)

    def test_resolve_strategy_dispatch(self):
        assert isinstance(resolve_strategy(None), AllReduce)
        assert isinstance(resolve_strategy(CodistConfig(n_models=2)),
                          PredictionExchange)
        assert isinstance(
            resolve_strategy(CodistConfig(n_models=2, mode="checkpoints")),
            CheckpointExchange)
        assert isinstance(
            resolve_strategy(CodistConfig(n_models=2, pipelined=True)),
            PipelinedPredictions)


# ----------------------------------------------------------------------------
# shard_map strategy: needs a multi-device "pod" axis -> subprocess
# ----------------------------------------------------------------------------

def run_sub(code: str, devices: int = 2) -> dict:
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=520)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_shardmap_matches_prediction_exchange():
    """Satellite parity claim: at period=1 and compression='none' the
    explicit shard_map exchange and the pjit prediction exchange produce
    identical losses (same math, pinned schedule)."""
    code = """
import json
import jax, jax.numpy as jnp
from dataclasses import replace
from repro.configs import CodistConfig, TrainConfig, get_reduced
from repro.models import build_model
from repro.data import MarkovLM, make_lm_batch
from repro.train import (ShardMapCompressed, stack_batches, train_codist)

cfg = replace(get_reduced('qwen1.5-0.5b'), num_layers=1, d_model=32,
              d_ff=64, vocab_size=64, num_heads=2, num_kv_heads=2,
              head_dim=16)
model = build_model(cfg)
task = MarkovLM(vocab=64, seed=0)
tc = TrainConfig(lr=1e-2, total_steps=4, warmup_steps=0, optimizer='sgdm',
                 seed=0)
codist = CodistConfig(n_models=2, period=1, alpha0=1.0, distill_loss='mse',
                      compression='none')
def batches(step):
    return stack_batches([make_lm_batch(task, 4, 16, step, None, seed=0)
                          for _ in range(2)])
_, h_pred = train_codist(model, codist, tc, batches, log_every=1)
from repro.launch.mesh import auto_mesh
mesh = auto_mesh((2,), ('pod',))
_, h_sm = train_codist(model, codist, tc, batches, log_every=1,
                       strategy=ShardMapCompressed(codist, mesh))
print('RESULT ' + json.dumps({
    'pred': h_pred.series('loss'), 'sm': h_sm.series('loss'),
    'pred_dist': h_pred.series('distill_loss'),
    'sm_dist': h_sm.series('distill_loss')}))
"""
    r = run_sub(code)
    np.testing.assert_allclose(r["sm"], r["pred"], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(r["sm_dist"], r["pred_dist"], rtol=1e-4,
                               atol=1e-5)
    assert max(r["pred_dist"]) > 0  # the distillation term is actually live


def test_cli_codist_shardmap_smoke():
    """--mode codist-shardmap trains end-to-end from the CLI (the launcher
    forces the pod-axis host devices itself)."""
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--mode",
         "codist-shardmap", "--steps", "3", "--batch", "2", "--seq", "16",
         "--log-every", "1", "--eval-every", "0"],
        capture_output=True, text=True, env=env, timeout=520)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    assert "done: 3 steps" in out.stdout
