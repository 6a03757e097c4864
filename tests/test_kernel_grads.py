"""Gradient parity of the custom-VJP fused losses vs the jnp references,
plus the structural guarantee the tentpole is about: with ``fused_losses``
enabled, no (T, V)-shaped fp32 temporary exists in the loss computation in
either direction (verified by jaxpr inspection), and every exchange
strategy's step runs end-to-end on the fused path.

All kernels run in interpret=True mode (CPU container); tolerance <=1e-4.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import codistillation as cd
from repro.kernels import ops
from repro.kernels import ref


TOL = dict(rtol=1e-4, atol=1e-4)


def _data(t=48, v=200, scale=3.0, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    logits = jax.random.normal(ks[0], (2, t // 2, v)) * scale
    target = jax.random.normal(ks[1], (2, t // 2, v)) * scale
    labels = jax.random.randint(ks[2], (2, t // 2), 0, v)
    mask = (jax.random.uniform(ks[3], (2, t // 2)) > 0.3).astype(jnp.float32)
    return logits, target, labels, mask


class TestFusedCEGrads:
    @pytest.mark.parametrize("ls", [0.0, 0.1])
    @pytest.mark.parametrize("masked", [False, True])
    def test_grad_matches_jnp_reference(self, ls, masked):
        logits, _, labels, mask = _data()
        m = mask if masked else None
        ref_fn = lambda x: cd.cross_entropy(x, labels, ls, m, fused=False)
        fused_fn = lambda x: ops.fused_cross_entropy_loss(x, labels, ls, m,
                                                          interpret=True)
        np.testing.assert_allclose(fused_fn(logits), ref_fn(logits), **TOL)
        np.testing.assert_allclose(jax.grad(fused_fn)(logits),
                                   jax.grad(ref_fn)(logits), **TOL)

    def test_grad_wrt_label_smoothing_schedule(self):
        """ls is a traced scalar (schedule output) — must stay differentiable
        through the custom-VJP boundary."""
        logits, _, labels, mask = _data()
        ref_fn = lambda s: cd.cross_entropy(logits, labels, s, mask,
                                            fused=False)
        fused_fn = lambda s: ops.fused_cross_entropy_loss(
            logits, labels, s, mask, interpret=True)
        np.testing.assert_allclose(jax.grad(fused_fn)(0.1),
                                   jax.grad(ref_fn)(0.1), **TOL)

    def test_bf16_logits(self):
        logits, _, labels, _ = _data(scale=2.0)
        lb = logits.astype(jnp.bfloat16)
        got = jax.grad(lambda x: ops.fused_cross_entropy_loss(
            x, labels, 0.1, interpret=True))(lb)
        want = jax.grad(lambda x: cd.cross_entropy(x, labels, 0.1,
                                                   fused=False))(lb)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=1e-2, atol=1e-2)


class TestFusedDistillGrads:
    @pytest.mark.parametrize("mode", ["mse", "kl"])
    @pytest.mark.parametrize("masked", [False, True])
    def test_grads_match_jnp_reference(self, mode, masked):
        logits, target, _, mask = _data()
        m = mask if masked else None
        ref_f = cd.distill_mse if mode == "mse" else cd.distill_kl
        ref_fn = lambda a, b: ref_f(a, b, m, fused=False)
        fused_fn = lambda a, b: ops.fused_distill_mean(a, b, mode, m,
                                                       interpret=True)
        np.testing.assert_allclose(fused_fn(logits, target),
                                   ref_fn(logits, target), **TOL)
        for argnum in (0, 1):  # student AND (stop-gradient-free) target side
            np.testing.assert_allclose(
                jax.grad(fused_fn, argnum)(logits, target),
                jax.grad(ref_fn, argnum)(logits, target), **TOL)

    @pytest.mark.parametrize("mode", ["mse", "kl"])
    def test_per_token_kernel_grad_vs_ref_oracle(self, mode):
        """Bare kernel-level parity against kernels/ref.py oracles."""
        t, v = 32, 128
        a = jax.random.normal(jax.random.key(0), (t, v)) * 2
        b = jax.random.normal(jax.random.key(1), (t, v)) * 2
        oracle = ref.distill_mse_ref if mode == "mse" else ref.distill_kl_ref
        fused_fn = lambda x, y: jnp.sum(ops.fused_distill_mean(
            x, y, mode, interpret=True)) * t  # sum of per-token losses
        ref_fn = lambda x, y: jnp.sum(oracle(x, y))
        np.testing.assert_allclose(jax.grad(fused_fn)(a, b),
                                   jax.grad(ref_fn)(a, b), **TOL)


class TestCombinedKernelGrads:
    @pytest.mark.parametrize("mode", ["mse", "kl"])
    def test_combined_matches_separate(self, mode):
        logits, target, labels, mask = _data()
        ref_f = cd.distill_mse if mode == "mse" else cd.distill_kl

        def fused_total(a, b):
            task, dist = ops.fused_ce_distill(a, b, labels, mode, 0.1, mask,
                                              interpret=True)
            return task + 0.7 * dist

        def ref_total(a, b):
            return (cd.cross_entropy(a, labels, 0.1, mask, fused=False)
                    + 0.7 * ref_f(a, b, mask, fused=False))

        np.testing.assert_allclose(fused_total(logits, target),
                                   ref_total(logits, target), **TOL)
        for argnum in (0, 1):
            np.testing.assert_allclose(
                jax.grad(fused_total, argnum)(logits, target),
                jax.grad(ref_total, argnum)(logits, target), **TOL)


# ----------------------------------------------------------------------------
# structural guarantee: no (T, V) fp32 temporaries outside the kernels
# ----------------------------------------------------------------------------

def _iter_eqns(jaxpr):
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for val in jax.tree.leaves(eqn.params, is_leaf=lambda x: isinstance(
                x, (Jaxpr, ClosedJaxpr))):
            if isinstance(val, ClosedJaxpr):
                yield from _iter_eqns(val.jaxpr)
            elif isinstance(val, Jaxpr):
                yield from _iter_eqns(val)


# data movement of the logits themselves or call boundaries returning the
# (T, V) gradient — not math temporaries (inner jaxprs are recursed anyway)
_ALLOWED_TV_PRODUCERS = {"pallas_call", "reshape", "squeeze", "slice",
                         "transpose", "copy", "convert_element_type",
                         "jit", "custom_vjp_call", "custom_vjp_call_jaxpr",
                         "custom_jvp_call"}


def _tv_offenders(fn, *args, shape):
    from jax.interpreters import partial_eval as pe
    closed = jax.make_jaxpr(fn)(*args)
    # drop dead code first (e.g. instantiated-then-unused zero cotangents
    # that XLA would DCE anyway)
    jaxpr, _ = pe.dce_jaxpr(closed.jaxpr,
                            [True] * len(closed.jaxpr.outvars))
    offenders = set()
    for eqn in _iter_eqns(jaxpr):
        for var in eqn.outvars:
            aval = var.aval
            if (getattr(aval, "shape", None) == shape
                    and aval.dtype == jnp.float32
                    and eqn.primitive.name not in _ALLOWED_TV_PRODUCERS):
                offenders.add(eqn.primitive.name)
    return offenders


class TestNoVocabWidthTemporaries:
    # block-aligned (no wrapper padding) AND strictly larger than one
    # (256, 512) block, so interpret-mode kernel internals (which trace as
    # ordinary tile-shaped eqns) can never collide with the full (T, V) shape
    T, V = 512, 1024

    def _args(self):
        logits = jax.random.normal(jax.random.key(0), (self.T, self.V))
        target = jax.random.normal(jax.random.key(1), (self.T, self.V))
        labels = jax.random.randint(jax.random.key(2), (self.T,), 0, self.V)
        return logits, target, labels

    def test_fused_ce_value_and_grad_is_clean(self):
        logits, _, labels = self._args()
        fn = jax.value_and_grad(
            lambda x: ops.fused_cross_entropy_loss(x, labels, 0.1,
                                                   interpret=True))
        assert _tv_offenders(fn, logits, shape=(self.T, self.V)) == set()

    @pytest.mark.parametrize("mode", ["mse", "kl"])
    def test_fused_distill_value_and_grad_is_clean(self, mode):
        logits, target, _ = self._args()
        fn = jax.value_and_grad(
            lambda a: ops.fused_distill_mean(a, target, mode,
                                             interpret=True))
        assert _tv_offenders(fn, logits, shape=(self.T, self.V)) == set()

    @pytest.mark.parametrize("mode", ["mse", "kl"])
    def test_combined_value_and_grad_is_clean(self, mode):
        logits, target, labels = self._args()
        fn = jax.value_and_grad(lambda a: sum(ops.fused_ce_distill(
            a, target, labels, mode, 0.1, interpret=True)))
        assert _tv_offenders(fn, logits, shape=(self.T, self.V)) == set()

    def test_jnp_path_is_dirty(self):
        """Sanity: the check has teeth — the jnp path DOES materialize."""
        logits, _, labels = self._args()
        fn = jax.value_and_grad(
            lambda x: cd.cross_entropy(x, labels, 0.1, fused=False))
        assert _tv_offenders(fn, logits, shape=(self.T, self.V)) != set()


# ----------------------------------------------------------------------------
# every step variant runs end-to-end with fused_losses enabled
# ----------------------------------------------------------------------------

class TestStepVariantsFused:
    @pytest.fixture(scope="class")
    def setup(self):
        from dataclasses import replace
        from repro.configs import get_reduced
        from repro.data import MarkovLM, make_lm_batch
        from repro.models import build_model
        from repro.optim import make_optimizer
        from repro.train import init_codist_state, init_train_state, \
            stack_batches
        cfg = replace(get_reduced("qwen1.5-0.5b"), num_layers=1, d_model=32,
                      d_ff=64, vocab_size=64, num_heads=2, num_kv_heads=2,
                      head_dim=16)
        model = build_model(cfg)
        task = MarkovLM(vocab=64, seed=0)
        opt_init, _ = make_optimizer("sgdm")
        state = init_codist_state(model, jax.random.key(0), 2, opt_init,
                                  with_stale=True)
        single = init_train_state(model, jax.random.key(0), opt_init)
        batch1 = make_lm_batch(task, 2, 16, 0, None, seed=0)
        batch = stack_batches([batch1, batch1])
        return model, state, single, batch1, batch

    def _tc(self, fused):
        from repro.configs import TrainConfig
        return TrainConfig(lr=1e-2, total_steps=10, warmup_steps=0,
                           optimizer="sgdm", label_smoothing=0.1,
                           fused_losses=fused)

    @pytest.mark.parametrize("distill_loss", ["mse", "kl"])
    def test_prediction_step(self, setup, distill_loss):
        from repro.configs import CodistConfig
        from repro.train.engine import PredictionExchange, build_train_step
        model, state, _, _, batch = setup
        codist = CodistConfig(n_models=2, distill_loss=distill_loss)
        for distill in (True, False):
            v = "on" if distill else "off"
            s_f, m_f = build_train_step(
                model, self._tc(True), codist,
                PredictionExchange(codist)).variants[v](state, batch)
            s_r, m_r = build_train_step(
                model, self._tc(False), codist,
                PredictionExchange(codist)).variants[v](state, batch)
            assert np.isfinite(float(m_f["loss"]))
            np.testing.assert_allclose(float(m_f["loss"]),
                                       float(m_r["loss"]), rtol=1e-4,
                                       atol=1e-4)

    def test_checkpoint_step(self, setup):
        from repro.configs import CodistConfig
        from repro.train.engine import CheckpointExchange, build_train_step
        model, state, _, _, batch = setup
        codist = CodistConfig(n_models=2, mode="checkpoints")
        _, m_f = build_train_step(
            model, self._tc(True), codist,
            CheckpointExchange(codist)).variants["on"](state, batch)
        _, m_r = build_train_step(
            model, self._tc(False), codist,
            CheckpointExchange(codist)).variants["on"](state, batch)
        np.testing.assert_allclose(float(m_f["loss"]), float(m_r["loss"]),
                                   rtol=1e-4, atol=1e-4)

    def test_pipelined_step(self, setup):
        from repro.configs import CodistConfig
        from repro.train.engine import PipelinedPredictions, build_train_step
        from repro.train.state import init_peer_state
        model, state, _, _, batch = setup
        codist = CodistConfig(n_models=2, pipelined=True)
        logits, _ = model.forward(
            jax.tree.map(lambda x: x[0], state.params),
            jax.tree.map(lambda x: x[0], batch))
        peer = init_peer_state(batch, (2,) + logits.shape)
        st = state._replace(peer=peer)
        _, m_f = build_train_step(
            model, self._tc(True), codist,
            PipelinedPredictions(codist)).variants["on"](st, batch)
        _, m_r = build_train_step(
            model, self._tc(False), codist,
            PipelinedPredictions(codist)).variants["on"](st, batch)
        np.testing.assert_allclose(float(m_f["loss"]), float(m_r["loss"]),
                                   rtol=1e-4, atol=1e-4)

    def test_allreduce_step(self, setup):
        from repro.train.engine import AllReduce, build_train_step
        model, _, single, batch1, _ = setup
        _, m_f = build_train_step(
            model, self._tc(True), None,
            AllReduce()).variants["on"](single, batch1)
        _, m_r = build_train_step(
            model, self._tc(False), None,
            AllReduce()).variants["on"](single, batch1)
        np.testing.assert_allclose(float(m_f["loss"]), float(m_r["loss"]),
                                   rtol=1e-4, atol=1e-4)
