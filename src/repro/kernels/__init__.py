"""Pallas TPU kernels for the compute hot spots, validated in interpret mode.

  fused_ce        — streaming cross-entropy over vocab tiles, forward +
                    backward (softmax rebuilt from the saved logZ residual)
  distill_loss    — streaming codistillation D(y, y') (mse / kl), forward +
                    backward (five-accumulator KL residuals)
  combined_loss   — COMBINED CE + distill: one read of each logits tile per
                    model, both losses and both gradients
  causal_attention — fused causal GQA attention core, forward + one
                    backward kernel over sequence-minor operands, for
                    training and prefill on the TPU
  flash_attention — online-softmax GQA attention (causal / sliding window),
                    forward only
  paged_cache     — serving-fleet paged KV pool gather/scatter (scalar-
                    prefetched block tables; decode reads only live blocks)

Each has a pure-jnp oracle in ``ref.py`` (``causal_attention``'s is the
dense core in ``models/attention.py``, tests/test_causal_attention.py) and
a jit'd public wrapper in ``ops.py`` (auto interpret on CPU, Mosaic on
TPU). The differentiable
entry points — ``fused_cross_entropy_loss``, ``fused_distill_mean``,
``fused_ce_distill`` — wrap forward+backward in ``jax.custom_vjp`` and are
what ``core.codistillation`` dispatches to under the ``fused_losses`` flag;
gradient parity vs the jnp references is tested in tests/test_kernel_grads.py.
See docs/fused_losses.md for the paper-term-to-kernel mapping.
"""
from repro.kernels.ops import (  # noqa: F401
    attention,
    auto_interpret,
    cross_entropy_tokens,
    distill_loss_tokens,
    fused_ce_distill,
    fused_cross_entropy_loss,
    fused_causal_attention,
    fused_distill_mean,
    fused_losses_default,
)
from repro.kernels.paged_cache import (  # noqa: F401
    paged_gather,
    paged_gather_ref,
    paged_scatter,
    paged_scatter_ref,
)
