"""Codistillation (Algorithm 1) as a composable JAX module.

The ``n`` codistilling models are represented as a **stacked pytree** — every
parameter gains a leading axis of size ``n``. Under pjit that axis is sharded
over the ``"pod"`` mesh axis, so each pod physically holds and trains one
replica; referencing another model's logits inside the loss becomes a pod-axis
all-gather of logits, which is exactly the paper's "communicate predictions"
implementation (Section 3).

The total loss for one step is

    L(theta_1..n) = (1/n) sum_i [ task(f_i(x_i), y_i)
                    + alpha/(n-1) sum_{j!=i} D(f_i(x_i), sg(f_j(x_i))) ]

With coordinated sampling (prediction mode) x_i == x_j, so a single vmap'd
forward produces every f_j(x_i) needed; ``stop_gradient`` on the target side
makes one backward pass compute exactly the Algorithm-1 update for all models
simultaneously.

Loss math dispatches through the ``fused_losses`` flag (see ``_fused_enabled``
and docs/fused_losses.md): when enabled, the streaming custom-VJP Pallas
kernels in ``repro.kernels`` replace the jnp paths below, eliminating every
(T, V) fp32 temporary from the forward and backward of the hot path.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import CodistConfig

PyTree = Any


# ----------------------------------------------------------------------------
# fused-loss dispatch
# ----------------------------------------------------------------------------
# Every loss below takes ``fused``: None => auto (on for TPU), bool => forced.
# When enabled, the streaming custom-VJP Pallas kernels in repro.kernels.ops
# replace the jnp math — same values and gradients (parity-tested to <=1e-4 in
# tests/test_kernel_grads.py) without materializing (T, V) fp32 temporaries
# (logsumexp / softmax / one-hot at vocab width) in forward OR backward.

def _fused_enabled(fused: Optional[bool]) -> bool:
    if fused is None:
        # auto: pallas_call carries no SPMD partitioning rule, so when a
        # tensor-parallel axis is active (vocab-sharded lm head) the kernels
        # would force a full logits gather — exactly what the one-hot jnp CE
        # below avoids. Auto keeps the jnp path there; fused=True overrides.
        from repro.models.sharding_hints import tensor_parallel_active
        if tensor_parallel_active():
            return False
        from repro.kernels.ops import fused_losses_default
        return fused_losses_default()
    return bool(fused)


# ----------------------------------------------------------------------------
# task losses
# ----------------------------------------------------------------------------

def cross_entropy(logits: jax.Array, labels: jax.Array,
                  label_smoothing: jax.Array | float = 0.0,
                  mask: Optional[jax.Array] = None,
                  fused: Optional[bool] = None) -> jax.Array:
    """Mean token-level CE with optional label smoothing and validity mask.

    logits: (..., V) float; labels: (...) int32; mask: (...) broadcastable.
    """
    if _fused_enabled(fused):
        from repro.kernels.ops import fused_cross_entropy_loss
        return fused_cross_entropy_loss(logits, labels, label_smoothing, mask)
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    # one-hot contraction instead of take_along_axis: SPMD-friendly when the
    # vocab axis is sharded (partial sums per shard + a scalar-sized psum,
    # instead of an all-gather of the full logits tensor).
    onehot = jax.nn.one_hot(labels, v, dtype=logits.dtype)
    true_logit = jnp.sum(logits * onehot, axis=-1)
    nll = logz - true_logit
    ls = jnp.asarray(label_smoothing, jnp.float32)
    # smoothed loss: (1-ls)*nll + ls * mean_v (logz - logit_v)
    smooth = logz - jnp.mean(logits, axis=-1)
    loss = (1.0 - ls) * nll + ls * smooth
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(loss)


def accuracy(logits: jax.Array, labels: jax.Array,
             mask: Optional[jax.Array] = None) -> jax.Array:
    pred = jnp.argmax(logits, axis=-1)
    correct = (pred == labels).astype(jnp.float32)
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(correct * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(correct)


# ----------------------------------------------------------------------------
# distillation losses D(y, y')   (paper: MSE between UNCENTERED logits, A.3)
# ----------------------------------------------------------------------------

def distill_mse(logits: jax.Array, target_logits: jax.Array,
                mask: Optional[jax.Array] = None,
                fused: Optional[bool] = None) -> jax.Array:
    """Mean squared error between logits — the paper's D."""
    if _fused_enabled(fused):
        from repro.kernels.ops import fused_distill_mean
        return fused_distill_mean(logits, target_logits, "mse", mask)
    d = (logits.astype(jnp.float32) - target_logits.astype(jnp.float32)) ** 2
    per_tok = jnp.mean(d, axis=-1)
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(per_tok * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(per_tok)


def distill_kl(logits: jax.Array, target_logits: jax.Array,
               mask: Optional[jax.Array] = None,
               temperature: float = 1.0,
               fused: Optional[bool] = None) -> jax.Array:
    """KL(softmax(target) || softmax(logits)) — Zhang et al. / Anil et al.'s D."""
    if temperature == 1.0 and _fused_enabled(fused):
        from repro.kernels.ops import fused_distill_mean
        return fused_distill_mean(logits, target_logits, "kl", mask)
    lt = target_logits.astype(jnp.float32) / temperature
    ls = logits.astype(jnp.float32) / temperature
    p = jax.nn.softmax(lt, axis=-1)
    per_tok = jnp.sum(p * (jax.nn.log_softmax(lt, axis=-1)
                           - jax.nn.log_softmax(ls, axis=-1)), axis=-1)
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(per_tok * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(per_tok)


def distill_ce(logits: jax.Array, target_logits: jax.Array,
               mask: Optional[jax.Array] = None) -> jax.Array:
    """Soft cross-entropy against the peer's softmax."""
    p = jax.nn.softmax(target_logits.astype(jnp.float32), axis=-1)
    per_tok = -jnp.sum(p * jax.nn.log_softmax(logits.astype(jnp.float32), -1), -1)
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(per_tok * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(per_tok)


_DISTILL = {"mse": distill_mse, "kl": distill_kl, "ce": distill_ce}


def distill_pair(kind: str, logits: jax.Array, target_logits: jax.Array,
                 mask: Optional[jax.Array] = None,
                 fused: Optional[bool] = None) -> jax.Array:
    if kind in ("mse", "kl"):
        return _DISTILL[kind](logits, target_logits, mask, fused=fused)
    return _DISTILL[kind](logits, target_logits, mask)  # 'ce': jnp only


# ----------------------------------------------------------------------------
# beyond-paper: compressed prediction exchange
# ----------------------------------------------------------------------------

def _hierarchical_topk(x: jax.Array, k: int, segments: int = 16):
    """Exact top-k via per-segment top-k + top-k of the candidate union.

    Equivalent to ``jax.lax.top_k`` (every global top-k element is in its
    segment's top-k) but SPMD-friendly: with the vocab sharded over the tensor
    axis, stage 1 sorts only the unsharded within-segment dim — XLA's global
    top-k would otherwise gather the full fp32 logits tensor (the dominant
    cross-pod collective in the naive compressed exchange).
    """
    from repro.models.sharding_hints import hint
    *lead, v = x.shape
    if v % segments or v // segments < k:
        return jax.lax.top_k(x, k)
    seg = v // segments
    xs = hint(x.reshape(*lead, segments, seg), "wire")
    lv, li = jax.lax.top_k(xs, k)                       # (..., segments, k)
    lv, li = hint(lv, "wire"), hint(li, "wire")
    li = li + (jnp.arange(segments) * seg)[:, None]
    lv = lv.reshape(*lead, segments * k)
    li = li.reshape(*lead, segments * k)
    gv, gi = jax.lax.top_k(hint(lv, "wire"), k)         # (..., k)
    idx = jnp.take_along_axis(li, gi, axis=-1)
    return hint(gv, "wire"), hint(idx, "wire")


def compress_targets(cfg: CodistConfig, target_logits: jax.Array) -> Dict:
    """Compress the peer logits before they cross the pod boundary.

    Returns an array-only 'wire' pytree (vmappable over the stacked model
    axis — this is what makes compression happen on the PRODUCER pod, so the
    cross-pod collective moves the compressed representation, not the raw
    (B, S, V) logits). ``distill_vs_compressed`` consumes it; all static
    metadata (kind, stride) is recomputed from cfg + shapes.
    """
    if cfg.compression == "bf16":
        return {"vals": target_logits.astype(jnp.bfloat16)}
    if cfg.compression == "topk":
        vals, idx = _hierarchical_topk(target_logits, cfg.topk)
        return {"vals": vals, "idx": idx}
    if cfg.compression == "subsample" and cfg.subsample:
        # strided token subset along the sequence axis (axis=-2 of (B,S,V))
        s = target_logits.shape[-2]
        stride = max(1, s // cfg.subsample)
        sl = target_logits[..., ::stride, :][..., : cfg.subsample, :]
        return {"vals": sl}
    return {"vals": target_logits}


def _subsample_stride(cfg: CodistConfig, full_seq: int) -> int:
    return max(1, full_seq // cfg.subsample)


def _compress_stacked(cfg: CodistConfig, targets: jax.Array) -> Dict:
    """compress_targets over the stacked (n, ...) axis, pod-local when a
    pod-axis mesh is active (see codist_loss)."""
    from repro.models.sharding_hints import current_mesh
    mesh = current_mesh()
    if mesh is not None and "pod" in mesh.axis_names:
        from jax.sharding import PartitionSpec as P

        def comp(t):
            return compress_targets(cfg, t)

        out_specs = jax.tree.map(lambda _: P("pod"),
                                 jax.eval_shape(comp, targets))
        return jax.shard_map(comp, mesh=mesh, in_specs=P("pod"),
                             out_specs=out_specs, axis_names={"pod"},
                             check_vma=False)(targets)
    return compress_targets(cfg, targets)


def _podlocal_codist_terms(cfg: CodistConfig, mesh,
                           logits_all: jax.Array, labels_all: jax.Array,
                           alpha, label_smoothing,
                           mask_all: Optional[jax.Array]):
    """(task, distill) per model with a PINNED exchange schedule.

    Everything is computed inside a shard_map manual over "pod": each pod
    evaluates its own model's task CE and compresses its logits locally; the
    ONLY cross-pod communication is ``jax.lax.all_gather`` of the compressed
    wire. Consuming ``logits_all[i]`` at the pjit top level instead lets the
    partitioner mask+all-reduce full logits-shaped tensors across pods (the
    dominant cross-pod collective in the naive lowering).
    """
    from jax.sharding import PartitionSpec as P
    n = logits_all.shape[0]
    if mask_all is None:
        mask_all = jnp.ones(labels_all.shape, jnp.float32)

    def per_pod(lg1, lb1, m1, ls):
        lg, lb, m = lg1[0], lb1[0], m1[0]
        task = cross_entropy(lg, lb, ls, m)
        wire = compress_targets(cfg, jax.lax.stop_gradient(lg))
        wires_all = jax.tree.map(lambda x: jax.lax.all_gather(x, "pod"), wire)
        idx = jax.lax.axis_index("pod")
        dist = jnp.zeros((), jnp.float32)
        for j in range(n):
            wire_j = jax.tree.map(lambda x: x[j], wires_all)
            d = distill_vs_compressed(cfg, lg, wire_j, m)
            dist = dist + jnp.where(idx == j, 0.0, d)
        dist = dist / max(1, n - 1)
        return jnp.stack([task, dist])[None]        # (1, 2) pod-sharded

    rows = jax.shard_map(
        per_pod, mesh=mesh,
        in_specs=(P("pod"), P("pod"), P("pod"), P()),
        out_specs=P("pod", None),
        axis_names={"pod"}, check_vma=False,
    )(logits_all, labels_all, mask_all,
      jnp.asarray(label_smoothing, jnp.float32))
    return rows[:, 0], rows[:, 1]


def distill_vs_compressed(cfg: CodistConfig, logits: jax.Array, wire: Dict,
                          mask: Optional[jax.Array] = None,
                          fused: Optional[bool] = None) -> jax.Array:
    kind = cfg.compression if cfg.compression != "none" else "none"
    if cfg.compression == "subsample" and not cfg.subsample:
        kind = "none"
    if kind in ("none", "bf16"):
        # full-vocab-width targets: the streaming kernels apply
        return distill_pair(cfg.distill_loss, logits, wire["vals"], mask,
                            fused=fused)
    if kind == "topk":
        own = jnp.take_along_axis(logits, wire["idx"], axis=-1)
        if cfg.distill_loss == "mse":
            d = (own.astype(jnp.float32) - wire["vals"].astype(jnp.float32)) ** 2
            per_tok = jnp.mean(d, axis=-1)
        else:  # renormalized soft-CE over the top-k support
            p = jax.nn.softmax(wire["vals"].astype(jnp.float32), -1)
            per_tok = -jnp.sum(p * jax.nn.log_softmax(own.astype(jnp.float32), -1), -1)
        if mask is not None:
            m = mask.astype(jnp.float32)
            return jnp.sum(per_tok * m) / jnp.maximum(jnp.sum(m), 1.0)
        return jnp.mean(per_tok)
    if kind == "subsample":
        stride = _subsample_stride(cfg, logits.shape[-2])
        k = wire["vals"].shape[-2]
        own = logits[..., ::stride, :][..., :k, :]
        sub_mask = None
        if mask is not None:
            sub_mask = mask[..., ::stride][..., :k]
        # subsampled tokens keep full vocab width: kernels still apply
        return distill_pair(cfg.distill_loss, own, wire["vals"], sub_mask,
                            fused=fused)
    raise ValueError(kind)


# ----------------------------------------------------------------------------
# Algorithm 1: the combined codistillation loss over stacked logits
# ----------------------------------------------------------------------------

def codist_loss(cfg: CodistConfig,
                logits_all: jax.Array,          # (n, ..., V)
                labels_all: jax.Array,          # (n, ...)
                alpha: jax.Array | float,
                label_smoothing: jax.Array | float = 0.0,
                mask_all: Optional[jax.Array] = None,
                peer_logits_all: Optional[jax.Array] = None,
                peer_pairwise: Optional[jax.Array] = None,
                fused: Optional[bool] = None,
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Mean over models of (task + alpha * mean_peers D(own, sg(peer))).

    ``peer_logits_all`` overrides the distillation targets (pipelined exchange
    provides stale logits); ``peer_pairwise`` has shape (n, n, ...) where
    [i, j] = model j's predictions on model i's batch (checkpoint mode, where
    every group evaluates the stale replicas on its OWN minibatch). Default is
    the live stacked logits (prediction mode with coordinated sampling).

    With ``fused`` enabled (auto on TPU) and a full-vocab-width first peer
    wire, each model's task CE and first distillation term come from the
    COMBINED Pallas kernel — one read of that model's (T, V) logits instead
    of two sweeps.
    """
    n = logits_all.shape[0]
    targets = peer_logits_all if peer_logits_all is not None else logits_all
    targets = jax.lax.stop_gradient(targets)
    if peer_pairwise is not None:
        peer_pairwise = jax.lax.stop_gradient(peer_pairwise)

    # pod-axis mesh active + live prediction exchange: pin the exchange
    # schedule with the pod-local shard_map path — the ONLY cross-pod
    # communication is the all_gather of the (compressed) wire. The naive
    # pjit lowering lets the partitioner mask+all-reduce full logits-shaped
    # tensors across pods instead.
    from repro.models.sharding_hints import current_mesh
    mesh = current_mesh()
    if (mesh is not None and "pod" in mesh.axis_names
            and cfg.compression == "topk"
            and peer_logits_all is None and peer_pairwise is None and n > 1):
        task, dist = _podlocal_codist_terms(cfg, mesh, logits_all, labels_all,
                                            alpha, label_smoothing, mask_all)
        alpha = jnp.asarray(alpha, jnp.float32)
        total = jnp.mean(task + alpha * dist)
        return total, {
            "loss": total, "task_loss": jnp.mean(task),
            "distill_loss": jnp.mean(dist),
            "task_loss_per_model": task, "distill_loss_per_model": dist,
            "alpha": alpha,
        }

    # compress on the PRODUCER side so only the compressed wire crosses the
    # pod links. XLA's sort partitioner REPLICATES top_k operands across every
    # mesh axis (it would move the raw logits cross-pod and compress after),
    # so when a pod-axis mesh is active the compression runs inside a narrow
    # shard_map manual over "pod" — correctness identical, schedule pinned.
    wires_all = _compress_stacked(cfg, targets)
    use_fused = _fused_enabled(fused)

    task_losses = []
    distill_losses = []
    for i in range(n):
        m_i = None if mask_all is None else mask_all[i]
        wires_i = []
        for j in range(n):
            if j == i:
                continue
            if peer_pairwise is not None:
                wires_i.append(compress_targets(cfg, peer_pairwise[i, j]))
            else:
                wires_i.append(jax.tree.map(lambda x: x[j], wires_all))
        # hot path: fuse the task CE with the first distillation term so the
        # student logits are swept once (combined kernel); extra peers reuse
        # the streaming pairwise kernel.
        combined = (use_fused and wires_i
                    and cfg.distill_loss in ("mse", "kl")
                    and set(wires_i[0]) == {"vals"}
                    and wires_i[0]["vals"].shape == logits_all[i].shape)
        if combined:
            from repro.kernels.ops import fused_ce_distill
            task_i, d0 = fused_ce_distill(
                logits_all[i], wires_i[0]["vals"], labels_all[i],
                mode=cfg.distill_loss, label_smoothing=label_smoothing,
                mask=m_i)
            wire_d = [d0] + [distill_vs_compressed(cfg, logits_all[i], w,
                                                   m_i, fused=use_fused)
                             for w in wires_i[1:]]
        else:
            task_i = cross_entropy(logits_all[i], labels_all[i],
                                   label_smoothing, m_i, fused=use_fused)
            wire_d = [distill_vs_compressed(cfg, logits_all[i], w, m_i,
                                            fused=use_fused)
                      for w in wires_i]
        task_losses.append(task_i)
        distill_losses.append(sum(wire_d) / (n - 1) if wire_d
                              else jnp.asarray(0.0, jnp.float32))

    task = jnp.stack(task_losses)
    dist = jnp.stack(distill_losses)
    alpha = jnp.asarray(alpha, jnp.float32)
    total = jnp.mean(task + alpha * dist)
    metrics = {
        "loss": total,
        "task_loss": jnp.mean(task),
        "distill_loss": jnp.mean(dist),
        "task_loss_per_model": task,
        "distill_loss_per_model": dist,
        "alpha": alpha,
    }
    return total, metrics


# ----------------------------------------------------------------------------
# stacked-pytree helpers
# ----------------------------------------------------------------------------

def init_stacked(init_fn: Callable[[jax.Array], PyTree], key: jax.Array,
                 n: int) -> PyTree:
    """n independent inits, stacked along a new leading axis."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def model_slice(stacked: PyTree, i: int) -> PyTree:
    return jax.tree.map(lambda x: x[i], stacked)


def stack_models(trees: list[PyTree]) -> PyTree:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def param_distance_from(params: PyTree, ref: PyTree) -> jax.Array:
    """||theta - theta_0||_2 — used for the Fig. 7 regularization-effect study."""
    sq = jax.tree.map(lambda a, b: jnp.sum((a.astype(jnp.float32)
                                            - b.astype(jnp.float32)) ** 2),
                      params, ref)
    return jnp.sqrt(sum(jax.tree.leaves(sq)))
