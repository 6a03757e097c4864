"""Bring-up smoke test on a TPU: the codistillation trainer and the serving
fleet at qwen1.5-0.5b's published widths, through the entry points the
launchers use.

    python chip_smoke.py             # one chip: train + serve phases
    python chip_smoke.py --chips 4   # only the four-chip codist phase

One chip (the default):

* train: 2-peer prediction exchange (``train_codist``) and the all-reduce
  baseline (``train_allreduce``), 24 layers at full width, SGD with
  momentum, remat, fused Pallas losses, batch 4 x 512 per peer, 4 steps,
  all losses finite. Each step-0 loss matches the same step with the jnp
  losses (``fused_losses=False``) within ``LOSS_RTOL``.
* serve: a 2-peer fleet (``FleetRouter``, as ``repro.launch.serve`` builds
  it) with a bf16 paged KV cache and the fused paged-attention kernel
  answers 8 greedy requests, each with its requested token count and no
  lost or duplicated token; one request's tokens equal those of the same
  fleet on the jnp attention path (``fused_attention=False``).

``--chips 4``: four peers, one per chip, through ``ShardMapCompressed`` over
a ``("pod",)`` mesh, with the state created sharded on the pod axis, for 3
steps at batch 2 x 512 per peer; each peer's task loss matches the same
steps of ``PredictionExchange`` under the pjit state shardings within
``LOSS_RTOL``. The pjit reference runs the jnp losses: XLA cannot partition
a Mosaic kernel outside ``shard_map``, and at 4 x 512 per peer it needs
16.6 GB of a chip's 15.75 GB (compile for a described v5e).

Compile time, warm step time and peak device memory are printed as info
lines. The script exits non-zero, printing no result, unless JAX finds a
TPU; it never falls back to the CPU or to interpret mode. Any failed check
raises. The last line of a passing run is the JSON result.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen1.5-0.5b"
SEED = 0
BATCH, SEQ = 4, 512          # per peer
FOUR_CHIP_BATCH = 2          # per peer, --chips 4
TRAIN_STEPS = 4
LOSS_RTOL = 2e-3             # fused vs jnp losses, and shard_map vs pjit
N_REQUESTS, MAX_PROMPT, MAX_NEW = 8, 40, 16


def info(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    info(f"ok: {what}")


def _gib(n: float) -> str:
    return f"{n / 2 ** 30:.3f} GiB"


def peak_bytes(device) -> int:
    return int(device.memory_stats().get("peak_bytes_in_use", 0))


# ----------------------------------------------------------------------------
# device check
# ----------------------------------------------------------------------------

def device_check(chips: int):
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import auto_interpret, fused_losses_default
    from repro.serve import resolve_cache_dtype
    from repro.serve.fleet import FleetConfig

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "nothing is run", file=sys.stderr)
        sys.exit(2)
    info(f"device: platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devices)}")
    check(len(devices) >= chips, f"{chips} chip(s) visible")
    check(not auto_interpret(), "Pallas kernels compile to Mosaic "
                                "(auto_interpret() is False)")
    check(fused_losses_default(), "fused losses resolve to on")
    check(FleetConfig().fused_attention is not False,
          "fused paged attention resolves to on")
    check(jnp.dtype(resolve_cache_dtype("auto")) == jnp.bfloat16,
          "the fleet's default KV cache is bf16")
    return devices


# ----------------------------------------------------------------------------
# training
# ----------------------------------------------------------------------------

def _lm_setup():
    from repro.configs import get_config
    from repro.data import MarkovLM
    from repro.models import build_model
    cfg = get_config(ARCH)
    vocab = min(cfg.vocab_size, 512)          # as repro.launch.train
    task = MarkovLM(vocab=vocab, seed=SEED, effective_vocab=min(vocab, 256))
    return cfg, build_model(cfg), task


def _train_config(steps: int, fused: bool):
    from repro.configs import TrainConfig
    return TrainConfig(lr=1e-3, lr_schedule="constant", warmup_steps=0,
                       total_steps=steps, optimizer="sgdm", remat=True,
                       fused_losses=fused, seed=SEED)


def _finite(hist, key: str):
    vals = [v for r in hist.records for k, v in r.items()
            if k == key or k.startswith(key + "_")]
    return vals, bool(vals) and all(math.isfinite(v) for v in vals)


def train_phase(kind: str, device) -> None:
    import jax

    from repro.configs import CodistConfig
    from repro.data import make_lm_batch
    from repro.optim import make_optimizer
    from repro.train import (AllReduce, PredictionExchange, build_train_step,
                             stack_batches, train_allreduce, train_codist)

    _, model, task = _lm_setup()
    codist = CodistConfig(n_models=2) if kind == "codist" else None
    strategy = PredictionExchange(codist) if codist else AllReduce()

    def batch(step):
        if codist is None:
            return make_lm_batch(task, BATCH, SEQ, step, None, seed=SEED)
        return stack_batches([make_lm_batch(task, BATCH, SEQ, step, None,
                                            seed=SEED)
                              for _ in range(codist.n_models)])

    opt_init, _ = make_optimizer("sgdm")
    variant = strategy.variant_for(strategy.plan(0))

    # step 0 with the jnp losses: the parity reference
    tc_ref = _train_config(1, fused=False)
    state = strategy.init_state(model, tc_ref, jax.random.key(SEED),
                                opt_init, batch(0))
    metrics = build_train_step(model, tc_ref, codist, strategy).jitted(
        variant)(state, batch(0))[1]
    ref_loss = float(metrics["loss"])
    del state, metrics

    # the fused step on its own: compile time, warm step time
    tc = _train_config(TRAIN_STEPS, fused=True)
    step = build_train_step(model, tc, codist, strategy).jitted(variant)
    state = strategy.init_state(model, tc, jax.random.key(SEED), opt_init,
                                batch(0))
    t0 = time.perf_counter()
    compiled = step.lower(state, batch(0)).compile()
    compile_s = time.perf_counter() - t0
    times = []
    for k in range(3):
        b = jax.block_until_ready(batch(k))
        t0 = time.perf_counter()
        state, metrics = jax.block_until_ready(compiled(state, b))
        times.append(time.perf_counter() - t0)
    del state, metrics, compiled, step
    info(f"info: {kind} step compile_s={compile_s:.2f} "
         f"warm_step_s={min(times[1:]):.4f} (steps: "
         + " ".join(f"{t:.4f}" for t in times) + ")")

    # the user path: the training loop, 4 steps
    if codist is None:
        it = (batch(k) for k in range(TRAIN_STEPS))
        _, hist = train_allreduce(model, tc, it, log_every=1)
    else:
        _, hist = train_codist(model, codist, tc, batch, log_every=1)
    losses, finite = _finite(hist, "task_loss")
    info(f"{kind} task losses: " + " ".join(f"{v:.5f}" for v in losses))
    check(finite and len(hist.records) == TRAIN_STEPS,
          f"{kind}: {TRAIN_STEPS} steps, all task losses finite")
    loss0 = hist.records[0]["loss"]
    err = abs(loss0 - ref_loss) / max(abs(ref_loss), 1e-6)
    info(f"{kind} step-0 loss fused={loss0:.6f} jnp={ref_loss:.6f} "
         f"rel_err={err:.2e}")
    check(err <= LOSS_RTOL,
          f"{kind}: step-0 loss with fused losses matches the jnp losses "
          f"(rel err {err:.2e} <= {LOSS_RTOL})")
    info(f"info: {kind} peak_bytes_in_use={peak_bytes(device)} "
         f"({_gib(peak_bytes(device))}, process peak so far)")


# ----------------------------------------------------------------------------
# serving
# ----------------------------------------------------------------------------

def _fleet(model, peer_params, fused: bool):
    from repro.serve import resolve_cache_dtype
    from repro.serve.fleet import FleetConfig, FleetRouter
    block = 16
    fc = FleetConfig(max_slots=8, block_size=block, num_blocks=129,
                     max_blocks_per_slot=-(-(MAX_PROMPT + MAX_NEW) // block),
                     fused_attention=fused)
    return FleetRouter(model, peer_params, config=fc, policy="round_robin",
                       cache_dtype=resolve_cache_dtype("auto"))


def _tokens_of(router, rid: int):
    recs = [r for e in router.engines for r in e.records
            if r.request.rid == rid and not r.canary]
    return list(recs[0].tokens)


def serve_phase(device) -> None:
    import jax

    from repro.serve.fleet import Workload, generate_workload

    cfg, model, _ = _lm_setup()
    peer_params = [model.init(jax.random.key(SEED + i)) for i in range(2)]
    wl = generate_workload("steady", N_REQUESTS, cfg.padded_vocab, seed=SEED,
                           max_prompt=MAX_PROMPT, max_new=MAX_NEW)
    t0 = time.perf_counter()
    router = _fleet(model, peer_params, fused=True)
    rep = router.run(wl, slo_ms=50.0)
    wall = time.perf_counter() - t0
    info(f"serve: completed={rep.completed}/{N_REQUESTS} "
         f"generated_tokens={rep.generated_tokens}/{wl.total_output_tokens} "
         f"lost={rep.lost_tokens} duplicated={rep.duplicated_tokens} "
         f"digest={rep.stream_digest[:16]}")
    info(f"info: serve wall_s={wall:.2f} (compiles included) "
         f"peak_bytes_in_use={peak_bytes(device)} "
         f"({_gib(peak_bytes(device))}, process peak so far)")
    check(rep.completed == N_REQUESTS and rep.rejected == 0,
          f"{N_REQUESTS}/{N_REQUESTS} requests answered")
    check(rep.generated_tokens == wl.total_output_tokens
          and rep.lost_tokens == 0 and rep.duplicated_tokens == 0,
          "every request got its requested token count; 0 lost, "
          "0 duplicated")

    # parity on the chip: request 0 again on the jnp attention path
    fused_tokens = _tokens_of(router, 0)
    del router
    one = Workload(wl.scenario, wl.seed, [wl.requests[0]])
    oracle = _fleet(model, peer_params, fused=False)
    oracle.run(one, slo_ms=50.0)
    oracle_tokens = _tokens_of(oracle, 0)
    info(f"request 0 tokens fused={fused_tokens} jnp={oracle_tokens}")
    check(fused_tokens == oracle_tokens
          and len(fused_tokens) == wl.requests[0].max_new,
          "request 0: fused paged attention emits the jnp path's tokens")


# ----------------------------------------------------------------------------
# four chips: one peer per chip
# ----------------------------------------------------------------------------

def four_chip_phase(devices) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import CodistConfig
    from repro.data import make_lm_batch
    from repro.launch.mesh import auto_mesh
    from repro.launch.sharding import state_shardings
    from repro.optim import make_optimizer
    from repro.train import (PredictionExchange, ShardMapCompressed,
                             stack_batches, train_codist)

    n, steps = 4, 3
    _, model, task = _lm_setup()
    mesh = auto_mesh((n,), ("pod",))
    codist = CodistConfig(n_models=n)
    tc = _train_config(steps, fused=True)
    opt_init, _ = make_optimizer("sgdm")
    pod = NamedSharding(mesh, P("pod"))

    def batch(step):
        return jax.device_put(stack_batches([
            make_lm_batch(task, FOUR_CHIP_BATCH, SEQ, step, None, seed=SEED)
            for _ in range(n)]), pod)

    def run(strategy, tc):
        init = lambda: strategy.init_state(  # noqa: E731
            model, tc, jax.random.key(SEED), opt_init)
        shardings = state_shardings(jax.eval_shape(init), mesh, stacked=True)
        state = jax.jit(init, out_shardings=shardings)()
        leaf = jax.tree.leaves(state.params)[0]
        check(len(leaf.sharding.device_set) == n and
              leaf.addressable_shards[0].data.shape[0] == 1,
              f"{strategy.name}: state created one peer per chip")
        _, hist = train_codist(model, codist, tc, batch, log_every=1,
                               state=state, strategy=strategy)
        return np.asarray([[r[f"task_loss_per_model_{i}"] for i in range(n)]
                           for r in hist.records])

    sm = run(ShardMapCompressed(codist, mesh), tc)
    peaks = [peak_bytes(d) for d in devices[:n]]
    info("info: shard_map per-device peak_bytes_in_use: "
         + " ".join(f"{d.id}:{p} ({_gib(p)})"
                    for d, p in zip(devices, peaks)))
    pj = run(PredictionExchange(codist), _train_config(steps, fused=False))
    info(f"shard_map task losses per step x peer: {sm.tolist()}")
    info(f"pjit      task losses per step x peer: {pj.tolist()}")
    check(sm.shape == (steps, n) and bool(np.isfinite(sm).all()),
          f"shard_map: {steps} steps x {n} peers, losses finite")
    err = float(np.max(np.abs(sm - pj) / np.maximum(np.abs(pj), 1e-6)))
    check(err <= LOSS_RTOL,
          f"per-peer task losses of shard_map and pjit agree "
          f"(max rel err {err:.2e} <= {LOSS_RTOL})")
    spread = max(peaks) / max(min(peaks), 1)
    check(spread < 1.5, f"per-device peaks even (max/min {spread:.2f}): "
                        "no peer lands whole on one chip")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip codist phase")
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro  # noqa: F401
    except ImportError:
        print("chip_smoke: the repository's src/ is not beside this script",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    devices = device_check(args.chips)
    warm = os.path.isdir(cache) and bool(os.listdir(cache))
    info(f"compile cache: {cache} ({'warm' if warm else 'cold'} at start)")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase(devices)
    else:
        train_phase("codist", devices[0])
        gc.collect()
        train_phase("allreduce", devices[0])
        gc.collect()
        import jax
        live = sum(a.nbytes for a in jax.live_arrays())
        info(f"info: live device bytes after training: {live}")
        serve_phase(devices[0])
    info(f"info: total_s={time.perf_counter() - t0:.1f}")
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
