"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --mode codist --codist-n 2 --steps 200 --batch 8 --seq 128 \
        --out results/train_run

``--mode`` maps one-to-one onto the engine's exchange strategies:

    allreduce         AllReduce            gradient sync baseline
    codist            PredictionExchange   Algorithm 1 logits exchange
    codist-ckpt       CheckpointExchange   Anil et al. stale replicas
    codist-pipelined  PipelinedPredictions previous-step targets
    codist-shardmap   ShardMapCompressed   explicit compressed pod exchange
    codist-async      AsyncPrediction      virtual cluster on independent
                                           step clocks (repro.runtime) with
                                           seeded fault injection: --faults,
                                           --elastic, --staleness-bound

By default it runs the REDUCED config (``--reduced``) with synthetic data,
which is what the CPU tests use; ``--no-reduced`` takes the published
config. On one TPU v5e, qwen1.5-0.5b's 2-peer codist step fits with
``--no-reduced --remat --optimizer sgdm --batch 4 --seq 512``.
``codist-shardmap`` shard_maps over a "pod" mesh axis of size
``--codist-n``; on CPU that many host devices are forced (via XLA_FLAGS,
before jax initializes — hence the deferred imports below).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

MODES = ["codist", "codist-ckpt", "codist-pipelined", "codist-shardmap",
         "codist-async", "allreduce"]


def _ensure_pod_devices(argv) -> None:
    """codist-shardmap needs a "pod" mesh axis of size n_models; on hosts
    without that many devices, force host devices BEFORE jax initializes."""
    pre = argparse.ArgumentParser(add_help=False)
    pre.add_argument("--mode", default="codist")
    pre.add_argument("--codist-n", type=int, default=2)
    args, _ = pre.parse_known_args(argv)
    flags = os.environ.get("XLA_FLAGS", "")
    if (args.mode == "codist-shardmap"
            and "xla_force_host_platform_device_count" not in flags):
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={args.codist_n}"
        ).strip()


def main() -> None:
    _ensure_pod_devices(sys.argv[1:])
    import jax

    from repro.configs import (CodistConfig, TrainConfig, get_config,
                               get_reduced, list_archs)
    from repro.data import MarkovLM, make_lm_batch
    from repro.models import build_model
    from repro.train import (ShardMapCompressed, stack_batches,
                             train_allreduce, train_codist)

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=list_archs())
    ap.add_argument("--mode", default="codist", choices=MODES)
    ap.add_argument("--codist-n", type=int, default=2)
    ap.add_argument("--period", type=int, default=1)
    ap.add_argument("--alpha", type=float, default=1.0)
    ap.add_argument("--alpha-growth", type=float, default=1.0)
    ap.add_argument("--distill-loss", default="mse",
                    choices=["mse", "kl", "ce"])
    ap.add_argument("--compression", default="none",
                    choices=["none", "topk", "bf16", "subsample"])
    ap.add_argument("--topk", type=int, default=64)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8, help="per-model batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--lr-schedule", default="cosine",
                    choices=["cosine", "step", "constant"])
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--weight-decay", type=float, default=1e-4)
    ap.add_argument("--wd-schedule", action="store_true",
                    help="paper's decayed weight decay")
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "sgdm"])
    ap.add_argument("--fused-losses", default="auto",
                    choices=["auto", "on", "off"],
                    help="custom-VJP Pallas loss kernels (auto: on for TPU; "
                         "'on' uses interpret mode on CPU — slow)")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="the smoke-test cut of --arch (2 layers, narrow "
                         "widths); --no-reduced runs the published config")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize each layer's activations in the "
                         "backward pass (TrainConfig.remat)")
    ap.add_argument("--faults", default="",
                    help="codist-async fault spec, e.g. "
                         "'straggler=1*4@0.2,preempt=1@3+5,fail=1@30,"
                         "hetero=0.3' (see repro.runtime.parse_faults)")
    ap.add_argument("--elastic", type=float, default=0.0,
                    help="codist-async: a fresh peer joins at this simulated "
                         "time (burn-in before it distills)")
    ap.add_argument("--staleness-bound", type=int, default=-1,
                    help="codist-async: drop peer payloads older than S "
                         "local steps (-1 = keep-last, unbounded)")
    ap.add_argument("--join-burn-in", type=int, default=5,
                    help="codist-async: local steps a joining peer trains "
                         "before its distillation loss activates")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="codist-async: snapshot each peer every N local "
                         "steps (enables failure recovery)")
    ap.add_argument("--recover-after", type=float, default=10.0,
                    help="codist-async: simulated seconds before a failed "
                         "peer rejoins from its snapshot")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eval-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--out", default="")
    ap.add_argument("--trace", default="",
                    help="write a Chrome/Perfetto trace here (codist-async: "
                         "virtual cluster clock; other modes: step clock). "
                         "Bit-identical per seed — see docs/observability.md")
    ap.add_argument("--metrics", default="",
                    help="write the repro.obs metrics registry as JSON here")
    ap.add_argument("--alerts", default="",
                    help="evaluate Watchtower alert rules over the live "
                         "metrics (codist-async: virtual cluster clock; "
                         "other modes: step clock) and write the alert "
                         "JSONL here")
    ap.add_argument("--rules", default="",
                    help="JSON alert-rules file for --alerts (default: the "
                         "built-in rule pack)")
    ap.add_argument("--flight-recorder", default="",
                    help="dump postmortem bundles into this directory on "
                         "every fired alert or injected fault "
                         "(requires --alerts)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.rules and not args.alerts:
        ap.error("--rules requires --alerts")
    if args.flight_recorder and not args.alerts:
        ap.error("--flight-recorder requires --alerts")
    tracer = metrics = watch = recorder = None
    if args.metrics or args.alerts:
        from repro.obs import MetricsRegistry
        metrics = MetricsRegistry()
    if args.alerts:
        from repro.obs import Watchtower, default_rules, load_rules
        rules = (load_rules(args.rules) if args.rules else default_rules())
        # the Watchtower rides the same clock as the tracer would: virtual
        # cluster seconds for codist-async, the step clock otherwise
        if args.mode == "codist-async":
            watch = Watchtower(metrics, rules, unit_us=1_000_000.0,
                               clock="sim_s")
        else:
            watch = Watchtower(metrics, rules, unit_us=1000.0,
                               clock="steps")

    def _save_obs():
        if tracer is not None and args.trace:
            tracer.save(args.trace)
            print(f"wrote {args.trace} ({tracer.n_events} trace events)")
        if metrics is not None and args.metrics:
            metrics.save(args.metrics)
            print(f"wrote {args.metrics}")
        if watch is not None:
            watch.save(args.alerts)
            s = watch.summary()
            print(f"wrote {args.alerts} ({s['n_events']} alert events; "
                  f"still firing: {', '.join(s['firing']) or 'none'})")
        if recorder is not None:
            print(f"flight recorder: {len(recorder.dumped)} postmortem "
                  f"bundle(s) in {args.flight_recorder}")

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = build_model(cfg)
    vocab = min(cfg.vocab_size, 512)
    task = MarkovLM(vocab=vocab, seed=args.seed,
                    effective_vocab=min(vocab, 256))
    tc = TrainConfig(
        lr=args.lr, lr_schedule=args.lr_schedule, warmup_steps=args.warmup,
        total_steps=args.steps, weight_decay=args.weight_decay,
        weight_decay_schedule=(5e-4, 1e-5, 0.0) if args.wd_schedule else (),
        optimizer=args.optimizer, seed=args.seed, remat=args.remat,
        fused_losses={"auto": None, "on": True, "off": False}[
            args.fused_losses])

    def eval_batches(step):
        if args.mode == "allreduce":
            return make_lm_batch(task, args.batch, args.seq, 10_000 + step,
                                 None, seed=args.seed + 1)
        return stack_batches([
            make_lm_batch(task, args.batch, args.seq, 10_000 + step, None,
                          seed=args.seed + 1)
            for _ in range(args.codist_n)])

    if args.mode == "codist-async":
        from dataclasses import replace as _replace

        from repro.runtime import AsyncScheduler, parse_faults

        faults = parse_faults(args.faults, args.codist_n, seed=args.seed)
        if args.elastic > 0:
            faults = _replace(faults,
                              joins=((faults.n_peers, args.elastic),))
        codist = CodistConfig(
            n_models=args.codist_n, mode="predictions", period=args.period,
            alpha0=args.alpha, alpha_growth=args.alpha_growth,
            distill_loss=args.distill_loss, compression=args.compression,
            topk=args.topk, steps_per_epoch=max(1, args.steps // 10))

        def async_batches(step):
            return make_lm_batch(task, args.batch, args.seq, step, None,
                                 seed=args.seed)

        ckpt_dir = None
        if args.checkpoint_every:
            ckpt_dir = os.path.join(args.out or ".", "runtime_ckpt")
        if args.trace or args.flight_recorder:
            from repro.obs import for_sim_seconds
            tracer = for_sim_seconds()
        if args.flight_recorder:
            from repro.obs import FlightRecorder
            recorder = FlightRecorder(args.flight_recorder, metrics=metrics)
            tracer.recorder = recorder
            watch.on_alert(recorder.on_alert)
            watch.on_fault(recorder.on_fault)
        t0 = time.time()
        report = AsyncScheduler(
            model, tc, codist, async_batches, faults,
            staleness_bound=(None if args.staleness_bound < 0
                             else args.staleness_bound),
            checkpoint_dir=ckpt_dir, checkpoint_every=args.checkpoint_every,
            recover_after=(args.recover_after if args.checkpoint_every
                           else None),
            join_burn_in=args.join_burn_in, log_every=args.log_every,
            tracer=tracer, metrics=metrics, watch=watch).run()
        dt = time.time() - t0
        for pid in sorted(report.histories):
            for rec in report.histories[pid].records:
                msg = " ".join(
                    f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
                    for k, v in rec.items()
                    if k in ("peer", "step", "task_loss", "distill_loss",
                             "staleness", "alpha", "sim_time"))
                print(msg, flush=True)
        print(f"sim_time={report.sim_time:.2f} "
              f"time_to_first={report.time_to_first:.2f} "
              f"comm_events={report.comm_events} "
              f"comm_bytes={report.comm_bytes:.0f} "
              f"staleness_mean={report.staleness['staleness_mean']:.3f} "
              f"dropped={report.staleness['payloads_dropped']}")
        print(f"done: {args.steps} steps x {faults.n_total} peers "
              f"in {dt:.1f}s (simulated {report.sim_time:.1f}s)")
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            report.save_histories(args.out)
            from repro.checkpoint import save_pytree
            for pid, st in report.states.items():
                save_pytree(os.path.join(args.out, f"final_peer{pid}"),
                            st.params)
            print(f"wrote per-peer JSONL histories + checkpoints to "
                  f"{args.out}")
        _save_obs()
        return

    if args.trace or args.flight_recorder:
        from repro.obs import for_steps
        tracer = for_steps()
    if args.flight_recorder:
        from repro.obs import FlightRecorder
        recorder = FlightRecorder(args.flight_recorder, metrics=metrics)
        tracer.recorder = recorder
        watch.on_alert(recorder.on_alert)
        watch.on_fault(recorder.on_fault)
    t0 = time.time()
    if args.mode == "allreduce":
        def it():
            s = 0
            while True:
                yield make_lm_batch(task, args.batch, args.seq, s, None,
                                    seed=args.seed)
                s += 1
        state, hist = train_allreduce(model, tc, it(),
                                      eval_batches=eval_batches,
                                      eval_every=args.eval_every,
                                      log_every=args.log_every,
                                      tracer=tracer, metrics=metrics,
                                      watch=watch)
    else:
        codist = CodistConfig(
            n_models=args.codist_n,
            mode="checkpoints" if args.mode == "codist-ckpt" else "predictions",
            pipelined=args.mode == "codist-pipelined",
            period=args.period, alpha0=args.alpha,
            alpha_growth=args.alpha_growth, distill_loss=args.distill_loss,
            compression=args.compression, topk=args.topk,
            steps_per_epoch=max(1, args.steps // 10))
        strategy = None
        if args.mode == "codist-shardmap":
            if jax.device_count() < args.codist_n:
                raise SystemExit(
                    f"codist-shardmap needs >= {args.codist_n} devices for "
                    f"the 'pod' axis; have {jax.device_count()}")
            from repro.launch.mesh import auto_mesh
            mesh = auto_mesh((args.codist_n,), ("pod",))
            strategy = ShardMapCompressed(codist, mesh)
        coordinated = codist.mode == "predictions"

        def batches(step):
            return stack_batches([
                make_lm_batch(task, args.batch, args.seq, step,
                              None if coordinated else g, seed=args.seed)
                for g in range(args.codist_n)])

        state, hist = train_codist(model, codist, tc, batches,
                                   eval_batches=eval_batches,
                                   eval_every=args.eval_every,
                                   log_every=args.log_every,
                                   strategy=strategy,
                                   tracer=tracer, metrics=metrics,
                                   watch=watch)
    dt = time.time() - t0

    for rec in hist.records:
        msg = " ".join(f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
                       for k, v in rec.items()
                       if k in ("step", "task_loss", "distill_loss",
                                "eval_loss", "lr", "wd", "alpha",
                                "comm_bytes"))
        print(msg, flush=True)
    print(f"done: {args.steps} steps in {dt:.1f}s "
          f"({dt / args.steps * 1e3:.0f} ms/step)")
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, "history.json"), "w") as f:
            json.dump(hist.records, f, indent=1)
        from repro.checkpoint import save_pytree
        save_pytree(os.path.join(args.out, "final"), state.params)
        print(f"wrote {args.out}/history.json and final checkpoint")
    _save_obs()
    # a variant traced again mid-run was recompiled: its step names the cause
    print("step variants traced at steps: " + " ".join(
        f"{v}={','.join(map(str, ks))}"
        for v, ks in sorted(hist.traced_at.items())), flush=True)


if __name__ == "__main__":
    main()
