"""Serving launcher: the continuous-batching fleet over codistilled peers.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b \
        --peers 2 --scenario bursty --requests 32 --slo-ms 50 \
        --router least_loaded

Runs a seeded open-loop workload (see ``repro.serve.fleet.workload``'s
scenario catalog) through N peer engines and prints the SLO report
(simulated-time latencies: bit-deterministic for a given seed). ``--report``
writes the full JSON report; ``--snapshot-dir`` points the router's
staleness-bounded weight refresh at ``checkpoint/io.py`` peer snapshots
(e.g. from ``--mode codist-async --checkpoint-every``). The legacy
single-engine batched-generate path lives behind ``--single``.

Chaos serving (docs/chaos.md): ``--faults`` takes the SAME spec syntax as
``repro.launch.train`` (``straggler=1*4@0.2,preempt=1@40+400,fail=1@60``;
pauses in simulated ms here) and injects it on the fleet's decode-tick
clock. Defenses are on by default when faults are injected — disable with
``--no-defend`` for the undefended baseline, add ``--hedge`` for hedged
dispatch, and flip ``--degraded-admission off`` to keep full queue bounds
under reduced capacity. ``--recover-after-ms`` revives failed peers from
their snapshots.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_reduced, list_archs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.runtime.clock import parse_faults
from repro.serve import Engine, resolve_cache_dtype
from repro.serve.fleet import (POLICIES, SCENARIOS, ChaosConfig, FleetConfig,
                               FleetDefense, FleetRouter, generate_workload)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=list_archs())
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="the smoke-test cut of --arch; --no-reduced serves "
                         "the published config")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cache-dtype", default="auto",
                    help="KV/state cache dtype: auto (bf16 on TPU, fp32 in "
                         "interpret mode), bf16, fp16, fp32, or a quantized "
                         "paged-pool dtype — int8, fp8/float8_e4m3fn "
                         "(fleet mode only; per-row fp32 scales, dequantized "
                         "inside the decode kernel)")
    ap.add_argument("--fused-attention", default="auto",
                    choices=("auto", "on", "off"),
                    help="decode attention path: the fused paged-attention "
                         "kernel (auto/on; Mosaic on TPU, interpret on CPU) "
                         "or the jnp gather+dense-softmax oracle (off)")
    ap.add_argument("--max-new", type=int, default=16)
    # ---- fleet mode ----
    ap.add_argument("--peers", type=int, default=2,
                    help="codistilled replicas behind the router")
    ap.add_argument("--scenario", default="steady", choices=list(SCENARIOS))
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slo-ms", type=float, default=50.0,
                    help="TTFT SLO (simulated ms)")
    ap.add_argument("--router", default="round_robin", choices=list(POLICIES))
    ap.add_argument("--canary-every", type=int, default=0,
                    help="duplicate every k-th request to the next peer and "
                         "track distill_pair divergence (0: off)")
    ap.add_argument("--slots", type=int, default=8,
                    help="decode slots per peer")
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--num-blocks", type=int, default=128)
    ap.add_argument("--max-prompt", type=int, default=48)
    ap.add_argument("--snapshot-dir", default="",
                    help="poll checkpoint/io.py peer snapshots for "
                         "staleness-bounded weight refresh")
    ap.add_argument("--refresh-every-ms", type=float, default=0.0)
    ap.add_argument("--staleness-bound", type=int, default=0)
    # ---- speculative decoding (docs/serving.md) ----
    ap.add_argument("--speculative", action="store_true",
                    help="peer-speculative decoding: a codistilled partner "
                         "drafts k tokens, the target verifies them in one "
                         "batched forward — bit-identical to plain decode "
                         "at temperature 0 (sets --router speculative)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="draft tokens proposed per speculative round")
    ap.add_argument("--draft-peer", default="ring",
                    help="'ring' pairs every peer with its neighbor (all "
                         "peers serve); an integer dedicates that peer to "
                         "drafting (excluded from the serving rotation)")
    ap.add_argument("--identical-peers", action="store_true",
                    help="init every peer from the SAME key — the "
                         "converged-codistillation limit (accept rate 1.0; "
                         "used by the spec-decode CI smoke)")
    # ---- chaos (docs/chaos.md) ----
    ap.add_argument("--faults", default="none",
                    help="seeded fault spec on the decode-tick clock, same "
                         "syntax as repro.launch.train (pauses in sim ms): "
                         "straggler=P*F@FRAC,preempt=P@T+PAUSE,fail=P@T,"
                         "hetero=SIGMA")
    ap.add_argument("--fault-horizon", type=int, default=4096,
                    help="fault-schedule realization horizon (decode ticks)")
    ap.add_argument("--recover-after-ms", type=float, default=0.0,
                    help="revive failed peers from their snapshot after this "
                         "much simulated time (0: stay dead)")
    ap.add_argument("--no-defend", action="store_true",
                    help="inject faults WITHOUT router defenses (the "
                         "undefended baseline)")
    ap.add_argument("--hedge", action="store_true",
                    help="hedged dispatch: run the slowest-decile requests "
                         "on two peers, first winner cancels the other")
    ap.add_argument("--degraded-admission", default="on",
                    choices=("on", "off"),
                    help="scale queue bounds with available capacity so a "
                         "shrunken fleet sheds at the edge")
    ap.add_argument("--report", default="", help="write the JSON report here")
    # ---- observability (docs/observability.md) ----
    ap.add_argument("--trace", default="",
                    help="write a Chrome/Perfetto trace of the run here "
                         "(simulated-ms clock; bit-identical per seed)")
    ap.add_argument("--metrics", default="",
                    help="write the repro.obs metrics registry (counters/"
                         "gauges/histograms) as JSON here")
    ap.add_argument("--alerts", default="",
                    help="evaluate Watchtower alert rules over the live "
                         "metrics on the decode-tick clock and write the "
                         "alert JSONL here (bit-identical per seed)")
    ap.add_argument("--rules", default="",
                    help="JSON alert-rules file for --alerts (default: the "
                         "built-in rule pack, SLO taken from --slo-ms)")
    ap.add_argument("--flight-recorder", default="",
                    help="keep a bounded ring of recent trace events and "
                         "dump postmortem bundles into this directory on "
                         "every fired alert or injected fault "
                         "(requires --alerts)")
    # ---- legacy single-engine mode ----
    ap.add_argument("--single", action="store_true",
                    help="legacy path: one Engine.generate batch, no fleet")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = build_model(cfg)
    cache_dtype = resolve_cache_dtype(args.cache_dtype)

    if args.single:
        from repro.kernels.paged_cache import is_quantized_dtype
        if is_quantized_dtype(cache_dtype):
            ap.error(f"--cache-dtype {args.cache_dtype} is a quantized "
                     "paged-pool dtype: fleet mode only (drop --single)")
        if args.trace or args.metrics or args.alerts or args.flight_recorder:
            ap.error("--trace/--metrics/--alerts/--flight-recorder "
                     "instrument the fleet's simulated clock: fleet mode "
                     "only (drop --single)")
        return _single(args, cfg, model, cache_dtype)
    if cfg.is_encdec or cfg.num_patches or not hasattr(model, "decode"):
        import sys
        print(f"--arch {args.arch} is not token-only LM serving "
              "(enc-dec / VLM / vision): the fleet's workload generator "
              "drives text prompts only — use --single for the legacy "
              "batched-generate path", file=sys.stderr)
        sys.exit(2)

    if args.speculative:
        args.router = "speculative"
    spec = None
    if args.router == "speculative":
        from repro.serve.fleet import SpecConfig
        if args.draft_peer == "ring":
            draft_peer = None
        else:
            try:
                draft_peer = int(args.draft_peer)
            except ValueError:
                ap.error(f"--draft-peer {args.draft_peer!r}: expected "
                         "'ring' or a peer index")
            if not 0 <= draft_peer < args.peers:
                ap.error(f"--draft-peer {draft_peer} out of range for "
                         f"--peers {args.peers}")
        spec = SpecConfig(k=args.draft_k, draft_peer=draft_peer)
    if args.identical_peers:
        peer_params = [model.init(jax.random.key(args.seed))] * args.peers
    else:
        peer_params = [model.init(jax.random.key(args.seed + i))
                       for i in range(args.peers)]
    fc = FleetConfig(max_slots=args.slots, block_size=args.block_size,
                     num_blocks=args.num_blocks,
                     max_blocks_per_slot=max(
                         1, -(-(args.max_prompt + args.max_new)
                              // args.block_size)),
                     fused_attention={"auto": None, "on": True,
                                      "off": False}[args.fused_attention])
    chaos = defense = None
    if args.faults and args.faults != "none":
        chaos = ChaosConfig(
            parse_faults(args.faults, args.peers, seed=args.seed),
            horizon_ticks=args.fault_horizon,
            recover_after_ms=args.recover_after_ms)
    if (chaos is not None and not args.no_defend) or args.hedge:
        defense = FleetDefense(
            hedging=args.hedge,
            degraded_admission=(args.degraded_admission == "on"))
    if args.rules and not args.alerts:
        ap.error("--rules requires --alerts")
    if args.flight_recorder and not args.alerts:
        ap.error("--flight-recorder requires --alerts (bundles dump on "
                 "fired alerts and injected faults)")
    tracer = metrics = watch = recorder = None
    if args.trace or args.metrics or args.alerts:
        from repro.obs import MetricsRegistry, for_sim_ms
        # the flight recorder rides the tracer's event stream, so it
        # implies an internal tracer even without --trace; likewise
        # alerting implies an internal registry even without --metrics —
        # neither internal artifact is written to disk
        tracer = (for_sim_ms() if (args.trace or args.flight_recorder)
                  else None)
        metrics = (MetricsRegistry() if (args.metrics or args.alerts)
                   else None)
    if args.alerts:
        from repro.obs import (FlightRecorder, Watchtower, default_rules,
                               load_rules)
        rules = (load_rules(args.rules) if args.rules
                 else default_rules(slo_ms=args.slo_ms))
        watch = Watchtower(metrics, rules, unit_us=1000.0, clock="sim_ms")
        if args.flight_recorder:
            recorder = FlightRecorder(args.flight_recorder, metrics=metrics)
            tracer.recorder = recorder
            watch.on_alert(recorder.on_alert)
            watch.on_fault(recorder.on_fault)
    router = FleetRouter(model, peer_params, config=fc, policy=args.router,
                         cache_dtype=cache_dtype,
                         canary_every=args.canary_every,
                         snapshot_dir=args.snapshot_dir or None,
                         refresh_every_ms=args.refresh_every_ms,
                         staleness_bound=args.staleness_bound,
                         chaos=chaos, defense=defense,
                         tracer=tracer, metrics=metrics, watch=watch,
                         spec=spec)
    if recorder is not None:
        # postmortems carry the offending ids: live request/queue state per
        # peer at dump time (all simulated-clock state — deterministic)
        recorder.context_fn = lambda: {
            "peers": [
                {"peer": i, "dead": e.dead,
                 "now_ms": round(e.now_ms, 6),
                 "live_rids": sorted(sl.record.request.rid
                                     for sl in e.slots.values()),
                 "queued": len(e.waiting)}
                for i, e in enumerate(router.engines)]}
    if args.snapshot_dir:
        n = router.refresh_now()
        print(f"initial weight refresh: {n}/{args.peers} peers from "
              f"{args.snapshot_dir}")
    wl = generate_workload(args.scenario, args.requests, cfg.padded_vocab,
                           seed=args.seed, max_prompt=args.max_prompt,
                           max_new=args.max_new)
    t0 = time.time()
    rep = router.run(wl, slo_ms=args.slo_ms)
    wall = time.time() - t0
    print(f"arch={args.arch} scenario={args.scenario} router={args.router} "
          f"peers={args.peers} requests={args.requests} seed={args.seed}")
    print(f"completed={rep.completed} rejected={rep.rejected} "
          f"generated_tokens={rep.generated_tokens}")
    print(f"TTFT p50/p99 = {rep.p50_ttft_ms:.1f}/{rep.p99_ttft_ms:.1f} ms "
          f"(sim)  e2e p50/p99 = {rep.p50_e2e_ms:.1f}/{rep.p99_e2e_ms:.1f} ms")
    print(f"SLO({rep.slo_ms:.0f}ms TTFT) attainment = "
          f"{rep.slo_attainment:.3f}  sim tok/s = {rep.sim_tokens_per_s:.1f}"
          f"  wall tok/s = {rep.generated_tokens / max(wall, 1e-9):.1f}")
    print(f"pool peak util = {rep.peak_pool_utilization:.2f}  "
          f"kv_bytes = {rep.kv_bytes_written}  refreshes = {rep.refreshes} "
          f"(dropped stale: {rep.refreshes_dropped_stale})")
    if rep.canary.get("count"):
        print(f"canary: n={rep.canary['count']} "
              f"mean_mse={rep.canary['mean_mse']:.4f} "
              f"token_agreement={rep.canary['token_agreement']:.3f}")
    if spec is not None:
        print(f"speculative: k={spec.k} accept_rate="
              f"{rep.spec_accept_rate:.3f} rounds={rep.spec_rounds} "
              f"drafted/accepted = "
              f"{rep.spec_drafted_tokens}/{rep.spec_accepted_tokens}  "
              f"fallback_ticks={rep.spec_fallback_ticks}")
    if chaos is not None or defense is not None:
        print(f"chaos: defended={'no' if defense is None else 'yes'} "
              f"goodput tok/s = {rep.goodput_tokens_per_s:.1f}  "
              f"lost/dup tokens = {rep.lost_tokens}/{rep.duplicated_tokens}")
        print(f"  migrations={rep.migrations} "
              f"(failed: {rep.migration_failures})  hedges={rep.hedges} "
              f"(wins: {rep.hedge_wins})  preemptions={rep.preemptions}  "
              f"died/recovered={rep.peers_died}/{rep.peers_recovered}")
    print(f"stream digest = {rep.stream_digest}")
    if args.report:
        with open(args.report, "w") as f:
            f.write(rep.to_json() + "\n")
        print(f"wrote {args.report}")
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


def _single(args, cfg, model, cache_dtype) -> None:
    params = model.init(jax.random.key(args.seed))
    engine = Engine(model, params, cache_dtype=cache_dtype)
    key = jax.random.key(args.seed + 1)
    batch = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.padded_vocab)}
    if cfg.num_patches:
        batch["patches"] = 0.1 * jax.random.normal(
            key, (args.batch, cfg.num_patches, cfg.d_model))
    if cfg.is_encdec:
        batch["frames"] = 0.1 * jax.random.normal(
            key, (args.batch, cfg.num_audio_frames, cfg.d_model))
    t0 = time.time()
    result = engine.generate(batch, args.max_new, args.temperature, args.seed)
    dt = time.time() - t0
    toks = args.batch * args.max_new
    print(f"arch={args.arch} batch={args.batch} prompt={args.prompt_len} "
          f"new={args.max_new}")
    print(f"generated {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s, {dt / args.max_new * 1e3:.1f} ms/step)")
    print("first sequence:", result.tokens[0, args.prompt_len:].tolist())


if __name__ == "__main__":
    main()
