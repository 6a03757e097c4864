"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combination.

Run as a module:
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k \
        --mesh multi --mode codist

Proves the distribution config is coherent without hardware: a sharding
mismatch, compile-time OOM or unsupported collective fails here. Per combo it
records memory_analysis(), cost_analysis() and the parsed collective schedule
(intra- vs cross-pod bytes) for EXPERIMENTS.md §Dry-run / §Roofline.
"""
# The VERY FIRST lines, before ANY other import — jax locks the device count
# on first init. 512 host devices serve both the 256-chip single-pod mesh and
# the 2x256 multi-pod mesh.
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from dataclasses import replace  # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (ASSIGNED_ARCHS, INPUT_SHAPES, CodistConfig,  # noqa: E402
                           TrainConfig, get_config)
from repro.launch import sharding as sh  # noqa: E402
from repro.launch import specs as sp     # noqa: E402
from repro.launch.hlo_analysis import parse_collectives  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.roofline import build_report  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models import sharding_hints as hints  # noqa: E402
from repro.optim import make_optimizer  # noqa: E402
from repro.train.engine import (AllReduce, PredictionExchange,  # noqa: E402
                                build_train_step)
from repro.train.state import CodistState, TrainState  # noqa: E402

SDS = jax.ShapeDtypeStruct

# dense-family archs take the sliding-window variant for long_500k (the
# sub-quadratic carve-in); whisper skips it entirely (see DESIGN.md).
SLIDING_WINDOW_FOR_LONG = 8192
SKIP = {("whisper-tiny", "long_500k")}


def dryrun_config(arch: str):
    """Full config adapted for dry-run numerics: bf16 params+activations."""
    cfg = get_config(arch)
    return replace(cfg, dtype="bfloat16", param_dtype="bfloat16")


def adapt_for_shape(cfg, shape_name: str):
    if shape_name == "long_500k" and not cfg.attention_free \
            and cfg.attn_layer_period == 0:
        # dense/moe/vlm: sliding-window attention => O(W) decode state
        cfg = replace(cfg, sliding_window=SLIDING_WINDOW_FOR_LONG)
    return cfg


def pick_microbatch(cfg, shape, data_ways: int, n_models: int = 1,
                    target_gb: float = 2.5) -> int:
    """Gradient-accumulation factor: keep the per-device activations saved
    for backward (one (B,S,d) bf16 residual per scanned layer) under
    ``target_gb``. k must keep B/n/k divisible by the data axis."""
    if getattr(cfg, "kind", None):  # conv models: small
        return 1
    if shape.kind != "train":  # one-token decode / fwd-only prefill
        return 1
    b = shape.global_batch // max(1, n_models)
    per_dev = b / data_ways
    carry_gb = per_dev * shape.seq_len * cfg.d_model * 2 * cfg.num_layers / 1e9
    k, max_k = 1, max(1, b // data_ways)
    while carry_gb / k > target_gb and k < max_k:
        k *= 2
    return min(k, max_k)


def _train_lowering(model, cfg, shape, mesh, mode: str, codist_n: int,
                    remat: bool, extra: Optional[Dict] = None,
                    microbatch: Optional[int] = None,
                    variant: Optional[Dict] = None):
    variant = variant or {}
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    multi = "pod" in sizes
    if mode == "allreduce":
        data_ways = sizes["data"] * (sizes.get("pod", 1))
        k = microbatch or pick_microbatch(cfg, shape, data_ways)
        tc = TrainConfig(optimizer="sgdm", remat=remat, total_steps=1000,
                         microbatch=k, opt_dtype="bfloat16",
                         accum_dtype="bfloat16")
        step = build_train_step(model, tc, None, AllReduce()).variants["on"]
        params_sds = sp.params_specs(model)
        opt_init, _ = make_optimizer("sgdm", dtype="bfloat16")
        opt_sds = jax.eval_shape(opt_init, params_sds)
        state_sds = TrainState(params_sds, opt_sds,
                               SDS((), jnp.int32))
        batch_sds = sp.train_batch_specs(cfg, shape, microbatch=k)
    else:
        k = microbatch or pick_microbatch(cfg, shape, sizes["data"], codist_n)
        tc = TrainConfig(optimizer="sgdm", remat=remat, total_steps=1000,
                         microbatch=k, opt_dtype="bfloat16",
                         accum_dtype="bfloat16")
        codist = CodistConfig(n_models=codist_n, mode="predictions",
                              **(extra or {}))
        step = build_train_step(model, tc, codist,
                                PredictionExchange(codist)).variants["on"]
        params_sds = sp.stacked_params_specs(model, codist_n)
        opt_init, _ = make_optimizer("sgdm", dtype="bfloat16")
        opt_sds = jax.eval_shape(opt_init, params_sds)
        state_sds = CodistState(params_sds, opt_sds, SDS((), jnp.int32),
                                None, None)
        batch_sds = sp.train_batch_specs(cfg, shape, n_stack=codist_n,
                                         microbatch=k)
    stacked = mode != "allreduce"
    state_sh = sh.state_shardings(
        state_sds, mesh, stacked=stacked,
        fsdp_axis=variant.get("train_fsdp_axis", "data"),
        moe_expert_axis=variant.get("moe_expert_axis"))
    batch_sh = sh.batch_shardings(batch_sds, mesh, stacked=stacked,
                                  microbatched=k > 1)
    multi = "pod" in mesh.axis_names
    batch_axes = ("data",) if stacked else (
        ("pod", "data") if multi else ("data",))
    tp_size = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    with jax.set_mesh(mesh), hints.activation_sharding(
            batch_axes, "model", tp_size, mesh):
        lowered = jax.jit(step, in_shardings=(state_sh, batch_sh)).lower(
            state_sds, batch_sds)
    return lowered


def _prefill_lowering(model, cfg, shape, mesh):
    cap = shape.seq_len

    def prefill_step(params, batch):
        return model.prefill(params, batch, cap, cache_dtype=jnp.bfloat16)

    params_sds = sp.params_specs(model)
    batch_sds = sp.prefill_batch_specs(cfg, shape)
    params_sh = sh.state_shardings(params_sds, mesh)
    batch_sh = sh.batch_shardings(batch_sds, mesh)
    with jax.set_mesh(mesh):
        lowered = jax.jit(prefill_step,
                          in_shardings=(params_sh, batch_sh)).lower(
            params_sds, batch_sds)
    return lowered


def _decode_lowering(model, cfg, shape, mesh, variant: Optional[Dict] = None):
    variant = variant or {}

    def decode_step(params, cache, tokens, pos):
        return model.decode(params, cache, tokens, pos)

    params_sds = sp.params_specs(model)
    cache_sds = sp.cache_specs(model, cfg, shape)
    tok_sds = sp.decode_token_specs(shape)
    pos_sds = SDS((), jnp.int32)
    # 'ws'  = fully weight-stationary: params on the model axis only
    #         (replicated over data) — no re-gathers, but every device reads
    #         the full TP shard per step;
    # '2d'  = FFN/head/embedding 2D-sharded over (data x model) weight-
    #         stationary, attention keeps FSDP+TP (the serving sweet spot).
    # 'repl-batch' = batch-replicated decode: activations are tiny at decode,
    #                so replicate them and psum partial matmuls — weights stay
    #                fully sharded (FSDP+TP) and never move; the cache shards
    #                over TIME (context parallelism) instead of batch.
    ds = variant.get("decode_sharding", "fsdp")
    fsdp = None if ds == "ws" else "data"
    params_sh = sh.state_shardings(
        params_sds, mesh, fsdp_axis=fsdp,
        moe_expert_axis=variant.get("moe_expert_axis"),
        two_d_ffn=ds == "2d")
    cache_sh = sh.cache_shardings(cache_sds, mesh, shape.global_batch,
                                  prefer_time=ds == "repl-batch")
    if ds == "repl-batch":
        tok_sh = jax.tree.map(lambda _: sh.replicated(mesh), tok_sds)
    else:
        tok_sh = sh.batch_shardings(tok_sds, mesh)
    pos_sh = sh.replicated(mesh)
    with jax.set_mesh(mesh):
        lowered = jax.jit(decode_step, in_shardings=(
            params_sh, cache_sh, tok_sh, pos_sh)).lower(
            params_sds, cache_sds, tok_sds, pos_sds)
    return lowered


def _lower_for(model, cfg, shape, mesh, mode: str, codist_n: int,
               remat: bool, codist_extra=None, microbatch=None,
               variant=None):
    if shape.kind == "train":
        return _train_lowering(
            model, cfg, shape, mesh,
            "codist" if mode == "codist" else "allreduce",
            codist_n, remat, codist_extra, microbatch, variant)
    if shape.kind == "prefill":
        return _prefill_lowering(model, cfg, shape, mesh)
    return _decode_lowering(model, cfg, shape, mesh, variant)


def _extract_cost(compiled, multi_pod: bool, devices_per_pod: int = 256):
    cost = compiled.cost_analysis() or {}
    coll = parse_collectives(compiled.as_text(),
                             devices_per_pod=devices_per_pod if multi_pod
                             else 0)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": float(coll.total_bytes),
        "cross_pod_bytes": float(coll.cross_pod_bytes),
    }


def corrected_cost(arch: str, cfg, shape, mesh, multi_pod: bool, mode: str,
                   codist_n: int, remat: bool, codist_extra=None,
                   variant=None):
    """XLA cost_analysis counts while-loop bodies ONCE, so scanned-layer costs
    are invisible at full depth. Probe the SAME program with the layer scan
    UNROLLED (and SSM chunk scans widened to one full-sequence chunk) at
    n_scan=1 and n_scan=2 — making every FLOP/collective statically visible —
    then extrapolate: cost(full) = c1 + (n_scan_full - 1) * (c2 - c1).

    Gradient accumulation (microbatch k>1) is a while loop too, and its body
    REPEATS the FSDP weight gathers k times per step. Probes therefore run at
    ONE microbatch's batch size (B/k) with k forced to 1, and the
    extrapolated cost is scaled by k — this overcounts the (cheap, collective-
    free) optimizer epilogue by (k-1)x, which is recorded in `k_scaled`.
    """
    from repro.models.runtime_flags import probe_mode
    period = 1 if cfg.family == "ssm" else (cfg.attn_layer_period or 1)
    n_scan_full = cfg.num_layers // period
    if n_scan_full < 2:
        return None
    k_used = 1
    if shape.kind == "train":
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if mode == "codist":
            k_used = pick_microbatch(cfg, shape, sizes["data"], codist_n)
        else:
            k_used = pick_microbatch(
                cfg, shape, sizes["data"] * sizes.get("pod", 1))
    probe_shape = shape
    if k_used > 1:
        probe_shape = dataclasses.replace(
            shape, global_batch=shape.global_batch // k_used)
    probes = []
    for i in (1, 2):
        kw = {"num_layers": period * i}
        if cfg.encoder_layers:
            if cfg.num_layers != cfg.encoder_layers:
                return None  # extrapolation needs both loops scaling together
            kw["encoder_layers"] = i
            kw["num_layers"] = i
        cfg_i = replace(cfg, **kw)
        model_i = build_model(cfg_i)
        with probe_mode():
            lowered = _lower_for(model_i, cfg_i, probe_shape, mesh, mode,
                                 codist_n, remat, codist_extra, microbatch=1,
                                 variant=variant)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dpp = mesh.devices.size // sizes.get("pod", 1)
        probes.append(_extract_cost(lowered.compile(), multi_pod, dpp))
    c1, c2 = probes
    out = {}
    for key in c1:
        # deltas are per-layer costs and cannot be negative; tiny negatives
        # are fusion noise between the two probe compiles — clamp.
        delta = max(0.0, c2[key] - c1[key])
        out[key] = (c1[key] + (n_scan_full - 1) * delta) * k_used
    out["n_scan"] = n_scan_full
    out["k_scaled"] = k_used
    out["probe1"] = c1
    out["probe2"] = c2
    return out


def run_one(arch: str, shape_name: str, multi_pod: bool, mode: str = "auto",
            codist_n: int = 2, remat: bool = True, verbose: bool = True,
            codist_extra: Optional[Dict] = None,
            variant: Optional[Dict] = None) -> Dict:
    """Lower + compile one combination; returns the result record."""
    shape = INPUT_SHAPES[shape_name]
    cfg = adapt_for_shape(dryrun_config(arch), shape_name)
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = mesh.devices.size
    if mode == "auto":
        # the paper's deployment: codistillation for training across pods,
        # plain serving (one model) for inference shapes
        mode = "codist" if (shape.kind == "train" and multi_pod) else (
            "allreduce" if shape.kind == "train" else shape.kind)

    t0 = time.time()
    lowered = _lower_for(model, cfg, shape, mesh, mode, codist_n, remat,
                         codist_extra, variant=variant)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes",
                                            None),
        }
    except Exception as e:  # pragma: no cover
        mem_d = {"error": str(e)}

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dpp = (chips // sizes["pod"]) if multi_pod else 0
    hlo = compiled.as_text()
    coll = parse_collectives(hlo, devices_per_pod=dpp)

    # correct for XLA's count-scan-body-once cost analysis
    corr = None
    try:
        corr = corrected_cost(arch, cfg, shape, mesh, multi_pod, mode,
                              codist_n, remat, codist_extra, variant)
    except Exception as e:  # pragma: no cover
        print(f"[dryrun] cost extrapolation failed for {arch}: {e}",
              flush=True)
    if corr is not None:
        flops, byts = corr["flops"], corr["bytes"]
        coll_b, cross_b = corr["coll_bytes"], corr["cross_pod_bytes"]
    else:
        flops = float(cost.get("flops", 0.0))
        byts = float(cost.get("bytes accessed", 0.0))
        coll_b, cross_b = float(coll.total_bytes), float(coll.cross_pod_bytes)
    report = build_report(arch, shape, mesh_name, chips, flops, byts,
                          coll_b, cross_b,
                          cfg if not hasattr(cfg, "kind") else None,
                          note=mode)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "mode": mode,
        "variant": variant or {}, "codist_extra": codist_extra or {},
        "chips": chips, "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "cost": {k: float(v) for k, v in cost.items()
                 if isinstance(v, (int, float))},
        "memory": mem_d,
        "collectives": {"counts": coll.counts(), "bytes_by_kind": coll.by_kind(),
                        "total_bytes": coll.total_bytes,
                        "cross_pod_bytes": coll.cross_pod_bytes,
                        "intra_pod_bytes": coll.intra_pod_bytes},
        "cost_corrected": corr,
        "roofline": report.to_dict(),
        "status": "ok",
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name} ({mode}): "
              f"compile {t_compile:.1f}s, flops/dev {flops:.3e}, "
              f"coll {coll.total_bytes/1e6:.1f}MB "
              f"(cross-pod {coll.cross_pod_bytes/1e6:.1f}MB), "
              f"bottleneck={report.bottleneck}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "allreduce", "codist"])
    ap.add_argument("--codist-n", type=int, default=2)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--decode-sharding", default="fsdp",
                    choices=["fsdp", "ws", "2d", "repl-batch"])
    ap.add_argument("--moe-experts", default="",
                    help="mesh axis to shard MoE experts over (e.g. data)")
    ap.add_argument("--no-train-fsdp", action="store_true",
                    help="TP-only sharding for non-expert train params")
    ap.add_argument("--compression", default="",
                    choices=["", "none", "topk", "bf16", "subsample"])
    ap.add_argument("--topk", type=int, default=64)
    ap.add_argument("--subsample", type=int, default=0)
    ap.add_argument("--tag", default="", help="suffix for the output file")
    ap.add_argument("--all", action="store_true",
                    help="all archs x shapes for the chosen mesh")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    combos = []
    archs = [args.arch] if args.arch else ASSIGNED_ARCHS
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    for a in archs:
        for s in shapes:
            if (a, s) in SKIP:
                print(f"[dryrun] SKIP {a} x {s} (see DESIGN.md)", flush=True)
                continue
            combos.append((a, s))
    if not args.all and args.arch is None:
        combos = combos[:1]

    multi = args.mesh == "multi"
    variant = {}
    if args.decode_sharding != "fsdp":
        variant["decode_sharding"] = args.decode_sharding
    if args.moe_experts:
        variant["moe_expert_axis"] = args.moe_experts
    if args.no_train_fsdp:
        variant["train_fsdp_axis"] = None
    codist_extra = {}
    if args.compression and args.compression != "none":
        codist_extra["compression"] = args.compression
        if args.compression == "topk":
            codist_extra["topk"] = args.topk
        if args.compression == "subsample":
            codist_extra["subsample"] = args.subsample
    results = []
    suffix = f"_{args.tag}" if args.tag else ""
    out_path = os.path.join(args.out,
                            f"dryrun_{args.mesh}_{args.mode}{suffix}.json")
    # resume support: skip combos already recorded as ok
    done = set()
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f)
        done = {(r["arch"], r["shape"]) for r in results
                if r.get("status") == "ok"}
    for a, s in combos:
        if (a, s) in done:
            print(f"[dryrun] cached {a} x {s}", flush=True)
            continue
        try:
            rec = run_one(a, s, multi, args.mode, args.codist_n,
                          remat=not args.no_remat,
                          codist_extra=codist_extra or None,
                          variant=variant or None)
        except Exception as e:
            rec = {"arch": a, "shape": s, "mesh": args.mesh,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            print(f"[dryrun] FAIL {a} x {s}: {e}", flush=True)
        results = [r for r in results
                   if not (r["arch"] == a and r["shape"] == s)]
        results.append(rec)
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1, default=str)
    ok = sum(1 for r in results if r.get("status") == "ok")
    print(f"[dryrun] {ok}/{len(results)} ok -> {out_path}", flush=True)


if __name__ == "__main__":
    main()
