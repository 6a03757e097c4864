"""One run of one cell: set-up, the measured window, the traced steps, and the
comparison that decides ``correct``.

The step is the one ``program.build`` makes from the traffic file's
``strategy``: ``prediction`` (Algorithm 1, ``models`` peers stacked on one
chip) or ``all_reduce`` (the paper's baseline, one model).

Set-up (``setup_s``) runs from process start to the end of the third step:
the state is made on the device from the seed in one jitted call, and the
first three steps go through ``StepBundle.apply`` with the window's own
batches (the first compiles, or loads from the persistent cache). The window
then drives the same state and the same compiled step. ``traffic_gen`` makes
the batches of each ``log_every`` steps in one call at the first of them, and
a loss is read to the host every ``log_every`` steps, as ``repro.train.loop``
does, but ``READ_LAG`` intervals late, as an asynchronous logger reads it.
Both keep the chip's queue deep: the host of a one-chip machine stops for
about 0.1 s every few seconds and now and then for seconds, and while it
stops the chip runs on only through the work already queued (on a v5e the
runtime held dispatches back at about 32 programs in flight). The host stops
dispatching when the steps it has queued would take the device to
``seconds``, and the window ends with ``block_until_ready``.
``tokens_per_s`` is every model's tokens over the window's wall time.

A traced run (``trace=True``) profiles ``trace_steps`` steps inside the
window, with host spans around batch making, dispatch and the log read, and
reports the per-layer metrics instead of the end-to-end ones.

After the window the state is freed, the device's peak is read, and the
plain reference replays the first three steps (``check.py``).
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

from chipbench import check, manifest, program, traffic_gen, weights

# The loss of a log step is read to the host this many log intervals later,
# so that the chip has that much work queued whenever the host stops.
READ_LAG = 2
DISPATCH_WAIT_S = 0.02   # a dispatch that took longer was held by the runtime


def info(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts compile requests (persistent-cache loads included) while
    ``active``: inside the window there should be none."""

    def __init__(self):
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if self.active and name == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def _profile_options():
    """Device ops and host spans; no Python call events, no HLO dumps."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def _span(on: bool, name: str):
    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


def peak_bytes(devices: List) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(bench: Dict, cell: Dict, seed: int, seconds: float, trace: bool,
             devices: List, t_start: float, trace_dir: Optional[Path] = None,
             peaks: Optional[Dict] = None, traffic: Optional[Dict] = None,
             limits: Optional[Dict] = None) -> Dict:
    """The result line of one run. ``traffic`` and ``limits`` default to the
    cell's files."""
    cfg = manifest.config(bench, cell["config"])
    traffic = traffic or manifest.traffic(cell["traffic"])
    limits = limits or manifest.limits(cell["name"])
    ref = manifest.reference(cfg)
    layout = ref.param_layout(cfg)
    used = devices[:int(cell["chips"])]
    compiles = CompileCounter()

    prog = program.build(cfg, traffic, layout)
    log_every = int(traffic["log_every"])
    make_batches = traffic_gen.batch_fn(traffic, log_every)
    dkey = weights.data_key(seed)
    state, prog_read, batches = check.program_steps(prog, seed, make_batches,
                                                    dkey)
    # tracing and compiling left millions of long-lived objects; a full
    # collection over them in the window stalls the host for up to 0.5 s
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    info(f"info: setup_s={setup_s:.3f} first losses="
         + " ".join(f"{x:.6f}" for x in prog_read["losses"]))

    # ---- the measured window -------------------------------------------
    n_trace = int(traffic["trace_steps"]) if trace else 0
    trace_after = log_every      # the traced steps start a batch call
    losses, marks = [], []
    logged = []                  # losses of the last log steps, not yet read
    read_step = None             # the step of the loss read last
    waits = 0                    # dispatches the runtime held back
    k = check.STEPS
    traced = None
    compiles.active = True
    t0 = time.perf_counter()
    while True:
        w = k - check.STEPS
        if trace and w == trace_after:
            # start drained, so the window holds only the traced steps' work
            jax.block_until_ready(state)
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=_profile_options())
        tracing = trace and trace_after <= w < trace_after + n_trace
        with _span(tracing, "bench.step"):
            with _span(tracing, "bench.batch"):
                if w % log_every == 0:
                    chunk = make_batches(dkey, k)
                b = chunk[w % log_every]
            t_apply = time.perf_counter()
            with _span(tracing, "bench.dispatch"):
                state, metrics, _ = prog.bundle.apply(state, b, k)
            waits += time.perf_counter() - t_apply > DISPATCH_WAIT_S
            losses.append(metrics["loss"])
            if (w + 1) % log_every == 0:
                logged.append((w, metrics["loss"]))
                if len(logged) > READ_LAG:
                    read_step, loss = logged.pop(0)
                    with _span(tracing, "bench.log_read"):
                        float(loss)
                marks.append(time.perf_counter())
        if trace and w == trace_after + n_trace - 1:
            with _span(True, "bench.drain"):
                jax.block_until_ready(state)
            jax.profiler.stop_trace()
            traced = n_trace
        k += 1
        if trace and not traced:
            continue
        if read_step is None or len(marks) < 2:
            done_at = time.perf_counter()
        else:
            # the device finishes step w about as many steps after the last
            # read as it then still had queued
            step_s = (marks[-1] - marks[-2]) / log_every
            done_at = marks[-1] + (w - read_step) * step_s
        if done_at - t0 >= seconds:
            break
    jax.block_until_ready(state)
    window_s = time.perf_counter() - t0
    compiles.active = False
    steps = len(losses)
    host_losses = np.asarray(jax.device_get(losses), np.float64)
    failed = int(np.sum(~np.isfinite(host_losses)))
    info(f"info: window steps={steps} window_s={window_s:.4f} "
         f"compiles_in_window={compiles.count} dispatch_waits={waits} "
         f"last_loss={host_losses[-1]:.6f}")
    info(f"info: s/step per {log_every}-step interval: " + " ".join(
        f"{(b - a) / log_every:.4f}" for a, b in zip([t0] + marks, marks)))

    mem_peak = peak_bytes(used)
    del state, metrics, losses, logged, chunk, b
    prog = None

    # ---- the comparison --------------------------------------------------
    t_ref = time.perf_counter()
    ref_read = check.ReferenceSteps(ref, cfg, traffic)(seed, batches)
    info(f"info: reference_s={time.perf_counter() - t_ref:.3f}")
    read = check.readings(prog_read, ref_read)
    ok, checks = check.verdict(read, limits)
    ok = ok and failed == 0 and steps > 0

    dev = used[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": mem_peak}
    out = {"correct": bool(ok), "attempted": steps, "failed": failed,
           "metrics": {}, "device": device}
    tokens = steps * _tokens_per_step(traffic)
    if not trace:
        values = {"tokens_per_s": tokens / window_s, "setup_s": setup_s}
        for m in manifest.end_to_end(bench, cell["name"]):
            if m["name"] in values:
                out["metrics"][m["name"]] = {"value": values[m["name"]],
                                             "unit": m["unit"]}
    else:
        from chipbench import trace_reduce
        red = trace_reduce.load_dir(trace_dir, len(used))
        ctx = trace_reduce.Context(
            trace=red, cfg=cfg, traffic=traffic, peaks=peaks,
            chips=len(used), tokens_per_step=_tokens_per_step(traffic),
            steps=traced)
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.window_s
        for m in manifest.per_layer(bench, cell["name"]):
            value = manifest.metric_reader(m["name"])(ctx)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = ctx.breakdown()
    out["checks"] = checks
    for name in check.NUMBERS:
        if name not in checks:
            info(f"info: {name} {read[name][0]:.6g} read, not compared "
                 f"({read[name][1]})")
    for name, c in checks.items():
        info(f"check: {name} {c['value']:.6g} limit {c['limit']:.6g} "
             f"({c['leaf']})")
    return out


def _tokens_per_step(traffic: Dict) -> int:
    return int(traffic["models"]) * int(traffic["batch"]) * int(traffic["seq"])

