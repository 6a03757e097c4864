"""The reduction from a profiler trace to the per-layer numbers, on a small
recorded trace against a brute-force count over every nanosecond of the
window. ``data/small_trace.pbtxt`` is cut from a traced run of
``train-codist2-qwen1.5-0.5b`` on one v5e: the start of the first traced step
(the batch generator) and the four loss-kernel calls of that step, with
everything on device 0 between them left out, and the harness's host spans."""
import os

import numpy as np
import pytest

from chipbench import flops, manifest, trace_reduce
from chipbench.tests import tiny  # noqa: F401  (puts the repo on the path)

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.pbtxt")


@pytest.fixture(scope="module")
def ctx():
    from jax.profiler import ProfileData
    with open(DATA) as f:
        tr = trace_reduce.from_profile(ProfileData.from_text_proto(f.read()))
    bench = manifest.load()
    cell = manifest.cell(bench, "train-codist2-qwen1.5-0.5b")
    return trace_reduce.Context(
        trace=tr, cfg=manifest.config(bench, cell["config"]),
        traffic=manifest.traffic(cell["traffic"]),
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}, chips=1,
        tokens_per_step=4096, steps=1)


def _mask(ctx, events):
    m = np.zeros(ctx.hi - ctx.lo, bool)
    for e in events:
        s, t = max(e.start, ctx.lo) - ctx.lo, min(e.end, ctx.hi) - ctx.lo
        if t > s:
            m[s:t] = True
    return m


def test_window_and_busy_union(ctx):
    d0 = ctx.devices[0]
    steps = [s for s in ctx.trace.spans if s.name == "bench.step"]
    assert ctx.lo == min(steps[0].start, ctx.trace.ops[d0][0].start)
    assert ctx.hi == max(e.end for e in ctx.trace.ops[d0])
    busy = _mask(ctx, ctx.trace.ops[d0])
    assert ctx.busy_ns(d0) == int(busy.sum())
    assert 0 < ctx.busy_ns(d0) < ctx.hi - ctx.lo


def test_loss_kernel_time_and_bytes(ctx):
    d0 = ctx.devices[0]
    kern = [e for e in ctx.trace.ops[d0] if flops.is_loss_kernel(e.name)]
    # per model: one forward (CE + MSE parts), one backward (both grads)
    fwd = [e for e in kern if "fused_ce_distill_parts" in e.name]
    bwd = [e for e in kern if "fused_ce_distill_grad" in e.name]
    assert len(fwd) == 2 and len(bwd) == 2 and len(kern) == 4
    want = sum(min(e.end, ctx.hi) - max(e.start, ctx.lo) for e in kern)
    assert ctx.op_ns(d0, flops.is_loss_kernel) == want
    share = manifest.metric_reader("loss_kernel_share")(ctx)
    assert share == pytest.approx(100.0 * want / ctx.busy_ns(d0))
    # bf16 (2048, 152064) logits: the forward reads two, the backward reads
    # two and writes two; the (2048, 1) columns are 4 bytes a token
    tile, col = 2048 * 152064 * 2, 2048 * 4
    assert flops.loss_kernel_cost(fwd[0].name)[1] == 2 * tile + 5 * col
    assert flops.loss_kernel_cost(bwd[0].name)[1] == 4 * tile + 5 * col
    least = sum(flops.loss_kernel_cost(e.name)[1] / 819e9 for e in kern)
    roof = manifest.metric_reader("loss_kernel_roofline")(ctx)
    assert roof == pytest.approx(100.0 * least / (sum(e.dur for e in kern)
                                                  * 1e-9))
    assert 0 < roof < 100


def test_idle_gaps_named_by_the_open_host_span(ctx):
    d0 = ctx.devices[0]
    busy = _mask(ctx, ctx.trace.ops[d0])
    idle = np.flatnonzero(~busy)
    runs = np.split(idle, np.flatnonzero(np.diff(idle) > 1) + 1)
    want = sorted(((r[-1] - r[0] + 1) for r in runs if r.size), reverse=True)
    got = ctx.breakdown()["idle_gaps"]
    assert [round(s * 1e9) for _, s in got] == want[:len(got)]
    for name, _ in got:
        assert name.startswith("bench.") or name == "no harness span"
    assert manifest.metric_reader("device_idle_share")(ctx) == pytest.approx(
        100.0 * (1 - busy.sum() / busy.size))
