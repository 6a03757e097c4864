"""The reduction by scope (``chipbench/scopes.py``) and the program's host
spans. ``data/tiny_scopes_trace.pbtxt`` is cut from a traced run of the tiny
configuration (``tiny.py``) on one v5e: three traced steps, trimmed to the
first two, with the batch generator's call before them; each op keeps its
metadata's ``tf_op`` and ``program_id``, and the host keeps the harness's
``bench.`` and the program's ``train.`` spans."""
import os
import re

import jax
import pytest

from chipbench import manifest, scopes, trace_reduce
from chipbench.tests import tiny

DATA = os.path.join(os.path.dirname(__file__), "data")
READERS = ("attention_share", "mlp_share", "head_share", "optimizer_share",
           "recompute_share")


def _context(name):
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, name)) as f:
        xspace = ProfileData.text_proto_to_serialized_xspace(f.read())
    tr = trace_reduce.from_profile(ProfileData.from_serialized_xspace(xspace))
    bench = manifest.load()
    cell = manifest.cell(bench, tiny.CELL)
    ctx = trace_reduce.Context(
        trace=tr, cfg=manifest.config(bench, cell["config"]),
        traffic=manifest.traffic(cell["traffic"]),
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}, chips=1,
        tokens_per_step=4096, steps=2)
    ctx.xspace = xspace
    return ctx


@pytest.fixture(scope="module")
def ctx():
    return _context("tiny_scopes_trace.pbtxt")


def test_path_strips_the_transform_wrappers():
    assert scopes.path("jit(step)/transpose(jvp(vmap(layers)))/while/body/"
                       "closed_call/checkpoint/rematted_computation/attn/"
                       "bsd,dhk->bshk/dot_general") == (
        "step", "layers", "while", "body", "closed_call", "checkpoint",
        scopes.REMAT, "attn", "bsd,dhk->bshk", "dot_general")
    assert scopes.path("") == ("",)


def test_a_trace_without_the_programs_scopes_reads_nothing():
    """``small_trace.pbtxt`` predates the scopes: every new reader returns
    None."""
    ctx = _context("small_trace.pbtxt")
    assert scopes.of(ctx) is None
    for name in READERS:
        assert manifest.metric_reader(name)(ctx) is None


def test_ops_keep_their_program_and_path(ctx):
    red = scopes.of(ctx)
    assert red is not None
    progs = {op.program for op in red.ops}
    assert progs == {"jit_step", "jit_chipbench_batch"}
    # the batch generator's ops are never under the step's scopes
    for op in red.ops:
        if op.program == "jit_chipbench_batch":
            assert not set(op.path) & set(scopes.SCOPES), op
    d0 = ctx.devices[0]
    assert sorted((o.event.start, o.event.end) for o in red.ops) == sorted(
        (e.start, e.end) for e in ctx.trace.ops[d0])


def test_self_times_match_the_contexts_and_fill_busy_time(ctx):
    red = scopes.of(ctx)
    per = {}
    for op, t in zip(red.ops, red.self_ns):
        k = trace_reduce.short(op.event.name)
        per[k] = per.get(k, 0) + t
    assert per == ctx.self_times(ctx.devices[0])
    assert sum(red.self_ns) == red.busy_ns == ctx.busy_ns(ctx.devices[0])


def _ops_from_text():
    """(start_ns, end_ns, tf_op) of every op on device 0's ``XLA Ops`` line,
    read from the text of the trace with regular expressions alone."""
    with open(os.path.join(DATA, "tiny_scopes_trace.pbtxt")) as f:
        text = f.read()
    dev = text.split("planes {")[1]
    tf_op = {}
    for line in dev.splitlines():
        key = re.match(r"  event_metadata \{ key: (\d+) ", line)
        op = re.search(r'str_value: "([^"]*)"', line)
        if key and op:
            tf_op[int(key.group(1))] = op.group(1)
    ops_line = dev.split('name: "XLA Ops"')[1].split("  }")[0]
    base = int(re.search(r"timestamp_ns: (\d+)", ops_line).group(1))
    out = []
    for mid, off, dur in re.findall(r"metadata_id: (\d+) offset_ps: (\d+) "
                                    r"duration_ps: (\d+)", ops_line):
        start = base + int(off) // 1000          # whole ns, as ProfileData
        out.append((start, start + int(dur) // 1000, tf_op.get(int(mid), "")))
    return out


def test_shares_by_hand(ctx):
    """Self time brute force: each op's interval less the union of the ops
    inside it, summed by the scopes its ``tf_op`` names."""
    ops = _ops_from_text()
    assert len(set((s, e) for s, e, _ in ops)) == len(ops)
    busy = ctx.busy_ns(ctx.devices[0])
    want = {}
    for name, names in (("attention_share", {"attn"}),
                        ("mlp_share", {"mlp"}),
                        ("head_share", {"embed", "head"}),
                        ("optimizer_share", {"optimizer"}),
                        ("recompute_share", {scopes.REMAT})):
        ns = 0
        for s, e, op_name in ops:
            if names & set(scopes.path(op_name.rsplit(":", 1)[0])):
                inner = [(a, b) for a, b, _ in ops
                         if s <= a and b <= e and (a, b) != (s, e)]
                ns += e - s - trace_reduce.length(trace_reduce.union(inner))
        want[name] = 100.0 * ns / busy
    red = scopes.of(ctx)
    for name in READERS:
        got = manifest.metric_reader(name)(ctx)
        assert got == pytest.approx(want[name]) and 0 < got < 100, name
    # the layers' halves lie inside "layers"; the top-level scopes and the
    # ops under none of them account for all busy time
    assert red.ns("attn") + red.ns("mlp") <= red.ns("layers")
    top = sum(t for op, t in zip(red.ops, red.self_ns)
              if set(op.path) & set(scopes.TOP))
    assert top + sum(t for _, t in red.unscoped()) == busy


def test_dispatch_leads_pair_each_dispatch_with_its_steps_program(ctx):
    """The i-th ``train.dispatch`` against the i-th run of the step program.
    In this trace each run reads as starting before the span that launched
    it, by 26 and 127 us: the profiler's host and device clocks disagree by
    that much, which bounds how finely a gap can be tied to a host span."""
    red = scopes.of(ctx)
    disp = [s for s in red.spans if s.name == "train.dispatch"]
    runs = [e for p, e in red.modules if p == "jit_step"]
    assert [s.step for s in disp] == [5, 6] and len(runs) == 2
    assert scopes.dispatch_leads(red) == [
        runs[0].start - disp[0].start, runs[1].start - disp[1].start] == [
        45458277 - 45484329, 46731325 - 46858069]


def test_program_spans_share_the_profilers_clock(tmp_path):
    """Two ``StepBundle.apply`` calls under a CPU profile: their
    ``train.dispatch`` spans carry steps 0 and 1, sit in one list with the
    harness's ``bench.`` spans, each inside its ``bench.dispatch``, while
    ``trace_reduce`` still reads the ``bench.`` spans alone."""
    from jax.profiler import ProfileData
    from chipbench import program, traffic_gen, weights
    bench, cell = tiny.bench_and_cell(tmp_path)
    cfg = manifest.config(bench, cell["config"])
    traffic = tiny.traffic()
    prog = program.build(cfg, traffic, manifest.reference(cfg)
                         .param_layout(cfg))
    state = prog.init_state(weights.weights_key(3))
    batches = traffic_gen.batch_fn(traffic, 2)(weights.data_key(3), 0)
    trace_dir = tmp_path / "trace"
    jax.profiler.start_trace(str(trace_dir))
    for k, b in enumerate(batches):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            state, _, _ = prog.bundle.apply(state, b, k)
    jax.block_until_ready(state)
    jax.profiler.stop_trace()
    (path,) = trace_dir.glob("**/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    spans = scopes.spans(pd)
    disp = [s for s in spans if s.name == "train.dispatch"]
    outer = [s for s in spans if s.name == "bench.dispatch"]
    assert [s.step for s in disp] == [0, 1]
    assert all(o.start <= d.start and d.end <= o.end
               for o, d in zip(outer, disp))
    assert [(s.name, s.start, s.end) for s in spans
            if s.name.startswith("bench.")] == [
        (s.name, s.start, s.end) for s in trace_reduce.from_profile(pd).spans]
    assert prog.bundle.traced_at == {"on": [0]}
