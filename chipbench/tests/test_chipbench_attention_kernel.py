"""``attention_kernel_share`` on small synthetic traces: device 0's ops as
the profiler names them (the HLO text of each op), with the fused causal
attention kernel's custom calls among them or not."""
import pytest

from chipbench import manifest, trace_reduce
from chipbench.metrics import attention_kernel_share as akm

CALL = ', custom_call_target="tpu_custom_call"'
FWD = ("%causal_attention_fwd.16 = (bf16[2,4,1024,512]{3,2,1,0}, "
       "f32[2,4,16,1,512]{4,3,2,1,0}) custom-call(bf16[2,4,1024,512]"
       "{3,2,1,0} %bitcast.1, bf16[2,4,1024,512]{3,2,1,0} %bitcast.2)" + CALL)
BWD = ("%causal_attention_bwd.10 = (bf16[2,4,1024,512]{3,2,1,0}, "
       "bf16[2,4,1024,512]{3,2,1,0}, bf16[2,4,1024,512]{3,2,1,0}) "
       "custom-call(bf16[2,4,1024,512]{3,2,1,0} %bitcast.3)" + CALL)
REMAT = ("%causal_attention_fwd.17.clone = (bf16[2,4,1024,512]{3,2,1,0}, "
         "f32[2,4,16,1,512]{4,3,2,1,0}) custom-call(bf16[2,4,1024,512]"
         "{3,2,1,0} %bitcast.4)" + CALL)
# ops that name a kernel's result but are not the kernel
USER = ("%fusion.7 = bf16[2,4,512,1024]{3,2,1,0} fusion(bf16[2,4,1024,512]"
        "{3,2,1,0} %causal_attention_fwd.16), kind=kLoop")
LOSS = ("%fused_ce_distill_grad.2 = (bf16[2048,152064]{1,0}) custom-call("
        "bf16[2048,152064]{1,0} %bitcast.16)" + CALL)
MATMUL = "%fusion.12 = bf16[2048,1024]{1,0} fusion(bf16[2048,1024]{1,0} %p)"


def _ctx(ops):
    """A one-device context over ``ops``: (op text, start ns, end ns)."""
    trace = trace_reduce.Trace(
        ops={0: [trace_reduce.Event(n, s, e) for n, s, e in ops]},
        spans=[trace_reduce.Event("bench.step", 0, 1000)])
    return trace_reduce.Context(trace=trace, cfg={}, traffic={}, peaks=None,
                                chips=1, tokens_per_step=4096, steps=1)


def test_the_benchmark_reads_it_in_both_cells():
    bench = manifest.load()
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "attention_kernel_share"]
    assert entry["layer"] == "attention" and entry["moves"] == "tokens_per_s"
    assert set(entry["workloads"]) == {c["name"] for c in bench["workloads"]}
    ctx = _ctx([(FWD, 0, 100), (MATMUL, 100, 400)])
    assert manifest.metric_reader("attention_kernel_share")(ctx) == 25.0


@pytest.mark.parametrize("op,want", [(FWD, True), (BWD, True), (REMAT, True),
                                     (USER, False), (LOSS, False),
                                     (MATMUL, False)])
def test_which_ops_are_the_kernel(op, want):
    assert akm.is_attention_kernel(op) is want


def test_no_kernel_op_reads_zero():
    ctx = _ctx([(MATMUL, 0, 400), (USER, 400, 500), (LOSS, 600, 1000)])
    assert akm.read(ctx) == 0.0


def test_share_of_busy_time():
    """Forward 100 ns, its recompute 50, backward 150 over 800 ns of busy
    time (a 200 ns gap is not busy)."""
    ctx = _ctx([(MATMUL, 0, 200), (FWD, 200, 300), (USER, 300, 400),
                (REMAT, 600, 650), (BWD, 650, 800), (MATMUL, 800, 1000)])
    assert ctx.busy_ns(0) == 800
    assert akm.read(ctx) == pytest.approx(100.0 * 300 / 800)


def test_a_trace_with_no_device_time_reads_nothing():
    ctx = _ctx([(FWD, 10, 10)])
    assert akm.read(ctx) is None
