"""attention_kernel_share: device time of the fused causal attention
kernels' custom calls (the forward, also as remat recomputes it, and the
backward: ``src/repro/kernels/causal_attention.py``) over device 0's busy
time. Reads 0.0 where none of them ran, so a fall-back to the dense S x S
core shows."""
import re

from chipbench import trace_reduce

# the kernels' HLO names come from their ``pallas_call`` names, e.g.
# ``%causal_attention_fwd.16``, ``%causal_attention_bwd.10``
KERNEL = re.compile(r"%causal_attention_(fwd|bwd)\b")


def is_attention_kernel(op: str) -> bool:
    return ("tpu_custom_call" in op
            and KERNEL.match(trace_reduce.short(op)) is not None)


def read(ctx):
    d0 = ctx.devices[0]
    busy = ctx.busy_ns(d0)
    if busy <= 0:
        return None
    return 100.0 * ctx.op_ns(d0, is_attention_kernel) / busy
