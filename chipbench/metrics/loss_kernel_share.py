"""loss_kernel_share: device time of the fused loss kernels (cross-entropy,
distillation, combined; forward and backward) over device 0's busy time."""
from chipbench import flops


def read(ctx):
    d0 = ctx.devices[0]
    busy = ctx.busy_ns(d0)
    kern = ctx.op_ns(d0, flops.is_loss_kernel)
    if busy <= 0 or kern <= 0:
        return None
    return 100.0 * kern / busy
