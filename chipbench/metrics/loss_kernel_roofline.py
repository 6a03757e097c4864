"""loss_kernel_roofline: the least time the chip could take for the fused loss
kernels' calls, the larger of operations over peak FLOP/s and bytes over HBM
bandwidth for each call (``flops.loss_kernel_cost``, from the shapes in the
op's text; the bytes bound every call), over their summed device time on
device 0."""
from chipbench import flops


def read(ctx):
    if not ctx.peaks:
        return None
    least, spent = 0.0, 0
    for e in ctx.ops(ctx.devices[0], flops.is_loss_kernel):
        ops, nbytes = flops.loss_kernel_cost(e.name)
        least += max(ops / ctx.peaks["bf16_flops"],
                     nbytes / ctx.peaks["hbm_bytes_per_s"])
        spent += e.dur
    if spent <= 0:
        return None
    return 100.0 * least / (spent * 1e-9)
