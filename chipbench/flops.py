"""Operations and bytes from shapes: the model's work per token, and each
loss kernel's work per call.

Model FLOPs per trained token are 6 x the parameters that take part in a
matrix product (every layer's projections and the output head; not the
embedding lookup) plus causal attention's two products, forward and
backward: ``6 * L * heads * head_dim * seq`` per token. Recomputation under
remat is not counted.

A loss kernel streams (T, V) logits tiles through VMEM. Its bytes are what
it reads and writes in HBM: every operand and result, from the shapes in the
op's own text in the trace. Its operations are elementwise, a few dozen per
logit, so the bytes bound every call (about 3 operations per byte, where the
v5e's ridge is 240).
"""
from __future__ import annotations

import re
from typing import Dict, List, Tuple

from chipbench.shapes import dims


def matmul_params(cfg: Dict) -> int:
    """Parameters that enter a matrix product, per model."""
    z = dims(cfg)
    d, h, kv, hd, ff, L, V = (z[k] for k in ("d", "h", "kv", "hd", "ff", "L", "V"))
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = 3 * d * ff
    return L * (attn + mlp) + d * V


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    z = dims(cfg)
    attn = 6 * z["L"] * z["h"] * z["hd"] * seq     # causal: half of 12
    return 6.0 * matmul_params(cfg) + attn


# The fused loss kernels appear in the device trace as Mosaic custom calls
# named after the jitted wrapper that launched them, e.g.
# ``%jvp_jit_fused_ce_distill_parts__.3`` (forward) and
# ``%transpose_jvp_jit_fused_ce_distill_grad___.4`` (backward); the op text
# carries every operand's and result's shape.
LOSS_KERNEL = re.compile(r"fused_(cross_entropy|ce_distill|distill)")
_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|f64|"
                    r"f8e4m3fn|f8e5m2)\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2,
          "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4,
          "s64": 8, "u64": 8, "f64": 8}
# elementwise operations per logit, an upper count over the kernels' bodies
# (src/repro/kernels/{fused_ce,distill_loss,combined_loss}.py: the combined
# CE+KL backward is the largest at about 24)
LOSS_OPS_PER_LOGIT = 24


def is_loss_kernel(op: str) -> bool:
    return "tpu_custom_call" in op and bool(LOSS_KERNEL.search(op))


def _shapes(op: str) -> List[Tuple[int, int]]:
    """(elements, bytes per element) of every result and operand of an HLO
    op's text (layout constraints after the call are not counted)."""
    head = op.split(", custom_call_target=")[0]
    out = []
    for dtype, dims in _SHAPE.findall(head):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out.append((n, _BYTES[dtype]))
    return out


def loss_kernel_cost(op: str) -> Tuple[float, float]:
    """(operations, bytes in and out of HBM) of one loss-kernel call."""
    shapes = _shapes(op)
    logits = max(n for n, _ in shapes)
    return (float(LOSS_OPS_PER_LOGIT * logits),
            float(sum(n * b for n, b in shapes)))
