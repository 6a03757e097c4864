"""The table of peaks and the operation counts, against hand counts."""
import pytest

from chipbench import flops, manifest, peaks


def _cfg(name):
    return manifest.config(manifest.load(), name)


def test_v5e_peaks_and_unknown_kind_refused():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice, match="no published peaks"):
        peaks.peaks_for("TPU v4")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


def test_qwen_0_5b_flops_by_hand():
    cfg = _cfg("qwen1.5-0.5b")
    # 24 layers x (4 * 1024^2 attention + 3 * 1024 * 2816 mlp) + tied head
    # 1024 x 152064 (151936 padded to a multiple of 256)
    layer = 4 * 1024 * 1024 + 3 * 1024 * 2816
    assert layer == 12_845_056
    assert flops.matmul_params(cfg) == 24 * layer + 1024 * 152064
    assert flops.matmul_params(cfg) == 463_994_880
    # causal attention: 6 * 24 layers * 16 heads * 64 * 512 positions
    attn = 6 * 24 * 16 * 64 * 512
    assert flops.train_flops_per_token(cfg, 512) == 6 * 463_994_880 + attn
    assert flops.train_flops_per_token(cfg, 512) == pytest.approx(2.8595e9,
                                                                  rel=1e-4)


def test_qwen_4b_slice_flops_by_hand():
    cfg = _cfg("qwen1.5-4b-slice")
    # 5 layers x (4 * 2560^2 + 3 * 2560 * 6912) + untied head over the
    # vocabulary slice 18992 padded to 19200 (the lookup is not counted)
    layer = 4 * 2560 * 2560 + 3 * 2560 * 6912
    assert layer == 79_298_560
    assert flops.matmul_params(cfg) == 5 * layer + 2560 * 19200
    attn = 6 * 5 * 20 * 128 * 512
    assert flops.train_flops_per_token(cfg, 512) == \
        6 * (5 * layer + 2560 * 19200) + attn


def test_loss_kernel_names_and_costs():
    fwd = ("%jvp_jit_fused_ce_distill_parts__.3 = (f32[2048,1]{1,0:T(8,128)}, "
           "f32[2048,1]{1,0}) custom-call(s32[2048,1]{1,0} %bitcast.978, "
           "bf16[2048,152064]{1,0:T(8,128)(2,1)} %bitcast.17), "
           "custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
           "{s32[2048,1]{1,0}, bf16[2048,152064]{1,0}}")
    assert flops.is_loss_kernel(fwd)
    assert not flops.is_loss_kernel("%fusion.288 = bf16[2,4,512,152064] "
                                    "fusion(%x), kind=kOutput")
    assert not flops.is_loss_kernel(
        "%flash_attention.1 = bf16[8] custom-call(), "
        "custom_call_target=\"tpu_custom_call\"")
    ops, nbytes = flops.loss_kernel_cost(fwd)
    # the constraints after the call are not counted twice
    assert nbytes == 2 * 2048 * 4 + 2048 * 4 + 2048 * 152064 * 2
    assert ops == flops.LOSS_OPS_PER_LOGIT * 2048 * 152064
