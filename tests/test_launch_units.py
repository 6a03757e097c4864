"""Unit tests for the launch substrate: HLO collective parsing, sharding
rules, roofline math, comm-cost integration — no device mesh needed."""
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import INPUT_SHAPES, get_config
from repro.launch import hlo_analysis as ha
from repro.launch import roofline as rl


class TestHloParser:
    def test_shape_bytes(self):
        assert ha._shape_bytes("bf16[16,4096]{1,0}") == 16 * 4096 * 2
        assert ha._shape_bytes("f32[8]") == 32
        assert ha._shape_bytes("(f32[2,2], bf16[4])") == 16 + 8
        assert ha._shape_bytes("pred[]") == 1
        assert ha._shape_bytes("token[]") == 0

    def test_explicit_replica_groups(self):
        line = ('  %ag = bf16[8,16]{1,0} all-gather(bf16[2,16]{1,0} %p), '
                'channel_id=1, replica_groups={{0,1},{2,3}}, dimensions={0}')
        s = ha.parse_collectives(line, devices_per_pod=2)
        assert len(s.ops) == 1
        assert s.ops[0].kind == "all-gather"
        assert s.ops[0].operand_bytes == 2 * 16 * 2
        assert not s.ops[0].cross_pod  # {0,1} and {2,3} stay within pods

    def test_cross_pod_groups(self):
        line = ('  %ar = f32[4]{0} all-reduce(f32[4]{0} %p), channel_id=2, '
                'replica_groups={{0,2},{1,3}}, to_apply=%add')
        s = ha.parse_collectives(line, devices_per_pod=2)
        assert s.ops[0].cross_pod  # 0 and 2 are in different pods

    def test_iota_replica_groups(self):
        # [2,2]<=[4]: groups [[0,1],[2,3]] — intra-pod at dpp=2
        line = ('  %ag = f32[4]{0} all-gather(f32[2]{0} %p), channel_id=3, '
                'replica_groups=[2,2]<=[4], dimensions={0}')
        s = ha.parse_collectives(line, devices_per_pod=2)
        assert not s.ops[0].cross_pod
        # transposed iota: [2,2]<=[2,2]T(1,0): groups [[0,2],[1,3]] — cross
        line2 = line.replace("[2,2]<=[4]", "[2,2]<=[2,2]T(1,0)")
        s2 = ha.parse_collectives(line2, devices_per_pod=2)
        assert s2.ops[0].cross_pod

    def test_collective_permute_pairs(self):
        line = ('  %cp = f32[8]{0} collective-permute(f32[8]{0} %p), '
                'channel_id=4, source_target_pairs={{0,2},{2,0}}')
        s = ha.parse_collectives(line, devices_per_pod=2)
        assert s.ops[0].cross_pod
        assert s.cross_pod_bytes == 32

    def test_summary_accounting(self):
        text = "\n".join([
            '  %a = f32[4]{0} all-reduce(f32[4]{0} %p), replica_groups={{0,1}}',
            '  %b = f32[8]{0} all-gather(f32[2]{0} %q), replica_groups={{0,2}}',
        ])
        s = ha.parse_collectives(text, devices_per_pod=2)
        assert s.total_bytes == 16 + 8
        assert s.cross_pod_bytes == 8
        assert s.intra_pod_bytes == 16
        assert s.counts() == {"all-reduce": 1, "all-gather": 1}


class TestRoofline:
    def test_terms_and_bottleneck(self):
        shape = INPUT_SHAPES["train_4k"]
        cfg = get_config("qwen1.5-0.5b")
        r = rl.build_report("qwen1.5-0.5b", shape, "16x16", 256,
                            hlo_flops=1.97e14, hlo_bytes=8.19e11,
                            collective_bytes=5e10, cross_pod_bytes=0.0,
                            cfg=cfg)
        assert r.compute_s == pytest.approx(1.0)
        assert r.memory_s == pytest.approx(1.0)
        assert r.collective_s == pytest.approx(1.0)
        r2 = rl.build_report("x", shape, "m", 256, 1e12, 8.19e12, 1e9, 0, cfg)
        assert r2.bottleneck == "memory"

    def test_model_flops_kinds(self):
        cfg = get_config("qwen1.5-0.5b")
        n = rl.active_params(cfg)
        tr = rl.model_flops(cfg, INPUT_SHAPES["train_4k"])
        pf = rl.model_flops(cfg, INPUT_SHAPES["prefill_32k"])
        dc = rl.model_flops(cfg, INPUT_SHAPES["decode_32k"])
        assert tr == pytest.approx(6 * n * 256 * 4096)
        assert pf == pytest.approx(2 * n * 32 * 32768)
        assert dc == pytest.approx(2 * n * 128)

    def test_moe_active_params_much_smaller(self):
        cfg = get_config("arctic-480b")
        assert rl.active_params(cfg) < 0.1 * cfg.param_count()


class TestShardingRules:
    @pytest.fixture(scope="class")
    def mesh(self):
        # AbstractMesh avoids touching real devices
        from jax.sharding import AbstractMesh
        return AbstractMesh((16, 16), ("data", "model"))

    def test_attention_head_fallback_replicates(self, mesh):
        from repro.launch.sharding import param_spec
        # 28 heads not divisible by 16 -> head dim must NOT slide to head_dim
        spec = param_spec("layers/sub0/mix/wq", (28, 3584, 28, 128), mesh,
                          scanned=True)
        assert spec[2] is None and spec[3] is None
        assert spec[1] == "data"
        # 64 heads divide -> sharded over model
        spec2 = param_spec("layers/sub0/mix/wq", (95, 8192, 64, 128), mesh,
                           scanned=True)
        assert spec2[2] == "model"

    def test_ffn_slide_fallback(self, mesh):
        from repro.launch.sharding import param_spec
        # whisper d_ff=1536 divisible; d_model=384 divisible
        spec = param_spec("dec_layers/ffn/w_up", (4, 384, 1536), mesh,
                          scanned=True)
        assert spec == jax.sharding.PartitionSpec(None, "data", "model")

    def test_expert_axis_option(self, mesh):
        from repro.launch.sharding import param_spec
        spec = param_spec("layers/sub0/ffn/w_gate", (35, 128, 7168, 4864),
                          mesh, scanned=True, moe_expert_axis="data")
        assert spec[1] == "data" and spec[3] == "model" and spec[2] is None

    def test_scan_axis_never_sharded(self, mesh):
        from repro.launch.sharding import param_spec
        spec = param_spec("layers/sub0/ffn/w_up", (96, 8192, 22016), mesh,
                          scanned=True)
        assert spec[0] is None

    def test_stacked_codist_axis(self):
        from jax.sharding import AbstractMesh

        from repro.launch.sharding import param_spec
        mesh = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
        spec = param_spec("layers/sub0/ffn/w_up", (2, 24, 1024, 2816), mesh,
                          stacked=True, scanned=True)
        assert spec[0] == "pod" and spec[1] is None

    def test_two_d_ffn_decode(self):
        from jax.sharding import AbstractMesh

        from repro.launch.sharding import param_spec
        mesh = AbstractMesh((16, 16), ("data", "model"))
        spec = param_spec("layers/sub0/ffn/w_up", (28, 3584, 18944), mesh,
                          scanned=True, two_d_ffn=True)
        assert spec[2] == ("data", "model")
        # attention untouched by the 2d-ffn variant
        spec2 = param_spec("layers/sub0/mix/wo", (28, 3584, 3584), mesh,
                           scanned=True, two_d_ffn=True)
        assert spec2[1] == "model" and spec2[2] == "data"


class TestHierarchicalTopK:
    def test_exact_vs_lax(self):
        import numpy as np
        from repro.core.codistillation import _hierarchical_topk
        x = jax.random.normal(jax.random.key(3), (5, 2048))
        for k in (1, 16, 100):
            v1, i1 = jax.lax.top_k(x, k)
            v2, i2 = _hierarchical_topk(x, k, segments=16)
            np.testing.assert_allclose(np.asarray(v1), np.asarray(v2))
            np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    def test_fallback_small_vocab(self):
        from repro.core.codistillation import _hierarchical_topk
        x = jax.random.normal(jax.random.key(0), (3, 100))
        v, i = _hierarchical_topk(x, 50, segments=16)  # 100/16 < 50 -> fallback
        assert v.shape == (3, 50)


# ----------------------------------------------------------------------------
# chip entry points: no CPU fallback, fixed compile-cache location
# ----------------------------------------------------------------------------

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_refuses_to_run_without_the_chip(where, tmp_path):
    """Under JAX_PLATFORMS=cpu (and with nothing of the repo beside it) the
    smoke exits non-zero before any phase and prints no result line."""
    script = os.path.join(_ROOT, "chip_smoke.py")
    if where == "alone":
        shutil.copy(script, tmp_path / "chip_smoke.py")
        script = str(tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, script], cwd=os.path.dirname(script),
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "ok:" not in out.stdout            # no phase ran
    expect = "no TPU" if where == "checkout" else "src/ is not beside"
    assert expect in out.stderr


def test_compile_cache_dir_is_fixed(monkeypatch):
    from repro.launch.compile_cache import CHECKOUT, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        assert path == os.path.join(_ROOT, ".jax_cache")
        assert str(CHECKOUT) == _ROOT
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
