"""A whole run on the CPU at a tiny size, with the look for a chip skipped:
a sound step is ``correct``, and a broken one is not. The tiny model runs
its activations in float32, so a sound step reads near zero against the
cell's limits and only a fault can fail them."""
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

from chipbench import harness, manifest, program
from chipbench.tests import tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(tmp_path, seed=2**33 + 5, name=tiny.CELL, traffic="codist2"):
    bench, cell = tiny.bench_and_cell(tmp_path, traffic, name,
                                      activation_dtype="float32")
    return harness.run_cell(bench, cell, seed, 0.3, False, jax.devices(),
                            time.perf_counter(), traffic=tiny.traffic(traffic),
                            limits=manifest.limits(name))


def _run_allreduce(tmp_path):
    return _run(tmp_path, 2**33 + 9, tiny.ALLREDUCE_CELL, "allreduce8")


def test_sound_run_is_correct(tmp_path):
    out = _run(tmp_path)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert list(out)[-1] == "checks"


def test_step_returning_its_state_unchanged_is_not_correct(tmp_path,
                                                           monkeypatch):
    from repro.train import engine
    monkeypatch.setattr(engine.ExchangeStrategy, "post_update",
                        lambda self, state, *a, **k: state)
    out = _run(tmp_path)
    assert out["correct"] is False
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_half_the_batch_left_out_is_not_correct(tmp_path, monkeypatch):
    from repro.train import engine
    monkeypatch.setattr(
        engine.ExchangeStrategy, "prepare",
        lambda self, state, b, k: jax.tree.map(
            lambda x: x[:, : x.shape[1] // 2], b))
    out = _run(tmp_path)
    assert out["correct"] is False


def test_sound_allreduce_run_is_correct(tmp_path):
    out = _run_allreduce(tmp_path)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["checks"]) == set(manifest.limits(tiny.ALLREDUCE_CELL))


def test_allreduce_step_returning_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    from repro.train import engine
    monkeypatch.setattr(engine.AllReduce, "post_update",
                        lambda self, state, *a, **k: state)
    out = _run_allreduce(tmp_path)
    assert out["correct"] is False
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_allreduce_half_the_batch_left_out_is_not_correct(tmp_path,
                                                          monkeypatch):
    from repro.train import engine
    # AllReduce overrides the base prepare: one model's batch has no model axis
    monkeypatch.setattr(
        engine.AllReduce, "prepare",
        lambda self, state, b, k: jax.tree.map(
            lambda x: x[: x.shape[0] // 2], b))
    out = _run_allreduce(tmp_path)
    assert out["correct"] is False


def test_build_refuses_an_unknown_strategy(tmp_path):
    bench, cell = tiny.bench_and_cell(tmp_path)
    cfg = manifest.config(bench, cell["config"])
    layout = manifest.reference(cfg).param_layout(cfg)
    traffic = dict(tiny.traffic(), strategy="checkpoint")
    with pytest.raises(ValueError, match="strategy 'checkpoint': the harness "
                       "builds 'prediction' and 'all_reduce' only"):
        program.build(cfg, traffic, layout)
    # all_reduce takes one model: two would need a stacked state
    with pytest.raises(ValueError, match="strategy 'all_reduce' with 2"):
        program.build(cfg, dict(tiny.traffic("allreduce8"), models=2), layout)


def _cli(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", tiny.CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    res = _cli(ROOT, {})
    assert res.returncode != 0 and res.stdout.strip() == ""
    assert "no TPU" in res.stderr


def test_refuses_in_a_directory_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _cli(tmp_path, {"PYTHONPATH": ""})
    assert res.returncode != 0 and res.stdout.strip() == ""
