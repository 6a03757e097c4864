"""BENCHMARK.json and the files its names point to."""
import json
import re

import pytest

from chipbench import check, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return manifest.load()


def test_finds_config_cell_traffic_limits_and_metric_by_name(bench):
    cell = manifest.cell(bench, "train-codist2-qwen1.5-0.5b")
    cfg = manifest.config(bench, cell["config"])
    assert cfg["hidden_size"] == 1024 and cfg["name"] == "qwen1.5-0.5b"
    assert manifest.traffic(cell["traffic"])["models"] == 2
    assert set(manifest.limits(cell["name"])) == {"loss_gap", "grad_gap",
                                                  "change_gap"}
    assert callable(manifest.metric_reader("step_mfu"))
    assert hasattr(manifest.reference(cfg), "total_loss")
    with pytest.raises(manifest.ManifestError, match="no workload named"):
        manifest.cell(bench, "no-such-cell")
    with pytest.raises(manifest.ManifestError, match="missing file"):
        manifest.metric_reader("no_such_metric")


def test_arch_found_by_model_type_takes_qkv_bias_from_the_file(bench):
    from repro.configs import get_config
    cfg = manifest.config(bench, "qwen1.5-0.5b")
    base = get_config(cfg["run"]["program_arch"])
    arch = manifest.arch(cfg)
    assert arch.program_config(cfg, base).qkv_bias is True
    assert arch.program_config(dict(cfg, qkv_bias=False),
                               base).qkv_bias is False
    with pytest.raises(manifest.ManifestError, match="missing file"):
        manifest.arch(dict(cfg, model_type="no_such_arch"))


def test_every_name_resolves_to_its_files(bench):
    for c in bench["configs"]:
        cfg = manifest.config(bench, c["name"])
        manifest.reference(cfg)
        manifest.arch(cfg)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for w in bench["workloads"]:
        manifest.config(bench, w["config"])
        manifest.traffic(w["traffic"])
        lim = manifest.limits(w["name"])
        assert lim and set(lim) <= set(check.NUMBERS)
        assert manifest.end_to_end(bench, w["name"])
        assert manifest.per_layer(bench, w["name"])
    for m in bench["per_layer"]:
        manifest.metric_reader(m["name"])


def test_contract_shapes(bench):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert all(UNIT.match(m["unit"]) for k in ("end_to_end", "per_layer")
               for m in bench[k])
    assert {m["name"] for m in bench["end_to_end"]} >= {"setup_s"}
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= cells
    texts = [x[k] for g in ("configs", "workloads", "per_layer")
             for x in bench[g] for k in ("why", "layer", "source") if k in x]
    assert all(0 < len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts + bench["command"])
    assert len(json.dumps(bench)) < 64 * 1024
