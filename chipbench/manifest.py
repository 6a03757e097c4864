"""Find a cell, its configuration, its traffic, its limits and its metrics by
the names in ``BENCHMARK.json``. Everything a cell needs is a file of its own:

    chipbench/configs/<config>.json    sizes as run, source, reduced, assumed
    chipbench/traffic/<traffic>.json   strategy, models, batch, seq, optimizer
    chipbench/limits/<cell>.json       the limits of the numbers compared
    chipbench/metrics/<metric>.py      one reader per per-layer metric
    chipbench/reference/<module>.py    the plain reference a config names
    chipbench/archs/<model_type>.py    the program's model for a config
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class ManifestError(LookupError):
    pass


def _load_json(path: Path) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"missing file {path}") from None


def load(root: Optional[Path] = None) -> Dict:
    return _load_json(Path(root or ROOT) / "BENCHMARK.json")


def _named(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(f"no {what} named {name!r}; known: "
                        f"{sorted(e['name'] for e in entries)}")


def cell(bench: Dict, name: str) -> Dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: Dict, name: str, root: Optional[Path] = None) -> Dict:
    entry = _named(bench["configs"], name, "config")
    cfg = _load_json(Path(root or ROOT) / entry["file"])
    cfg["name"] = name
    return cfg


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> Dict:
    out = _load_json(Path(bench_dir) / "traffic" / f"{name}.json")
    out["name"] = name
    return out


def limits(cell_name: str, bench_dir: Path = BENCH_DIR) -> Dict:
    return _load_json(Path(bench_dir) / "limits" / f"{cell_name}.json")


def _module(path: Path, name: str):
    if not path.is_file():
        raise ManifestError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read(ctx)`` of ``metrics/<name>.py``."""
    mod = _module(Path(bench_dir) / "metrics" / f"{name}.py",
                  f"chipbench_metric_{name.replace('.', '_')}")
    return mod.read


def reference(cfg: Dict, bench_dir: Path = BENCH_DIR):
    name = cfg["reference"]
    return _module(Path(bench_dir) / "reference" / f"{name}.py",
                   f"chipbench_reference_{name}")


def arch(cfg: Dict, bench_dir: Path = BENCH_DIR):
    """The ``program_config(cfg, base)`` mapping of the config's
    ``model_type``."""
    name = cfg["model_type"]
    return _module(Path(bench_dir) / "archs" / f"{name}.py",
                   f"chipbench_arch_{name}")


def _applies(metric: Dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end(bench: Dict, cell_name: str) -> List[Dict]:
    return [m for m in bench["end_to_end"] if _applies(m, cell_name)]


def per_layer(bench: Dict, cell_name: str) -> List[Dict]:
    return [m for m in bench["per_layer"] if _applies(m, cell_name)]
