"""Run one cell of the on-chip benchmark and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``, ``chipbench/``
and the program's ``src/``. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
with ``--trace 1`` a ``breakdown``), then ``checks``: each number compared
with its limit, which also close standard error. The run exits non-zero and
prints no result when JAX finds no TPU, fewer chips than the cell asks for,
or a chip whose ``device_kind`` has no peaks in ``chipbench/peaks.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# a directory of the benchmark's own: JAX's cache eviction trips over entries
# that other tools left in a shared one
CACHE_DIR = os.path.join(ROOT, ".jax_cache", "chipbench")
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")


def fail(msg: str, code: int = 2) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    # libtpu would log under /tmp; the run writes only inside its checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench import manifest
    try:
        bench = manifest.load()
        cell = manifest.cell(bench, args.workload)
    except manifest.ManifestError as e:
        return fail(str(e))
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail("the program (src/repro) is not in this checkout")

    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from chipbench import harness, peaks
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return fail(f"JAX found no TPU (platform {dev.platform!r})")
    try:
        table = peaks.peaks_for(dev.device_kind)
    except peaks.UnknownDevice as e:
        return fail(str(e))
    if len(devices) < int(cell["chips"]):
        return fail(f"{cell['name']} needs {cell['chips']} chips; JAX found "
                    f"{len(devices)}")
    harness.info(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
                 f"cache {CACHE_DIR}")
    out = harness.run_cell(bench, cell, args.seed, args.seconds,
                           bool(args.trace), devices, T_START,
                           trace_dir=TRACE_DIR, peaks=table)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
