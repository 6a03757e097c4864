"""Readings that set a cell's limits: the program, the control and the faults,
each against the plain reference at the cell's own size, over many seeds.

    python chipbench/control.py --workload <cell> --seeds 12 [--control-seeds 3]

For every seed it prints one JSON line with the readings (``check.NUMBERS``),
and the ``correct`` that the cell's limits (``limits/<cell>.json``) give
them through ``check.verdict``, of:

* ``program``: the program's first three steps (the timed path's own step
  and batches) against the reference at float32 ``highest``;
* ``control``: the reference computed in float8 e4m3 against the same,
  the precision one below the configuration's bfloat16 products;
* ``half_batch``: the reference on half of each batch's rows, the mean
  taken over the rest (a fault a training cell can have);
* ``witness_bf16``: the reference with bfloat16 products, a witness of what
  the program's own precision costs.

The control, the fault and the witness run on the first ``--control-seeds``
seeds. A last line counts, for each of them, the seeds that came out
``correct``. Like ``run.py``
it needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=7_000_000_001)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache", "chipbench"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("chipbench: JAX found no TPU", file=sys.stderr)
        return 2
    from chipbench import manifest
    bench = manifest.load()
    cell = manifest.cell(bench, args.workload)
    readers = Readers(bench, cell)
    tally = {}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        row = readers(seed, extra=i < args.control_seeds)
        for name in KINDS:
            if name in row:
                t = tally.setdefault(name, {"correct": 0, "seeds": 0})
                t["correct"] += int(row[name]["correct"])
                t["seeds"] += 1
        print(json.dumps(row), flush=True)
    print(json.dumps({"correct_of_seeds": tally}), flush=True)
    return 0


KINDS = ("program", "control", "half_batch", "witness_bf16")


class Readers:
    """The program and the references of one cell, built once; called once
    per seed."""

    def __init__(self, bench, cell):
        from chipbench import check, manifest, program, traffic_gen
        self.cfg = cfg = manifest.config(bench, cell["config"])
        self.traffic = traffic = manifest.traffic(cell["traffic"])
        ref = manifest.reference(cfg)
        self.prog = program.build(cfg, traffic, ref.param_layout(cfg))
        self.make_batches = traffic_gen.batch_fn(traffic,
                                                 int(traffic["log_every"]))
        self.limits = manifest.limits(cell["name"])
        self.refs = {
            "reference": check.ReferenceSteps(ref, cfg, traffic),
            "control": check.ReferenceSteps(ref, cfg, traffic, "fp8"),
            "half_batch": check.ReferenceSteps(
                ref, cfg, traffic, rows=int(traffic["batch"]) // 2),
            "witness_bf16": check.ReferenceSteps(ref, cfg, traffic, "bf16"),
        }

    def __call__(self, seed: int, extra: bool) -> dict:
        """One seed's readings; ``extra`` adds the control, the fault and
        the witness."""
        from chipbench import check, weights
        t0 = time.perf_counter()
        state, prog_read, batches = check.program_steps(
            self.prog, seed, self.make_batches, weights.data_key(seed))
        del state
        t1 = time.perf_counter()
        base = self.refs["reference"](seed, batches)
        t2 = time.perf_counter()
        row = {"seed": seed, "program_s": t1 - t0, "reference_s": t2 - t1,
               "losses": {"program": prog_read["losses"],
                          "reference": base["losses"]},
               "program": self._judged(check.readings(prog_read, base))}
        if extra:
            for name in KINDS[1:]:
                other = self.refs[name](seed, batches)
                row[name] = self._judged(check.readings(other, base))
        return row

    def _judged(self, read: dict) -> dict:
        """The readings with the verdict the cell's limits give them."""
        from chipbench import check
        ok, _ = check.verdict(read, self.limits)
        return {"correct": ok,
                **{k: [v, where] for k, (v, where) in read.items()}}


if __name__ == "__main__":
    sys.exit(main())
