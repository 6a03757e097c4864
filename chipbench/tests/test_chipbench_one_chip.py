"""The one-chip cells' build and check, pinned to the bit: the reference's
readings of the tiny ``codist2`` and ``allreduce8`` cells and the text of
their lowered step hash as recorded, so that a change to the harness that
leaves these cells alone shows that it does."""
import hashlib

import jax
import numpy as np
import pytest

from chipbench import check, manifest, program, traffic_gen, weights
from chipbench.tests import tiny

SEED = 2**33 + 5
# sha256 of the reference's losses and of its grad and change norms, leaf
# by leaf, as the single-device ReferenceSteps read them
DIGESTS = {"codist2": "79083a6fd7f0ed11", "allreduce8": "f5bf9629648a6ae6"}
# sha256 of the text of the lowered "on" step, lowered from abstract shapes
STEP_DIGESTS = {"codist2": "786f0d11c84ca757",
                "allreduce8": "c4ae9f38aab00e6a"}
CELLS = {"codist2": tiny.CELL, "allreduce8": tiny.ALLREDUCE_CELL}


def _digest(read) -> str:
    h = hashlib.sha256()
    h.update(repr(read["losses"]).encode())
    for part in ("grad", "change"):
        for k in sorted(read[part]):
            h.update(k.encode())
            h.update(np.ascontiguousarray(read[part][k]).tobytes())
    return h.hexdigest()[:16]


def _cell(tmp_path, name):
    bench, _ = tiny.bench_and_cell(tmp_path, name, CELLS[name],
                                   activation_dtype="float32")
    cfg = manifest.config(bench, "tiny")
    return cfg, tiny.traffic(name), manifest.reference(cfg)


def _batches(traffic):
    mb = traffic_gen.batch_fn(traffic, int(traffic["log_every"]))
    dkey = weights.data_key(SEED)
    out = []
    while len(out) < check.STEPS:
        out.extend(mb(dkey, len(out)))
    return out[:check.STEPS]


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_one_chip_reference_readings_are_unchanged(tmp_path, name):
    cfg, traffic, ref = _cell(tmp_path, name)
    steps = check.ReferenceSteps(ref, cfg, traffic)
    assert _digest(steps(SEED, _batches(traffic))) == DIGESTS[name]


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_one_chip_step_text_is_unchanged(tmp_path, name):
    cfg, traffic, ref = _cell(tmp_path, name)
    prog = program.build(cfg, traffic, ref.param_layout(cfg))
    state = jax.eval_shape(prog.init_state, weights.weights_key(SEED))
    batch = jax.eval_shape(lambda: _batches(traffic)[0])
    text = prog.bundle.jitted("on").lower(state, batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        STEP_DIGESTS[name]
