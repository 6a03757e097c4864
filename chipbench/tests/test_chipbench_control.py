"""The control at a size a test run can hold: the reference in float8 fails
the cell's limits, and reads above the program in its own bfloat16. (At this
size bfloat16 alone reads near the limits, which are set for the cell's
published widths: see PERF.md.)"""
from chipbench import control, manifest
from chipbench.tests import tiny


def _row(tmp_path, monkeypatch, seed, *names):
    bench, cell = tiny.bench_and_cell(tmp_path, *names)
    monkeypatch.setattr(manifest, "traffic",
                        lambda name, _real=manifest.traffic: tiny.shrink(
                            _real(name)))
    return control.Readers(bench, cell)(seed, extra=True)


def _check(row):
    assert row["control"]["correct"] is False, row["control"]
    for k in ("grad_gap", "change_gap"):
        assert row["program"][k][0] < row["control"][k][0] / 2, (
            row["program"], row["control"])
    assert row["half_batch"]["correct"] is False, row["half_batch"]


def test_control_fails_where_the_program_passes(tmp_path, monkeypatch):
    _check(_row(tmp_path, monkeypatch, 2**31 + 11))


def test_allreduce_control_fails_where_the_program_passes(tmp_path,
                                                          monkeypatch):
    _check(_row(tmp_path, monkeypatch, 2**31 + 17, "allreduce8",
                tiny.ALLREDUCE_CELL))
