"""The comparison that decides ``correct``, on readings written by hand."""
import math

import numpy as np
import pytest

from chipbench import check


def test_verdict_compares_only_the_numbers_the_limits_name():
    read = {"loss_gap": (0.5, "step 1"), "grad_gap": (0.001, "a[0]"),
            "change_gap": (math.inf, "b[2]")}
    ok, checks = check.verdict(read, {"grad_gap": 0.01})
    assert ok and list(checks) == ["grad_gap"]
    assert checks["grad_gap"] == {"value": 0.001, "limit": 0.01,
                                  "leaf": "a[0]"}
    assert check.verdict(read, {"grad_gap": 0.01, "change_gap": 1.0})[0] \
        is False
    for bad in ({"loss": 1.0}, {}):
        with pytest.raises(ValueError):
            check.verdict(read, bad)


def test_worst_leaf_gap_is_taken_against_the_median_leaf_at_least():
    ref = {"a": np.array([1.0, 1.0]), "b": np.array([4.0]),
           "c": np.array([1e-9])}
    prog = {"a": np.array([1.0, 1.05]), "b": np.array([4.0]),
            "c": np.array([0.2])}
    # the median leaf's norm is 1: c's gap of 0.2 counts against it, not
    # against its own 1e-9
    gap, where = check.worst_leaf_gap(prog, ref)
    assert gap == pytest.approx(0.2) and where == "c[0]"
    skip = {"a": np.array([False, False]), "b": np.array([False]),
            "c": np.array([True])}
    gap, where = check.worst_leaf_gap(prog, ref, skip)
    assert gap == pytest.approx(0.05) and where == "a[1]"
