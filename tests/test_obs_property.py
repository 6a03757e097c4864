"""Hypothesis property tests for the observability layer: span nesting /
monotonic-clock invariants of ``repro.obs.trace.Tracer`` and the exact-
quantile guarantee of ``repro.obs.metrics.Histogram``.

Lives apart from ``tests/test_obs.py`` so the deterministic obs tests run
even where the optional ``hypothesis`` dev dependency isn't installed
(this module skips cleanly, same pattern as ``tests/test_property.py``).
"""
import os
import sys

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.obs import Histogram, TraceError, Tracer  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import trace_check  # noqa: E402

S = settings(max_examples=25, deadline=None)


class TestTracerProperties:
    @S
    @given(durs=st.lists(st.floats(0.0, 10.0), min_size=1, max_size=8),
           t0=st.floats(0.0, 100.0))
    def test_nested_spans_always_validate(self, durs, t0):
        """Any properly-nested LIFO span stack with non-decreasing times
        exports a validator-clean trace."""
        tr = Tracer(unit_us=1000.0)
        t = t0
        for i, d in enumerate(durs):
            tr.begin(f"s{i}", t, pid=0, tid=0)
            t += d
        for i in reversed(range(len(durs))):
            tr.end(f"s{i}", t, pid=0, tid=0)
            t += 0.5
        doc = tr.to_dict()
        errors = []
        trace_check.check_events(doc["traceEvents"], errors)
        assert errors == []
        assert not tr.open_spans()

    @S
    @given(ts=st.lists(st.floats(0.0, 1000.0), min_size=2, max_size=16))
    def test_export_order_is_time_sorted(self, ts):
        tr = Tracer(unit_us=1000.0)
        for i, t in enumerate(ts):
            tr.instant(f"e{i}", t, pid=0, tid=0)
        out = [e["ts"] for e in tr.to_dict()["traceEvents"]]
        assert out == sorted(out)

    @S
    @given(back=st.floats(0.001, 50.0), t=st.floats(1.0, 100.0))
    def test_backwards_clock_always_raises(self, back, t):
        tr = Tracer()
        tr.begin("a", t, pid=0, tid=0)
        with pytest.raises(TraceError):
            tr.end("a", t - back, pid=0, tid=0)


class TestHistogramProperties:
    @S
    @given(vals=st.lists(st.floats(0.0, 1e6), min_size=1, max_size=200),
           q=st.floats(0.0, 100.0))
    def test_percentile_matches_numpy_exactly(self, vals, q):
        h = Histogram()
        for v in vals:
            h.observe(v)
        assert h.percentile(q) == float(np.percentile(np.asarray(vals), q))

    @S
    @given(vals=st.lists(st.floats(0.0, 1e4), min_size=0, max_size=100))
    def test_bucket_counts_partition_the_samples(self, vals):
        h = Histogram()
        for v in vals:
            h.observe(v)
        d = h.to_dict()
        assert sum(d["buckets"].values()) == len(vals)
        assert d["count"] == len(vals)
        if vals:
            assert d["sum"] == pytest.approx(sum(vals))
