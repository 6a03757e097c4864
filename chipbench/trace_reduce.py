"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer readers
report.

The trace is read with ``jax.profiler.ProfileData``. Device planes are named
``/device:TPU:<i>``; their ``XLA Ops`` line holds one event per operation
that ran. Host spans written by the harness (``jax.profiler.TraceAnnotation``, names
starting ``bench.``) sit on the host plane's thread lines.

The traced window runs from the start of the first ``bench.step`` span to the
end of the last device operation. Busy time is the union of the operations'
intervals inside it; an idle gap is a stretch of it in which no operation ran,
named by the innermost harness span open at the gap's middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]                  # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int
    end: int

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]             # device id -> its operations
    spans: List[Event]                      # harness host spans


def from_profile(pd) -> Trace:
    ops: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops.setdefault(int(m.group(1)), []).extend(
                    Event(e.name, int(e.start_ns), int(e.end_ns))
                    for e in line.events)
            elif not m:
                spans.extend(Event(e.name, int(e.start_ns), int(e.end_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    for v in ops.values():
        v.sort(key=lambda e: (e.start, -e.end))       # a parent before its body
    spans.sort(key=lambda e: e.start)
    return Trace(ops, spans)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def load_dir(trace_dir, chips: int) -> Trace:
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    tr = load(files[-1])
    if len(tr.ops) < chips:
        raise ValueError(f"trace holds {len(tr.ops)} device(s), the cell "
                         f"uses {chips}")
    return tr


# ----------------------------------------------------------------------------
# interval arithmetic
# ----------------------------------------------------------------------------

def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the union of ``a`` that no interval of ``b`` covers."""
    out: List[Interval] = []
    b = union(b)
    j = 0
    for s, e in union(a):
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return subtract([(lo, hi)], busy)


# ----------------------------------------------------------------------------
# the context the readers get
# ----------------------------------------------------------------------------

def short(name: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``%fusion.12``."""
    return name.split(" = ", 1)[0]


@dataclasses.dataclass
class Context:
    trace: Trace
    cfg: Dict
    traffic: Dict
    peaks: Optional[Dict]
    chips: int
    tokens_per_step: int
    steps: int                              # steps inside the traced window

    def __post_init__(self):
        tr = self.trace
        steps = [s for s in tr.spans if s.name == "bench.step"]
        self.devices = sorted(tr.ops)[:self.chips]
        # the trace starts drained: every device op in it is the traced
        # steps' (the host and device clocks differ by a fraction of a ms)
        first = min(e.start for d in self.devices for e in tr.ops[d])
        self.lo = min(steps[0].start, first) if steps else first
        self.hi = max(e.end for d in self.devices for e in tr.ops[d])

    # -- windows and busy time ---------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def ops(self, device: int, pred: Callable[[str], bool] = lambda n: True
            ) -> List[Event]:
        return [e for e in self.trace.ops[device] if pred(e.name)
                and e.end > self.lo and e.start < self.hi]

    def busy_intervals(self, device: int) -> List[Interval]:
        return clip(union([(e.start, e.end) for e in self.ops(device)]),
                    self.lo, self.hi)

    def busy_ns(self, device: int) -> int:
        return length(self.busy_intervals(device))

    @property
    def busy_s(self) -> float:
        return sum(self.busy_ns(d) for d in self.devices) * 1e-9 \
            / len(self.devices)

    def op_ns(self, device: int, pred: Callable[[str], bool]) -> int:
        """Summed device time of the matching operations, clipped to the
        window."""
        return length([iv for e in self.ops(device, pred)
                       for iv in clip([(e.start, e.end)], self.lo, self.hi)])

    # -- what the host did in each idle gap --------------------------------
    def span_at(self, t: int) -> str:
        best = None
        for s in self.trace.spans:
            if s.start <= t < s.end and (best is None or s.dur < best.dur):
                best = s
        return best.name if best else "no harness span"

    def self_times(self, device: int) -> Dict[str, int]:
        """Summed self time per op (its duration less the ops nested in it,
        as a while loop holds its body's), keyed by the op's short name."""
        per: Dict[str, int] = {}
        stack: List[List] = []               # [event, time of its children]
        for e in self.ops(device) + [Event("", 1 << 62, 1 << 62)]:
            while stack and stack[-1][0].end <= e.start:
                done, child = stack.pop()
                per[short(done.name)] = per.get(short(done.name), 0) \
                    + done.dur - child
                if stack:
                    stack[-1][1] += done.dur
            stack.append([e, 0])
        return per

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        d0 = self.devices[0]
        ops = sorted(self.self_times(d0).items(), key=lambda kv: -kv[1])[:top]
        idle = gaps(self.busy_intervals(d0), self.lo, self.hi)
        idle.sort(key=lambda iv: iv[0] - iv[1])
        return {"device_ops": [[n, ns * 1e-9] for n, ns in ops],
                "idle_gaps": [[self.span_at((s + e) // 2), (e - s) * 1e-9]
                              for s, e in idle[:top]]}
