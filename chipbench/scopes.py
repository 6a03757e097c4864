"""Per-scope device time of a traced run, read from the program's named
scopes, and the program's own host spans.

The program tags its training step with ``jax.named_scope``: ``embed``,
``layers`` (holding ``attn`` and ``mlp``), ``head``, ``loss``, ``exchange``,
``optimizer`` and ``metrics``. The compiler keeps each op's scope path in its
``op_name``, and the TPU profiler writes that as the ``tf_op`` stat of the
op's event metadata on the device plane, beside the op's ``program_id``.
``jax.profiler.ProfileData`` gives no metadata stats, so this module decodes
the few protobuf fields it needs from the serialized XSpace itself and pairs
them with ProfileData's events in the order the line lists them. Keyed by
program, a ``%fusion.12`` of the batch generator is never one of the step's.

A path's elements are stripped of transform wrappers (``jvp(..)``,
``transpose(..)``, ``vmap(..)``). An op belongs to a scope when an element
equals the scope's name, and is recomputed when an element is
``rematted_computation``. Time is each op's self time, as
``Context.self_times`` computes it (a ``while`` op's body is not counted
twice), over device 0's busy time.

The program's host spans (``train.dispatch``, ``train.host_exchange``, each
with its ``step``) are collected beside the harness's ``bench.`` spans, and
idle gaps are then named by the innermost span of either.

A trace whose ops carry none of the scopes (a program that predates them)
gives no reduction: every reader of this module then returns ``None``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

from chipbench import trace_reduce
from chipbench.harness import info
from chipbench.run import TRACE_DIR
from chipbench.trace_reduce import DEVICE_PLANE, OPS_LINE, Event, Trace

TOP = ("embed", "layers", "head", "loss", "exchange", "optimizer", "metrics")
SCOPES = ("embed", "layers", "attn", "mlp", "head", "loss", "exchange",
          "optimizer", "metrics")
REMAT = "rematted_computation"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("bench.", "train.")

_WRAPPED = re.compile(r"[\w.\-]+\((.*)\)")
_MODULE = re.compile(r"^(.*)\((\d+)\)$")


def path(op_name: str) -> Tuple[str, ...]:
    """``jit(step)/transpose(jvp(vmap(layers)))/while/body/attn/dot_general``
    -> ``("step", "layers", "while", "body", "attn", "dot_general")``."""
    out = []
    for el in op_name.split("/"):
        while (m := _WRAPPED.fullmatch(el)):
            el = m.group(1)
        out.append(el)
    return tuple(out)


# ----------------------------------------------------------------------------
# the serialized XSpace, read by hand: the fields this module needs
# ----------------------------------------------------------------------------

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        x = b[i]
        i += 1
        r |= (x & 0x7F) << s
        s += 7
        if x < 0x80:
            return r, i


def _fields(b: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, bytes for
    a length-delimited field or a fixed 8 or 4 bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


@dataclasses.dataclass
class _Plane:
    meta: Dict[int, Tuple[str, str, int]]   # id -> (name, tf_op, program_id)
    lines: Dict[str, List[int]]             # line name -> its events' ids


def _device_plane(xspace: bytes, plane_name: str) -> Optional[_Plane]:
    """XSpace.planes (1) -> XPlane: name (2), lines (3), event_metadata (4),
    stat_metadata (5). XLine: name (2), events (4); XEvent: metadata_id (1).
    XEventMetadata: id (1), name (2), stats (5); XStat: metadata_id (1),
    uint64 (3), int64 (4), str (5), ref (7); XStatMetadata: id (1), name (2).
    """
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        parts = list(_fields(plane))
        name = next((v.decode() for g, v in parts if g == 2), "")
        if name != plane_name:
            continue
        stat_names = {}
        for g, v in parts:
            if g == 5:
                entry = dict(_fields(v))
                sm = dict(_fields(entry.get(2, b"")))
                stat_names[sm.get(1, 0)] = sm.get(2, b"").decode()
        meta = {}
        for g, v in parts:
            if g != 4:
                continue
            em = list(_fields(dict(_fields(v)).get(2, b"")))
            mid = next((x for h, x in em if h == 1), 0)
            ev_name = next((x.decode() for h, x in em if h == 2), "")
            tf_op, program = "", 0
            for h, x in em:
                if h != 5:
                    continue
                st = dict(_fields(x))
                stat = stat_names.get(st.get(1))
                if stat == "tf_op":
                    tf_op = (st[5].decode() if 5 in st
                             else stat_names.get(st.get(7), ""))
                elif stat == "program_id":
                    program = st.get(3, st.get(4, 0))
            meta[mid] = (ev_name, tf_op, program)
        lines = {}
        for g, v in parts:
            if g != 3:
                continue
            ln = list(_fields(v))
            lname = next((x.decode() for h, x in ln if h == 2), "")
            if lname in (OPS_LINE, MODULES_LINE):
                lines[lname] = [dict(_fields(x)).get(1, 0)
                                for h, x in ln if h == 4]
        return _Plane(meta, lines)
    return None


# ----------------------------------------------------------------------------
# the reduction
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Span(Event):
    step: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class Op:
    event: Event
    program: str                 # the module's name, e.g. ``jit_step``
    path: Tuple[str, ...]        # the op_name's elements, unwrapped


def spans(pd) -> List[Span]:
    """The harness's ``bench.`` and the program's ``train.`` host spans, in
    order of start, each with its ``step`` stat where it has one."""
    out = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIXES):
                    step = dict(e.stats).get("step")
                    out.append(Span(e.name, int(e.start_ns), int(e.end_ns),
                                    None if step is None else int(step)))
    out.sort(key=lambda s: s.start)
    return out


def device_ops(pd, xspace: bytes, device: int = 0
               ) -> Tuple[List[Op], List[Tuple[str, Event]]]:
    """Every op on a device's ``XLA Ops`` line with its program and path,
    and the ``XLA Modules`` line's (program, event) pairs."""
    name = f"/device:TPU:{device}"
    raw = _device_plane(xspace, name)
    plane = next((p for p in pd.planes if p.name == name), None)
    if raw is None or plane is None:
        return [], []
    modules = {}
    for m in raw.meta.values():
        got = _MODULE.match(m[0])
        if got:
            modules[int(got.group(2))] = got.group(1)
    ops, mods = [], []
    for line in plane.lines:
        ids = raw.lines.get(line.name)
        if ids is None:
            continue
        events = list(line.events)
        if len(events) != len(ids):
            raise ValueError(f"{line.name}: {len(events)} events, "
                             f"{len(ids)} in the serialized plane")
        for e, mid in zip(events, ids):
            ev = Event(e.name, int(e.start_ns), int(e.end_ns))
            meta_name, tf_op, program = raw.meta.get(mid, ("", "", 0))
            if line.name == MODULES_LINE:
                mods.append((_MODULE.sub(r"\1", meta_name), ev))
            else:
                ops.append(Op(ev, modules.get(program, str(program)),
                              path(tf_op.rsplit(":", 1)[0])))
    return ops, mods


@dataclasses.dataclass
class Reduction:
    ops: List[Op]
    self_ns: List[int]           # per op, as Context.self_times counts it
    busy_ns: int                 # device 0's busy time in the window
    spans: List[Span]
    modules: List[Tuple[str, Event]]

    def ns(self, *names: str) -> int:
        return sum(t for op, t in zip(self.ops, self.self_ns)
                   if any(n in op.path for n in names))

    def share(self, *names: str) -> float:
        return 100.0 * self.ns(*names) / self.busy_ns

    def unscoped(self) -> List[Tuple[str, int]]:
        """Ops under none of the top-level scopes, largest self time
        first, each named ``<program>/<op>``."""
        per: Dict[str, int] = {}
        for op, t in zip(self.ops, self.self_ns):
            if not set(op.path) & set(TOP):
                key = f"{op.program}/{trace_reduce.short(op.event.name)}"
                per[key] = per.get(key, 0) + t
        return sorted(per.items(), key=lambda kv: -kv[1])


def reduce(ctx, xspace: bytes) -> Optional[Reduction]:
    """The reduction of the trace ``ctx`` was made from, or ``None`` when its
    ops carry none of the program's scopes or device 0 was never busy.
    Raises ``ValueError`` when ``xspace`` is not the trace ``ctx`` holds."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(xspace)
    d0 = ctx.devices[0]
    ops, mods = device_ops(pd, xspace, d0)
    if sorted((op.event.start, op.event.end) for op in ops) != sorted(
            (e.start, e.end) for e in ctx.trace.ops[d0]):
        raise ValueError("the serialized trace is not the one reduced")
    busy = ctx.busy_ns(d0)
    if busy <= 0 or not any(set(op.path) & set(TOP) for op in ops):
        return None
    # Context.self_times over the same events, each keyed by its index
    keyed = sorted((Event(str(i), op.event.start, op.event.end)
                    for i, op in enumerate(ops)),
                   key=lambda e: (e.start, -e.end))
    sub = dataclasses.replace(ctx, trace=Trace({d0: keyed}, ctx.trace.spans))
    per = sub.self_times(d0)
    self_ns = [per.get(str(i), 0) for i in range(len(ops))]
    return Reduction(ops, self_ns, busy, spans(pd), mods)


def _newest_trace(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def of(ctx) -> Optional[Reduction]:
    """The reduction for a reader's context, made once per context: from
    ``ctx.xspace`` where the caller set it, else from the newest trace under
    the directory ``run.py`` gives the harness. It prints the scope lines to
    standard error the first time."""
    if "_scopes" in vars(ctx):
        return ctx._scopes
    red = None
    try:
        xspace = getattr(ctx, "xspace", None)
        if xspace is None:
            found = _newest_trace(TRACE_DIR)
            if found:
                with open(found, "rb") as f:
                    xspace = f.read()
        if xspace is not None:
            red = reduce(ctx, xspace)
    except (OSError, ValueError, IndexError) as e:
        info(f"info: scopes: no reduction ({e})")
    ctx._scopes = red
    if red is not None:
        report(ctx, red)
    return red


def share(ctx, *names: str) -> Optional[float]:
    """Self time of the ops under any of ``names`` over device 0's busy
    time, in percent."""
    red = of(ctx)
    return None if red is None else red.share(*names)


# ----------------------------------------------------------------------------
# the stderr lines of a traced run
# ----------------------------------------------------------------------------

def dispatch_leads(red: Reduction) -> List[int]:
    """For each ``train.dispatch`` span, in order, how long before its step
    program's device execution it began (ns; negative: after). The i-th
    dispatch launched the i-th execution of the step program, the program
    whose ops carry the scopes."""
    step_prog = max({op.program for op in red.ops},
                    key=lambda p: sum(t for op, t in zip(red.ops, red.self_ns)
                                      if op.program == p
                                      and set(op.path) & set(TOP)))
    runs = sorted(e.start for p, e in red.modules if p == step_prog)
    disp = [s for s in red.spans if s.name == "train.dispatch"]
    return [r - s.start for s, r in zip(disp, runs)]


def report(ctx, red: Reduction, top: int = 5) -> None:
    parts = [f"{n} {red.share(n):.3f}%" for n in SCOPES]
    parts.append(f"recompute {red.share(REMAT):.3f}%")
    rest = red.unscoped()
    none = 100.0 * sum(t for _, t in rest) / red.busy_ns
    info("info: scope self time over device 0's busy time: "
         + " ".join(parts) + f" none {none:.3f}% (largest: " + ", ".join(
             f"{k} {100.0 * t / red.busy_ns:.3f}%" for k, t in rest[:top])
         + ")")
    leads = dispatch_leads(red)
    named = dataclasses.replace(ctx, trace=Trace(ctx.trace.ops, red.spans))
    gaps = named.breakdown(top)["idle_gaps"]
    info(f"info: train.dispatch spans {len(leads)}, before their step's "
         f"device program {sum(x > 0 for x in leads)}, lead ms "
         + " ".join(f"{x * 1e-6:.3f}" for x in leads)
         + "; idle gaps by innermost span: " + ", ".join(
             f"{n} {s * 1e3:.4f} ms" for n, s in gaps))
