"""Reduction of a profiler trace to device busy time, op time and idle gaps.

A traced run wraps its window in ``jax.profiler`` and the calls it makes
into the program in ``jax.profiler.TraceAnnotation`` spans named
``bench.*``.  The profiler writes one ``.xplane.pb``; :func:`load` reads it
with nothing but JAX and keeps two things:

* device ops: the events of the ``XLA Ops`` line of each ``/device:TPU:<n>``
  plane, as ``(name, start_ns, end_ns)`` on the host's clock.  An event is
  named by its HLO instruction as text (``%copy.11 = f32[...] copy(...)``);
  the control-flow instructions (``while``, ``conditional``, ``call``) are
  left out, since they only contain the ops that run inside them;
* host spans: the ``bench.*`` events of the host plane, and the other
  events of the thread that holds them: JAX's own spans (dispatch,
  execution, waits) and the Python tracer's function calls.

:func:`reduce` turns them into what the per-layer readers and the
breakdown use.  Busy time is the union of the op intervals inside the
window (overlapping ops count once), averaged over the chips that ran
anything; an idle gap is a stretch of the window with no op running, and it
is put down to what the host did at its midpoint: the innermost benchmark
span there and, after `` > ``, the innermost other event of its thread, so
that a gap inside ``bench.solve`` shows whether the host was dispatching,
waiting for the device, or running Python of its own.
A trace without a TPU plane (a rehearsal on the CPU) reduces to nothing.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
CONTAINERS = frozenset(("while", "conditional", "call"))
OPCODE = re.compile(r" = .*? ([a-z][a-z0-9\-]*)\(")
LAYOUT = re.compile(r"\{[^{}]*\}")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
NO_SPAN = "outside benchmark spans"
INNER = 60   # characters kept of an inner event's name in a gap's label


@dataclasses.dataclass
class Trace:
    """Raw events of one trace, times in ns on the host's clock."""
    ops: dict[int, list[tuple[str, float, float]]]   # chip -> ops
    spans: list[tuple[str, float, float]]            # bench.* host spans
    host: list[tuple[str, float, float]] = dataclasses.field(
        default_factory=list)   # other events of the spans' thread


@dataclasses.dataclass
class Reduction:
    window_s: float                 # the traced window (bench.window span)
    busy_s: float                   # busy union, mean over chips
    chips: int                      # chips with ops in the window
    op_seconds: dict[str, float]    # op name -> device seconds, mean over chips
    gaps: list[tuple[str, float]]   # (host span, seconds), every idle gap

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds_of(self, match) -> float:
        """Device seconds of the ops whose name ``match`` accepts."""
        return sum(s for name, s in self.op_seconds.items() if match(name))

    def breakdown(self, top: int = 10) -> dict:
        by_op = collections.defaultdict(float)
        for name, s in self.op_seconds.items():
            by_op[short(name)] += s
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        by_span = collections.defaultdict(lambda: [0.0, 0, 0.0])
        for span, s in self.gaps:
            acc = by_span[span]
            acc[0] += s
            acc[1] += 1
            acc[2] = max(acc[2], s)
        gaps = sorted(by_span.items(), key=lambda kv: -kv[1][0])[:top]
        return {
            "device_ops": [[name, s] for name, s in ops],
            "idle_gaps": [[f"{span} ({n} gaps, longest {longest * 1e3} ms)",
                           total] for span, (total, n, longest) in gaps],
        }


def opcode(name: str) -> str | None:
    """The HLO opcode of an op event named by its instruction text."""
    m = OPCODE.search(name)
    return m.group(1) if m else None


def short(name: str) -> str:
    """``%name opcode shape`` of an op event, without layouts and operands."""
    head, sep, rest = name.partition(" = ")
    op = opcode(name)
    if not sep or op is None:
        return name[:120]
    shape = LAYOUT.sub("", rest[:rest.find(" " + op + "(")])
    return f"{head} {op} {shape}"[:120]


def find_xplane(directory: str) -> str | None:
    """The newest ``.xplane.pb`` the profiler wrote under ``directory``."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> Trace:
    """Device ops and benchmark spans of the xplane file at ``path``."""
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(profile) -> Trace:
    ops: dict[int, list] = {}
    spans, host = [], []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(int(m.group(1)), []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                        if opcode(e.name) not in CONTAINERS)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events]
                ours = [ev for ev in events if ev[0].startswith(SPAN_PREFIX)]
                if ours:
                    spans.extend(ours)
                    host.extend(ev for ev in events
                                if not ev[0].startswith(SPAN_PREFIX))
    return Trace(ops=ops, spans=spans, host=host)


def _union(intervals):
    """Disjoint, sorted union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _timeline(spans):
    """``(starts, labels)``: the window cut where spans begin or end, each
    piece named by the shortest span that holds it."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    starts, labels = [], []
    by_start = sorted(spans, key=lambda sp: sp[1])
    active, i = [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(by_start) and by_start[i][1] <= a:
            active.append(by_start[i])
            i += 1
        active = [sp for sp in active if sp[2] >= b]
        starts.append(a)
        labels.append(min(active, key=lambda sp: sp[2] - sp[1])[0]
                      if active else NO_SPAN)
    if cuts:
        starts.append(cuts[-1])
        labels.append(NO_SPAN)
    return starts, labels


def _label(timeline, t):
    starts, labels = timeline
    i = bisect.bisect_right(starts, t) - 1
    return labels[i] if i >= 0 else NO_SPAN


def reduce(trace: Trace) -> Reduction | None:
    """Busy time, op time and idle gaps inside the ``bench.window`` span.

    Returns None where the trace has no TPU ops or no window span."""
    windows = [(s, e) for name, s, e in trace.spans if name == WINDOW_SPAN]
    if not windows or not any(trace.ops.values()):
        return None
    w0, w1 = windows[0]
    outer = _timeline([sp for sp in trace.spans if sp[0] != WINDOW_SPAN])
    inner = _timeline([ev for ev in trace.host if ev[2] > w0 and ev[1] < w1])

    def doing(t):
        label, what = _label(outer, t), _label(inner, t)
        return label if what == NO_SPAN else f"{label} > {what[:INNER]}"
    busy = 0.0
    op_ns: dict[str, float] = collections.defaultdict(float)
    gaps = []
    chips = 0
    for chip in sorted(trace.ops):
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in trace.ops[chip]
                   if e > w0 and s < w1]
        if not clipped:
            continue
        chips += 1
        for name, s, e in clipped:
            op_ns[name] += e - s
        union = _union((s, e) for _, s, e in clipped)
        busy += sum(e - s for s, e in union)
        edges = [w0] + [t for iv in union for t in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((doing((s + e) / 2), (e - s) * 1e-9))
    if not chips:
        return None
    return Reduction(
        window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9 / chips, chips=chips,
        op_seconds={n: ns * 1e-9 / chips for n, ns in op_ns.items()},
        gaps=gaps)
