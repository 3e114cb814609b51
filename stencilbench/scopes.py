"""The program's own scopes and spans in a traced run, read from its xplane.

The program names its phases in two ways:

* device scopes: ``jax.named_scope("repro.sweep" | "repro.boundary" |
  "repro.check")`` write the HLO ``op_name`` metadata of the ops traced
  under them.  In the trace of a TPU v5e that path is the ``tf_op`` stat of
  the *metadata* of each ``XLA Ops`` event (``<op_name>:<op type>``, e.g.
  ``jit(loop)/while/body/repro.check/reduce_sum:``), not a stat of the event
  itself, and ``jax.profiler.ProfileData`` shows only the event's own
  stats; so this module decodes the protobuf itself.  An op is put in the
  innermost ``repro.*`` component of its path; an op with none is
  unscoped.  XLA gives the ops it inserts the metadata of what they serve:
  on a v5e the relayout at a program's entry (``%copy``) and those at the
  edge of the solve loop (``%copy``, ``%copy.1``: ``jit(loop)/while``) are
  unscoped, as is the loop's control, while the relayouts in and after
  the kernel's pass loop of a fixed-sweep call (``%copy.11``,
  ``%copy.1``) carry ``repro.sweep``, the scope of that loop;
* host spans: ``jax.profiler.TraceAnnotation`` events named
  ``repro.solve.dispatch``, ``.wait`` and ``.readback`` around each
  ``Solver.solve``, on the host plane.  The profiler writes the
  annotation's ``solve`` argument as a stat of the event (its name stays
  bare), which ties the three spans of one solve together.

The conventions are ``trace.py``'s: times in ns on the host's clock (a
line's timestamp plus the event's offset), ops clipped to the
``bench.window`` span, the control-flow containers left out, and times
averaged over the chips that ran anything; busy time is the reduction's.
The trace of a run is decoded once per process.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import os

from stencilbench import run, trace

SCOPE_PREFIX = "repro."
SPAN_PREFIX = "repro.solve."
UNSCOPED = "(no scope)"
SCOPE_STAT = "tf_op"
SOLVE_STAT = "solve"


@dataclasses.dataclass
class Scopes:
    """Device ops with their scope and the program's host spans, in ns."""
    window: tuple[float, float] | None    # the bench.window span
    ops: dict[int, list[tuple[str, str, float, float]]]  # chip -> ops
    spans: list[tuple[str, int | None, float, float]]   # (name, solve, ...)

    def _clipped(self):
        w0, w1 = self.window
        for chip in sorted(self.ops):
            ops = [(name, scope, max(s, w0), min(e, w1))
                   for name, scope, s, e in self.ops[chip]
                   if e > w0 and s < w1]
            if ops:
                yield ops

    def seconds(self):
        """``({scope: seconds}, {unscoped op: seconds})`` of the ops in the
        window, a mean over the chips that ran anything; each op counts its
        own time, as ``trace.Reduction.op_seconds`` does."""
        chips = 0
        by_scope = collections.defaultdict(float)
        unscoped = collections.defaultdict(float)
        for ops in self._clipped():
            chips += 1
            for name, scope, s, e in ops:
                by_scope[scope] += e - s
                if scope == UNSCOPED:
                    unscoped[trace.short(name)] += e - s
        scale = 1e-9 / max(chips, 1)
        return ({k: v * scale for k, v in by_scope.items()},
                {k: v * scale for k, v in unscoped.items()})

    def idle_in(self, span: str) -> list[tuple[int | None, float]]:
        """``(solve id, seconds)`` for each ``span`` span in the window: the
        device's idle time that overlaps the span (the exact overlap, the
        span clipped to the window), a mean over the chips."""
        w0, w1 = self.window
        unions = [trace._union((s, e) for _, _, s, e in ops)
                  for ops in self._clipped()]
        out = []
        for name, solve, s0, e0 in self.spans:
            a, b = max(s0, w0), min(e0, w1)
            if name != span or b <= a:
                continue
            idle = sum((b - a) - sum(max(0.0, min(e, b) - max(s, a))
                                     for s, e in union)
                       for union in unions)
            out.append((solve, idle * 1e-9 / max(len(unions), 1)))
        return out


# -- the xplane protobuf, decoded by hand --------------------------------

def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one protobuf message: an
    int for a varint, a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} not expected")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf):
    """``(metadata id, value)`` of an XStat: its int or string value."""
    mid, value = 0, None
    for f, v in _fields(buf):
        if f == 1:
            mid = v
        elif f in (3, 4):          # uint64_value, int64_value
            value = v
        elif f == 5:               # str_value
            value = _text(v)
    return mid, value


def _entry(buf):
    """The key and the value message of a protobuf map entry."""
    key, value = 0, b""
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane(buf):
    """``(name, lines, event metadata, stat names)`` of one XPlane: each line
    ``(name, timestamp_ns, [events])``, each event ``(metadata id,
    offset_ps, duration_ps, stats)``, each metadata ``(name, stats)``."""
    name, raw_lines, meta, stat_names = "", [], {}, {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            raw_lines.append(v)
        elif f == 4:
            key, m = _entry(v)
            mname, stats = "", []
            for g, w in _fields(m):
                if g == 2:
                    mname = _text(w)
                elif g == 5:
                    stats.append(_stat(w))
            meta[key] = (mname, stats)
        elif f == 5:
            key, m = _entry(v)
            stat_names[key] = next((_text(w) for g, w in _fields(m)
                                    if g == 2), "")
    lines = []
    for raw in raw_lines:
        lname, ts, events = "", 0, []
        for f, v in _fields(raw):
            if f == 2:
                lname = _text(v)
            elif f == 3:
                ts = v
            elif f == 4:
                mid = off = dur = 0
                stats = []
                for g, w in _fields(v):
                    if g == 1:
                        mid = w
                    elif g == 2:
                        off = w
                    elif g == 3:
                        dur = w
                    elif g == 4:
                        stats.append(_stat(w))
                events.append((mid, off, dur, stats))
        lines.append((lname, ts, events))
    return name, lines, meta, stat_names


def scope_of(op_name: str) -> str:
    """The innermost ``repro.*`` component of an op's scope path (the
    ``tf_op`` stat, ``<op_name>:<op type>``), or :data:`UNSCOPED`."""
    path = op_name.rpartition(":")[0] if ":" in op_name else op_name
    inner = [c for c in path.split("/") if c.startswith(SCOPE_PREFIX)]
    return inner[-1] if inner else UNSCOPED


def decode(data: bytes) -> Scopes:
    """Device ops with their scopes, and the window and program spans, of a
    serialized XSpace."""
    ops: dict[int, list] = {}
    spans, window = [], None
    for f, v in _fields(memoryview(data)):
        if f != 1:
            continue
        name, lines, meta, stat_names = _plane(v)
        m = trace.DEVICE_PLANE.match(name)
        if m:
            scope_ids = {k for k, n in stat_names.items() if n == SCOPE_STAT}
            scopes = {mid: scope_of(next((str(val) for sid, val in stats
                                          if sid in scope_ids), ""))
                      for mid, (_, stats) in meta.items()}
            for lname, ts, events in lines:
                if lname != trace.OPS_LINE:
                    continue
                chip = ops.setdefault(int(m.group(1)), [])
                for mid, off, dur, _ in events:
                    op = meta.get(mid, ("", []))[0]
                    if trace.opcode(op) in trace.CONTAINERS:
                        continue
                    start = ts + off * 1e-3
                    chip.append((op, scopes.get(mid, UNSCOPED), start,
                                 start + dur * 1e-3))
        elif name.startswith("/host:"):
            solve_ids = {k for k, n in stat_names.items() if n == SOLVE_STAT}
            for _, ts, events in lines:
                for mid, off, dur, stats in events:
                    ename = meta.get(mid, ("", []))[0]
                    start = ts + off * 1e-3
                    if ename == trace.WINDOW_SPAN and window is None:
                        window = (start, start + dur * 1e-3)
                    elif ename.startswith(SPAN_PREFIX):
                        solve = next((val for sid, val in stats
                                      if sid in solve_ids), None)
                        spans.append((ename, solve, start,
                                      start + dur * 1e-3))
    return Scopes(window=window, ops=ops, spans=spans)


@functools.lru_cache(maxsize=4)
def _load(path: str, mtime: float) -> Scopes:
    with open(path, "rb") as f:
        return decode(f.read())


def load(path: str) -> Scopes | None:
    """The trace at ``path``; None where it has no TPU op or no window."""
    scopes = _load(path, os.path.getmtime(path))
    if scopes.window is None or not any(scopes.ops.values()):
        return None
    return scopes


def of_cell(cell: dict) -> Scopes | None:
    """The trace of the cell's last traced run, where ``run.py`` left it."""
    path = trace.find_xplane(run.trace_dir(cell["name"]))
    return load(path) if path else None


def busy_share(reduction, cell: dict, scope: str) -> float | None:
    """Device time of the ops in ``scope`` over the busy time of
    ``reduction``, in per cent; None off the chip or where no op of the
    window carries the scope."""
    scopes = None if reduction is None else of_cell(cell)
    if scopes is None or not reduction.busy_s:
        return None
    seconds = scopes.seconds()[0].get(scope)
    return 100.0 * seconds / reduction.busy_s if seconds else None


def idle_per_span(reduction, cell: dict, span: str) -> float | None:
    """Device idle seconds inside the ``span`` spans, over their number;
    None off the chip or where the window holds no such span."""
    scopes = None if reduction is None else of_cell(cell)
    if scopes is None:
        return None
    idle = [seconds for _, seconds in scopes.idle_in(span)]
    return sum(idle) / len(idle) if idle else None
