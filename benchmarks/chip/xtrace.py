"""From a profiler trace to the numbers the per-layer metrics read.

A traced run writes one ``*.xplane.pb`` (an ``XSpace`` protobuf).  Per
chip (plane ``/device:TPU:<id>``) the line ``XLA Ops`` holds one event per
executed HLO instruction, nested where a ``while`` or a call holds
others.  Each event's metadata carries the JAX name stack (``tf_op``,
e.g. ``jit(superstep)/while/body/closed_call/vmap()/top_k:``) and the
program's source lines (``source_stack``); ``jax.profiler.ProfileData``
reads the events, and :func:`event_metadata` reads those two stats from
the protobuf, which ``ProfileData`` does not expose.  The host plane
holds the spans the run mirrored into the trace (``chunk.dispatch``,
``prefetch.stage``) and JAX's own (``np.asarray(jax.Array)``: a wait for
the device's metrics).

* the traced window, on the trace's own clock: from a chip's first op
  to its last (the run starts the profiler once the window has opened,
  with the first chunk already running, and stops it when the final
  state is ready);
* busy time: the union of a chip's ``XLA Ops`` intervals;
* an op's device time: its duration, with what is nested in it, and
  for the ranking of ops its exclusive time (less the instructions
  nested in it), so that a loop is not counted with its body;
* idle gaps: the stretches of the traced window no chip op covers, each
  named by the host span that overlaps it most.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_SPANS = ("chunk.dispatch", "prefetch.stage", "eval.dispatch",
              "np.asarray(jax.Array)", "PjitFunction")


# ---------------------------------------------------------------------------
# the protobuf's event metadata (wire format, read by hand)
# ---------------------------------------------------------------------------

def _varint(b, i):
    shift = result = 0
    while True:
        c = b[i]
        i += 1
        result |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return result, i


def _fields(b):
    """(field number, value) of one message: ints for varints, memoryviews
    for length-delimited fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 1:
            value, i = b[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(b, i)
            value, i = b[i:i + size], i + size
        elif wire == 5:
            value, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield field, value


def event_metadata(raw: bytes) -> Dict[str, Dict[str, Dict[str, str]]]:
    """Per device plane name: event name -> {"tf_op", "source_stack"}.

    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map entries
    of key 1, XEventMetadata 2), .stat_metadata = 5 (id 1, XStatMetadata
    2 with .name 2); XEventMetadata.name = 2, .stats = 5; XStat.metadata_id
    = 1, .str_value = 5."""
    out = {}
    for field, plane in _fields(memoryview(raw)):
        if field != 1:
            continue
        name, metas, stat_names = None, [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                metas.append(v)
            elif f == 5:
                for g, w in _fields(v):
                    if g == 2:
                        d = dict(_fields(w))
                        stat_names[d.get(1)] = bytes(d.get(2, b"")).decode()
        if name is None or not DEVICE_PLANE.match(name):
            continue
        table = {}
        for entry in metas:
            for g, w in _fields(entry):
                if g != 2:
                    continue
                ev_name, stats = None, {}
                for h, x in _fields(w):
                    if h == 2:
                        ev_name = bytes(x).decode(errors="replace")
                    elif h == 5:
                        d = dict(_fields(x))
                        key = stat_names.get(d.get(1))
                        if key in ("tf_op", "source_stack") and 5 in d:
                            stats[key] = bytes(d[5]).decode(
                                errors="replace")
                if ev_name is not None:
                    table[ev_name] = stats
        out[name] = table
    return out


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")


def parse_instruction(text):
    """``%sort.53 = (f32[..]) sort(...)`` -> ("sort.53", "sort", shape)."""
    m = _INSTR.match(text)
    if not m:
        return text.split(" ")[0].lstrip("%"), "", ""
    return m.group(1), m.group(3), m.group(2)


class Op:
    __slots__ = ("name", "opcode", "shape", "tf_op", "source", "start",
                 "end", "self_ns", "parent")

    def __init__(self, text, start, end, meta):
        self.name, self.opcode, self.shape = parse_instruction(text)
        self.tf_op = meta.get("tf_op", "")
        self.source = meta.get("source_stack", "")
        self.start, self.end = start, end
        self.self_ns = end - start
        self.parent = None

    @property
    def key(self):
        """A name that survives renumbering: the name stack below the
        superstep, else the opcode and the result's shape."""
        stack = self.tf_op.rstrip(":")
        stack = re.sub(r"^jit\(superstep\)/", "", stack)
        stack = stack.replace("while/body/closed_call/", "")
        if stack and stack != "while":
            return f"{stack} [{self.opcode}]"
        return f"{self.opcode} {re.sub(r'{[^}]*}', '', self.shape)[:80]}"


def _nest(ops: List[Op]):
    """Links each event to the one it lies in and sets exclusive times:
    an instruction's duration less the instructions nested in it.  A
    nested event that is no instruction (a named region of a kernel) is
    part of its instruction's own time."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: List[Op] = []
    for op in ops:
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack and op.end <= stack[-1].end:
            op.parent = stack[-1]
            owner = op.parent
            while not owner.opcode and owner.parent is not None:
                owner = owner.parent
            if not op.opcode:
                op.self_ns = 0
            elif owner.opcode:
                owner.self_ns -= op.end - op.start
        stack.append(op)


def _union(intervals):
    total, cur_s, cur_e = 0, None, None
    merged = []
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                merged.append((cur_s, cur_e))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        merged.append((cur_s, cur_e))
    total = sum(e - s for s, e in merged)
    return total, merged


class Reduced:
    """The device ops of each chip over the traced window, and the host
    spans."""

    def __init__(self, ops: Dict[int, List[Op]],
                 host: List[Tuple[str, int, int]]):
        self.ops = ops
        self.host = host
        for chip_ops in ops.values():
            _nest(chip_ops)
        self._busy = {d: _union([(o.start, o.end) for o in v])
                      for d, v in ops.items()}

    def has_device_events(self):
        return any(self.ops.values())

    def span_s(self, device=None):
        """The traced window: seconds from a chip's first op to its last,
        on one chip or the mean over the chips that ran any."""
        spans = {d: (m[-1][1] - m[0][0]) / 1e9
                 for d, (_, m) in self._busy.items() if m}
        if device is not None:
            return spans.get(device, 0.0)
        return sum(spans.values()) / len(spans) if spans else 0.0

    def busy_s(self, device=None):
        """Seconds in which some op ran: on one chip, or the mean over
        the chips."""
        if device is not None:
            return self._busy[device][0] / 1e9
        return sum(b[0] for b in self._busy.values()) / len(self._busy) / 1e9

    def op_seconds(self, match, per_chip=max):
        """Device seconds of the ops ``match(op)`` selects, each with what
        is nested in it and none counted twice: ``per_chip`` (max, or a
        mean) over the chips."""
        def outermost(o):
            p = o.parent
            while p is not None:
                if match(p):
                    return False
                p = p.parent
            return True

        vals = [sum(o.end - o.start for o in v if match(o) and outermost(o))
                / 1e9 for v in self.ops.values()]
        return per_chip(vals) if vals else 0.0

    def count(self, match):
        return max((sum(1 for o in v if match(o)) for v in self.ops.values()),
                   default=0)

    def top_ops(self, n):
        """The ops with most exclusive time, mean over chips, by name."""
        tot = collections.Counter()
        for v in self.ops.values():
            for o in v:
                if o.self_ns:
                    tot[o.key] += o.self_ns
        k = max(len(self.ops), 1)
        return [[name, ns / k / 1e9] for name, ns in tot.most_common(n)]

    def idle_gaps(self, n):
        """The longest stretches of the traced window in which no op ran
        on the first chip, each named by the host span that overlaps it
        most."""
        if not self._busy:
            return []
        chip = min(self._busy)
        _, merged = self._busy[chip]
        gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            best, best_ov = "no host span", 0
            for name, hs, he in self.host:
                ov = min(e, he) - max(s, hs)
                if ov > best_ov:
                    best, best_ov = name, ov
            out.append([f"host: {best}", (e - s) / 1e9])
        return out


def reduce_space(raw: bytes, device_ids) -> Reduced:
    """An ``XSpace``'s bytes -> :class:`Reduced` for the given chips."""
    import jax
    meta = event_metadata(raw)
    data = jax.profiler.ProfileData.from_serialized_xspace(raw)
    ops, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in device_ids:
            table = meta.get(plane.name, {})
            chip = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    chip.append(Op(ev.name, int(ev.start_ns),
                                   int(ev.end_ns), table.get(ev.name, {})))
            ops[int(m.group(1))] = chip
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPANS):
                        host.append((ev.name.split("(")[0]
                                     if ev.name.startswith("PjitFunction")
                                     else ev.name,
                                     int(ev.start_ns), int(ev.end_ns)))
    for d in device_ids:
        ops.setdefault(d, [])
    return Reduced(ops, host)


def reduce_dir(trace_dir, device_ids) -> Reduced:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    with open(paths[-1], "rb") as f:
        return reduce_space(f.read(), device_ids)


# ---------------------------------------------------------------------------
# compilations inside the window
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts XLA compilations (JAX's backend-compile events) while
    ``active()`` holds."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.names = []
        self._active = lambda: False
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    @property
    def n(self):
        return len(self.names)

    def _listen(self, event, duration, **kwargs):
        if event == self._EVENT and self._active():
            self.names.append(kwargs.get("fun_name", "?"))

    def counting(self, active):
        import contextlib

        @contextlib.contextmanager
        def scope():
            self._active = active
            try:
                yield self
            finally:
                self._active = lambda: False
        return scope()
