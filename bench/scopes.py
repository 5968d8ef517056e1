"""Device time by the program's own names, read from the trace file that
`bench/trace.py` recorded.

The program names its work in two ways: `jax.named_scope`s around its
layers ("attention", "mamba", "mlp", "accumulate", "optimizer"), which
XLA carries into each op's `op_name` metadata, and host spans through
`repro.runtime.spans` (`jax.profiler.TraceAnnotation`s named "repro:*")
on the profiler's one clock. `bench/trace.py`'s `Trace` keeps neither:
it holds op intervals, programs and the benchmark's own "bench:" spans.
`load` reads the rest from the same `.xplane.pb`: each op's name-stack
path, aligned with `Trace.ops[device]`, and the "repro:" spans. A run
of a program without these names reads no path and no span, and every
function here then gives None, so its metric is left out of the line.

Times are in nanoseconds on the trace's own clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import struct
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from bench import common, trace as T

PROGRAM_PREFIX = "repro:"
# a name-stack path's segments ("jit(f)/while/body/attention/dot_general")
SEGMENT = re.compile(r"[/;]")
# the event-metadata stat of a device op that holds its op_name
OP_NAME_STAT = "tf_op"
TRACE_DIR = common.BENCH / ".cache" / "trace"


@dataclasses.dataclass
class Names:
    paths: Dict[str, List[str]]     # device -> each op's path, as Trace.ops
    spans: List[T.Interval]         # the program's "repro:" host spans


# ------------------------------------------------ the XSpace wire format
# The trace file is an XSpace protobuf. JAX's ProfileData gives an
# event's own stats but not its metadata's, where a device op's op_name
# is, so this walks the wire format for the few fields needed (field
# numbers of tsl's xplane.proto): XSpace.planes 1; XPlane.name 2,
# lines 3, event_metadata 4, stat_metadata 5 (map entries: key 1,
# value 2); XLine.name 2, events 4; XEvent.metadata_id 1;
# XEventMetadata.id 1, name 2, stats 5; XStatMetadata.id 1, name 2;
# XStat.metadata_id 1, double 2, uint64 3, int64 4, str 5, bytes 6,
# ref 7 (the id of a stat metadata whose name is the value).
Span = Tuple[int, int]


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf: bytes, span: Span) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: an int for a
    varint, a (start, end) slice for a length-delimited field, the raw
    bytes of a fixed-width one."""
    i, end = span
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        else:
            n = 8 if kind == 1 else 4
            value, i = buf[i:i + n], i + n
        yield key >> 3, kind, value


def _all(buf: bytes, span: Span, number: int) -> list:
    return [v for n, _, v in _fields(buf, span) if n == number]


def _text(buf: bytes, span: Span) -> str:
    return buf[span[0]:span[1]].decode(errors="replace")


def _name(buf: bytes, msg: Span) -> str:
    return "".join(_text(buf, s) for s in _all(buf, msg, 2))


def _map(buf: bytes, plane: Span, number: int) -> Dict[int, Span]:
    """A map<int64, message> field of a plane: key -> the value's slice."""
    out = {}
    for entry in _all(buf, plane, number):
        key, value = 0, None
        for n, _, v in _fields(buf, entry):
            if n == 1:
                key = v
            elif n == 2:
                value = v
        if value is not None:
            out[key] = value
    return out


def _stat_value(buf: bytes, stat: Span, stat_names: Dict[int, str]):
    for n, _, v in _fields(buf, stat):
        if n == 2:
            return struct.unpack("<d", v)[0]
        if n in (3, 4):
            return v
        if n == 5:
            return _text(buf, v)
        if n == 6:
            return buf[v[0]:v[1]]
        if n == 7:
            return stat_names.get(v, "")
    return None


def _metadata(buf: bytes, meta: Span, stat_names: Dict[int, str]
              ) -> Tuple[str, Dict[str, object]]:
    """An event metadata's name and its stats by name."""
    stats = {}
    for stat in _all(buf, meta, 5):
        ids = _all(buf, stat, 1)
        stats[stat_names.get(ids[0] if ids else 0, "")] = \
            _stat_value(buf, stat, stat_names)
    return _name(buf, meta), stats


def _metadata_ids(buf: bytes, line: Span) -> List[int]:
    """The metadata id of each event of a line, in order."""
    out = []
    for ev in _all(buf, line, 4):
        i, end = ev
        mid = 0
        while i < end:
            key, i = _varint(buf, i)
            if key == 0x08:                     # metadata_id, a varint
                mid, i = _varint(buf, i)
                break
            kind = key & 7
            if kind == 0:
                _, i = _varint(buf, i)
            elif kind == 2:
                n, i = _varint(buf, i)
                i += n
            else:
                i += 8 if kind == 1 else 4
        out.append(mid)
    return out


def device_planes(buf: bytes) -> Iterator[Tuple[str, Span]]:
    """(device, plane slice) of each "/device:TPU:<n>" plane, as
    `bench/trace.py` names devices."""
    for plane in _all(buf, (0, len(buf)), 1):
        name = _name(buf, plane)
        if re.fullmatch(r"/device:TPU:\d+", name):
            yield name.rsplit(":", 1)[1], plane


def op_events(buf: bytes, plane: Span
              ) -> Iterator[Tuple[str, Dict[str, object]]]:
    """Name and metadata stats of each XLA op event of a device plane,
    in the order `bench/trace.py` keeps them."""
    stat_names = {k: _name(buf, v)
                  for k, v in _map(buf, plane, 5).items()}
    metas = _map(buf, plane, 4)
    seen: Dict[int, Tuple[str, Dict[str, object]]] = {}
    for line in _all(buf, plane, 3):
        if _name(buf, line) != "XLA Ops":
            continue
        for mid in _metadata_ids(buf, line):
            if mid not in seen:
                meta = metas.get(mid)
                seen[mid] = _metadata(buf, meta, stat_names) if meta \
                    else ("", {})
            yield seen[mid]


# ------------------------------------------------------------- reading
def load(path: str, trace: T.Trace) -> Names:
    """The program's names in the trace file that `trace` was read from.
    A device whose ops do not match `trace`'s one for one gets no
    paths."""
    with open(path, "rb") as f:
        buf = f.read()
    paths: Dict[str, List[str]] = {}
    for dev, plane in device_planes(buf):
        events = list(op_events(buf, plane))
        ops = trace.ops.get(dev, [])
        if len(events) != len(ops) or any(
                T.op_name(name) != op[2] for (name, _), op in
                zip(events, ops)):
            continue
        paths[dev] = [str(stats.get(OP_NAME_STAT) or "")
                      for _, stats in events]
    from jax.profiler import ProfileData
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
             for plane in ProfileData.from_serialized_xspace(buf).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(PROGRAM_PREFIX)]
    return Names(paths, spans)


def names(ctx) -> Names:
    """The program's names for a metric reader's context, loaded once
    into ctx["names"]: from the newest trace file a traced run wrote
    (each traced run empties its cell's trace directory first, and
    `load` checks the file against ctx["trace"])."""
    if "names" not in ctx:
        files = glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"),
                          recursive=True)
        ctx["names"] = load(max(files, key=os.path.getmtime),
                            ctx["trace"]) if files else Names({}, [])
    return ctx["names"]


# ----------------------------------------------------------- reduction
def enclosing(intervals: Sequence[T.Interval], starts: Sequence[float],
              t: float) -> Optional[T.Interval]:
    """The interval of a sorted, non-overlapping list that holds t."""
    i = bisect.bisect_right(starts, t) - 1
    return intervals[i] if i >= 0 and t < intervals[i][1] else None


def scope_ns(trace: T.Trace, nm: Names, device: str, scope: str,
             programs: Optional[Sequence[str]] = None) -> Optional[float]:
    """Device time of the ops whose name-stack path has `scope` as a
    segment (the union of their intervals, clipped to the window; control
    flow that encloses ops is left out). With `programs` (program-name
    prefixes, as in bench/phases.json), only ops that run inside those
    programs count. None where no op of the device carries the scope:
    the program under test does not name it."""
    ops, paths = trace.ops.get(device, []), nm.paths.get(device, [])
    scoped = {p: scope in SEGMENT.split(p) for p in set(paths)}
    if not any(scoped.values()):
        return None
    picked = [op for op, p in zip(ops, paths)
              if scoped[p] and not T.CONTAINER.match(op[2])]
    if programs is not None:
        mods = sorted(m for m in trace.modules.get(device, [])
                      if m[2].startswith(tuple(programs)))
        starts = [m[0] for m in mods]
        picked = [op for op in picked if enclosing(mods, starts, op[0])]
    lo, hi = trace.window()
    return T.length(T.union(T.clip(picked, lo, hi)))


def scope_share(trace: T.Trace, nm: Names, device: str, scope: str
                ) -> Optional[float]:
    """`scope_ns` over the device's busy time, in %."""
    busy = T.busy_ns(trace, device)
    ns = scope_ns(trace, nm, device, scope)
    if ns is None or busy <= 0:
        return None
    return 100.0 * ns / busy


def overlap_ns(a: Sequence[Tuple[float, float]],
               b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    segments."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def span_idle_ns(trace: T.Trace, nm: Names, device: str, name: str
                 ) -> Tuple[float, int]:
    """Device idle time inside the program's host spans called `name`
    (without its prefix), and how many of them the window holds (both
    clipped to the window)."""
    lo, hi = trace.window()
    spans = T.clip([s for s in nm.spans if s[2] == PROGRAM_PREFIX + name],
                   lo, hi)
    return overlap_ns(T.idle_gaps(trace, device), T.union(spans)), len(spans)


def host_activity(trace: T.Trace, nm: Names, t: float) -> str:
    """The innermost host span open at time t (the one that started
    last), the benchmark's or the program's, without its prefix; or
    "outside-spans"."""
    best = None
    for s, e, name in list(trace.spans) + list(nm.spans):
        if name != T.WINDOW_SPAN and s <= t < e and \
                (best is None or s > best[0]):
            best = (s, name)
    return best[1].split(":", 1)[1] if best else "outside-spans"


def gap_breakdown(trace: T.Trace, nm: Names, device: str, n: int = 10
                  ) -> List[Tuple[str, float]]:
    """Idle nanoseconds per innermost host span, the program's phases
    included, largest first."""
    tot: Dict[str, float] = {}
    for a, b in T.idle_gaps(trace, device):
        k = host_activity(trace, nm, (a + b) / 2)
        tot[k] = tot.get(k, 0.0) + (b - a)
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]
