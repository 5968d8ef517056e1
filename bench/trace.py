"""Profiler capture and the reduction from a device trace to numbers.

`Tracer` records a JAX profiler trace of the end of a run's window;
`load` reads the `.xplane.pb` it wrote into a `Trace`: per device, the
intervals of its operations and of its XLA programs, and the host spans
the benchmark wrote with `jax.profiler.TraceAnnotation` (names starting
"bench:"). Everything after `load` is plain Python on that record, so
the tests check it on a small recorded trace without a chip.

Times are in nanoseconds on the trace's own clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float, str]          # (start, end, name)

SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
# control flow whose events enclose the ops of its body: counted in the
# busy union (which nesting does not change) but not as work of its own
CONTAINER = re.compile(r"(while|conditional|call)(\.\d+)?$")


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Interval]]            # device -> op intervals
    modules: Dict[str, List[Interval]]        # device -> program intervals
    spans: List[Interval]                     # the benchmark's host spans

    def window(self) -> Tuple[float, float]:
        w = [s for s in self.spans if s[2] == WINDOW_SPAN]
        if not w:
            raise ValueError("trace holds no bench:window span")
        return w[0][0], w[0][1]

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        tup = lambda xs: [tuple(x) for x in xs]
        return cls({k: tup(v) for k, v in d["ops"].items()},
                   {k: tup(v) for k, v in d["modules"].items()},
                   tup(d["spans"]))


# ------------------------------------------------------------ capture
def span(name: str):
    """A host span the reduction can read: `with span("serve_tick"): ...`."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


class Tracer:
    """Profiles the stretch of a run between `open` and `close`: a
    profiler trace with a bench:window span around it. Jobs open it at
    `start` (a perf_counter time), so that only the steady end of the
    window is traced and the trace stays small enough to read."""

    def __init__(self, directory: str, start: float):
        self.directory, self.start = directory, start
        self.path: Optional[str] = None
        self.active = False

    def open(self):
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        # no Python tracer: it records every Python call of the host loop
        # (millions in a serving window); the benchmark's own spans are
        # TraceAnnotations, which the host tracer keeps
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self.span.__enter__()
        self.active = True

    def close(self):
        import jax
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False
        self.path = sorted(glob.glob(os.path.join(
            self.directory, "**", "*.xplane.pb"), recursive=True))[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Interval]] = {}
    modules: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                re.fullmatch(r"/device:TPU:\d+", plane.name):
            dev = plane.name.rsplit(":", 1)[1]
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(dev, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         op_name(e.name)) for e in line.events)
                elif line.name == "XLA Modules":
                    modules[dev] = [(e.start_ns, e.start_ns + e.duration_ns,
                                     e.name) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    return Trace(ops, modules, spans)


def op_name(hlo: str) -> str:
    """'%fusion.27 = f32[...] fusion(...), ...' -> 'fusion.27'."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """'convolution_add_fusion.12' -> 'convolution_add_fusion'."""
    return re.sub(r"\.\d+$", "", name)


def describe(path: str) -> List[str]:
    """Plane and line names with event counts (for a first look)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"{plane.name} | {line.name} | {len(evs)} | "
                       f"{[e.name for e in evs[:3]]}")
    return out


# ---------------------------------------------------------- reduction
def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi), n) for s, e, n in intervals
            if e > lo and s < hi]


def union(intervals: Iterable[Interval]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e, _ in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(segments: Iterable[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in segments)


def busy_ns(trace: Trace, device: str) -> float:
    lo, hi = trace.window()
    return length(union(clip(trace.ops.get(device, []), lo, hi)))


def idle_gaps(trace: Trace, device: str) -> List[Tuple[float, float]]:
    lo, hi = trace.window()
    gaps, t = [], lo
    for s, e in union(clip(trace.ops.get(device, []), lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def phase_of(name: str, table: Dict[str, Sequence[str]]) -> Optional[str]:
    """The phase whose table entry is a prefix of the program's name
    (trace names carry a suffix such as "(12345)")."""
    for phase, prefixes in table.items():
        if any(name.startswith(p) for p in prefixes):
            return phase
    return None


def phase_ns(trace: Trace, device: str, table: Dict[str, Sequence[str]]
             ) -> Dict[str, float]:
    """Device time of each phase's programs, clipped to the window."""
    lo, hi = trace.window()
    out: Dict[str, float] = {}
    for s, e, n in clip(trace.modules.get(device, []), lo, hi):
        ph = phase_of(n, table)
        if ph is not None:
            out[ph] = out.get(ph, 0.0) + (e - s)
    return out


def module_count(trace: Trace, device: str, table, phase: str) -> int:
    lo, hi = trace.window()
    return sum(1 for s, e, n in clip(trace.modules.get(device, []), lo, hi)
               if phase_of(n, table) == phase)


def top_ops(trace: Trace, device: str, n: int = 10
            ) -> List[Tuple[str, float]]:
    lo, hi = trace.window()
    tot: Dict[str, float] = {}
    for s, e, name in clip(trace.ops.get(device, []), lo, hi):
        if CONTAINER.match(name):
            continue
        k = op_kind(name)
        tot[k] = tot.get(k, 0.0) + (e - s)
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def host_activity(trace: Trace, t: float) -> str:
    """The innermost benchmark span open at time t (the one that started
    last), or "outside-spans"."""
    best = None
    for s, e, name in trace.spans:
        if name != WINDOW_SPAN and s <= t < e and \
                (best is None or s > best[0]):
            best = (s, name)
    return best[1][len(SPAN_PREFIX):] if best else "outside-spans"


def gap_breakdown(trace: Trace, device: str, n: int = 10
                  ) -> List[Tuple[str, float]]:
    """Idle time summed by what the host was doing at each gap's middle,
    largest first."""
    tot: Dict[str, float] = {}
    for s, e in idle_gaps(trace, device):
        what = host_activity(trace, (s + e) / 2)
        tot[what] = tot.get(what, 0.0) + (e - s)
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def summary(trace: Trace, n_chips: int) -> dict:
    """busy_s / window_s (averaged over chips) and the breakdown lists."""
    lo, hi = trace.window()
    devs = sorted(trace.ops)[:n_chips] or ["0"]
    busy = [busy_ns(trace, d) for d in devs]
    first = devs[0]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[k, v / 1e9] for k, v in top_ops(trace, first)],
        "idle_gaps": [[k, v / 1e9] for k, v in gap_breakdown(trace, first)],
    }
