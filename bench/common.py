"""Shared plumbing of the chip benchmark: files found by name, the device
check, the compile cache, statistics and the result line.

Nothing here touches JAX while it is imported: the device check and the
cache set-up run only when a run asks for them.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional, Sequence

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# fixed, inside the checkout: the path is part of the cache key
CACHE_DIR = BENCH / ".cache" / "jax"


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return load_json(ROOT / "BENCHMARK.json")


def find(items: Sequence[Dict[str, Any]], name: str, what: str):
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def load_module(path: Path) -> ModuleType:
    """Import a file of the benchmark by path (metric readers have dots
    in their names, so they are not importable as packages)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def config_doc(name: str) -> Dict[str, Any]:
    cfg = find(benchmark()["configs"], name, "config")
    return load_json(ROOT / cfg["file"])


def traffic_doc(name: str) -> Dict[str, Any]:
    return load_json(BENCH / "traffic" / f"{name}.json")


def reference_module(cfg_doc: Dict[str, Any]) -> ModuleType:
    return load_module(BENCH / "models" / f"{cfg_doc['reference']}.py")


def peaks(device_kind: str) -> Dict[str, Any]:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"bench: device kind {device_kind!r} is not in "
                       f"bench/peaks.json; add its published peaks")
    return table[device_kind]


# ------------------------------------------------------------- device
def require_chips(n: int) -> Dict[str, Any]:
    """The run's device record. Exits non-zero, before any result, when
    JAX finds no TPU or fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{d.platform!r}); the benchmark runs on the "
                         f"chip only")
    if len(devices) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX found "
                         f"{len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": n}


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed directory of the checkout. Set
    in the environment too, so the program's own cache helper (which
    honours JAX_COMPILATION_CACHE_DIR) takes the same directory."""
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CompileCounter:
    """Counts programs JAX traced and compiled (its own monitoring
    events), so a run can report any that fell inside its window."""

    def __init__(self):
        import jax
        self.traced = self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.traced += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1

    def snapshot(self):
        return (self.traced, self.compiled)

    def report(self, since, what: str = "window"):
        log(f"bench: {self.traced - since[0]} programs traced and "
            f"{self.compiled - since[1]} compiled inside the {what}")


class Stalls:
    """Where a window lost time: the longest gaps a job marked (from its
    last mark or `begin` to `mark`: a step, a tick) and the garbage
    collections that ran, each with its offset from the window's start.
    Printed on stderr before the result, so that a slow run says where
    it stalled."""

    def __init__(self):
        self.t0 = self.last = time.perf_counter()
        self.gaps: List[tuple] = []
        self.gcs: List[tuple] = []
        self._gc_start = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._gc_start is not None:
            self.gcs.append((time.perf_counter() - self._gc_start,
                             self._gc_start - self.t0, info["generation"]))
            self._gc_start = None

    def begin(self):
        """Start the next gap here, not at the last mark."""
        self.last = time.perf_counter()

    def mark(self):
        now = time.perf_counter()
        self.gaps.append((now - self.last, self.last - self.t0))
        self.last = now

    def report(self, what: str, n: int = 3):
        gc.callbacks.remove(self._on_gc)
        gaps = sorted(self.gaps, reverse=True)
        fmt = lambda xs: ", ".join(f"{x[0]:.3f} s at {x[1]:.1f} s"
                                   for x in xs) or "none"
        log(f"bench: longest {what}s: {fmt(gaps[:n])} "
            f"(of {len(gaps)})")
        log(f"bench: {len(self.gcs)} garbage collections in the window, "
            f"longest: {fmt(sorted(self.gcs, reverse=True)[:n])}")


def add_program_to_path():
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def memory_peak_bytes(devices) -> Optional[int]:
    peaks_ = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else None


# --------------------------------------------------------- statistics
def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of all values; +inf
    entries (requests that failed) count as the largest."""
    xs = sorted(values)
    if not xs:
        return math.nan
    k = max(0, math.ceil(q / 100.0 * len(xs)) - 1)
    return xs[k]


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def emit(correct: bool, attempted: int, failed: int,
         metrics: Dict[str, Any], device: Dict[str, Any],
         checks: List[Dict[str, Any]],
         breakdown: Optional[Dict[str, Any]] = None):
    """The compared numbers go last, on stderr and in the result line;
    the result line is the last line of stdout."""
    for c in checks:
        log(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}")
    line: Dict[str, Any] = {"correct": bool(correct),
                            "attempted": int(attempted),
                            "failed": int(failed), "metrics": metrics,
                            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    # a reading that is not finite is printed as the largest float
    line["checks"] = {c["name"]: {"value": min(c["value"], 1.7e308),
                                  "limit": c["limit"]} for c in checks}
    print(json.dumps(line), flush=True)


def judge(readings: Dict[str, float], limits: Dict[str, float]
          ) -> tuple:
    """(correct, checks): each reading beside its limit. A reading that
    is missing or not finite fails."""
    checks, ok = [], True
    for name, limit in limits.items():
        v = readings.get(name, math.inf)
        if v is None or not math.isfinite(v):
            v = math.inf
        checks.append({"name": name, "value": float(v),
                       "limit": float(limit)})
        ok &= v <= limit
    return ok, checks
