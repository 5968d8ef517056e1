"""Host spans on the profiler's clock.

`span(name)` marks a stretch of the program's host work with a
`jax.profiler.TraceAnnotation` named "repro:<name>". A profiler trace
shows it on the same clock as the device's operations, so a gap in the
device's work can be named by what the host was doing then. Given a
dict as `into`, the span also adds its wall seconds to `into[name]`:
that is how the serving engine keeps its per-tick phase times.

There is no switch: an annotation while no trace is being recorded
costs about a microsecond, and these spans run a few times per serving
tick or training step.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, Optional

import jax

PREFIX = "repro:"


@contextlib.contextmanager
def span(name: str, into: Optional[Dict[str, float]] = None
         ) -> Iterator[None]:
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(PREFIX + name):
        try:
            yield
        finally:
            if into is not None:
                into[name] = into.get(name, 0.0) + (time.perf_counter()
                                                    - t0)
