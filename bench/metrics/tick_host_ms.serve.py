"""Host time per serving tick: the device's idle time inside the
program's `repro:serve.step` spans (one per `ServeEngine.step`) over the
number of those spans in the traced window, in ms. Source: device trace
(the spans are on the profiler's clock)."""
from bench import scopes as S


def read(ctx):
    tr = ctx["trace"]
    devs = sorted(tr.ops)[:1]
    if not devs:
        return None
    idle, n = S.span_idle_ns(tr, S.names(ctx), devs[0], "serve.step")
    return idle / n / 1e6 if n else None
