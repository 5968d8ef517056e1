"""Share of the traced serving window in which no operation ran on the
device: 1 - (union of op intervals) / window. Source: device trace."""
from bench import trace as T


def read(ctx):
    tr = ctx["trace"]
    devs = sorted(tr.ops)[:ctx["chips"]]
    if not devs:
        return None
    lo, hi = tr.window()
    return max(100.0 * (1.0 - T.busy_ns(tr, d) / (hi - lo)) for d in devs)
