"""Prefill programs' share of the device's busy time in the traced
serving window. Programs are mapped to phases by bench/phases.json.
Source: device trace."""
from bench import trace as T


def read(ctx):
    tr = ctx["trace"]
    devs = sorted(tr.ops)[:1]
    if not devs:
        return None
    busy = T.busy_ns(tr, devs[0])
    if busy <= 0:
        return None
    pre = T.phase_ns(tr, devs[0], ctx["phases"]).get("prefill", 0.0)
    return 100.0 * pre / busy
