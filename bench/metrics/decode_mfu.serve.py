"""Model FLOP utilization of the decode ticks: their model FLOPs (the
configuration's own count) over their decode programs' device time
times the bf16 peak. Source: device trace."""
from bench import trace as T


def read(ctx):
    tr = ctx["trace"]
    devs = sorted(tr.ops)[:1]
    if not devs or not ctx.get("ticks"):
        return None
    dev_s = T.phase_ns(tr, devs[0], ctx["phases"]).get("decode", 0.0) / 1e9
    n_prog = T.module_count(tr, devs[0], ctx["phases"], "decode")
    n_ticks = sum(1 for d, _ in ctx["ticks"] if d)
    if dev_s <= 0 or n_prog != n_ticks:
        return None
    ref, cfg = ctx["reference"], ctx["cfg"]
    flops = sum(ref.decode_flops(cfg, rows, decoded)
                for decoded, rows in ctx["ticks"] if decoded)
    return 100.0 * flops / (dev_s * ctx["peaks"]["bf16_flops"])
