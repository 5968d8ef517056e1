"""Decode ticks' share of their roofline: the least time the chip could
take for the traced ticks' work over the device time of their decode
programs. The least time of a tick is the larger of its FLOPs over the
bf16 peak and its bytes over the HBM bandwidth; bytes are every weight
once plus the latent rows live in the slots it decoded (counted by the
client from the tokens each request held). Counts come from the
configuration's reference module. Source: device trace."""
from bench import trace as T


def bound(ctx):
    """(least seconds, FLOP-bound ticks, byte-bound ticks) of the
    traced ticks that decoded."""
    ref, cfg, pk = ctx["reference"], ctx["cfg"], ctx["peaks"]
    least, by_flops, by_bytes = 0.0, 0, 0
    for decoded, rows in ctx["ticks"]:
        if not decoded:
            continue
        tf = ref.decode_flops(cfg, rows, decoded) / pk["bf16_flops"]
        tb = ref.decode_bytes(cfg, rows) / pk["hbm_bytes_per_s"]
        least += max(tf, tb)
        by_flops += tf >= tb
        by_bytes += tb > tf
    return least, by_flops, by_bytes


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
    return 100.0 * bound(ctx)[0] / dev_s
