"""Share of the decode programs' device time (programs mapped to phases
by bench/phases.json) spent in ops under the program's `attention`
scope: the paged latent attention of each layer's decode step. Source:
device trace."""
from bench import scopes as S, trace as T


def read(ctx):
    tr = ctx["trace"]
    devs = sorted(tr.ops)[:1]
    if not devs:
        return None
    attn = S.scope_ns(tr, S.names(ctx), devs[0], "attention",
                      ctx["phases"]["decode"])
    decode = T.phase_ns(tr, devs[0], ctx["phases"]).get("decode", 0.0)
    if attn is None or decode <= 0:
        return None
    return 100.0 * attn / decode
