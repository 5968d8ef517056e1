"""Share of the device's busy time in the traced training window spent in
ops under the program's `mlp` scope (the MLP, forward and backward): the
union of those ops' intervals, found by their name-stack path, over the
union of all ops. Source: device trace."""
from bench import scopes as S


def read(ctx):
    tr = ctx["trace"]
    devs = sorted(tr.ops)[:1]
    return S.scope_share(tr, S.names(ctx), devs[0], "mlp") if devs \
        else None
