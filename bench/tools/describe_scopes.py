"""Print what the program's own names say of the newest trace of a cell:
the metadata stats of its first few XLA Ops events (where an op's
op_name is found), the device time under each layer scope with the
unscoped remainder, the host time per program span, and the idle gaps
named by the innermost host span, the program's phases included.

    python3 bench/tools/describe_scopes.py <cell> [<scope> ...]
"""
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import scopes as S, trace as T  # noqa: E402

SCOPES = ["attention", "mamba", "mlp", "accumulate", "optimizer"]

cell = sys.argv[1]
scopes = sys.argv[2:] or SCOPES
path = sorted(glob.glob(f"bench/.cache/trace/{cell}/**/*.xplane.pb",
                        recursive=True))[-1]
with open(path, "rb") as f:
    buf = f.read()
for dev, plane in S.device_planes(buf):
    for i, (name, stats) in enumerate(S.op_events(buf, plane)):
        if i == 3:
            break
        print(dev, T.op_name(name), "metadata stats:",
              {k: str(v)[:200] for k, v in stats.items()})
tr = T.load(path)
nm = S.load(path, tr)
for dev in sorted(tr.ops):
    busy = T.busy_ns(tr, dev)
    shares = {s: S.scope_share(tr, nm, dev, s) for s in scopes}
    unscoped = [op for op, p in zip(tr.ops[dev], nm.paths.get(dev, []))
                if not set(S.SEGMENT.split(p)) & set(scopes)
                and not T.CONTAINER.match(op[2])]
    lo, hi = tr.window()
    rest = T.length(T.union(T.clip(unscoped, lo, hi)))
    print(dev, "paths", dev in nm.paths, "share %", json.dumps(shares),
          "unscoped ops %", 100.0 * rest / busy if busy else None)
    names = sorted({s[2] for s in nm.spans})
    print(dev, "host spans (idle ns, count):", json.dumps(
        {n: S.span_idle_ns(tr, nm, dev, n[len(S.PROGRAM_PREFIX):])
         for n in names}))
    print(dev, "idle gaps s:", json.dumps(
        [(k, v / 1e9) for k, v in S.gap_breakdown(tr, nm, dev)]))
