"""Print the planes and lines of the newest trace of a cell, and save a
short stretch of its reduced record as JSON (a first look at a trace by
hand, and a small recorded trace for the tests).

    python3 bench/tools/describe_trace.py <cell> <out.json> [<ms>]
"""
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import trace as T  # noqa: E402

cell, out = sys.argv[1], sys.argv[2]
ms = float(sys.argv[3]) if len(sys.argv) > 3 else 20.0
paths = sorted(glob.glob(f"bench/.cache/trace/{cell}/**/*.xplane.pb",
                         recursive=True))
for line in T.describe(paths[-1]):
    print(line)
tr = T.load(paths[-1])
lo, hi = tr.window()
print("window_s", (hi - lo) / 1e9, "summary", json.dumps(T.summary(tr, 4)))
cut = lo + ms * 1e6
short = T.Trace({d: T.clip(v, lo, cut) for d, v in tr.ops.items()},
                {d: T.clip(v, lo, cut) for d, v in tr.modules.items()},
                [(s, min(e, cut), n) for s, e, n in tr.spans
                 if lo <= s < cut] + [(lo, cut, T.WINDOW_SPAN)])
short.spans = [s for s in short.spans
               if not (s[2] == T.WINDOW_SPAN and s[1] != cut)]
with open(out, "w") as f:
    json.dump(short.to_json(), f)
