"""Set a cell's check limit from the readings ``calibrate.py`` took.

    python3 bench/tools/set_limit.py granite-8b.code bench_out/calibrate.jsonl

The lower reading is the largest widest gap the program gave over its
seeds.  The upper reading is the least that any control read on three seeds
or more gave, taken over the controls whose least reading is three times the
lower or more.  The limit is lower^0.4 * upper^0.6: above the lower, below
the upper, with more room above the lower.  Writes the cell file and prints
the readings with the verdict ``correct`` gives each control reading at the
new limit; exits 1, writing nothing, when no control separates.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.lib import check  # noqa: E402


def readings(recs: list[dict]) -> dict:
    """The lower and upper readings of ``calibrate.py``'s ``limits`` records,
    the limit between them (None where no control separates), and the
    verdict ``correct`` gives each control reading at that limit."""
    lower = max(r["widest_gap"] for r in recs)
    out = {"seeds": [r["seed"] for r in recs], "program": [r["widest_gap"] for r in recs],
           "lower": lower, "controls": {}, "limit": None}
    uppers = {}
    for key in sorted({k for r in recs for k in r if k.startswith("control_") and k.endswith("_widest_gap")}):
        fmt = key[len("control_"):-len("_widest_gap")]
        ctrl = [r[key] for r in recs if key in r]
        out["controls"][fmt] = {"readings": ctrl, "least": min(ctrl)}
        if len(ctrl) >= 3 and lower > 0 and min(ctrl) >= 3 * lower:
            uppers[fmt] = min(ctrl)
    if not uppers:
        return out
    upper = min(uppers.values())
    limit = float(f"{lower**0.4 * upper**0.6:.4g}")
    out.update(upper=upper, upper_from=min(uppers, key=uppers.get), limit=limit)
    for c in out["controls"].values():
        c["correct"] = [check.verdict(v, 0, limit) for v in c["readings"]]
    return out


def main(cell: str, path: str) -> int:
    recs = [json.loads(l) for l in open(path) if l.strip()]
    out = dict(readings([r for r in recs if r.get("cell") == cell and r.get("mode") == "limits"]), cell=cell)
    print(json.dumps(out))
    if out["limit"] is None:
        return 1
    p = os.path.join(ROOT, "bench", "cells", cell + ".json")
    d = json.load(open(p))
    d["check"]["limit"] = out["limit"]
    with open(p, "w") as f:
        f.write(json.dumps(d, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
