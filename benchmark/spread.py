#!/usr/bin/env python3
"""The builder's reading of a cell's two sets of runs (never the driver's).

    python3 benchmark/spread.py <dir> <prefix>

Reads ``<dir>/<prefix>_s1_<i>.json`` and ``<prefix>_s2_<i>.json`` (the
``--dump`` of each run, same seeds in both sets) and prints, per end-to-end
metric, each set's median and spread (first to third quartile by
``statistics.quantiles(n=4)``, over the median), the wider of the two, the
mean of the two with each set's farthest run left out (the driver's reading
for tightness), and the second median against the first."""

from __future__ import annotations

import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import arith  # noqa: E402


def trimmed(values):
    med = statistics.median(values)
    far = max(values, key=lambda v: abs(v - med))
    rest = list(values)
    rest.remove(far)
    return arith.spread(rest)


def main(argv) -> int:
    folder, prefix = Path(argv[0]), argv[1]
    sets = []
    for k in (1, 2):
        files = sorted(folder.glob(f"{prefix}_s{k}_*.json"))
        sets.append([json.loads(f.read_text()) for f in files])
    names = sorted({m for runs in sets for r in runs for m in r["result"]["metrics"]})
    print(f"{prefix}: runs {[len(s) for s in sets]}, correct "
          f"{[sum(r['result']['correct'] for r in s) for s in sets]}")
    for name in names:
        vals = [[r["result"]["metrics"][name]["value"] for r in runs] for runs in sets]
        vals = [v for v in vals if len(v) >= 3]
        if not vals:
            continue
        spreads = [arith.spread(v) for v in vals]
        meds = [statistics.median(v) for v in vals]
        line = (f"  {name}: medians {[round(m, 3) for m in meds]} spreads "
                f"{[f'{100 * s:.3f}%' for s in spreads]} widest {100 * max(spreads):.3f}% "
                f"trimmed mean {100 * statistics.mean(trimmed(v) for v in vals):.3f}%")
        if len(meds) == 2:
            line += f" second/first {100 * (meds[1] / meds[0] - 1):+.3f}%"
        both = [x for v in vals for x in v]
        line += f" all-runs spread {100 * arith.spread(both):.3f}%"
        print(line)
    checks = [r["check"] for runs in sets for r in runs if r.get("check")]
    for key in ("gap_max", "logprob_err_mean", "topk_err_mean", "tokens", "probed_tokens"):
        got = [c[key] for c in checks if key in c]
        if got:
            print(f"  check {key}: min {min(got):.6g} max {max(got):.6g}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
