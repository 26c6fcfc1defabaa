"""Percentiles and spreads, one definition for the whole benchmark."""
from __future__ import annotations

import json
import statistics
import sys


def percentile(values, q: float) -> float:
    """q-th percentile (0-100) with linear interpolation between order
    statistics (numpy's default, 'inclusive' quantiles)."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    if len(xs) == 1:
        return xs[0]
    h = (len(xs) - 1) * q / 100.0
    lo = int(h)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (h - lo) * (xs[hi] - xs[lo])


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, as ``statistics.quantiles(values, n=4)`` gives them."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def summarize(lines) -> dict:
    """Median and spread of each metric over benchmark result lines (the
    JSON objects ``bench/run.py`` prints last)."""
    runs = [json.loads(s) for s in lines if s.startswith("{")]
    names = sorted({k for r in runs for k in r["metrics"]})
    out = {}
    for n in names:
        vals = [r["metrics"][n]["value"] for r in runs if n in r["metrics"]]
        out[n] = {"runs": len(vals), "median": statistics.median(vals),
                  "spread": spread(vals) if len(vals) > 1 else None}
    out["correct"] = [r["correct"] for r in runs]
    return out


if __name__ == "__main__":
    # python3 bench/stats.py results.jsonl ...: medians and spreads
    for path in sys.argv[1:]:
        with open(path) as f:
            print(path, json.dumps(summarize(f.readlines()), indent=1))
