"""Spreads of a cell's runs, from which its bounds are set.

    python3 chipbench/spreads.py <results.jsonl> [--sets 2]

The file holds the result lines of ``run.py``, one per run, the sets one
after another (each set the same seeds).  For each metric it prints every
set's median and spread (the distance between the quartiles over the
median, ``stats.spread``), the spread of all runs, the mean of the
sets' spreads with each set's run farthest from its median left out, and
five times the wider set's spread, which is where a bound is set.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def _drop_farthest(v):
    med = statistics.median(v)
    far = max(range(len(v)), key=lambda i: abs(v[i] - med))
    return v[:far] + v[far + 1:]


def main(argv=None) -> None:
    from chipbench import stats

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("results")
    ap.add_argument("--sets", type=int, default=2)
    args = ap.parse_args(argv)
    with open(args.results) as f:
        rows = [json.loads(ln) for ln in f if ln.startswith("{")]
    n = len(rows) // args.sets
    sets = [rows[i * n:(i + 1) * n] for i in range(args.sets)]
    print(f"{len(rows)} runs; correct {[r['correct'] for r in rows]}")
    for name in sorted({k for r in rows for k in r["metrics"]}):
        per = [[float(r["metrics"][name]["value"]) for r in s] for s in sets]
        wide = max(stats.spread(v) for v in per)
        every = stats.spread([x for v in per for x in v])
        trimmed = statistics.mean(stats.spread(_drop_farthest(v))
                                  for v in per)
        print(name, json.dumps(dict(
            medians=[statistics.median(v) for v in per],
            spreads=[stats.spread(v) for v in per], all_runs=every,
            trimmed=trimmed,
            five_times_widest=5 * wide, values=per)))


if __name__ == "__main__":
    main()
