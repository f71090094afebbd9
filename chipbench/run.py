"""Run one cell of ``BENCHMARK.json`` once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, from a run that traces a few seconds of the window.
The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``) and, last, ``check``: each number compared with its
limit, which also end standard error.  Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # noqa: E402  (set-up is timed from here)

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

_REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(_REPO), str(_REPO / "src")]
# JAX's persistent compilation cache: at a fixed path inside the checkout,
# whatever the environment names, so that two checkouts share nothing;
# every program is kept, however short its compilation, so that a second
# run compiles nothing
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(_REPO / ".jax_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")


def note(*parts: object) -> None:
    print("[chipbench]", *parts, file=sys.stderr, flush=True)


def _num(x):
    """JSON has no infinity: an infinite tail is written as a string."""
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    if isinstance(x, dict):
        return {k: _num(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_num(v) for v in x]
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import spec
    from chipbench.harness import CompileClock, NoChip, run_cell

    try:
        cell = spec.cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        note(f"FAILED: {e}")
        return 2
    compiles = CompileClock()
    trace_dir = str(_REPO / "bench_results" / "trace" / cell.name)
    try:
        res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       T_PROCESS, compiles, log=note, trace_dir=trace_dir)
    except NoChip as e:
        note(f"FAILED: {e}")
        return 1
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": res["metrics"],
            "device": res["device"]}
    if "breakdown" in res:
        line["breakdown"] = res["breakdown"]
    line["check"] = res["check"]
    note(f"run took {time.perf_counter() - T_PROCESS:.1f} s")
    for name, c in res["check"].items():
        note(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(_num(line)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
