"""Readings that set a cell's ``logit_gap`` limit: the program's on many
seeds, and the control's, in one process.

    python3 chipbench/control.py --workload <cell> --seeds 11,12,13 \\
        --seconds 45

Each seed is a whole run of the cell at its own load and window (its
shapes compile when first used: the timing of these runs is not
measured).  After the window the sampled requests are scored by the
float32 reference (the program's reading) and by the same reference in
float8 put in the program's place (the control: the gap of the token the
float8 forward puts first, judged by the same rule as the program, so
its ``correct`` has to come out false).  One JSON row per seed goes to standard error and to
``bench_results/control-<cell>.jsonl``.  The limit lies above the largest
program reading and below the smallest control reading.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

_REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(_REPO), str(_REPO / "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(_REPO / ".jax_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")


def note(*parts: object) -> None:
    print("[control]", *parts, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from chipbench import spec
    from chipbench.harness import CompileClock, run_cell

    cell = spec.cell(args.workload)
    compiles = CompileClock()
    out = _REPO / "bench_results" / f"control-{cell.name}.jsonl"
    out.parent.mkdir(exist_ok=True)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        res = run_cell(cell, seed, args.seconds, False, t0, compiles,
                       log=note, control=True)
        row = dict(cell=cell.name, seed=seed,
                   program_gap=res["program_gap"],
                   control_gap=res["check"]["logit_gap"]["value"],
                   limit=res["check"]["logit_gap"]["limit"],
                   control_correct=res["correct"],
                   length_errors=res["check"]["length_errors"]["value"],
                   attempted=res["attempted"], failed=res["failed"],
                   seconds=time.perf_counter() - t0)
        note(json.dumps(row))
        with open(out, "a") as f:
            f.write(json.dumps(row) + "\n")
        del res
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
