"""Find a cell's knee: the highest arrival rate at which the backlog does
not grow over the window.

    python3 chipbench/sweep.py --workload <cell> --rates 4,6,8 \\
        --seconds 40 [--seed 1] [--lead 40] [--drain 15]

One process, one set-up: the cell's stack is built and warmed once, then
its traffic mix is sent at each rate in turn for its lead (the mix's, or
``--lead``) and ``--seconds`` more, and drained (for at most the mix's
cap, or ``--drain``) before the next.  Per rate it prints the backlog's
growth over the window, in requests and in output tokens owed (requests
due and not completed, counted at their whole output length), both about
0 below the knee; the output tokens per second offered and completed;
the latency tails and the share of requests within the mix's limits.
Each row is appended as JSON to ``bench_results/sweep-<cell>.jsonl``.
The rate a cell runs at is written into its traffic file by hand from
this table.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

_REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(_REPO), str(_REPO / "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(_REPO / ".jax_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")


def note(*parts: object) -> None:
    print("[sweep]", *parts, file=sys.stderr, flush=True)


def _owed(recs, t: float) -> list:
    """Requests due by ``t`` and not completed by ``t``."""
    return [q for q in recs if q.due <= t
            and not (q.ok and q.done is not None and q.done <= t)]


def backlog(recs, t: float) -> int:
    return len(_owed(recs, t))


def owed_tokens(recs, t: float) -> int:
    return sum(q.gen_len for q in _owed(recs, t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--lead", type=float, default=None,
                    help="seconds of traffic before the window")
    ap.add_argument("--drain", type=float, default=None,
                    help="most seconds to wait for the window's requests")
    args = ap.parse_args(argv)

    from chipbench import spec
    from chipbench.harness import CompileClock, setup

    cell = spec.cell(args.workload)
    compiles = CompileClock()
    stack = setup(cell, args.seed, True, note)
    out = _REPO / "bench_results" / f"sweep-{cell.name}.jsonl"
    out.parent.mkdir(exist_ok=True)
    asyncio.run(_sweep(cell, stack, compiles, args, out))
    return 0


async def _sweep(cell, stack, compiles, args, out) -> None:
    from chipbench import stats
    from chipbench.harness import Run, end_to_end, serve_async
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = copy.deepcopy(cell.traffic)
        mix["arrivals"]["rate_rps"] = rate
        if args.lead is not None:
            mix["lead_s"] = args.lead
        if args.drain is not None:
            mix["drain_cap_s"] = args.drain
        stack.slices.records.clear()
        recs, box = await serve_async(stack, mix, args.seed, args.seconds,
                                      note)
        a, b = box["t_open"], box["t_close"]
        run = Run(cell=cell, model=stack.conf["model"],
                  records=stack.slices.records, requests=recs,
                  first_dispatch=stack.slices.first_dispatch, window=(a, b))
        e = end_to_end(run, mix, 0.0)
        inside = [r for r in run.records if a < r["t1"] <= b]
        row = dict(rate_rps=rate, lead_s=mix["lead_s"], window_s=b - a,
                   backlog_open=backlog(recs, a),
                   backlog_close=backlog(recs, b),
                   backlog_growth_rps=(backlog(recs, b) - backlog(recs, a))
                   / (b - a),
                   owed_tok_open=owed_tokens(recs, a),
                   owed_tok_close=owed_tokens(recs, b),
                   owed_growth_tok_s=(owed_tokens(recs, b)
                                      - owed_tokens(recs, a)) / (b - a),
                   offered_tok_s=sum(q.gen_len for q in run.due_in(a, b))
                   / (b - a),
                   out_tok_s=e["out_tok_s"], ttft_p95_ms=e["ttft_p95_ms"],
                   norm_lat_p95_ms=e["norm_lat_p95_ms"],
                   slo_met_share=e["slo_met_share"],
                   ttft_p50_ms=e["_info"]["ttft_p50_ms"],
                   rows_mean=(sum(r["rows"] for r in inside) / len(inside)
                              if inside else 0.0),
                   slices=len(inside),
                   evictions=sum(r["evictions"] for r in inside),
                   compiles_in_window=compiles.between(a, b)[0],
                   completed=sum(q.ok for q in recs), sent=len(recs),
                   lateness_p50_ms=1e3 * stats.percentile(
                       [q.submit - q.due for q in recs if q.submit], 50))
        note(json.dumps(row))
        with open(out, "a") as f:
            f.write(json.dumps(dict(cell=cell.name, **row)) + "\n")


if __name__ == "__main__":
    sys.exit(main())
