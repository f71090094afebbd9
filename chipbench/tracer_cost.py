"""Run one cell as ``run.py`` does, with the program's own tracer recording.

    python3 chipbench/tracer_cost.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1> [--out DIR]

The served stack is the one ``run.py`` builds, with its ``repro.obs``
tracer switched on after the build: every program span, instant and
request lifecycle is kept in memory as a Chrome trace event on the host
clock.  The result line is ``run.py``'s, so the same seed run both ways
gives what recording costs.  With ``--out`` the program's trace
(``trace.json.gz``) and the requests as the client sent them
(``requests.json``: due time, rid, submit and first token on the host
clock, and the traced window) are written to ``DIR``.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import pathlib
import sys

_REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(_REPO), str(_REPO / "src")]

from chipbench import run as bench_run  # noqa: E402  (starts the set-up clock)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    args, rest = ap.parse_known_args(argv)

    from chipbench import harness
    from repro.obs import Tracer

    kept = {}
    build = harness.build_server
    drive = harness.drive

    def build_recording(conf, engine, est, seed):
        server = build(conf, engine, est, seed)
        obs = server.core.obs
        obs.tracer = Tracer()
        obs.enabled = True
        obs.attach(server.core)
        kept["server"] = server
        return server

    async def drive_kept(aio, plan, recs, slices, mix, t_start, seconds,
                         max_gen, tracer=None):
        kept["recs"], kept["tracer"] = recs, tracer
        return await drive(aio, plan, recs, slices, mix, t_start, seconds,
                           max_gen, tracer)

    harness.build_server = build_recording
    harness.drive = drive_kept
    rc = bench_run.main(rest)
    if args.out and "server" in kept:
        os.makedirs(args.out, exist_ok=True)
        with gzip.open(os.path.join(args.out, "trace.json.gz"), "wt") as f:
            f.write(kept["server"].core.obs.tracer.to_json())
        tw = kept["tracer"].window if kept.get("tracer") else None
        with open(os.path.join(args.out, "requests.json"), "w") as f:
            json.dump(dict(trace_window=tw, requests=[
                [q.due, q.rid, q.submit, q.first] for q in kept["recs"]]),
                f)
    return rc


if __name__ == "__main__":
    sys.exit(main())
