"""Record the small program trace that ``test_chipbench_program_spans.py``
reads.

    JAX_PLATFORMS=cpu python3 chipbench/tests/record_program_trace.py

One traced run of the harness on the CPU at the test size
(``tests/data``: the tiny configuration and traffic): the window's
``.xplane.pb``, whose host plane holds the program's ``scls.*`` spans, and
what the harness measured (each request's due time, rid and submit, each
request's first dispatch from ``SliceLog``, the traced window and the
four program-span metrics as the run read them) go to
``bench_results/program_trace_fixture/``; the JSON file and the trace,
compressed (``xz -9``), are committed under ``chipbench/tests/data/``.
"""
from __future__ import annotations

import glob
import json
import pathlib
import shutil
import sys
import time

_REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(_REPO), str(_REPO / "src")]

METRICS = ("submit_lag_p50_ms.lat", "sched_wait_p50_ms.lat",
           "est_err_p50_pct.lat", "slice_host_share.lat")
CELL = "tiny.cell"
SEED = 2 ** 31 + 23


def bench() -> dict:
    return {"workloads": [{"name": CELL, "config": "tiny",
                           "traffic": "tiny", "chips": 1}],
            "end_to_end": [{"name": "ttft_p95_ms", "unit": "ms"}],
            "per_layer": []}


def main() -> None:
    from chipbench import program_spans, spec
    from chipbench.harness import CompileClock, run_cell

    out = _REPO / "bench_results" / "program_trace_fixture"
    shutil.rmtree(out, ignore_errors=True)
    program_spans.TRACE_ROOT = out / "raw"
    cell = spec.cell(CELL, bench(), root=spec.HERE / "tests" / "data")
    seen = {}
    for name in METRICS:
        def capture(run, read=spec.reader(name)):
            seen["run"] = run
            return read(run)
        cell.per_layer.append({"name": name, "unit": "x"})
        cell.readers[name] = capture
    res = run_cell(cell, SEED, 4.0, True, time.perf_counter(),
                   CompileClock(), require_tpu=False, log=print,
                   trace_dir=str(out / "raw" / CELL))
    run = seen["run"]
    f = glob.glob(str(out / "raw" / "**" / "*.xplane.pb"), recursive=True)[0]
    shutil.copy(f, out / "program_trace.xplane.pb")
    shutil.rmtree(out / "raw")
    (out / "program_trace.json").write_text(json.dumps(dict(
        cell=CELL, trace_window=list(run.trace_window),
        requests=[[q.due, q.rid, q.submit] for q in run.requests],
        first_dispatch={str(k): v for k, v in run.first_dispatch.items()},
        metrics={k: v["value"] for k, v in res["metrics"].items()}),
        indent=1))
    print(json.dumps(res["metrics"]))


if __name__ == "__main__":
    main()
