"""Record the small trace that ``test_chipbench_trace.py`` reduces.

    python3 chipbench/tests/record_trace.py   # on a machine with a TPU

Inside a ``chipbench.trace_window`` span: three runs of a jitted bf16
matmul program (``jit_work``), each waited for inside a ``chipbench.work``
span, then a ``chipbench.sleep`` span of 50 ms in which the device does
nothing, then three more runs.  Writes the ``.xplane.pb`` and what the
host measured (``expected.json``) to ``bench_results/trace_fixture/``;
the two files are committed under ``chipbench/tests/data/``.
"""
from __future__ import annotations

import glob
import json
import os
import pathlib
import shutil
import sys
import time

_REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(_REPO), str(_REPO / "src")]


def main() -> None:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    out = _REPO / "bench_results" / "trace_fixture"
    shutil.rmtree(out, ignore_errors=True)
    tdir = out / "raw"
    x = jnp.ones((4096, 4096), jnp.bfloat16)

    def work(x):
        for _ in range(4):
            x = jnp.tanh(x @ x * (1.0 / 64))
        return x

    work = jax.jit(work)
    work(x).block_until_ready()  # compiled before the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    win = jax.profiler.TraceAnnotation("chipbench.trace_window")
    win.__enter__()
    t0 = time.perf_counter()
    runs = []
    for part in range(2):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.work"):
                a = time.perf_counter()
                work(x).block_until_ready()
                runs.append(time.perf_counter() - a)
        if part == 0:
            with jax.profiler.TraceAnnotation("chipbench.sleep"):
                time.sleep(0.05)
    t1 = time.perf_counter()
    win.__exit__(None, None, None)
    jax.profiler.stop_trace()
    f = glob.glob(str(tdir / "**" / "*.xplane.pb"), recursive=True)[0]
    shutil.copy(f, out / "small.xplane.pb")
    shutil.rmtree(tdir)
    (out / "expected.json").write_text(json.dumps(dict(
        window_s=t1 - t0, sleep_s=0.05, work_runs=len(runs),
        work_wall_s=runs, flops_per_run=4 * 2 * 4096 ** 3,
        device_kind=jax.devices()[0].device_kind), indent=1))
    print(json.dumps(dict(size=os.path.getsize(out / "small.xplane.pb"),
                          window_s=t1 - t0, runs=runs)))


if __name__ == "__main__":
    main()
