"""The arithmetic of the per-layer metrics.  Each metric is a file of its
own under ``chipbench/metrics/`` whose ``read(run)`` calls one of these;
each returns ``None`` when the run holds nothing for it to read.  All of
them read the traced window: the slices that began and ended inside it,
the requests due inside it, and the reduced trace."""
from __future__ import annotations

from typing import Optional

from chipbench import stats

#: the decode-slice program, by its jitted name
#: (``StaticEngine._serve_paged_fn``'s ``serve``)
DECODE_MODULE = "jit_serve"


def _slices(run):
    if run.trace_window is None:
        return []
    return run.traced_slices()


def decode_step_ms(run) -> Optional[float]:
    """Decode time per step: the engine's measured slice time less its
    measured prefill time, summed, over the steps summed."""
    s = _slices(run)
    steps = sum(r["steps"] for r in s)
    if not steps:
        return None
    return 1e3 * sum(r["wall"] - r["prefill"] for r in s) / steps


def queue_wait_p50_ms(run) -> Optional[float]:
    """Median wait from a request's due time to its first dispatch, over
    the requests due in the traced window (never dispatched: infinite)."""
    if run.trace_window is None:
        return None
    due = run.due_in(*run.trace_window)
    if not due:
        return None
    waits = [1e3 * (run.first_dispatch[q.rid] - q.due)
             if q.rid in run.first_dispatch else float("inf") for q in due]
    return stats.percentile(waits, 50)


def mfu(run) -> Optional[float]:
    """Model FLOPs of the slices inside the traced window (the
    configuration's ``counts``) over the window times the chip's bf16
    peak (percent)."""
    s = _slices(run)
    if not s or run.trace is None or run.peaks is None:
        return None
    work = sum(run.cell.counts.slice_flops(run.model, r) for r in s)
    return 100.0 * work / (run.trace["window_s"] * run.peaks["bf16_flops"])


def decode_roofline(run) -> Optional[float]:
    """The decode-slice program's least HBM time over its device time
    (percent; HBM bounds a decode step): per slice, its least bytes (the
    configuration's ``counts``) over the device time of that slice's
    ``jit_serve`` run."""
    s = _slices(run)
    if not s or run.trace is None or run.peaks is None:
        return None
    runs = run.trace["module_host"].get(DECODE_MODULE, [])
    if not runs:
        return None
    need, dev_s = 0.0, 0.0
    for r in s:
        # matched by midpoint: the trace's device clock runs about a
        # millisecond apart from the host's
        mine = [(a, b) for a, b in runs if r["t0"] <= (a + b) / 2 <= r["t1"]]
        if len(mine) != 1:
            continue
        dev_s += mine[0][1] - mine[0][0]
        need += run.cell.counts.decode_slice_min_bytes(run.model, r)
    if dev_s <= 0:
        return None
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / dev_s


def idle_share(run) -> Optional[float]:
    """Share of the traced window in which no operation ran on the
    device (percent)."""
    if run.trace is None:
        return None
    t = run.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
