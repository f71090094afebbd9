"""The traced window and its reduction to device busy time, idle gaps by
host span, device operations and program times.

A traced run records a few seconds of the window with the JAX profiler.
The host spans are the benchmark's own (``chipbench.*``
``TraceAnnotation``s around the calls into each layer); the device
events are the TPU's ``XLA Ops`` (every HLO operation, parents such as a
``while`` included) and ``XLA Modules`` (one event per program run, named
``jit_<function>(<hash>)``).  Host and device events share the trace's
clock.

- busy: the union of the ``XLA Ops`` intervals inside the window;
- idle gaps: the rest of the window, cut where a host span opens or
  closes, each piece named by the host span opened last among those open
  in it (one thread drives the server, so that is what the host was
  doing);
- device operations: leaf operations (those with no operation inside
  them), summed by program and operation name.
"""
from __future__ import annotations

import asyncio
import bisect
import glob
import os
import shutil
import time
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "chipbench.trace_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _base(module: str) -> str:
    return module.split("(", 1)[0]


def _op(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def leaves(events: List[Tuple[float, float, str]]):
    """The events that contain no other event (nesting on one line)."""
    ev = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    for i, (a, b, n) in enumerate(ev):
        nxt = ev[i + 1] if i + 1 < len(ev) else None
        if nxt is not None and nxt[0] < b and nxt[1] <= b:
            continue  # the next event starts inside this one: a parent
        out.append((a, b, n))
    return out


def _open_at(spans, starts, t: float) -> str:
    """The span opened last among those open at ``t``."""
    for k in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        if spans[k][1] >= t:
            return spans[k][2]
    return "none"


def read(path: str) -> Dict:
    """Host spans and device events of one ``.xplane.pb``, in ns."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    mods = [(e.start_ns, e.start_ns + e.duration_ns,
                             _base(e.name)) for e in line.events]
            devices.append(dict(name=plane.name, ops=ops, modules=mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("chipbench."):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    return dict(spans=spans, devices=devices)


def reduce(raw: Dict, top: int = 10) -> Dict:
    """Busy and idle seconds, idle time by host span and the leaf device
    operations that took most time, inside the trace's window span."""
    wins = [s for s in raw["spans"] if s[2] == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w0, w1 = wins[0][0], wins[0][1]
    spans = sorted(s for s in raw["spans"] if s[2] != WINDOW_SPAN)
    starts = [s[0] for s in spans]
    bounds = sorted({t for s in spans for t in s[:2]})
    busy_ns = 0.0
    idle: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    modules: Dict[str, List[Tuple[float, float]]] = {}
    for dev in raw["devices"]:
        busy = union(clip([(a, b) for a, b, _ in dev["ops"]], w0, w1))
        busy_ns += sum(b - a for a, b in busy)
        gaps, t = [], w0
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < w1:
            gaps.append((t, w1))
        for a, b in gaps:
            cuts = bounds[bisect.bisect_right(bounds, a):
                          bisect.bisect_left(bounds, b)]
            for p, q in zip([a] + cuts, cuts + [b]):
                name = _open_at(spans, starts, (p + q) / 2)
                idle[name] = idle.get(name, 0.0) + (q - p)
        mods = sorted(dev["modules"])
        mstarts = [m[0] for m in mods]
        for a, b, n in leaves(dev["ops"]):
            if b <= w0 or a >= w1:
                continue
            j = bisect.bisect_right(mstarts, a) - 1
            mod = mods[j][2] if j >= 0 and mods[j][1] >= b else "?"
            key = f"{mod}/{_op(n)}"
            ops[key] = ops.get(key, 0.0) + (min(b, w1) - max(a, w0))
        for a, b, n in mods:
            # by midpoint: the device's clock in the trace runs about a
            # millisecond apart from the host's
            if w0 <= (a + b) / 2 <= w1:
                modules.setdefault(n, []).append((a, b))
    n_dev = max(1, len(raw["devices"]))
    window_ns = w1 - w0
    return dict(
        window_ns=(w0, w1), window_s=window_ns / 1e9,
        busy_s=busy_ns / n_dev / 1e9,
        modules=modules,
        breakdown=dict(
            device_ops=[[k, v / 1e9] for k, v in
                        sorted(ops.items(), key=lambda x: -x[1])[:top]],
            idle_gaps=[[k, v / n_dev / 1e9] for k, v in
                       sorted(idle.items(), key=lambda x: -x[1])[:top]]))


class WindowTracer:
    """Traces ``seconds`` of the window, from its nominal start, into
    ``out_dir`` (emptied first)."""

    def __init__(self, out_dir: str, seconds: float) -> None:
        self.out_dir = out_dir
        self.seconds = seconds
        self.window: Optional[Tuple[float, float]] = None

    async def run(self, t_lo: float, t_hi: float) -> None:
        import jax

        from chipbench.harness import clock, span
        await asyncio.sleep(max(0.0, t_lo - time.perf_counter()))
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        ann = span("trace_window")
        ann.__enter__()
        t0 = clock()
        await asyncio.sleep(max(0.0, min(t0 + self.seconds, t_hi) - clock()))
        t1 = clock()
        ann.__exit__(None, None, None)
        self.window = (t0, t1)
        # collecting and writing the trace takes seconds: off the loop,
        # so the server keeps serving meanwhile
        await asyncio.to_thread(jax.profiler.stop_trace)

    def reduce(self, records: List[Dict]) -> Dict:
        """The reduction of the trace, with each program run mapped back
        to the host clock of the slice records."""
        files = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no trace under {self.out_dir}")
        out = reduce(read(files[0]))
        w0 = out["window_ns"][0]
        t0 = self.window[0]
        # host clock of each program run: trace ns -> perf_counter s
        out["module_host"] = {
            n: [(t0 + (a - w0) / 1e9, t0 + (b - w0) / 1e9) for a, b in iv]
            for n, iv in out.pop("modules").items()}
        return out
