"""Per-layer metrics read from the program's own spans.

The served path opens a ``jax.profiler.TraceAnnotation`` named
``scls.<name>`` at each layer boundary (``repro.obs``): ``scls.submit``
(admission), ``scls.pacer.step`` (one core event), ``scls.sched.tick``
(Alg. 1 batching and Eq. 10-11 placement), ``scls.slice`` (one dispatch,
with the batch's rows and its Eq. 3/4 ``est_time``) and the engine phases
``scls.engine.plan``, ``.prefill``, ``.prefill_tail``, ``.decode`` and
``.retain`` inside it.  A traced run's profiler session puts them in the
host plane of the window's ``.xplane.pb``, on the device trace's clock,
args and all.  This module reads them there; the run's program needs no
switch, and a program without the spans gives nothing (``None``).

The trace holds the spans of the traced window only, so each metric
counts the events that happen inside it: the submits made in it, the
first dispatches made in it and the slices run in it.  A slice span
carries, for each row, the host seconds since the row was last ready
(``waited``: since its submit ended, for a row's first slice), so a
request submitted before the trace began still has its wait.

Host clock: trace nanoseconds map to ``time.perf_counter`` seconds
through the benchmark's ``chipbench.trace_window`` span, whose start the
harness stamps as ``run.trace_window[0]``.
"""
from __future__ import annotations

import bisect
import glob
import math
import os
import statistics
import sys
from typing import Dict, List, Optional, Tuple

from chipbench import spec, stats, tracing

#: where ``chipbench/run.py`` has the profiler write a cell's trace
TRACE_ROOT = spec.REPO / "bench_results" / "trace"
PREFIX = "scls."
SLICE = "scls.slice"
#: a slice's device stages; the rest of the slice is host bookkeeping
DEVICE_STAGES = ("scls.engine.prefill", "scls.engine.prefill_tail",
                 "scls.engine.decode")
DECODE_SPAN = "scls.engine.decode"
DECODE_MODULE = "jit_serve"
#: the paged decode step's named scopes (``models/transformer.py``,
#: ``models/attention.py``, the engine's sampling), innermost wins
SCOPES = ("kv_append", "kv_gather", "attention", "mlp", "head",
          "sampling")


def note(*parts: object) -> None:
    print("[chipbench]", *parts, file=sys.stderr, flush=True)


def _value(v: object) -> object:
    return v.decode() if isinstance(v, bytes) else v


def read(path: str) -> Dict:
    """The ``scls.*`` host spans (with their args), the window span and
    the device's operations and program runs of one ``.xplane.pb``, in
    trace ns.  Each device operation keeps the innermost named scope
    found in its string stats (``None`` when none)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    spans: List[Dict] = []
    window = None
    ops: List[Tuple[float, float, str, Optional[str]]] = []
    mods: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == tracing.OPS_LINE:
                    for e in line.events:
                        ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                    e.name, _scope(e.stats)))
                elif line.name == tracing.MODULES_LINE:
                    mods += [(e.start_ns, e.start_ns + e.duration_ns,
                              tracing._base(e.name)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == tracing.WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith(PREFIX):
                        spans.append(dict(
                            name=e.name, a=e.start_ns,
                            b=e.start_ns + e.duration_ns,
                            args={k: _value(v) for k, v in e.stats}))
    spans.sort(key=lambda s: (s["a"], -s["b"]))
    return dict(spans=spans, window=window, ops=ops, modules=mods)


def _scope(stats) -> Optional[str]:
    best, depth = None, -1
    for _, v in stats:
        v = _value(v)
        if not isinstance(v, str) or "/" not in v:
            continue
        parts = v.split("/")
        for i, p in enumerate(parts):
            if p in SCOPES and i > depth:
                best, depth = p, i
    return best


def ints(v: object) -> List[int]:
    """A list arg as the trace holds it (space-separated)."""
    if isinstance(v, (int, float)):
        return [int(v)]
    return [int(x) for x in str(v).split()]


def floats(v: object) -> List[float]:
    if isinstance(v, (int, float)):
        return [float(v)]
    return [float(x) for x in str(v).split()]


def trace_file(run) -> Optional[str]:
    files = glob.glob(os.path.join(str(TRACE_ROOT / run.cell.name), "**",
                                   "*.xplane.pb"), recursive=True)
    return files[0] if files else None


def load(run) -> Optional[Dict]:
    """The run's program spans, read once and kept on ``run``; ``None``
    when the run was not traced or its program opens no spans.  The
    first read also logs where the window's idle time went, by program
    span, and the device time by named scope."""
    if run.trace_window is None:
        return None
    if hasattr(run, "_program_spans"):
        return run._program_spans
    path = trace_file(run)
    raw = read(path) if path else None
    if raw is None or raw["window"] is None or not raw["spans"]:
        out = None
    else:
        w0, w1 = raw["window"]
        t0 = run.trace_window[0]
        out = dict(raw, w0=w0, w1=w1,
                   host=lambda ns: t0 + (ns - w0) / 1e9)
        for line in report(out):
            note(line)
    run._program_spans = out
    return out


def _inside(s: Dict, P: Dict) -> bool:
    return P["w0"] <= s["a"] and s["b"] <= P["w1"]


def _starts_inside(s: Dict, P: Dict) -> bool:
    return P["w0"] <= s["a"] <= P["w1"]


def named(P: Dict, name: str) -> List[Dict]:
    return [s for s in P["spans"] if s["name"] == name]


def window_slices(P: Dict) -> List[Dict]:
    """The ``scls.slice`` spans that began and ended in the window."""
    return [s for s in named(P, SLICE) if _inside(s, P)]


# ---------------------------------------------------------------------------
# the metrics
# ---------------------------------------------------------------------------
def submit_lags_ms(run, P: Dict) -> Dict[int, float]:
    """rid -> start of its ``scls.submit`` minus its due time, for the
    submits that began in the window."""
    due = {q.rid: q.due for q in run.requests if q.rid is not None}
    out = {}
    for s in named(P, "scls.submit"):
        rid = s["args"].get("rid")
        if rid is not None and int(rid) in due and _starts_inside(s, P):
            out[int(rid)] = 1e3 * (P["host"](s["a"]) - due[int(rid)])
    return out


def sched_waits_ms(P: Dict) -> Dict[int, float]:
    """rid -> host ms from the end of its ``scls.submit`` to the start of
    its first ``scls.slice``, for the first slices that began in the
    window (the slice span's ``waited`` of each new row)."""
    out = {}
    for s in named(P, SLICE):
        if not _starts_inside(s, P):
            continue
        a = s["args"]
        for rid, fresh, w in zip(ints(a["rids"]), ints(a["fresh"]),
                                 floats(a["waited"])):
            if fresh and w >= 0:
                out[rid] = 1e3 * w
    return out


def submit_lag_p50_ms(run) -> Optional[float]:
    P = load(run)
    if P is None:
        return None
    lags = list(submit_lags_ms(run, P).values())
    return stats.percentile(lags, 50) if lags else None


def sched_wait_p50_ms(run) -> Optional[float]:
    P = load(run)
    if P is None:
        return None
    waits = list(sched_waits_ms(P).values())
    return stats.percentile(waits, 50) if waits else None


def est_errors(P: Dict, against: str = "span") -> List[float]:
    """Signed error of each window slice's ``est_time``, as a share of
    its measured time: the ``scls.slice`` span (``span``) or the engine's
    own ``ServeResult.wall_time`` (``wall``)."""
    out = []
    for s in window_slices(P):
        a = s["args"]
        t = (s["b"] - s["a"]) / 1e9 if against == "span" \
            else float(a["wall"])
        if t > 0:
            out.append((float(a["est_time"]) - t) / t)
    return out


def est_err_p50_pct(run) -> Optional[float]:
    P = load(run)
    if P is None:
        return None
    errs = est_errors(P)
    return stats.percentile([100 * abs(e) for e in errs], 50) \
        if errs else None


def children(P: Dict, parent: Dict, names) -> List[Dict]:
    return [s for s in P["spans"] if s["name"] in names
            and parent["a"] <= s["a"] and s["b"] <= parent["b"]]


def slice_host_share(run) -> Optional[float]:
    P = load(run)
    if P is None:
        return None
    sl = window_slices(P)
    total = sum(s["b"] - s["a"] for s in sl)
    if total <= 0:
        return None
    device = sum(c["b"] - c["a"] for s in sl
                 for c in children(P, s, DEVICE_STAGES))
    return 100.0 * (total - device) / total


# ---------------------------------------------------------------------------
# what the traced run logs
# ---------------------------------------------------------------------------
def idle_by_span(P: Dict) -> Dict[str, float]:
    """Idle device seconds of the window, by the innermost ``scls.*``
    span open in each piece of each gap (``none`` outside every span)."""
    w0, w1 = P["w0"], P["w1"]
    busy = tracing.union(tracing.clip([(a, b) for a, b, _, _ in P["ops"]],
                                      w0, w1))
    gaps, t = [], w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < w1:
        gaps.append((t, w1))
    spans = [(s["a"], s["b"], s["name"]) for s in P["spans"]]
    starts = [s[0] for s in spans]
    bounds = sorted({x for s in spans for x in s[:2]})
    idle: Dict[str, float] = {}
    for a, b in gaps:
        cuts = bounds[bisect.bisect_right(bounds, a):
                      bisect.bisect_left(bounds, b)]
        for p, q in zip([a] + cuts, cuts + [b]):
            name = tracing._open_at(spans, starts, (p + q) / 2)
            idle[name] = idle.get(name, 0.0) + (q - p) / 1e9
    return idle


def decode_coverage(P: Dict) -> Tuple[int, int]:
    """(runs of the decode program whose midpoint lies in the window,
    those of them whose midpoint lies inside a ``scls.engine.decode``)."""
    dec = sorted((s["a"], s["b"]) for s in named(P, DECODE_SPAN))
    starts = [a for a, _ in dec]
    n = hit = 0
    for a, b, name in P["modules"]:
        m = (a + b) / 2
        if name != DECODE_MODULE or not P["w0"] <= m <= P["w1"]:
            continue
        n += 1
        k = bisect.bisect_right(starts, m) - 1
        hit += k >= 0 and dec[k][1] >= m
    return n, hit


def device_by_scope(P: Dict) -> Dict[str, float]:
    """Device seconds of the window's leaf operations by named scope
    (``unscoped`` where an operation names none)."""
    w0, w1 = P["w0"], P["w1"]
    scope = {(a, b, n): sc for a, b, n, sc in P["ops"]}
    out: Dict[str, float] = {}
    for a, b, n in tracing.leaves([(a, b, n) for a, b, n, _ in P["ops"]]):
        if b <= w0 or a >= w1:
            continue
        key = scope.get((a, b, n)) or "unscoped"
        out[key] = out.get(key, 0.0) + (min(b, w1) - max(a, w0)) / 1e9
    return out


def _fmt(d: Dict[str, float]) -> str:
    return ", ".join(f"{k} {v:.6f}" for k, v in
                     sorted(d.items(), key=lambda x: -x[1]))


def report(P: Dict) -> List[str]:
    lines = []
    idle = idle_by_span(P)
    total = sum(idle.values())
    named_s = total - idle.get("none", 0.0)
    lines.append(f"program spans: idle {total:.6f} s of the window, "
                 f"{100 * named_s / total if total else math.nan:.2f}% "
                 f"under a scls span: {_fmt(idle)}")
    n, hit = decode_coverage(P)
    lines.append(f"program spans: {hit} of {n} {DECODE_MODULE} runs inside "
                 f"{DECODE_SPAN}")
    for against in ("span", "wall"):
        errs = est_errors(P, against)
        if errs:
            lines.append(
                f"program spans: est_time against the {against} time of "
                f"{len(errs)} slices: signed median "
                f"{100 * statistics.median(errs):.2f}%, absolute median "
                f"{100 * statistics.median([abs(e) for e in errs]):.2f}%")
    scopes = device_by_scope(P)
    if scopes and set(scopes) != {"unscoped"}:
        lines.append(f"program spans: device seconds by named scope: "
                     f"{_fmt(scopes)}")
    else:
        lines.append("program spans: the device operations carry no named "
                     "scope")
    return lines
