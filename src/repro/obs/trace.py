"""Chrome trace-event tracer for the serving stack (``repro.obs`` pillar 1).

Records *spans* (``ph="X"`` complete events), *instants* (``ph="i"``),
*async request-lifecycle spans* (``ph="b"``/``"e"``, one per rid), and
*counter tracks* (``ph="C"``) in the Chrome trace-event JSON format, so a
``trace.json`` exported here loads directly in Perfetto
(https://ui.perfetto.dev) or ``chrome://tracing``.

Timeline convention: the clock is the backend's.  On the sim backend
every timestamp is **core time** — the scheduler's discrete-event clock,
so the trace visualizes the discrete-event timeline exactly.  On the
real backend (``RealBackend.host_clock``) every timestamp is the **host
clock**, ``time.perf_counter`` seconds: the clock the client and the
benchmark measure on, and the one the profiler's own trace shares.

Program spans (:meth:`Tracer.span`) mark the layer boundaries of the
served path: ``submit``, ``pacer.step``, ``sched.tick``, ``slice`` and
the engine phases.  Each one opens a ``jax.profiler.TraceAnnotation``
named ``scls.<name>`` whether or not the tracer records, so a profiler
session (``jax.profiler.start_trace``) places it in the ``.xplane.pb``
host plane on the device trace's clock; outside a session that costs
about a microsecond.  A recording tracer on the host clock also keeps
each span as an ``X`` event whose args carry its span id, its parent's
id and the rids it serves.

Overhead discipline: tracing must never perturb scheduling (the golden
dispatch logs are asserted bit-exact with tracing on) and must cost near
zero when disabled.  The disabled tracer is :data:`NULL_TRACER` — every
emitter is a no-op ``pass`` and hot paths guard bulk work behind
``tracer.enabled``.  Nothing in this module draws randomness, and spans
record nothing on the sim path, so same seed ⇒ byte-identical trace.

Track layout (Perfetto rows):

  * pid 1 ("scheduler") / tid 0 ("control") — arrivals, admission
    verdicts, scheduling ticks, submits, pacer steps, compiles;
  * pid 1 / tid 100+w ("worker w") — per-worker slice spans with nested
    prefill/decode sections;
  * counter tracks (pid 1): ``queue_depth``, ``in_flight_slices``,
    ``free_pages``, ``retained_blocks``;
  * pid 2 ("requests") — async lifecycle spans, one per rid
    (arrival → finalize), carrying the terminal outcome.
"""
from __future__ import annotations

import itertools
import json
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "NULL_TRACER", "PID_SCHED", "PID_REQUESTS",
           "TID_CONTROL", "worker_tid", "Span", "innermost_span",
           "SPAN_PREFIX"]

#: prefix of every program span's name in the profiler's trace
SPAN_PREFIX = "scls."

#: process ids of the two Perfetto "processes" (see module docstring)
PID_SCHED = 1
PID_REQUESTS = 2
#: tid of the scheduler control track (arrivals / ticks / admission)
TID_CONTROL = 0
_TID_WORKER_BASE = 100


def worker_tid(wid: int) -> int:
    """Trace thread id of worker ``wid`` (its Perfetto row)."""
    return _TID_WORKER_BASE + int(wid)


def _us(t: float) -> float:
    """Seconds → trace microseconds, rounded so exports are stable across
    platforms (0.1 ns granularity is far below any modeled duration)."""
    return round(t * 1e6, 4)


#: the spans open on each thread, innermost last (compiles are named by
#: the innermost one; asyncio code opens spans only around synchronous
#: work, so one thread's spans nest)
_open = threading.local()


def _stack() -> list:
    st = getattr(_open, "spans", None)
    if st is None:
        st = _open.spans = []
    return st


def innermost_span() -> Optional["Span"]:
    """The innermost program span open on this thread, if any."""
    st = _stack()
    return st[-1] if st else None


def _xarg(v: object) -> object:
    """A span arg as the profiler's trace holds it: lists become
    space-separated strings (its encoding splits values at commas)."""
    if isinstance(v, (list, tuple)):
        return " ".join(str(x) for x in v)
    if isinstance(v, bool):
        return int(v)
    return v


class Span:
    """One program span: a ``TraceAnnotation`` named ``scls.<name>``,
    and a Chrome ``X`` event when its tracer records on the host clock.
    ``t0``/``t1`` are ``time.perf_counter`` seconds at entry and exit.
    Args known only at the end are added with :meth:`set`."""

    __slots__ = ("tracer", "name", "args", "late", "t0", "t1", "sid",
                 "parent", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.late: Optional[dict] = None
        self.t0 = self.t1 = 0.0
        self.sid = 0
        self.parent: Optional[Span] = None

    def set(self, **args: object) -> None:
        """Add args before the span closes."""
        if self.late is None:
            self.late = {}
        self.late.update(args)

    @property
    def duration(self) -> float:
        return self.t1 - self.t0

    def __enter__(self) -> "Span":
        st = _stack()
        self.parent = st[-1] if st else None
        st.append(self)
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(
                SPAN_PREFIX + self.name,
                **{k: _xarg(v) for k, v in self.args.items()
                   if v is not None})
        else:
            self._ann = TraceAnnotation(SPAN_PREFIX + self.name)
        self._ann.__enter__()
        if self.tracer.records_spans:
            self.sid = next(self.tracer._span_ids)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: object) -> None:
        self.t1 = time.perf_counter()
        if self.late and TraceAnnotation.is_enabled():
            self._ann.set_metadata(**{k: _xarg(v)
                                      for k, v in self.late.items()
                                      if v is not None})
        self._ann.__exit__(*exc)
        _stack().pop()
        if self.tracer.records_spans:
            self.tracer._record(self)


class Tracer:
    """Collects trace events against a pluggable clock.

    ``clock`` returns the current time in seconds; the serving stack binds
    it to the backend's clock — ``SchedulerCore.now`` on the sim backend,
    ``time.perf_counter`` on the real one (see :meth:`repro.obs.hub.
    Observability.attach`) — so all events share one timeline.
    Construct, attach, run, then :meth:`export` / :meth:`to_dict`.
    """

    #: hot paths may skip argument marshalling when this is False
    enabled: bool = True
    #: the clock is the host's ``time.perf_counter`` (real backend), so
    #: program spans are recorded; False on core time (sim backend)
    host_clock: bool = False

    def __init__(self, clock: Optional[Callable[[], float]] = None):
        self._clock: Callable[[], float] = clock or (lambda: 0.0)
        self._events: List[dict] = []
        self._span_ids = itertools.count(1)
        #: (pid, tid) -> row name; rendered as metadata events on export
        self._tracks: Dict[Tuple[int, int], str] = {
            (PID_SCHED, TID_CONTROL): "control"}
        self._process_names: Dict[int, str] = {PID_SCHED: "scheduler",
                                               PID_REQUESTS: "requests"}

    # ------------------------------------------------------------------
    # clock / track plumbing
    # ------------------------------------------------------------------
    def set_clock(self, clock: Callable[[], float],
                  host: bool = False) -> None:
        """Bind the trace clock; ``host`` says it is ``time.perf_counter``
        (the real backend), which is what program spans are stamped on."""
        self._clock = clock
        self.host_clock = host

    @property
    def now(self) -> float:
        """Current trace time in seconds (the bound clock)."""
        return self._clock()

    @property
    def records_spans(self) -> bool:
        """Program spans become ``X`` events: enabled, on the host clock."""
        return self.enabled and self.host_clock

    # ------------------------------------------------------------------
    # program spans
    # ------------------------------------------------------------------
    def span(self, name: str, **args: object) -> Span:
        """A program span ``scls.<name>`` around a ``with`` block (module
        docstring).  ``wid=`` puts it on that worker's track; otherwise
        it takes its parent's track, or the control track."""
        return Span(self, name, args)

    def _span_tid(self, sp: Span) -> int:
        while sp is not None and sp.tracer is self:
            if "wid" in sp.args:
                return self.declare_worker(int(sp.args["wid"]))  # type: ignore[arg-type]
            sp = sp.parent  # type: ignore[assignment]
        return TID_CONTROL

    def _record(self, sp: Span) -> None:
        par = sp.parent if (sp.parent is not None
                            and sp.parent.tracer is self) else None
        args = {k: v for k, v in sp.args.items() if v is not None}
        if sp.late:
            args.update(sp.late)
        args["span_id"] = sp.sid
        args["parent_id"] = par.sid if par is not None else 0
        self.complete(sp.name, sp.t0, sp.t1 - sp.t0, tid=self._span_tid(sp),
                      cat="span", args=args)

    def declare_worker(self, wid: int) -> int:
        """Name worker ``wid``'s track; returns its tid."""
        tid = worker_tid(wid)
        self._tracks.setdefault((PID_SCHED, tid), f"worker {wid}")
        return tid

    # ------------------------------------------------------------------
    # event emitters (all timestamps in seconds; stored as trace µs)
    # ------------------------------------------------------------------
    def complete(self, name: str, ts: float, dur: float, *,
                 tid: int = TID_CONTROL, cat: str = "sched",
                 args: Optional[dict] = None) -> None:
        """A span ``[ts, ts+dur]`` on one track (``ph="X"``)."""
        ev = dict(name=name, ph="X", ts=_us(ts), dur=_us(max(dur, 0.0)),
                  pid=PID_SCHED, tid=tid, cat=cat)
        if args:
            ev["args"] = args
        self._events.append(ev)

    def instant(self, name: str, ts: Optional[float] = None, *,
                tid: int = TID_CONTROL, cat: str = "sched",
                args: Optional[dict] = None) -> None:
        """A zero-duration marker (``ph="i"``, thread-scoped)."""
        ev = dict(name=name, ph="i", s="t",
                  ts=_us(self._clock() if ts is None else ts),
                  pid=PID_SCHED, tid=tid, cat=cat)
        if args:
            ev["args"] = args
        self._events.append(ev)

    def counter(self, name: str, value: float,
                ts: Optional[float] = None) -> None:
        """One sample on counter track ``name`` (``ph="C"``)."""
        self._events.append(dict(
            name=name, ph="C", ts=_us(self._clock() if ts is None else ts),
            pid=PID_SCHED, tid=TID_CONTROL, cat="counter",
            args={name: value}))

    def async_begin(self, name: str, aid: int, ts: Optional[float] = None,
                    *, cat: str = "request",
                    args: Optional[dict] = None) -> None:
        """Open async span ``aid`` (``ph="b"``) on the requests process."""
        ev = dict(name=name, ph="b", id=int(aid), cat=cat,
                  ts=_us(self._clock() if ts is None else ts),
                  pid=PID_REQUESTS, tid=TID_CONTROL)
        if args:
            ev["args"] = args
        self._events.append(ev)

    def async_end(self, name: str, aid: int, ts: Optional[float] = None,
                  *, cat: str = "request",
                  args: Optional[dict] = None) -> None:
        """Close async span ``aid`` (``ph="e"``)."""
        ev = dict(name=name, ph="e", id=int(aid), cat=cat,
                  ts=_us(self._clock() if ts is None else ts),
                  pid=PID_REQUESTS, tid=TID_CONTROL)
        if args:
            ev["args"] = args
        self._events.append(ev)

    # ------------------------------------------------------------------
    # export
    # ------------------------------------------------------------------
    def __len__(self) -> int:
        return len(self._events)

    def clear(self) -> None:
        self._events.clear()

    def to_dict(self) -> dict:
        """The Chrome trace-event JSON object (``{"traceEvents": [...]}``).

        Metadata events naming processes/threads are prepended so Perfetto
        labels every row; event order within the list is the deterministic
        emission order (the viewer sorts by ``ts`` anyway).
        """
        meta: List[dict] = []
        for pid, pname in sorted(self._process_names.items()):
            meta.append(dict(name="process_name", ph="M", pid=pid, tid=0,
                             args={"name": pname}))
        for (pid, tid), tname in sorted(self._tracks.items()):
            meta.append(dict(name="thread_name", ph="M", pid=pid, tid=tid,
                             args={"name": tname}))
            # thread_sort_index keeps worker rows in wid order
            meta.append(dict(name="thread_sort_index", ph="M", pid=pid,
                             tid=tid, args={"sort_index": tid}))
        return {"traceEvents": meta + self._events,
                "displayTimeUnit": "ms"}

    def to_json(self) -> str:
        """Deterministic serialization (same events ⇒ same bytes)."""
        return json.dumps(self.to_dict(), sort_keys=True,
                          separators=(",", ":"))

    def export(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json())


class _NullTracer(Tracer):
    """The disabled tracer: every emitter is a no-op ``pass`` so traced
    call sites cost one attribute lookup + an empty call when tracing is
    off (plus most sites guard on ``tracer.enabled`` and skip argument
    construction entirely)."""

    enabled = False

    def __init__(self):
        super().__init__()

    def set_clock(self, clock, host: bool = False) -> None:  # noqa: D102
        pass

    def declare_worker(self, wid: int) -> int:
        return worker_tid(wid)

    def complete(self, *a, **kw) -> None:
        pass

    def instant(self, *a, **kw) -> None:
        pass

    def counter(self, *a, **kw) -> None:
        pass

    def async_begin(self, *a, **kw) -> None:
        pass

    def async_end(self, *a, **kw) -> None:
        pass


#: the shared disabled tracer (stateless — safe to share everywhere)
NULL_TRACER = _NullTracer()
