"""Execution backends for :class:`repro.serving.core.SchedulerCore`.

The scheduling loop (arrival intake → predict → DP batch → offload →
slice dispatch → re-enqueue) lives exactly once, in ``SchedulerCore``;
what *varies* between the discrete-event simulator and the real cluster
is only how a dispatched unit of work turns into a duration and token
outcomes.  That variation is this module's ``Backend`` protocol:

  * :class:`SimBackend` — durations come from a calibrated ground-truth
    latency model (optionally noisy), token outcomes are derived
    analytically from each request's true generation length.  Streamed
    token ids are *synthetic* (the generation indices ``0,1,2,...``,
    synthesized lazily by the handle) so the streaming API behaves
    identically on both backends.
  * :class:`RealBackend` — batches run on real JAX
    :class:`~repro.engine.static_engine.StaticEngine` workers (every FLOP
    real), durations are measured wall time, token outcomes come from the
    model.  With ``kv_layout="paged"`` each worker owns a real
    :class:`~repro.kvcache.PageAllocator`.  The envelope lifetime is the
    ``kv_retain`` policy:

      - ``"slice"`` (default, PR 2 semantics): the ``(L_i + S)`` slice
        envelope is reserved at dispatch and released when the core
        processes the slice-completion event, and the engine re-prefills
        prompt + generated on every reschedule (paper §3.3);
      - ``"request"``: the engines store K/V *in* the pages
        (``StaticEngine.serve_batch_paged``) and keep each in-flight
        request's prefix pages resident across slices — a resumed slice
        remaps its retained pages into the batch block table and
        re-prefills nothing.  Pages are released only on
        finish/cancel (:meth:`finish_request`) or by the engine's
        evict-on-pressure / worker-migration fallback, which re-prefills
        classically so memory safety is unchanged.

Backends are intentionally *stateless about scheduling*: they never see
the pool, the offloader, or the predictor.  A new backend (e.g. an RPC
worker fleet) only has to answer "run this batch" and "the slice is
over".
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Protocol, Sequence, runtime_checkable

import numpy as np

from repro.core.estimator import ServingTimeEstimator
from repro.core.memory import MemoryEstimator, PagedMemoryEstimator
from repro.core.request import Batch, Request
from repro.engine.static_engine import EOS_DRIVEN, StaticEngine
from repro.kvcache import PageAllocator
from repro.obs.trace import NULL_TRACER, Tracer

# Per-request outcome dict keys (shared with StaticEngine.serve_batch
# results): tokens, n_valid, invalid, pad, finished.
RequestOutcome = Dict[str, object]


@dataclasses.dataclass
class BatchExecution:
    """What happened to one dispatched slice.

    ``per_request`` is aligned with ``Batch.requests``; ``tokens`` are the
    valid tokens this slice produced for that request — real model tokens
    on the real backend, or ``None`` on the sim backend (sim token ids are
    by definition the generation indices ``0..generated-1``, so streaming
    consumers synthesize them lazily instead of the core materializing
    millions of ints during offline paper-scale replays).
    ``finished`` marks EOS/forced completion as observed by the engine.
    ``reprefill_tokens`` counts tokens prefilled beyond each member's
    first prefill (the §3.3 rescheduling overhead this slice paid) — 0
    for retained residents on the persistent paged path.
    ``prefill_dur`` is the prefill portion of ``duration`` when the
    backend can attribute it (measured wall time on the paged real path,
    the deterministic model split on sim; ``None`` when the fused dense
    engine call makes the phases inseparable) — it feeds the trace's
    prefill/decode sub-spans and is never read by the scheduler.
    """

    duration: float
    steps: int
    early_return: bool
    per_request: List[RequestOutcome]
    reprefill_tokens: int = 0
    prefill_dur: Optional[float] = None
    #: prompt tokens satisfied by cross-request prefix-page sharing this
    #: slice (their prefill was a page-table remap) and the pages those
    #: joins took references on — 0 outside kv_retain="request"
    prefix_hit_tokens: int = 0
    shared_blocks: int = 0
    #: host seconds of the whole dispatch (the real backend's ``slice``
    #: program span: planning, device stages and retention); None on sim
    host_seconds: Optional[float] = None


@runtime_checkable
class Backend(Protocol):
    """What a SchedulerCore needs from an execution substrate."""

    #: whether continuous-batching modes (ILS / SCLS-CB) can run here
    supports_continuous: bool
    #: whether ``run_batch`` runs the slice to completion before it
    #: returns; the core then forms a worker's next batch when it frees
    runs_inline: bool

    def run_batch(self, wid: int, batch: Batch,
                  prev_tokens: Sequence[Sequence[int]]) -> BatchExecution:
        """Execute ``batch`` for one slice on worker ``wid``.

        Called at dispatch time; ``prev_tokens`` holds each member's
        previously generated tokens (the SCLS re-prefill input).  The
        returned ``duration`` is *virtual* time — the core schedules the
        completion event, applies token accounting, and re-enqueues
        unfinished requests when it fires.
        """
        ...

    def finish_batch(self, wid: int, batch: Batch) -> None:
        """The slice-completion event for ``batch`` is being processed:
        release any per-slice resources (e.g. the paged KV envelope)."""
        ...

    def finish_request(self, req: Request) -> None:
        """``req`` just went terminal (finished or cancelled): release any
        per-REQUEST resources retained across slices (the persistent
        paged prefix pages under ``kv_retain="request"``).  Must be an
        idempotent no-op when nothing is retained."""
        ...

    def release_session(self, session_id: int) -> None:
        """A multi-turn session closed: release any prefix pages anchored
        for it beyond its requests' lifetimes.  Idempotent no-op when the
        backend retains nothing per session."""
        ...

    def prefill_time(self, req: Request) -> float:
        """Continuous modes: virtual cost of one request's join prefill."""
        ...

    def span_time(self, avg_len: float, span: int, n_running: int) -> float:
        """Continuous modes: virtual cost of ``span`` decode iterations at
        parallelism ``n_running`` and mean cached length ``avg_len``."""
        ...


class SimBackend:
    """Latency-model backend: the discrete-event simulator's physics.

    The scheduler consults its own fitted estimator; *this* backend
    consumes time from the ground-truth profile ``true_lat`` (optionally
    log-normal noisy), so estimation error and its consequences are
    modeled faithfully — exactly the legacy ``ClusterSimulator`` split.
    """

    supports_continuous = True
    #: slices run on the virtual clock, in parallel across workers
    runs_inline = False

    def __init__(self, true_lat: ServingTimeEstimator,
                 noise_sigma: float = 0.0, seed: int = 0):
        self.true_lat = true_lat
        self.noise_sigma = float(noise_sigma)
        self.rng = np.random.default_rng(seed)

    def _noise(self) -> float:
        if self.noise_sigma <= 0:
            return 1.0
        return float(self.rng.lognormal(0.0, self.noise_sigma))

    # ------------------------------------------------------------------
    def run_batch(self, wid: int, batch: Batch,
                  prev_tokens: Sequence[Sequence[int]]) -> BatchExecution:
        steps = min(batch.slice_len,
                    max(r.remaining_gen for r in batch.requests))
        t_nominal = self.true_lat.t_serve(batch.size, batch.input_len, steps)
        dur = t_nominal * self._noise()
        # prefill share of the slice, for the trace's sub-spans: the
        # nominal model ratio applied to the single drawn duration.  MUST
        # NOT cost an extra rng draw — the golden dispatch logs pin the
        # noise stream, and observability may not perturb it.
        if t_nominal > 0:
            frac = self.true_lat.t_prefill(batch.size,
                                           batch.input_len) / t_nominal
            prefill_dur = dur * min(max(frac, 0.0), 1.0)
        else:
            prefill_dur = 0.0
        per: List[RequestOutcome] = []
        reprefill = 0
        for r in batch.requests:
            remaining = r.remaining_gen
            gen_now = min(remaining, steps)
            if r.generated > 0:  # §3.3: a reschedule re-prefills everything
                reprefill += r.effective_input_len
            per.append(dict(
                tokens=None,  # sim: synthesized lazily (generation indices)
                n_valid=gen_now,
                invalid=steps - gen_now,
                pad=batch.input_len - r.effective_input_len,
                finished=remaining - gen_now <= 0))
        return BatchExecution(duration=dur, steps=steps,
                              early_return=steps < batch.slice_len,
                              per_request=per,
                              reprefill_tokens=reprefill,
                              prefill_dur=prefill_dur)

    def finish_batch(self, wid: int, batch: Batch) -> None:
        pass  # no per-slice resources in virtual time

    def finish_request(self, req: Request) -> None:
        pass  # no per-request resources in virtual time

    def release_session(self, session_id: int) -> None:
        pass  # no per-session resources in virtual time

    def prefill_time(self, req: Request) -> float:
        return self.true_lat.t_prefill(
            1, req.effective_input_len) * self._noise()

    def span_time(self, avg_len: float, span: int, n_running: int) -> float:
        # Σ_{i=1..span} τ(avg+i, N) ≈ span · τ(avg + span/2, N)
        return span * self.true_lat.tau_decode(
            avg_len + span / 2.0, n_running) * self._noise()


class RealBackend:
    """Real-execution backend: StaticEngine workers, measured wall time.

    One physical host runs all engines, so each worker's timeline is
    virtual — the core advances it by the measured wall time of that
    worker's own batches, which is exactly what N parallel machines would
    observe.  Token outcomes (EOS, invalid, pads) come from the engine.

    ``kv_layout="paged"``: each worker gets a real
    :class:`~repro.kvcache.PageAllocator`.  ``kv_retain`` picks the
    envelope lifetime:

      * ``"slice"`` (default): ``run_batch`` reserves every member's
        ``(L_i + S)`` envelope and ``finish_batch`` releases it — the
        engine stays contiguous-transient and re-prefills on every
        reschedule (PR 2 semantics, a MemoryError means the DP batcher
        violated its own no-OOM constraint);
      * ``"request"``: the engines must be persistent-paged
        (``StaticEngine(kv_layout="paged")``); the backend dispatches
        through ``serve_batch_paged`` so resumed requests keep their
        prefix pages and re-prefill nothing, and pages are released only
        when the core finalizes the request (:meth:`finish_request`) or
        when the engine evicts under pressure.  A request whose next
        slice lands on a *different* worker releases its old worker's
        pages and re-prefills there (retention is per-engine; the
        re-prefill is counted in ``reprefill_tokens``).

    Continuous modes are not supported (the ILS baseline on real JAX
    lives in ``repro.engine.continuous_engine``).

    Observability runs on the host clock here (``host_clock``): each
    dispatch is a ``slice`` program span (``repro.obs``) carrying the
    batch's rows, its Eq. 3/4 ``est_time`` and what the slice did, and
    the engines' phases nest inside it.
    """

    supports_continuous = False
    #: ``run_batch`` executes the slice on the engine before it returns
    runs_inline = True
    #: repro.obs stamps this backend's traces on time.perf_counter
    host_clock = True

    def __init__(self, engines: Sequence[StaticEngine],
                 mem: Optional[MemoryEstimator] = None,
                 kv_layout: str = "dense",
                 sched_bucket: int = 1,
                 kv_retain: str = "slice"):
        self.engines = list(engines)
        self.tracer: Tracer = NULL_TRACER
        self.allocators: Optional[List[PageAllocator]] = None
        if kv_retain not in ("slice", "request"):
            raise ValueError(f"unknown kv_retain {kv_retain!r} "
                             f"(expected 'slice' or 'request')")
        self.kv_retain = kv_retain
        self.mem = mem if isinstance(mem, PagedMemoryEstimator) else None
        #: kv_retain="request": worker whose engine retains each rid's pages
        self._engine_of: Dict[int, int] = {}
        #: session_id -> (wid, rid) whose pages are anchored past the
        #: request's lifetime so the next turn's prefix join can hit them
        self._session_anchor: Dict[int, tuple] = {}
        if kv_retain == "request" and kv_layout != "paged":
            raise ValueError("kv_retain='request' needs kv_layout='paged'")
        if kv_layout == "paged":
            if not isinstance(mem, PagedMemoryEstimator):
                raise TypeError("kv_layout='paged' needs a PagedMemoryEstimator")
            if mem.bucket % sched_bucket:
                # fits() admits with mem.bucket over raw lengths, while the
                # slice-start reserve charges the batch input length (est-
                # bucketed); mem.bucket must be a multiple of est.bucket so
                # admission is at least as conservative as the reserve —
                # otherwise a legitimately admitted batch can MemoryError
                raise ValueError(
                    f"PagedMemoryEstimator.bucket ({mem.bucket}) must be a "
                    f"multiple of the estimator bucket ({sched_bucket})")
            if kv_retain == "request":
                for i, e in enumerate(self.engines):
                    if getattr(e, "kv_layout", "dense") != "paged":
                        raise TypeError(
                            f"kv_retain='request' needs persistent-paged "
                            f"engines (StaticEngine(kv_layout='paged')); "
                            f"engine {i} is {getattr(e, 'kv_layout', 'dense')!r}")
                    if e.allocator.page_tokens != mem.page_tokens:
                        raise ValueError(
                            f"engine {i} page_tokens "
                            f"({e.allocator.page_tokens}) != estimator's "
                            f"({mem.page_tokens})")
                    if e.allocator.n_pages < mem.total_blocks:
                        raise ValueError(
                            f"engine {i} pool ({e.allocator.n_pages} pages) "
                            f"smaller than the scheduler's budget "
                            f"({mem.total_blocks}): the batcher would "
                            f"over-admit")
                # the engines' own allocators ARE the slice envelopes here
                self.allocators = [e.allocator for e in self.engines]
            else:
                self.allocators = [PageAllocator(mem.total_blocks,
                                                 mem.page_tokens)
                                   for _ in self.engines]

    def bind_tracer(self, tracer: Tracer) -> None:
        """Open this backend's and its engines' program spans on
        ``tracer`` (``Observability.attach``)."""
        self.tracer = tracer
        for e in self.engines:
            e.tracer = tracer

    # ------------------------------------------------------------------
    def run_batch(self, wid: int, batch: Batch,
                  prev_tokens: Sequence[Sequence[int]]) -> BatchExecution:
        """One slice inside a ``slice`` program span; each member's
        ``host_ready`` becomes the span's end.  The span's per-row args
        list the rows in rid order (the dispatch log's)."""
        reqs = sorted(batch.requests, key=lambda r: r.rid)
        evicted = self._evictions()
        with self.tracer.span(
                "slice", wid=wid, rids=[r.rid for r in reqs],
                rows=len(reqs), ctx=[r.effective_input_len for r in reqs],
                fresh=[int(r.n_schedules == 0) for r in reqs],
                input_len=int(batch.input_len),
                slice_len=int(batch.slice_len),
                est_time=float(batch.est_time)) as sp:
            # host seconds each row waited since it was last ready (its
            # submit ended, or its previous slice did); -1 when unknown
            sp.set(waited=[round(sp.t0 - r.host_ready, 6)
                           if r.host_ready is not None else -1
                           for r in reqs])
            remaining = {r.rid: r.remaining_gen for r in reqs}
            ex = self._run_batch(wid, batch, prev_tokens)
            valid = {r.rid: min(int(o["n_valid"]), remaining[r.rid])
                     for r, o in zip(batch.requests, ex.per_request)}
            sp.set(steps=ex.steps, valid=[valid[r.rid] for r in reqs],
                   reprefill=ex.reprefill_tokens,
                   prefix_hit=ex.prefix_hit_tokens,
                   evictions=self._evictions() - evicted,
                   wall=ex.duration)
        ex.host_seconds = sp.duration
        for r in reqs:
            r.host_ready = sp.t1
        return ex

    def _evictions(self) -> int:
        return sum(getattr(e, "n_evictions", 0) for e in self.engines)

    def _run_batch(self, wid: int, batch: Batch,
                   prev_tokens: Sequence[Sequence[int]]) -> BatchExecution:
        eng = self.engines[wid]
        prompts = [r.prompt for r in batch.requests]
        # gen_len=None → EOS-driven: the engine detects the model's own EOS
        forced = [r.remaining_gen if r.gen_len is not None else EOS_DRIVEN
                  for r in batch.requests]
        if self.kv_retain == "request":
            # worker migration: pages retained elsewhere are unreachable
            # from this engine — release them there, re-prefill here
            for r in batch.requests:
                old = self._engine_of.get(r.rid)
                if old is not None and old != wid:
                    self.engines[old].release_request(r.rid)
                self._engine_of[r.rid] = wid
            res = eng.serve_batch_paged(prompts, batch.slice_len,
                                        [r.rid for r in batch.requests],
                                        forced_gen_lens=forced,
                                        already_generated=list(prev_tokens))
            self._sync_retained_gauge()
        else:
            if self.allocators is not None:
                alloc = self.allocators[wid]
                for r in batch.requests:
                    # slice start: every member holds the batch envelope
                    # L_i + S (rows are padded to the batch input length,
                    # as the engine's per-batch cache is)
                    # the envelope is owned by the dispatch protocol:
                    # SchedulerCore calls finish_batch at slice end
                    # (cancel paths included), which releases every member
                    alloc.reserve(r.rid, batch.input_len + batch.slice_len)  # repro: transfer(allocator-pairing) — finish_batch releases
            res = eng.serve_batch(prompts, batch.slice_len,
                                  forced_gen_lens=forced,
                                  already_generated=list(prev_tokens))
        return BatchExecution(duration=res.wall_time, steps=res.steps,
                              early_return=res.early_return,
                              per_request=list(res.results),
                              reprefill_tokens=res.reprefill_tokens,
                              prefill_dur=res.prefill_time,
                              prefix_hit_tokens=res.prefix_hit_tokens,
                              shared_blocks=res.shared_blocks)

    def finish_batch(self, wid: int, batch: Batch) -> None:
        if self.kv_retain == "request":
            return  # retention: the engine trimmed to the resident prefix
        if self.allocators is not None:
            alloc = self.allocators[wid]
            for r in batch.requests:  # slice end: envelope freed
                alloc.release(r.rid)

    def finish_request(self, req: Request) -> None:
        """Terminal (finished/cancelled): free the retained prefix pages.

        A *completed* request belonging to a session is anchored instead:
        its pages (prompt + answer — exactly the next turn's prefix) stay
        resident, replacing the session's previous anchor.  Anchored pages
        remain LRU-evictable under pool pressure and are dropped for good
        by :meth:`release_session` (or an engine eviction); a *cancelled*
        turn releases immediately like any other request.
        """
        if self.kv_retain != "request":
            return
        wid = self._engine_of.pop(req.rid, None)
        if wid is None:
            return
        sid = getattr(req, "session_id", None)
        if sid is not None and req.done and not req.cancelled:
            old = self._session_anchor.get(sid)
            if old is not None and old[1] != req.rid:
                self.engines[old[0]].release_request(old[1])
            self._session_anchor[sid] = (wid, req.rid)
        else:
            self.engines[wid].release_request(req.rid)
        self._sync_retained_gauge()

    def release_session(self, session_id: int) -> None:
        """Drop the session's anchored prefix pages (idempotent)."""
        anchor = self._session_anchor.pop(session_id, None)
        if anchor is not None:
            self.engines[anchor[0]].release_request(anchor[1])
            self._sync_retained_gauge()

    def batch_affinity(self, batch: Batch) -> Optional[int]:
        """Retention-affinity hint for the offloader's ε-tiebreak: the
        worker whose resident prefix pages cover the most tokens of this
        batch's prompts (``None`` when no worker holds a matching prefix).
        Content-based — it consults each engine's prefix index with the
        members' effective token streams, so it finds session anchors and
        shared system prompts alike."""
        if self.kv_retain != "request":
            return None
        streams = []
        for r in batch.requests:
            if r.prompt is None:
                continue
            gen = r.output_tokens or []
            streams.append(np.concatenate([np.asarray(r.prompt, np.int64),
                                           np.asarray(gen, np.int64)])
                           if gen else np.asarray(r.prompt, np.int64))
        if not streams:
            return None
        best_wid, best_hit = None, 0
        for wid, eng in enumerate(self.engines):
            if not getattr(eng, "prefix_sharing", False):
                continue
            hit = sum(eng._prefix.lookup(s)[1] for s in streams)
            if hit > best_hit:
                best_wid, best_hit = wid, hit
        return best_wid

    def _sync_retained_gauge(self) -> None:
        if self.mem is not None:
            self.mem.retained_blocks = sum(a.used_blocks
                                           for a in self.allocators)

    def free_blocks(self) -> List[int]:
        """Per-worker free KV-block counts (paged layout; ``[]`` when
        dense) — surfaced by the HTTP ``/healthz`` snapshot."""
        if self.allocators is None:
            return []
        return [a.free_blocks for a in self.allocators]

    def obs_snapshot(self) -> Dict[str, int]:
        """KV-pool state for the observability gauges / counter tracks
        (``repro.obs``); ``{}`` on the dense layout, where there is no
        page pool to report."""
        if self.allocators is None:
            return {}
        snap = dict(
            free_pages=sum(a.free_blocks for a in self.allocators),
            evictions=sum(getattr(e, "n_evictions", 0)
                          for e in self.engines))
        if self.kv_retain == "request":
            snap["retained_blocks"] = sum(a.used_blocks
                                          for a in self.allocators)
            snap["shared_blocks"] = sum(a.shared_blocks
                                        for a in self.allocators)
        return snap

    def prefill_time(self, req: Request) -> float:
        raise NotImplementedError(
            "RealBackend does not run continuous modes; use "
            "repro.engine.continuous_engine.ContinuousEngine")

    def span_time(self, avg_len: float, span: int, n_running: int) -> float:
        raise NotImplementedError(
            "RealBackend does not run continuous modes; use "
            "repro.engine.continuous_engine.ContinuousEngine")
