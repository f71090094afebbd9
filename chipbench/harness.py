"""One run of one cell: set-up, open-loop traffic, the measured window,
the check against the plain reference, and the result.

The stack is the one a deployment builds (``launch/serve.py``), through
``ServingConfig.build_real``: ``AsyncSliceServer`` over ``SchedulerCore``
(strategy ``scls``) over ``RealBackend(kv_layout="paged",
kv_retain="request")`` over one persistent paged ``StaticEngine`` with
prefix sharing, on one chip.  Requests carry their traffic-drawn output
lengths as forced lengths, so random weights do every real FLOP and stop
where the traffic says.

Set-up: weights from the seed on the device, the engine and its pool,
every prefill and decode shape the cell can reach served once (``grid``),
the Eq. 3/4 estimator fitted on the second call of a few of those shapes,
then ``lead_s`` seconds of the cell's own open-loop traffic.  The window
opens at the first slice end after the lead and closes at the last slice
end before ``--seconds`` more.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import math
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import check, grid, stats, traffic
from chipbench.spec import Cell

def clock() -> float:
    return time.perf_counter()


def model_config(conf: Dict):
    """The program's ``ModelConfig`` for a configuration file."""
    import jax.numpy as jnp
    from repro.models.common import ModelConfig

    m = dict(conf["model"])
    m["dtype"] = getattr(jnp, m["dtype"])
    return ModelConfig(**m)


def span(name: str, **kw):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation("chipbench." + name, **kw)


class CompileClock:
    """Backend compilations and their seconds, from ``jax.monitoring``
    (register once per process)."""

    def __init__(self) -> None:
        import jax
        self.events: List[tuple] = []  # (host clock at the end, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event: str, secs: float, **_: object) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((clock(), secs))

    def between(self, a: float, b: float) -> tuple:
        """(count, seconds) of the compilations that ended in [a, b]."""
        xs = [s for t, s in self.events if a <= t <= b]
        return len(xs), sum(xs)


@dataclasses.dataclass
class ReqRec:
    """One planned request as the client saw it (host clock)."""

    idx: int
    due: float
    prompt: np.ndarray
    gen_len: int
    submit: Optional[float] = None
    first: Optional[float] = None
    done: Optional[float] = None
    n_tok: int = 0
    ok: bool = False
    err: Optional[str] = None
    rid: Optional[int] = None
    tokens: Optional[List[int]] = None
    handle: object = None

    def ttft_ms(self) -> float:
        return (self.first - self.due) * 1e3 if (
            self.ok and self.first is not None) else math.inf

    def norm_lat_ms(self) -> float:
        return ((self.done - self.due) * 1e3 / self.gen_len
                if self.ok and self.done is not None else math.inf)


class SliceLog:
    """Benchmark-owned wrapper around ``RealBackend.run_batch``: one
    record per dispatched slice (host clock), plus each request's first
    dispatch."""

    def __init__(self, backend, engine) -> None:
        self.records: List[Dict] = []
        self.first_dispatch: Dict[int, float] = {}
        inner = backend.run_batch

        def run_batch(wid, batch, prev):
            t0 = clock()
            reqs = batch.requests
            for r in reqs:
                self.first_dispatch.setdefault(r.rid, t0)
            ctx = [r.effective_input_len for r in reqs]
            remaining = [r.remaining_gen for r in reqs]
            fresh = [r.n_schedules == 0 for r in reqs]
            ev0 = engine.n_evictions
            with span("run_batch", rows=len(reqs)):
                ex = inner(wid, batch, prev)
            t1 = clock()
            valid = [min(int(o["n_valid"]), rem)
                     for o, rem in zip(ex.per_request, remaining)]
            self.records.append(dict(
                t0=t0, t1=t1, rows=len(reqs), ctx=ctx, valid=valid,
                fresh=fresh, steps=int(ex.steps), wall=float(ex.duration),
                prefill=float(ex.prefill_dur or 0.0),
                reprefill=int(ex.reprefill_tokens),
                prefix_hit=int(ex.prefix_hit_tokens),
                evictions=engine.n_evictions - ev0))
            return ex

        backend.run_batch = run_batch


@dataclasses.dataclass
class Run:
    """What per-layer readers read: the run's records, its windows and,
    in a traced run, the reduced trace."""

    cell: Cell
    model: Dict
    records: List[Dict]
    requests: List[ReqRec]
    first_dispatch: Dict[int, float]
    window: tuple
    trace_window: Optional[tuple] = None
    trace: Optional[Dict] = None
    peaks: Optional[Dict] = None

    def traced_slices(self) -> List[Dict]:
        a, b = self.trace_window
        return [r for r in self.records if r["t0"] >= a and r["t1"] <= b]

    def due_in(self, a: float, b: float) -> List[ReqRec]:
        return [q for q in self.requests if a <= q.due < b]


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"{len(devs)} chip(s), the cell asks for {n}")
    return devs


def device_info(devs) -> Dict:
    d = devs[0]
    return dict(platform=d.platform, kind=d.device_kind, count=len(devs))


def memory_peak(devs, n: int) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs[:n]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------
def build_engine(conf: Dict, params, model, device):
    from repro.engine.static_engine import StaticEngine
    sv = conf["serving"]
    return StaticEngine(model, params, eos_id=1, pad_id=0,
                        len_bucket=sv["len_bucket"], kv_layout="paged",
                        page_tokens=sv["page_tokens"],
                        kv_pool_tokens=sv["kv_pool_pages"]
                        * sv["page_tokens"],
                        prefix_sharing=True, device=device)


def _serve_once(engine, slice_len: int, lens: List[int], steps: int, rng,
                vocab: int, rid0: int):
    prompts = [rng.integers(2, vocab, size=n).astype(np.int32)
               for n in lens]
    rids = list(range(rid0, rid0 + len(lens)))
    try:
        return engine.serve_batch_paged(prompts, slice_len, rids,
                                        forced_gen_lens=[steps] * len(lens))
    finally:
        for r in rids:
            engine.release_request(r)


def warm_up(conf: Dict, engine, vocab: int, log: Callable,
            grid_too: bool = True) -> Dict:
    """Serve every reachable shape once (``grid_too``); then fit Eq. 3/4
    on the second call of the estimator grid's shapes.  Returns the
    fitted estimator and the split of the seconds."""
    from repro.core.estimator import ServingTimeEstimator
    sv, eg = conf["serving"], conf["estimator"]
    S = sv["slice_len"]
    rng = np.random.default_rng(0)  # warm-up tokens: fixed, not the seed
    batches = (grid.warm_batches(sv, conf["max_effective_input"])
               if grid_too else [])
    t0 = clock()
    rid = 1 << 40  # clear of every rid the server assigns
    for lens in batches:
        with span("warm", rows=len(lens), longest=max(lens)):
            _serve_once(engine, S, lens, 1, rng, vocab, rid)
        rid += len(lens)
    t_warm = clock() - t0
    t0 = clock()
    pre, dec = [], []
    k = int(eg["decode_steps"])
    for n in eg["rows"]:
        for L in eg["lengths"]:
            lens = [L] * n
            if not grid.fits(sv, n, L):
                continue
            _serve_once(engine, S, lens, 1, rng, vocab, rid)  # any compile
            rid += n
            one = _serve_once(engine, S, lens, 1, rng, vocab, rid)
            rid += n
            more = _serve_once(engine, S, lens, k + 1, rng, vocab, rid)
            rid += n
            pre.append((n, L, one.prefill_time))
            step = ((more.wall_time - more.prefill_time)
                    - (one.wall_time - one.prefill_time)) / k
            dec.append((n, L, max(step, 1e-6)))
    est, prmse, drmse = ServingTimeEstimator.fit(pre, dec)
    t_est = clock() - t0
    log(f"warm-up: {len(batches)} batches in {t_warm:.2f} s; estimator "
        f"fitted on {len(pre)} prefill and {len(dec)} decode timings in "
        f"{t_est:.2f} s (rmse {prmse * 1e3:.2f} / {drmse * 1e3:.3f} ms); "
        f"prefill {[(n, L, round(t, 4)) for n, L, t in pre]}, decode per "
        f"step {[(n, L, round(t, 5)) for n, L, t in dec]}")
    return dict(est=est, warm_s=t_warm, est_s=t_est, batches=len(batches))


def build_server(conf: Dict, engine, est, seed: int):
    from repro.serving import ServingConfig
    sv = conf["serving"]
    delta = engine.model.kv_bytes_per_token()
    budget = sv["budget_pages"] * sv["page_tokens"] * delta
    scfg = ServingConfig(backend="real", strategy="scls", kv_layout="paged",
                         kv_retain="request", prefix_sharing=True,
                         workers=1, slice_len=sv["slice_len"],
                         max_gen=sv["max_gen"], gamma=sv["gamma"],
                         page_tokens=sv["page_tokens"],
                         packing=sv["packing"], m_available=budget,
                         zeta=1.0, mem_bucket=sv["mem_bucket"], seed=seed,
                         audit_capacity=0)
    mem = scfg.memory_estimator(delta)
    if mem.total_blocks != sv["budget_pages"]:
        raise RuntimeError(f"budget of {mem.total_blocks} pages, the "
                           f"configuration says {sv['budget_pages']}")
    return scfg.build_real([engine], est, mem)


# ---------------------------------------------------------------------------
# the open loop
# ---------------------------------------------------------------------------
async def drive(aio, plan: List[traffic.Planned], recs: List[ReqRec],
                slices: SliceLog, mix: Dict, t_start: float, seconds: float,
                max_gen: int, tracer=None) -> Dict:
    """Send ``plan`` open-loop from ``t_start``; measure; drain.  Returns
    the window and what the drain saw."""
    from repro.serving.admission import AdmissionRejected
    lead = float(mix["lead_s"])
    t_lo, t_hi = t_start + lead, t_start + lead + seconds
    tasks: List[asyncio.Task] = []

    async def client(rec: ReqRec, p: traffic.Planned) -> None:
        try:
            with span("submit"):
                h = aio.submit(p.prompt, gen_len=p.gen_len, max_gen=max_gen)
        except AdmissionRejected as e:
            rec.err = f"rejected: {e}"
            return
        rec.submit, rec.rid, rec.handle = clock(), h.rid, h
        async for chunk in h.slices():
            with span("deliver"):
                if rec.first is None and chunk:
                    rec.first = clock()
                rec.n_tok += len(chunk)
        req = await h.result()
        rec.done = clock()
        rec.ok = bool(req.done and not req.cancelled)
        rec.tokens = list(h.output_tokens)

    async def generator() -> None:
        for p in plan:
            due = t_start + p.due
            if due >= t_hi:
                break
            delay = due - clock()
            if delay > 0:
                with span("wait_arrival"):
                    await asyncio.sleep(delay)
            rec = ReqRec(p.idx, due, p.prompt, p.gen_len)
            recs.append(rec)
            tasks.append(asyncio.create_task(client(rec, p)))

    gen = asyncio.create_task(generator())
    trace_task = (asyncio.create_task(tracer.run(t_lo, t_hi))
                  if tracer is not None else None)
    await asyncio.sleep(max(0.0, t_hi - clock()))
    await gen
    if trace_task is not None:
        await trace_task
    ends = [r["t1"] for r in slices.records]
    t_open, t_close = stats.window(ends, t_lo, t_hi)
    drained = True
    if mix["judge"] == "latency":
        due = [t for q, t in zip(recs, tasks) if t_open <= q.due < t_close]
        if due:
            _, pending = await asyncio.wait(due,
                                            timeout=float(mix["drain_cap_s"]))
            drained = not pending
    # whatever is still queued or running is cancelled; it leaves at its
    # next slice boundary
    for q in recs:
        if q.handle is not None and not q.handle.finished:
            q.handle.cancel()
    await aio.drain()
    await asyncio.gather(*tasks, return_exceptions=True)
    for q in recs:
        q.handle = None
    return dict(t_open=t_open, t_close=t_close, drained=drained,
                t_end=clock())


# ---------------------------------------------------------------------------
# the whole run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Stack:
    """The serving stack of one cell, built once per process."""

    conf: Dict
    devs: list
    params: object
    engine: object
    server: object
    slices: SliceLog
    vocab: int
    split: Dict


def setup(cell: Cell, seed: int, require_tpu: bool, log: Callable,
          warm: bool = True) -> Stack:
    """Weights, engine, warm-up, estimator and server; ``warm=False``
    leaves shapes to compile when first used (calibration runs, whose
    timing is not measured)."""
    import jax

    from chipbench import weights
    from repro.models.registry import get_model

    devs = require_chips(cell.chips) if require_tpu else jax.devices()
    dev = devs[0]
    log(f"device {dev.device_kind} x{len(devs)}, jax {jax.__version__}, "
        f"compile cache {jax.config.jax_compilation_cache_dir}")
    conf = cell.config
    cfg = model_config(conf)
    model = get_model(cfg)
    split = {}
    t0 = clock()
    params = weights.make_params(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)), seed, dev)
    jax.block_until_ready(params)
    split["weights_s"] = clock() - t0
    t0 = clock()
    engine = build_engine(conf, params, model, dev)
    split["pool_s"] = clock() - t0
    w = warm_up(conf, engine, cfg.vocab_size, log, grid_too=warm)
    split["warm_s"], split["estimator_s"] = w["warm_s"], w["est_s"]
    server = build_server(conf, engine, w["est"], seed)
    slices = SliceLog(server.core.backend, engine)
    core = server.core
    step = core.step

    def traced_step():
        with span("step"):
            return step()
    core.step = traced_step
    return Stack(conf=conf, devs=devs, params=params, engine=engine,
                 server=server, slices=slices, vocab=cfg.vocab_size,
                 split=split)


async def serve_async(stack: Stack, mix: Dict, seed: int, seconds: float,
                      log: Callable, tracer=None):
    """Send the mix's traffic open-loop for its lead and ``seconds`` more,
    then drain.  Returns the request records and the window.  One event
    loop serves a stack for its whole life (the server's events bind to
    it)."""
    horizon = float(mix["lead_s"]) + seconds
    plan = traffic.schedule(mix, seed, horizon, stack.vocab)
    log(f"traffic: {traffic.summary(plan)} over {horizon:.1f} s at "
        f"{mix['arrivals']['rate_rps']} requests/s")
    recs: List[ReqRec] = []
    t_start = clock()
    box = await drive(stack.server.aio, plan, recs, stack.slices, mix,
                      t_start, seconds, stack.conf["serving"]["max_gen"],
                      tracer)
    box["t_start"] = t_start
    return recs, box


def describe(run: "Run", recs: List[ReqRec], log: Callable) -> None:
    t_open, t_close = run.window
    lateness = [q.submit - q.due for q in recs if q.submit is not None]
    inside = [r for r in run.records if t_open < r["t1"] <= t_close]
    log(f"requests: {len(recs)} sent, {sum(q.ok for q in recs)} completed, "
        f"{sum(q.err is not None for q in recs)} refused; generator "
        f"lateness p50 {stats.percentile(lateness, 50) * 1e3:.1f} ms, max "
        f"{max(lateness, default=0) * 1e3:.1f} ms; {len(inside)} slices in "
        f"the window, rows per slice "
        f"{[r['rows'] for r in inside]}, evictions "
        f"{sum(r['evictions'] for r in run.records)}, re-prefilled tokens "
        f"{sum(r['reprefill'] for r in run.records)}")


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, compiles: CompileClock,
             require_tpu: bool = True, log: Callable = print,
             trace_dir=None, control: bool = False) -> Dict:
    """One run.  Returns the result line's fields; with ``control`` the
    float8 reference takes the program's place in the verdict
    (``check.check``)."""
    from chipbench import tracing
    from chipbench.peaks import peaks as peak_table

    stack = setup(cell, seed, require_tpu, log, warm=not control)
    mix = cell.traffic
    tracer = tracing.WindowTracer(trace_dir, float(mix["trace_s"])) \
        if trace else None
    recs, box = asyncio.run(serve_async(stack, mix, seed, seconds, log,
                                        tracer))
    t_open, t_close = box["t_open"], box["t_close"]
    split = stack.split
    split["lead_s"] = t_open - box["t_start"]
    split["compiles"], split["compile_s"] = compiles.between(t_process,
                                                             t_open)
    setup_s = t_open - t_process
    in_window = compiles.between(t_open, t_close)
    log(f"set-up {setup_s:.3f} s: " + ", ".join(
        f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
        for k, v in split.items()))
    log(f"window {t_close - t_open:.3f} s from {split['lead_s']:.3f} s "
        f"after the traffic started; compilations in the window: "
        f"{in_window[0]} taking {in_window[1]:.3f} s; after it: "
        f"{compiles.between(t_close, clock())[0]}")
    devs = stack.devs
    run = Run(cell=cell, model=stack.conf["model"],
              records=stack.slices.records, requests=recs,
              first_dispatch=stack.slices.first_dispatch,
              window=(t_open, t_close),
              peaks=(peak_table(devs[0].device_kind) if require_tpu
                     else None))
    e2e = end_to_end(run, mix, setup_s)
    describe(run, recs, log)
    log("latency: " + str(e2e.pop("_info")))
    result = dict(attempted=e2e.pop("_attempted"),
                  failed=e2e.pop("_failed"))
    if trace:
        run.trace_window = tracer.window
        run.trace = tracer.reduce(stack.slices.records)
        metrics = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]](run)
            if v is not None:
                metrics[m["name"]] = dict(value=v, unit=m["unit"])
        result["breakdown"] = run.trace["breakdown"]
        extra = dict(busy_s=run.trace["busy_s"],
                     window_s=run.trace["window_s"])
    else:
        metrics = {m["name"]: dict(value=e2e[m["name"]], unit=m["unit"])
                   for m in cell.end_to_end}
        extra = {}
    result["metrics"] = metrics
    result["device"] = dict(device_info(devs),
                            memory_peak_bytes=memory_peak(devs, cell.chips),
                            **extra)
    # the check runs once the window has closed and the program's state
    # is freed: the reference then has the chip's memory to itself
    conf, params = stack.conf, stack.params
    del stack, run
    gc.collect()
    result.update(check.check(conf, cell.reference, params, recs, seed, log,
                              control=control))
    return result


def end_to_end(run: Run, mix: Dict, setup_s: float) -> Dict:
    t_open, t_close = run.window
    out = {"setup_s": setup_s,
           "out_tok_s": stats.window_rate(
               [(r["t1"], sum(r["valid"])) for r in run.records],
               t_open, t_close)}
    due = run.due_in(t_open, t_close)
    ttft = [q.ttft_ms() for q in due]
    norm = [q.norm_lat_ms() for q in due]
    lim = mix.get("limits", {})
    met = [q.ok and t <= lim.get("ttft_ms", math.inf)
           and n <= lim.get("norm_lat_ms", math.inf)
           for q, t, n in zip(due, ttft, norm)]
    out["ttft_p95_ms"] = stats.percentile(ttft, 95)
    out["norm_lat_p95_ms"] = stats.percentile(norm, 95)
    out["slo_met_share"] = 100.0 * sum(met) / len(met) if met else math.nan
    info = dict(due_in_window=len(due), slo_met_share=out["slo_met_share"])
    for name, xs in (("ttft", ttft), ("norm_lat", norm)):
        for pc in (50, 75, 90, 95, 99):
            info[f"{name}_p{pc}_ms"] = stats.percentile(xs, pc)
    out["_info"] = info
    if mix["judge"] == "latency":
        out["_attempted"] = len(due)
        out["_failed"] = sum(not q.ok for q in due)
    else:
        sent = [q for q in run.requests if t_open <= q.due < t_close]
        out["_attempted"] = len(sent)
        out["_failed"] = sum(q.err is not None for q in sent)
    return out
