"""repro.obs: Prometheus exposition invariants, Chrome-trace validity and
determinism, decision-audit ring semantics — and the cardinal rule that
full observability must not perturb scheduling (the golden dispatch logs
stay bit-exact with tracing, metrics, and auditing all on)."""
import copy
import importlib.util
import json
import os
import pathlib

import pytest

from repro.cluster.simulator import ClusterSimulator
from repro.cluster.trace import CODEFUSE, generate_trace
from repro.core.memory import AnalyticMemoryEstimator, LLAMA2_13B_DELTA
from repro.core.schedulers import make_strategy
from repro.obs import (NULL_TRACER, OBS_OFF, DecisionLog, MetricsRegistry,
                       Observability, Tracer, decisions_path_for, worker_tid)
from repro.serving import ServingConfig, default_sim_environment

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "golden_batch_compositions.json")

# the CI validator doubles as the test-suite definition of "valid"
_spec = importlib.util.spec_from_file_location(
    "validate_obs",
    pathlib.Path(__file__).parent.parent / "scripts" / "validate_obs.py")
validate_obs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(validate_obs)


# ---------------------------------------------------------------------------
# pillar 2: Prometheus metrics
# ---------------------------------------------------------------------------
def test_prometheus_render_invariants():
    reg = MetricsRegistry()
    c = reg.counter("demo_requests", "Requests served", ("outcome",))
    g = reg.gauge("demo_depth", "Queue depth")
    h = reg.histogram("demo_latency_seconds", "Latency",
                      buckets=(0.1, 1.0, 10.0))
    c.inc(outcome="ok")
    c.inc(2, outcome="err")
    g.set(7)
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    text = reg.render()
    assert text.endswith("\n")
    # counters get the _total suffix; TYPE lines precede samples
    assert "# TYPE demo_requests_total counter" in text
    assert 'demo_requests_total{outcome="err"} 2' in text
    assert 'demo_requests_total{outcome="ok"} 1' in text
    assert "# TYPE demo_depth gauge" in text and "demo_depth 7" in text
    # histogram: cumulative buckets ending in +Inf == _count, plus _sum
    assert 'demo_latency_seconds_bucket{le="0.1"} 1' in text
    assert 'demo_latency_seconds_bucket{le="1"} 3' in text
    assert 'demo_latency_seconds_bucket{le="10"} 4' in text
    assert 'demo_latency_seconds_bucket{le="+Inf"} 5' in text
    assert "demo_latency_seconds_count 5" in text
    assert "demo_latency_seconds_sum 56.05" in text
    # the CI validator agrees end to end
    assert validate_obs.validate_prometheus(text) == []
    fams = validate_obs.parse_prometheus(text)
    assert fams["demo_latency_seconds"]["type"] == "histogram"
    assert fams["demo_requests_total"]["samples"][
        'demo_requests_total{outcome="err"}'] == 2


def test_metric_declaration_and_observation_errors():
    reg = MetricsRegistry()
    c = reg.counter("x_total", "x", ("a",))
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1, a="v")
    with pytest.raises(ValueError, match="labels"):
        c.inc(b="wrong-label")
    # idempotent re-declaration returns the same object...
    assert reg.counter("x_total", "x", ("a",)) is c
    # ...but a type or label change is a hard error
    with pytest.raises(ValueError, match="re-registered"):
        reg.gauge("x_total", "x")
    with pytest.raises(ValueError, match="re-registered"):
        reg.counter("x_total", "x", ("a", "b"))
    with pytest.raises(ValueError, match="strictly increasing"):
        reg.histogram("h", "h", buckets=(1.0, 1.0))
    # declared name already ending in _total is not doubled
    assert c.sample_name == "x_total"


# ---------------------------------------------------------------------------
# pillar 1: Chrome trace events
# ---------------------------------------------------------------------------
def _demo_tracer():
    t = [0.0]
    tr = Tracer(clock=lambda: t[0])
    tr.declare_worker(0)
    tr.instant("arrival", 0.5, args=dict(rid=1))
    tr.async_begin("request", 1, 0.5)
    tr.counter("queue_depth", 3, ts=0.6)
    tr.complete("slice", 1.0, 0.25, tid=worker_tid(0),
                args=dict(rids=[1], input_len=8, slice_len=4))
    tr.async_end("request", 1, 2.0, args=dict(outcome="completed"))
    return tr


def test_tracer_emits_valid_chrome_trace_json():
    tr = _demo_tracer()
    obj = json.loads(tr.to_json())   # round-trips through real JSON
    assert validate_obs.validate_trace(obj) == []
    events = obj["traceEvents"]
    # metadata names both processes and the declared tracks
    meta = [e for e in events if e["ph"] == "M"]
    names = {(e["pid"], e.get("tid"), e["name"]): e["args"] for e in meta}
    assert names[(1, 0, "process_name")]["name"] == "scheduler"
    assert names[(2, 0, "process_name")]["name"] == "requests"
    assert names[(1, worker_tid(0), "thread_name")]["name"] == "worker 0"
    # timestamps are microseconds of the second-denominated inputs
    span = next(e for e in events if e["ph"] == "X")
    assert span["ts"] == 0.5e6 + 0.5e6 and span["dur"] == 0.25e6
    assert span["tid"] == worker_tid(0)
    # the standalone validator's mirrored track constant stays in sync
    assert validate_obs.TID_WORKER_BASE == worker_tid(0)


def test_tracer_serialization_is_deterministic():
    assert _demo_tracer().to_json() == _demo_tracer().to_json()


def test_validator_flags_unbalanced_async_spans():
    tr = _demo_tracer()
    tr.async_begin("request", 99, 3.0)   # opened, never finalized
    errs = validate_obs.validate_trace(tr.to_dict())
    assert any("never closed" in e and "99" in e for e in errs)


def test_null_tracer_and_shared_off_bundle_record_nothing():
    assert not NULL_TRACER.enabled and len(NULL_TRACER) == 0
    NULL_TRACER.instant("x")
    NULL_TRACER.complete("y", 0.0, 1.0)
    NULL_TRACER.counter("z", 1)
    assert len(NULL_TRACER) == 0
    assert not OBS_OFF.enabled
    assert OBS_OFF.registry is None and OBS_OFF.audit is None
    # a bare core gets the shared disabled bundle, not a fresh one
    true_lat, est, mem = default_sim_environment("hf")
    server = ServingConfig(strategy="scls", workers=2).build_sim(
        true_lat, est, mem)
    # ServingConfig servers get standard() obs: metrics + audit on,
    # tracing only with --trace-out
    assert server.core.obs.enabled
    assert server.core.obs.tracer is NULL_TRACER
    bare = ClusterSimulator(make_strategy("scls"), 2, true_lat, est, mem)
    assert bare.core.obs is OBS_OFF


def test_every_hook_site_is_guarded():
    """Overhead discipline: the hot path pays one attribute read + bool
    test per hook point when observability is off — every ``*.obs.on_*``
    call site sits behind a ``*.obs.enabled`` guard.  Checked repo-wide
    by the obs-guard static-analysis pass (which replaced the old
    string-count assertion: it pins the exact unguarded site instead of
    comparing substring tallies in one module)."""
    from repro.analysis import run_analysis
    report = run_analysis(rules=["obs-guard"])
    assert report.ok, "\n" + report.render()
    assert report.n_files > 50  # scanned all of src/repro, not one module


# ---------------------------------------------------------------------------
# pillar 3: decision audit
# ---------------------------------------------------------------------------
def test_decision_log_ring_and_query():
    log = DecisionLog(capacity=4)
    for i in range(10):
        log.record("batch" if i % 2 else "offload", ts=float(i),
                   rids=[i, 100 + i], worker=i % 3)
    assert len(log) == 4 and log.n_recorded == 10
    kept = log.to_list()
    assert [e["seq"] for e in kept] == [6, 7, 8, 9]  # oldest dropped
    # kind filter
    assert all(e["kind"] == "batch" for e in log.query(kind="batch"))
    # rid matches membership in ``rids`` and exact ``rid`` fields
    assert [e["seq"] for e in log.query(rid=107)] == [7]
    log.record("admission", ts=11.0, rid=42, action="reject")
    assert [e["kind"] for e in log.query(rid=42)] == ["admission"]
    # limit keeps the newest N, oldest-first
    assert [e["seq"] for e in log.query(limit=2)] == [9, 10]
    with pytest.raises(ValueError, match="capacity"):
        DecisionLog(capacity=0)


# ---------------------------------------------------------------------------
# the cardinal rule: zero scheduling perturbation
# ---------------------------------------------------------------------------
def _golden_cases():
    with open(GOLDEN) as f:
        g = json.load(f)
    # one static-mode run and one continuous-mode run, both with noise —
    # the RNG-sensitive paths where an accidental extra draw would show
    want = {("scls", 0.05), ("scls-cb", 0.05)}
    return [pytest.param(g["scenario_args"], r,
                         id=f"{r['strategy']}-sigma{r['noise_sigma']}")
            for r in g["runs"]
            if (r["strategy"], r["noise_sigma"]) in want]


@pytest.mark.parametrize("args, want", _golden_cases())
def test_golden_dispatch_log_bit_exact_with_full_observability(args, want):
    """Tentpole acceptance: the golden batch compositions recorded before
    ``repro.obs`` existed are reproduced bit-for-bit with tracing, metrics,
    and decision auditing all enabled — and the trace's dispatch spans
    reconstruct that exact log (every slice a span with matching rid set,
    worker track, and slice geometry)."""
    from repro.core.estimator import a100_llama13b_profile
    from repro.core.memory import A100_80GB_AVAILABLE
    from repro.serving import fitted_estimator
    true_lat = a100_llama13b_profile()
    est = fitted_estimator(true_lat, seed=0)
    mem = AnalyticMemoryEstimator(delta_bytes=LLAMA2_13B_DELTA,
                                  m_available=A100_80GB_AVAILABLE, zeta=0.9)
    trace = generate_trace(args["rate"], args["duration"], CODEFUSE,
                           seed=args["trace_seed"])
    s = make_strategy(want["strategy"], slice_len=args["slice_len"],
                      fixed_batch_size=args["fixed_batch_size"],
                      gamma=args["gamma"], max_parallel=args["max_parallel"])
    sim = ClusterSimulator(s, args["workers"], true_lat, est, mem,
                           noise_sigma=want["noise_sigma"],
                           seed=args["sim_seed"])
    sim.core.obs = Observability.standard(trace=True)
    sim.core.obs.attach(sim.core)
    res = sim.run(copy.deepcopy(trace), args["duration"])
    assert res.metrics.n_completed == want["n_completed"]
    assert sim.batch_log == want["batch_log"]

    obs = sim.core.obs
    tdict = obs.tracer.to_dict()
    assert validate_obs.validate_trace(tdict) == []
    # span-by-span reconstruction of the golden dispatch log
    assert validate_obs.trace_slice_log(tdict) == want["batch_log"]
    # the metrics pillar observed the same dispatches
    assert obs.ins.slices.value() == len(want["batch_log"])
    assert obs.ins.slice_time.count() == len(want["batch_log"])
    # the audit recorded a batch + offload pair per central dispatch, with
    # the Eq. 11 loads every placement saw at decision time
    n_static = sum(1 for e in want["batch_log"] if e[0] == "static")
    if n_static:
        offloads = obs.audit.query(kind="offload")
        assert len(offloads) >= 1
        assert all(set(e["loads"]) == {str(w)
                                       for w in range(args["workers"])}
                   for e in offloads)
        batches = obs.audit.query(kind="batch")
        assert all(e["mem_bound"] >= len(e["rids"]) for e in batches)


def test_sim_slice_spans_carry_prefill_decode_phases():
    """The sim backend splits each slice span into prefill + decode
    sub-spans from the latency model's nominal ratio — without costing an
    extra RNG draw (the golden test above is the proof)."""
    true_lat, est, mem = default_sim_environment("hf")
    cfg = ServingConfig(strategy="scls", workers=2, trace_out="unused.json")
    server = cfg.build_sim(true_lat, est, mem)
    server.replay(generate_trace(2.0, 10.0, CODEFUSE, seed=3))
    server.drain()
    events = server.core.obs.tracer.to_dict()["traceEvents"]
    slices = [e for e in events if e["ph"] == "X" and e["name"] == "slice"]
    prefills = [e for e in events if e["name"] == "prefill"]
    decodes = [e for e in events if e["name"] == "decode"]
    assert len(slices) >= 1
    assert len(prefills) == len(decodes) == len(slices)
    for s, p, d in zip(slices, prefills, decodes):
        assert s["ts"] == p["ts"] and s["tid"] == p["tid"] == d["tid"]
        assert 0.0 <= p["dur"] <= s["dur"]
        assert p["dur"] + d["dur"] == pytest.approx(s["dur"], abs=1e-3)


def test_same_seed_same_config_byte_identical_trace():
    """Determinism: on the sim backend nothing in the obs stack reads
    wall clocks or draws randomness, so same seed ⇒ same trace bytes."""
    def run():
        true_lat, est, mem = default_sim_environment("hf")
        cfg = ServingConfig(strategy="scls", workers=2, seed=4,
                            trace_out="unused.json")
        server = cfg.build_sim(true_lat, est, mem)
        server.replay(generate_trace(3.0, 15.0, CODEFUSE, seed=8))
        server.drain()
        assert server.core.obs.tracer.enabled
        return server.core.obs.tracer.to_json()

    a, b = run(), run()
    assert len(json.loads(a)["traceEvents"]) > 10
    assert a == b


def test_export_writes_trace_and_decisions(tmp_path):
    true_lat, est, mem = default_sim_environment("hf")
    cfg = ServingConfig(strategy="scls", workers=2,
                        trace_out=str(tmp_path / "t.json"))
    server = cfg.build_sim(true_lat, est, mem)
    server.replay(generate_trace(2.0, 8.0, CODEFUSE, seed=5))
    server.drain()
    paths = server.core.obs.export(cfg.trace_out)
    assert paths == [str(tmp_path / "t.json"),
                     str(tmp_path / "t.decisions.json")]
    assert decisions_path_for("x/trace.json") == "x/trace.decisions.json"
    with open(paths[0]) as f:
        assert validate_obs.validate_trace(json.load(f)) == []
    with open(paths[1]) as f:
        events = json.load(f)
    assert events and all({"seq", "ts", "kind"} <= set(e) for e in events)
    # the CLI entry point agrees
    metrics_file = tmp_path / "metrics.txt"
    metrics_file.write_text(server.core.obs.registry.render())
    assert validate_obs.main([paths[0], "--metrics", str(metrics_file),
                              "--decisions", paths[1]]) == 0


# ---------------------------------------------------------------------------
# per-reason rejection counts (satellite: RunMetrics + fig12)
# ---------------------------------------------------------------------------
def test_compute_metrics_carries_reject_reasons():
    from repro.cluster.metrics import compute_metrics
    m = compute_metrics("x", [], 10.0, [1.0], [1], 0, 0,
                        reject_reasons={"memory": 2, "deadline": 5})
    assert m.n_rejected_memory == 2 and m.n_rejected_deadline == 5
    row = m.row()
    assert row["n_rejected_memory"] == 2
    assert row["n_rejected_deadline"] == 5
    bare = compute_metrics("x", [], 10.0, [1.0], [1], 0, 0)
    assert bare.n_rejected_memory == bare.n_rejected_deadline == 0


# ---------------------------------------------------------------------------
# program spans on the real backend (host clock)
# ---------------------------------------------------------------------------
def _toy_estimator():
    from repro.core.estimator import LatencyCoeffs, ServingTimeEstimator
    return ServingTimeEstimator(LatencyCoeffs(1e-6, 1e-4, 1e-6, 1e-3),
                                LatencyCoeffs(1e-8, 1e-5, 1e-7, 1e-3))


@pytest.fixture(scope="module")
def real_traced(tmp_path_factory):
    """Four requests served through ``AsyncSliceServer`` over a retain-mode
    ``RealBackend`` (one persistent paged engine) with tracing on; the
    host clock read before and after the run."""
    import asyncio
    import time

    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.engine.static_engine import StaticEngine
    from repro.models.registry import get_model

    model = get_model(get_config("llama3.2-1b", reduced=True))
    params = model.init(jax.random.PRNGKey(0))
    cfg = ServingConfig(strategy="scls", backend="real", kv_layout="paged",
                        kv_retain="request", page_tokens=16, slice_len=8,
                        max_gen=32, gamma=0.25, m_available=64e6,
                        mem_bucket=8, trace_out=str(
                            tmp_path_factory.mktemp("t") / "t.json"))
    mem = cfg.memory_estimator(model.kv_bytes_per_token())
    eng = StaticEngine(model, params, eos_id=1, len_bucket=8,
                       kv_layout="paged", page_tokens=16,
                       kv_pool_tokens=mem.total_blocks * 16)
    server = cfg.build_real([eng], _toy_estimator(), mem)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, 200, size=n).astype(np.int32)
               for n in (9, 17, 12, 5)]

    async def main():
        aio = server.aio
        hs = [aio.submit(p, gen_len=g, max_gen=32)
              for p, g in zip(prompts, (12, 5, 20, 3))]
        return [await h.result() for h in hs]

    t_before = time.perf_counter()
    reqs = asyncio.run(main())
    t_after = time.perf_counter()
    assert all(r.done for r in reqs)
    events = server.core.obs.tracer.to_dict()["traceEvents"]
    return dict(server=server, reqs=reqs, events=events,
                t_before=t_before, t_after=t_after)


def _spans(events):
    return {e["args"]["span_id"]: e for e in events
            if e["ph"] == "X" and e.get("cat") == "span"}


def test_real_spans_nest_engine_in_slice_in_tick_in_pacer(real_traced):
    spans = _spans(real_traced["events"])
    by_name = {}
    for e in spans.values():
        by_name.setdefault(e["name"], []).append(e)
    assert {"submit", "pacer.step", "sched.tick", "slice", "engine.plan",
            "engine.prefill", "engine.decode",
            "engine.retain"} <= set(by_name)
    assert len(by_name["submit"]) == 4

    def parent(e):
        return spans.get(e["args"]["parent_id"])

    def inside(c, p):
        return (c["ts"] >= p["ts"] - 1e-3
                and c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-3)

    for name in ("engine.plan", "engine.prefill", "engine.decode",
                 "engine.retain"):
        for e in by_name[name]:
            p = parent(e)
            assert p["name"] == "slice" and inside(e, p)
            assert e["tid"] == p["tid"] == worker_tid(0)
    for s in by_name["slice"]:
        assert parent(s)["name"] in ("sched.tick", "pacer.step")
        assert inside(s, parent(s))
    chains = [s for s in by_name["slice"]
              if parent(s)["name"] == "sched.tick"
              and parent(parent(s))["name"] == "pacer.step"
              and parent(parent(parent(s))) is None]
    assert chains, "no slice dispatched from a tick inside a pacer step"
    # the dispatch log still comes back from the slice spans
    assert validate_obs.validate_trace(
        {"traceEvents": real_traced["events"]}) == []
    assert validate_obs.trace_slice_log(
        {"traceEvents": real_traced["events"]}) \
        == real_traced["server"].core.batch_log


def test_real_span_timestamps_are_host_seconds(real_traced):
    """Every event of the real backend's trace, spans and instants and
    the requests' async lifecycles, sits on ``time.perf_counter``."""
    lo, hi = real_traced["t_before"] * 1e6, real_traced["t_after"] * 1e6
    timed = [e for e in real_traced["events"] if e["ph"] != "M"]
    assert timed
    for e in timed:
        assert lo <= e["ts"] <= hi, e
        assert e["ts"] + e.get("dur", 0.0) <= hi
    begins = [e for e in timed if e["ph"] == "b"]
    submits = {e["args"]["rid"]: e for e in timed if e["name"] == "submit"}
    assert len(begins) == 4
    for b in begins:  # a lifecycle opens where its submit span ends
        s = submits[b["id"]]
        assert b["ts"] == pytest.approx(s["ts"] + s["dur"], abs=1.0)


def test_real_slice_span_args_are_the_batch_own(real_traced):
    """rids and the Eq. 3/4 ``est_time`` of each slice span are those the
    scheduler placed (the decision audit's offload records), in order,
    less the placements withdrawn before they started (the audit's
    withdraw records: the real backend runs slices inline)."""
    events = real_traced["events"]
    slices = sorted((e for e in events
                     if e["ph"] == "X" and e["name"] == "slice"),
                    key=lambda e: e["ts"])
    offloads = []
    for ev in real_traced["server"].core.obs.audit.to_list():
        key = (ev.get("worker"), ev.get("rids"), ev.get("est_time"))
        if ev["kind"] == "offload":
            offloads.append(ev)
        elif ev["kind"] == "withdraw":
            placed = [(o["worker"], o["rids"], o["est_time"])
                      for o in offloads]
            assert key in placed, ev  # only a placed batch is withdrawn
            del offloads[placed.index(key)]
    assert len(slices) == len(offloads) >= 2
    for s, o in zip(slices, offloads):
        a = s["args"]
        assert sorted(a["rids"]) == o["rids"]
        assert a["est_time"] == o["est_time"]
        assert a["rows"] == len(a["rids"]) == len(a["valid"])
        assert 1 <= a["steps"] <= 8
    reqs = {r.rid: r for r in real_traced["reqs"]}
    firsts = {}
    for s in slices:
        for rid, fresh in zip(s["args"]["rids"], s["args"]["fresh"]):
            if fresh:
                assert rid not in firsts
                firsts[rid] = s
    assert set(firsts) == set(reqs)
    # every valid token of every request came out of some slice span
    valid = {}
    for s in slices:
        for rid, v in zip(s["args"]["rids"], s["args"]["valid"]):
            valid[rid] = valid.get(rid, 0) + v
    assert valid == {rid: r.generated for rid, r in reqs.items()}


def test_real_latency_histograms_observe_host_seconds(real_traced):
    ins = real_traced["server"].core.obs.ins
    span_s = (real_traced["t_after"] - real_traced["t_before"])
    assert ins.ttft.count() == ins.response.count() == 4
    assert 0 < ins.ttft.sum() <= ins.response.sum() <= 4 * span_s
    slices = [e for e in real_traced["events"] if e["name"] == "slice"]
    assert ins.slice_time.count() == len(slices)
    assert ins.slice_time.sum() == pytest.approx(
        sum(e["dur"] for e in slices) / 1e6, rel=1e-3)


def test_compile_listener_counts_each_new_shape_once():
    import time
    from types import SimpleNamespace

    import jax
    import numpy as np
    obs = Observability.standard(trace=True)
    core = SimpleNamespace(n_workers=1, backend=SimpleNamespace(
        host_clock=True, bind_tracer=lambda tracer: None))
    obs.attach(core)
    salt = float(time.perf_counter_ns() % 9973)  # a program no cache holds
    f = jax.jit(lambda x: x * salt + 1.0)
    before = obs.ins.compiles.value()
    with obs.tracer.span("probe"):
        f(np.ones(3, np.float32)).block_until_ready()
    assert obs.ins.compiles.value() == before + 1
    with obs.tracer.span("probe"):
        f(np.ones(3, np.float32)).block_until_ready()
    assert obs.ins.compiles.value() == before + 1
    f(np.ones(5, np.float32)).block_until_ready()
    assert obs.ins.compiles.value() == before + 2
    comp = [e for e in obs.tracer.to_dict()["traceEvents"]
            if e["name"] == "compile"]
    assert [e["args"]["within"] for e in comp] == ["probe", None]
    assert all(e["dur"] > 0 for e in comp)


def test_disabled_and_sim_tracers_record_no_spans():
    """Spans cost a ``TraceAnnotation`` and no event when the tracer is
    off, or on the simulator's core clock (its trace stays byte-exact)."""
    with NULL_TRACER.span("probe", rids=[1, 2]) as sp:
        pass
    assert len(NULL_TRACER) == 0 and sp.t1 >= sp.t0
    sim = Tracer(clock=lambda: 0.0)
    with sim.span("probe", rids=[1]):
        with sim.span("inner"):
            pass
    assert len(sim) == 0
    true_lat, est, mem = default_sim_environment("hf")
    server = ServingConfig(strategy="scls", workers=2,
                           trace_out="unused.json").build_sim(
        true_lat, est, mem)
    server.submit(input_len=32, gen_len=40)
    server.drain()
    events = server.core.obs.tracer.to_dict()["traceEvents"]
    assert not [e for e in events if e.get("cat") == "span"]


def test_spans_reach_the_profiler_trace_with_their_args(tmp_path):
    """Inside a profiler session each span is a ``scls.<name>`` event of
    the host plane, with its args, the late ones included (lists as
    space-separated strings)."""
    import glob

    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with NULL_TRACER.span("outer", rids=[3, 4, 5]) as sp:
            with NULL_TRACER.span("inner", rows=2):
                pass
            sp.set(steps=7, valid=[1, 2, 3])
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path[0])
    found = {}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("scls."):
                    found[e.name] = (e.start_ns, e.duration_ns,
                                     dict(e.stats))
    assert set(found) == {"scls.outer", "scls.inner"}
    outer, inner = found["scls.outer"], found["scls.inner"]
    assert outer[2] == {"rids": "3 4 5", "steps": 7, "valid": "1 2 3"}
    assert inner[2] == {"rows": 2}
    assert outer[0] <= inner[0] and \
        inner[0] + inner[1] <= outer[0] + outer[1]
