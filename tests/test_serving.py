"""repro.serving: SchedulerCore/Backend equivalence with the legacy
runtimes, the SliceServer online API (submit / stream / cancel / drain),
and ServingConfig validation."""
import copy
import itertools
import json
import os
import pathlib

import numpy as np
import pytest

from repro.cluster.metrics import compute_metrics
from repro.cluster.simulator import ClusterSimulator
from repro.cluster.trace import CODEFUSE, generate_trace
from repro.core.memory import (AnalyticMemoryEstimator, LLAMA2_13B_DELTA,
                               PagedMemoryEstimator)
from repro.core.request import Request
from repro.core.schedulers import make_strategy
from repro.serving import (ServingConfig, SimBackend, SchedulerCore,
                           default_sim_environment)

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "golden_batch_compositions.json")


@pytest.fixture(scope="module")
def sim_env():
    return default_sim_environment("hf")  # analytic memory model


# ---------------------------------------------------------------------------
# tentpole acceptance: one SchedulerCore, zero scheduling drift
# ---------------------------------------------------------------------------
def _golden_runs():
    with open(GOLDEN) as f:
        g = json.load(f)
    return [pytest.param(g["scenario_args"], r,
                         id=f"{r['strategy']}-sigma{r['noise_sigma']}")
            for r in g["runs"]]


@pytest.mark.parametrize("args, want", _golden_runs())
def test_scheduler_core_matches_legacy_batch_compositions(args, want):
    """The refactored SchedulerCore must reproduce the pre-refactor
    ClusterSimulator's dispatch log (which requests ran together, on which
    worker, with what slice) bit-for-bit — goldens were recorded at commit
    307a423 by scripts/gen_equivalence_golden.py."""
    from repro.core.estimator import a100_llama13b_profile
    from repro.core.memory import A100_80GB_AVAILABLE
    from repro.serving import fitted_estimator
    true_lat = a100_llama13b_profile()  # the golden generator's exact env
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
    res = sim.run(copy.deepcopy(trace), args["duration"])
    assert res.metrics.n_completed == want["n_completed"]
    assert sim.batch_log == want["batch_log"]


def test_sim_and_real_share_one_core(sim_env):
    """Both legacy shims drive the same SchedulerCore class."""
    from repro.cluster.realtime import RealCluster
    import repro.serving.core as core_mod
    true_lat, est, mem = sim_env
    sim = ClusterSimulator(make_strategy("scls"), 2, true_lat, est, mem)
    assert type(sim.core) is core_mod.SchedulerCore
    assert RealCluster.__init__.__module__ == "repro.cluster.realtime"
    # the scheduling loop is gone from the shims
    import inspect
    import repro.cluster.simulator as sim_mod
    import repro.cluster.realtime as real_mod
    for mod in (sim_mod, real_mod):
        src = inspect.getsource(mod)
        for needle in ("dp_batch", "_on_tick", "next_interval", "heappush"):
            assert needle not in src, f"{mod.__name__} still has {needle}"


# ---------------------------------------------------------------------------
# SliceServer online API (sim backend)
# ---------------------------------------------------------------------------
def test_slice_server_streams_tokens_per_slice(sim_env):
    true_lat, est, mem = sim_env
    cfg = ServingConfig(strategy="scls", workers=2, slice_len=64, gamma=1.0)
    server = cfg.build_sim(true_lat, est, mem)
    # staggered submissions: the second arrives while the first is in flight
    h1 = server.submit(input_len=100, gen_len=200, arrival=0.0)
    h2 = server.submit(input_len=40, gen_len=30, arrival=2.0)
    stream = h1.tokens()
    first = list(itertools.islice(stream, 70))
    assert first == list(range(70))          # sim tokens = generation indices
    assert not h1.finished                   # 200 > 70: still generating
    assert h1.request.n_schedules >= 2       # 70 tokens needed >= 2 slices
    rest = list(stream)
    assert first + rest == list(range(200))
    assert h1.done and h1.request.generated == 200
    assert h2.result().done                  # driving h1 served h2 too
    m = server.drain()
    assert m.n_completed == 2
    assert m.ttft_mean > 0 and m.p99_response >= m.p95_response >= m.p50_response


def test_slice_server_throughput_matches_legacy_run(sim_env):
    """Replaying a trace through the online API matches the offline
    ``run()`` path within tolerance (tick phase differs slightly: online
    ticks start at first arrival, offline at t=0)."""
    true_lat, est, mem = sim_env
    trace = generate_trace(8.0, 60.0, CODEFUSE, seed=11)
    legacy = ClusterSimulator(make_strategy("scls"), 4, true_lat, est, mem,
                              seed=3).run(copy.deepcopy(trace), 60.0).metrics
    cfg = ServingConfig(strategy="scls", workers=4, seed=3)
    server = cfg.build_sim(true_lat, est, mem)
    server.replay(copy.deepcopy(trace))
    online = server.drain(60.0)
    assert online.n_completed == legacy.n_completed == len(trace)
    assert online.throughput == pytest.approx(legacy.throughput, rel=0.1)
    assert online.mean_response == pytest.approx(legacy.mean_response, rel=0.2)


def test_cancel_pending_lease_decays_offloader_load(sim_env):
    """Regression: a SCLS-CB lease cancelled while still pending on a
    worker must return its marginal load charge to the offloader — a
    leaked charge would skew max-min placement and Eq. 12 forever."""
    true_lat, est, _ = sim_env
    # token budget fits one (64+64)-token lease but not two, so the second
    # lease waits in the worker's pending queue (exact Eq. 5/9 admission)
    mem = AnalyticMemoryEstimator(delta_bytes=LLAMA2_13B_DELTA,
                                  m_available=170e6, zeta=0.9)
    cfg = ServingConfig(strategy="scls-cb", workers=1, slice_len=64,
                        gamma=1.0)
    server = cfg.build_sim(true_lat, est, mem)
    blocker = server.submit(input_len=64, gen_len=600)
    victim = server.submit(input_len=64, gen_len=600, arrival=0.1)
    while not any(r.rid == victim.rid
                  for w in server.core.workers for r in w.pending):
        assert server.step(), "victim never queued behind the blocker"
    assert victim.cancel()
    assert victim.cancelled and victim.finished
    assert victim.rid not in server.core._lease_est
    server.drain()
    assert blocker.done
    assert not server.core._lease_est
    assert max(server.core.offloader.loads.values()) == pytest.approx(
        0.0, abs=1e-12)


def test_cancel_before_any_generation_does_not_train_predictor(sim_env):
    """Regression: a request cancelled with generated == 0 carries no
    length evidence; recording it would log a phantom 1-token completion
    and bias calibrated caps toward zero."""
    true_lat, est, mem = sim_env
    cfg = ServingConfig(strategy="scls-pred", predictor="histogram",
                        workers=2)
    server = cfg.build_sim(true_lat, est, mem)
    h = server.submit(input_len=64, gen_len=200)
    h.cancel()
    server.drain()
    assert h.cancelled and h.request.generated == 0
    assert server.core.predictor.n_observed == 0


def test_cancel_from_pool_is_immediate(sim_env):
    true_lat, est, mem = sim_env
    cfg = ServingConfig(strategy="scls", workers=2)
    server = cfg.build_sim(true_lat, est, mem)
    h = server.submit(input_len=64, gen_len=500)
    assert h.cancel()
    assert h.finished and h.cancelled and not h.done
    assert h.request.generated == 0
    assert h.cancel()  # idempotent: still reports cancelled
    m = server.drain()
    assert m.n_completed == 0


def test_cancel_mid_flight_sim_backend_frees_blocks_and_trains_predictor():
    """Cancel during a slice on the sim backend: pages (continuous block
    charges) return to baseline and the predictor records the truncated
    length — the online-admission contract of the serving API."""
    # (a) scls-cb + paged: block charges on the workers must vanish
    true_lat, est, _ = default_sim_environment("hf")
    mem = PagedMemoryEstimator(delta_bytes=LLAMA2_13B_DELTA,
                               m_available=5e9, zeta=0.9, page_tokens=16)
    cfg = ServingConfig(strategy="scls-cb", kv_layout="paged", workers=2,
                        slice_len=64, gamma=1.0)
    server = cfg.build_sim(true_lat, est, mem)
    victim = server.submit(input_len=64, gen_len=600)
    others = [server.submit(input_len=32 + i, gen_len=100, arrival=0.5)
              for i in range(4)]
    while not victim.finished and victim.request.generated == 0:
        server.step()
    assert not victim.finished, "victim finished before it could be cancelled"
    victim.cancel()
    m = server.drain()
    assert victim.cancelled and not victim.done
    assert 0 < victim.request.generated < 600  # truncated mid-generation
    assert all(h.done for h in others)
    assert all(not w.running and not w.pending for w in server.core.workers)
    assert m.n_completed == 4

    # (b) scls-pred: the prediction pipeline must see the truncated length
    mem2 = AnalyticMemoryEstimator(delta_bytes=LLAMA2_13B_DELTA,
                                   m_available=5e9, zeta=0.9)
    cfg2 = ServingConfig(strategy="scls-pred", predictor="histogram",
                         workers=2, slice_len=64, gamma=1.0)
    server2 = cfg2.build_sim(true_lat, est, mem2)
    victim2 = server2.submit(input_len=64, gen_len=600)
    for i in range(4):
        server2.submit(input_len=32 + i, gen_len=100, arrival=0.5)
    while not victim2.finished and victim2.request.generated == 0:
        server2.step()
    victim2.cancel()
    server2.drain()
    assert victim2.cancelled and 0 < victim2.request.generated < 600
    # every terminal request (4 completed + 1 truncated) trained the online
    # predictor; the cancelled one contributed its realized length
    assert server2.core.predictor.n_observed == 5


def test_submit_before_armed_future_tick_is_not_starved(sim_env):
    """Regression: a far-future submission arms a tick at its arrival;
    a later submission with an EARLIER arrival must re-arm the tick at
    its own time instead of waiting for the future one."""
    true_lat, est, mem = sim_env
    server = ServingConfig(strategy="scls", workers=2, gamma=1.0).build_sim(
        true_lat, est, mem)
    late = server.submit(input_len=32, gen_len=20, arrival=100.0)
    early = server.submit(input_len=32, gen_len=20, arrival=0.0)
    early.result()
    assert early.request.first_token_time < 50.0
    server.drain()
    assert late.done and late.request.first_token_time >= 100.0


def test_build_sim_partial_args_stay_consistent(sim_env):
    """Regression: omitting only mem must not silently pair the caller's
    latency models with the DS rule table (nor refit a discarded default
    environment); the analytic A100 model is the partial-args default."""
    from repro.core.estimator import a100_llama13b_hf_profile
    from repro.serving import fitted_estimator
    hf_lat = a100_llama13b_hf_profile()
    hf_est = fitted_estimator(hf_lat)
    server = ServingConfig(strategy="scls", workers=2).build_sim(
        hf_lat, hf_est)
    assert isinstance(server.core.mem, AnalyticMemoryEstimator)
    assert server.core.backend.true_lat is hf_lat
    assert server.core.est is hf_est
    # paged configs get the paged pool instead
    paged = ServingConfig(strategy="scls-cb", kv_layout="paged",
                          workers=2).build_sim(hf_lat, hf_est)
    assert isinstance(paged.core.mem, PagedMemoryEstimator)


def test_submit_then_replay_no_rid_collision(sim_env):
    """Interactive submits use their own rid namespace, so mixing them
    with trace replay (rids 0..n) on one server must not collide."""
    true_lat, est, mem = sim_env
    server = ServingConfig(strategy="scls", workers=2).build_sim(
        true_lat, est, mem)
    h = server.submit(input_len=16, gen_len=8)
    trace = generate_trace(2.0, 10.0, CODEFUSE, seed=5)
    handles = server.replay(trace)
    m = server.drain()
    assert h.done and all(t.done for t in handles)
    assert m.n_completed == len(trace) + 1


def test_replay_and_submit_refused_after_close(sim_env):
    true_lat, est, mem = sim_env
    server = ServingConfig(strategy="scls", workers=2).build_sim(
        true_lat, est, mem)
    server.close()
    with pytest.raises(RuntimeError, match="closed"):
        server.submit(input_len=8, gen_len=4)
    with pytest.raises(RuntimeError, match="closed"):
        server.replay(generate_trace(1.0, 5.0, CODEFUSE, seed=6))


def test_drain_before_any_submission_yields_finite_metrics(sim_env):
    true_lat, est, mem = sim_env
    server = ServingConfig(strategy="scls", workers=2).build_sim(
        true_lat, est, mem)
    m = server.drain()
    for k, v in m.row().items():
        if isinstance(v, float):
            assert np.isfinite(v), f"{k} is not finite: {v}"
    assert m.n_requests == m.n_completed == 0


def test_sim_requests_do_not_materialize_token_lists(sim_env):
    """Offline sim replays must not pay for synthetic token storage: the
    core's token log stays empty and output_tokens stays None (legacy
    behavior); streaming handles synthesize indices lazily instead."""
    true_lat, est, mem = sim_env
    server = ServingConfig(strategy="scls", workers=2).build_sim(
        true_lat, est, mem)
    trace = generate_trace(2.0, 20.0, CODEFUSE, seed=9)
    handles = server.replay(trace)
    server.drain()
    assert not server.core.token_log
    assert all(r.output_tokens is None for r in trace)
    h = handles[0]
    assert h.output_tokens == list(range(h.request.generated))


# ---------------------------------------------------------------------------
# ServingConfig
# ---------------------------------------------------------------------------
def test_serving_config_validates_combinations():
    with pytest.raises(ValueError, match="strategy"):
        ServingConfig(strategy="nope")
    with pytest.raises(ValueError, match="prediction-aware"):
        ServingConfig(strategy="scls", predictor="histogram")
    with pytest.raises(ValueError, match="perfect"):
        ServingConfig(strategy="oracle", predictor="histogram")
    with pytest.raises(ValueError, match="continuous"):
        ServingConfig(strategy="ils", backend="real")
    with pytest.raises(ValueError, match="kv_layout"):
        ServingConfig(kv_layout="sparse")
    with pytest.raises(ValueError, match="coverage"):
        ServingConfig(coverage=1.5)
    with pytest.raises(ValueError, match="worker"):
        ServingConfig(workers=0)
    # valid combinations construct fine
    ServingConfig(strategy="scls-pred", predictor="proxy")
    ServingConfig(strategy="oracle", predictor="perfect")
    ServingConfig(strategy="scls-cb", kv_layout="paged")


def test_serving_config_from_dict_and_cli_roundtrip():
    cfg = ServingConfig.from_dict({"strategy": "lb", "workers": 3})
    assert cfg.strategy == "lb" and cfg.workers == 3
    with pytest.raises(ValueError, match="unknown ServingConfig keys"):
        ServingConfig.from_dict({"stratgy": "lb"})
    cli = ServingConfig.from_cli(
        ["--strategy", "scls-pred", "--predictor", "histogram",
         "--kv-layout", "paged", "--workers", "5"], gamma=0.25)
    assert (cli.strategy, cli.predictor, cli.kv_layout) == \
        ("scls-pred", "histogram", "paged")
    assert cli.workers == 5 and cli.gamma == 0.25
    assert ServingConfig.from_dict(cli.to_dict()) == cli
    with pytest.raises(SystemExit):  # invalid combo -> argparse error
        ServingConfig.from_cli(["--strategy", "scls", "--predictor", "proxy"])


def test_cli_reduced_flag_and_kv_budget():
    """--no-reduced serves the published widths; the toy preset stays the
    default; --m-available sets the per-worker KV budget."""
    assert ServingConfig.from_cli([]).reduced is True
    assert ServingConfig.from_cli(["--reduced"]).reduced is True
    cli = ServingConfig.from_cli(["--no-reduced", "--m-available", "4e9"])
    assert cli.reduced is False and cli.m_available == 4e9


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR is left to JAX; otherwise the cache sits
    at a fixed <repo>/.jax_cache."""
    import jax

    from repro.launch.serve import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert use_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = pathlib.Path(__file__).resolve().parents[1]
        assert use_compile_cache() == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(repo / ".jax_cache")
        assert use_compile_cache() == str(repo / ".jax_cache")  # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_strategy_config_and_memory_builders():
    cfg = ServingConfig(strategy="scls-cb", kv_layout="paged", page_tokens=8,
                        slice_len=32)
    s = cfg.strategy_config()
    assert s.name == "SCLS-CB" and s.kv_layout == "paged"
    mem = cfg.memory_estimator(delta_bytes=100.0)
    assert isinstance(mem, PagedMemoryEstimator)
    assert mem.page_tokens == 8
    dense = ServingConfig().memory_estimator(delta_bytes=100.0)
    assert isinstance(dense, AnalyticMemoryEstimator)


def test_serving_config_packing_validation_and_cli():
    """packing='envelope' (PR 10) is opt-in, paged-only, CLI-reachable."""
    assert ServingConfig().packing == "batch-max"
    assert ServingConfig().strategy_config().packing == "batch-max"
    with pytest.raises(ValueError, match="packing"):
        ServingConfig(packing="exact")
    with pytest.raises(ValueError, match="paged"):
        ServingConfig(packing="envelope")  # dense layout: no block pool
    cfg = ServingConfig(strategy="scls-cb", kv_layout="paged",
                        packing="envelope")
    assert cfg.strategy_config().packing == "envelope"
    cli = ServingConfig.from_cli(["--packing", "envelope",
                                  "--kv-layout", "paged"])
    assert cli.packing == "envelope"
    with pytest.raises(SystemExit):  # invalid combo -> argparse error
        ServingConfig.from_cli(["--packing", "envelope"])


def test_envelope_packing_sim_end_to_end(sim_env):
    """A paged sim run under packing='envelope' completes the same request
    set as batch-max (correctness is packing-invariant; only grouping may
    differ) — and SchedulerCore refuses envelope without a block pool."""
    true_lat, est, _ = sim_env
    trace = generate_trace(8.0, 20.0, CODEFUSE, seed=5)
    done = {}
    for packing in ("batch-max", "envelope"):
        cfg = ServingConfig(strategy="scls-cb", kv_layout="paged",
                            workers=2, packing=packing)
        server = cfg.build_sim(true_lat, est)
        assert isinstance(server.core.mem, PagedMemoryEstimator)
        server.replay(copy.deepcopy(trace))
        done[packing] = server.drain(20.0).n_completed
        assert done[packing] > 0
    assert done["envelope"] == done["batch-max"]

    # construction guard: envelope needs the paged pool's block accounting
    dense_env = default_sim_environment("hf")
    with pytest.raises(ValueError, match="PagedMemoryEstimator"):
        SchedulerCore(make_strategy("scls", kv_layout="paged",
                                    packing="envelope"),
                      SimBackend(dense_env[0]), 2, dense_env[1], dense_env[2])


def test_continuous_strategy_rejected_on_noncontinuous_backend(sim_env):
    true_lat, est, mem = sim_env

    class CentralOnly(SimBackend):
        supports_continuous = False

    with pytest.raises(ValueError, match="continuous"):
        SchedulerCore(make_strategy("ils"), CentralOnly(true_lat), 2, est, mem)


# ---------------------------------------------------------------------------
# metrics satellite: TTFT + latency percentiles
# ---------------------------------------------------------------------------
def test_compute_metrics_ttft_and_percentiles():
    reqs = []
    for i in range(100):
        r = Request(rid=i, arrival=0.0, input_len=8, gen_len=10)
        r.done = True
        r.finish_time = float(i + 1)    # latencies 1..100
        r.first_token_time = 0.25 * (i + 1)
        reqs.append(r)
    m = compute_metrics("x", reqs, 100.0, [100.0], [1], 0, 100)
    assert m.p50_response == pytest.approx(np.percentile(np.arange(1, 101), 50))
    assert m.p99_response == pytest.approx(np.percentile(np.arange(1, 101), 99))
    assert m.p50_response < m.p95_response < m.p99_response
    assert m.ttft_mean == pytest.approx(0.25 * np.mean(np.arange(1, 101)))
    assert m.ttft_p95 == pytest.approx(
        0.25 * np.percentile(np.arange(1, 101), 95))


# ---------------------------------------------------------------------------
# real backend (reduced model, every FLOP real)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def real_env():
    import jax
    from repro.configs import get_config
    from repro.engine.profiler import fit_estimator
    from repro.models.registry import get_model
    arch = get_config("llama3.2-1b", reduced=True)
    model = get_model(arch)
    params = model.init(jax.random.PRNGKey(0))
    est, _, _ = fit_estimator(model, params, batch_sizes=(1, 2),
                              input_lens=(16, 32), n_decode_iters=2, repeats=1)
    return arch, model, params, est


def _make_engines(model, params, n=2):
    from repro.engine.static_engine import StaticEngine
    return [StaticEngine(model, params, eos_id=1, len_bucket=8)
            for _ in range(n)]


def _in_flight(core, rid):
    return any(kind == "batch_done"
               and any(r.rid == rid for r in payload[1].requests)
               for _, _, kind, payload in core._events)


def test_real_backend_cancel_mid_slice_frees_pages_and_trains_predictor(real_env):
    """Satellite acceptance: cancelling mid-slice on the REAL backend leaks
    no pages (every allocator's free-block count returns to baseline) and
    the prediction pipeline records the truncated length."""
    arch, model, params, est = real_env
    scfg = ServingConfig(strategy="scls-pred", predictor="histogram",
                         backend="real", kv_layout="paged", page_tokens=16,
                         slice_len=8, max_gen=24, gamma=0.25,
                         m_available=64e6, mem_bucket=8)
    mem = scfg.memory_estimator(model.kv_bytes_per_token())
    server = scfg.build_real(_make_engines(model, params), est, mem)
    allocators = server.core.backend.allocators
    baseline = [a.free_blocks for a in allocators]
    rng = np.random.default_rng(0)

    def prompt(n):
        return rng.integers(0, arch.vocab_size, size=n).astype(np.int32)

    victim = server.submit(prompt(16), gen_len=20, max_gen=24, arrival=0.0)
    others = [server.submit(prompt(8 + i), gen_len=4 + i, max_gen=24,
                            arrival=0.1 * i) for i in range(4)]
    while not victim.finished and not _in_flight(server.core, victim.rid):
        server.step()
    assert not victim.finished, "victim completed before cancellation"
    # mid-slice: its (L_i + S) envelope is reserved right now
    assert any(a.used_blocks > 0 for a in allocators)
    assert victim.cancel()
    m = server.drain()
    assert victim.cancelled and not victim.done
    assert victim.request.generated < 20
    assert all(h.done for h in others)
    assert m.n_completed == 4
    # no page leaks: every worker's free list is back to baseline
    assert [a.free_blocks for a in allocators] == baseline
    assert all(not a.owners() for a in allocators)
    # online feedback observed all 5 terminal requests incl. the truncation
    assert server.core.predictor.n_observed == 5


def test_real_backend_streaming_token_parity(real_env):
    """Tokens streamed per slice through SliceServer equal direct one-shot
    generation (greedy determinism survives the online path)."""
    arch, model, params, est = real_env
    scfg = ServingConfig(strategy="scls", backend="real", slice_len=8,
                         max_gen=24, gamma=0.25, m_available=64e6,
                         mem_bucket=8)
    mem = scfg.memory_estimator(model.kv_bytes_per_token())
    engines = _make_engines(model, params)
    server = scfg.build_real(engines, est, mem)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, arch.vocab_size, size=n).astype(np.int32)
               for n in (12, 20, 7)]
    gens = (14, 9, 21)
    handles = [server.submit(p, gen_len=g, max_gen=24, arrival=0.2 * i)
               for i, (p, g) in enumerate(zip(prompts, gens))]
    streamed = [list(h.tokens()) for h in handles]
    server.drain()
    for h, p, g, got in zip(handles, prompts, gens, streamed):
        assert h.done and h.request.n_schedules >= 2  # sliced, not one-shot
        want = engines[0].serve_batch([p], slice_len=32,
                                      forced_gen_lens=[g]).results[0]["tokens"]
        assert got == want
        assert h.request.output_tokens == want


def test_real_backend_eos_driven_submission(real_env):
    """gen_len=None decodes until the model's own EOS (or max_gen)."""
    arch, model, params, est = real_env
    scfg = ServingConfig(strategy="scls", backend="real", slice_len=4,
                         max_gen=6, gamma=0.25, m_available=64e6,
                         mem_bucket=8)
    mem = scfg.memory_estimator(model.kv_bytes_per_token())
    server = scfg.build_real(_make_engines(model, params, n=1), est, mem)
    rng = np.random.default_rng(2)
    p = rng.integers(0, arch.vocab_size, size=10).astype(np.int32)
    h = server.submit(p, gen_len=None, max_gen=6)
    req = h.result()
    assert h.done
    assert 1 <= req.generated <= 6
    toks = req.output_tokens
    if 1 in toks:  # model emitted its EOS: stream ends right there
        assert toks.index(1) == len(toks) - 1
    else:          # never EOS'd: capped by max_gen
        assert req.generated == 6


def test_static_engine_per_row_eos_sentinel(real_env):
    """A forced length >= the sentinel makes that row EOS-driven while
    forced rows in the same batch keep exact emulated lengths."""
    arch, model, params, est = real_env
    eng = _make_engines(model, params, n=1)[0]
    rng = np.random.default_rng(3)
    p0 = rng.integers(0, arch.vocab_size, size=9).astype(np.int32)
    p1 = rng.integers(0, arch.vocab_size, size=13).astype(np.int32)
    res = eng.serve_batch([p0, p1], slice_len=6, forced_gen_lens=[3, 1 << 30])
    r0, r1 = res.results
    assert r0["n_valid"] == 3
    toks = r1["tokens"]
    if 1 in toks:
        assert toks.index(1) == len(toks) - 1 and r1["finished"]
    else:
        assert r1["n_valid"] == res.steps
