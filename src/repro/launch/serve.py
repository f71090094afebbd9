"""Serving launcher: run the full SCLS stack through the online
``repro.serving`` API (SliceServer over one SchedulerCore).

  # real JAX engines (default): every token really computed, on the toy
  # preset (--no-reduced serves the published widths; --m-available sizes
  # each worker's KV budget in bytes)
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
      --workers 2 --rate 2 --duration 15 --strategy scls

  # discrete-event sim backend (no model, CI smoke): same scheduler code
  PYTHONPATH=src python -m repro.launch.serve --backend sim --duration 3

  # observability (repro.obs): record a Perfetto-loadable Chrome trace of
  # the run (+ the scheduler decision audit next to it); with --http-port,
  # GET /metrics serves Prometheus text and /debug/decisions the audit
  PYTHONPATH=src python -m repro.launch.serve --backend sim --duration 3 \
      --trace-out trace.json

  # persistent paged KV storage: prefix pages survive across slices, so a
  # resumed slice re-prefills nothing (metrics: reprefill_tokens == 0 for
  # uninterrupted requests; --kv-retain slice restores §3.3 re-prefill)
  PYTHONPATH=src python -m repro.launch.serve --kv-layout paged \
      --kv-retain request --workers 1

  # prediction-aware scheduling (repro.predict): online histogram predictor
  PYTHONPATH=src python -m repro.launch.serve --strategy scls-pred \
      --predictor histogram --coverage 0.7

  # OpenAI-compatible HTTP endpoint with SLO-aware admission: concurrent
  # clients POST /v1/completions (stream=true -> SSE per slice), requests
  # predicted to miss --slo-ms get 429 + Retry-After before any prefill
  PYTHONPATH=src python -m repro.launch.serve --backend sim \
      --http-port 8000 --slo-ms 30000 --duration 0   # 0 = serve forever

The real backend profiles the engine, fits the Eq. 3/4 estimator, then
replays a Poisson trace through ``SliceServer`` — plus one *interactive*
request submitted mid-run, streamed per slice, to exercise the online
path (submit → tokens → result) a real deployment uses.  Worker ``w``
runs on device ``w mod len(jax.devices())``: on a four-chip host four
workers get one chip each, all driven from this one process.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os
import pathlib
import sys

from repro.cluster.trace import WorkloadSpec, generate_trace
from repro.configs import ARCHS, get_config
from repro.serving import ServingConfig, SliceServer, default_sim_environment


#: the repository root (this file is src/repro/launch/serve.py)
_REPO = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set.  Otherwise the cache lives at
    ``<repo>/.jax_cache`` (gitignored).  The path is fixed, never built
    from a temporary name, a process id or the time: a directory that
    moves between runs never hits."""
    import jax  # deferred: the sim path must not require a working model

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_server(cfg: ServingConfig) -> tuple[SliceServer, int]:
    """(server, vocab_size) for the configured backend."""
    if cfg.backend == "sim":
        true_lat, est, mem = default_sim_environment(
            paged=cfg.kv_layout == "paged", page_tokens=cfg.page_tokens)
        return cfg.build_sim(true_lat, est, mem), 0

    import jax  # deferred: the sim path must not require a working model

    from repro.engine.profiler import fit_estimator
    from repro.engine.static_engine import StaticEngine
    from repro.models.registry import get_model

    if cfg.arch not in ARCHS:
        raise SystemExit(f"unknown --arch {cfg.arch!r}; choose from "
                         f"{sorted(ARCHS)}")
    devices = jax.devices()
    print(f"[serve] platform={devices[0].platform} "
          f"device_kind={devices[0].device_kind} devices={len(devices)}")
    arch = get_config(cfg.arch, reduced=cfg.reduced)
    if arch.family not in ("dense", "moe", "ssm", "hybrid"):
        raise SystemExit(f"serve launcher drives token-only archs; "
                         f"{cfg.arch} needs frontend embeddings (use examples/)")
    model = get_model(arch)
    params = model.init(jax.random.PRNGKey(cfg.seed))
    est, prmse, drmse = fit_estimator(model, params, batch_sizes=(1, 2, 4),
                                      input_lens=(16, 32, 64))
    print(f"[serve] estimator fitted: prefill rmse {prmse*1e3:.2f} ms, "
          f"decode rmse {drmse*1e3:.2f} ms")
    mem = cfg.memory_estimator(model.kv_bytes_per_token())
    if cfg.kv_layout == "paged":
        print(f"[serve] paged KV: {mem.total_blocks} blocks of "
              f"{cfg.page_tokens} tokens per worker "
              f"(kv_retain={cfg.kv_retain})")
    if cfg.kv_retain == "request":
        # persistent paged storage: each engine owns the page pool the
        # scheduler budgets, and prefix pages survive across slices
        if model.paged_pages is None:
            raise SystemExit(f"--kv-retain request drives the persistent "
                             f"paged StaticEngine (dense, and MoE with "
                             f"latent attention); {cfg.arch} is "
                             f"{arch.family}")
        engines = [StaticEngine(model, params, eos_id=1, len_bucket=8,
                                kv_layout="paged",
                                page_tokens=cfg.page_tokens,
                                kv_pool_tokens=mem.total_blocks
                                * cfg.page_tokens,
                                prefix_sharing=cfg.prefix_sharing,
                                device=devices[w % len(devices)])
                   for w in range(cfg.workers)]
        if cfg.prefix_sharing:
            print("[serve] COW prefix sharing on: matching prompt "
                  "prefixes join resident pages refcounted "
                  "(--no-prefix-sharing disables)")
    else:
        engines = [StaticEngine(model, params, eos_id=1, len_bucket=8,
                                device=devices[w % len(devices)])
                   for w in range(cfg.workers)]
    return cfg.build_real(engines, est, mem), arch.vocab_size


def serve_http(cfg: ServingConfig, server: SliceServer, vocab: int) -> None:
    """--http-port mode: expose the server over the OpenAI-compatible
    HTTP front end until --duration elapses (<= 0 = forever)."""
    import time

    from repro.serving import HTTPFrontend

    model_name = cfg.arch if cfg.backend == "real" else "scls-sim"
    front = HTTPFrontend(server.aio, host=cfg.http_host,
                         port=cfg.http_port, model_name=model_name,
                         vocab_size=vocab)
    front.start()
    print(f"[serve] http listening on {front.url} "
          f"(model={model_name}, slo_ms={cfg.slo_ms}, "
          f"time_scale={cfg.time_scale})", flush=True)
    try:
        if cfg.duration > 0:
            time.sleep(cfg.duration)
        else:
            while True:
                time.sleep(3600)
    except KeyboardInterrupt:
        pass
    front.shutdown(drain=True)
    m = server.metrics()
    stats = server.admission_stats
    print(f"[serve] http served {m.n_completed} completions "
          f"({stats['n_submitted']} submitted, {stats['n_rejected']} "
          f"rejected, {stats['n_degraded']} degraded); "
          f"SLO attainment {m.slo_attainment:.2f}")
    _export_trace(cfg, server)


def _export_trace(cfg: ServingConfig, server: SliceServer) -> None:
    """--trace-out: write the Chrome trace (+ the decision-audit dump
    alongside it) after the run."""
    if cfg.trace_out is None:
        return
    for path in server.core.obs.export(cfg.trace_out):
        print(f"[serve] wrote {path}")


def main() -> None:
    cfg = ServingConfig.from_cli(
        description=__doc__.splitlines()[0],
        backend="real", workers=2, slice_len=8, max_gen=24, gamma=0.25,
        rate=2.0, duration=15.0, mem_bucket=8)
    print(f"[serve] backend={cfg.backend} strategy={cfg.strategy} "
          f"workers={cfg.workers}"
          + (f" arch={cfg.arch} (reduced={cfg.reduced})"
             if cfg.backend == "real" else ""))
    if cfg.backend == "real":
        print(f"[serve] compile cache: {use_compile_cache()}")
    server, vocab = build_server(cfg)

    if cfg.http_port is not None:
        serve_http(cfg, server, vocab)
        return

    spec = WorkloadSpec("demo", input_mu=3.0, input_sigma=0.7, gen_mu=2.3,
                        gen_sigma=0.7, max_input=64, max_gen=cfg.max_gen)
    trace = generate_trace(cfg.rate, cfg.duration, spec, seed=cfg.seed,
                           vocab_size=vocab or None)
    handles = server.replay(trace)

    # one interactive request through the online path: submit mid-run,
    # stream its tokens per slice, then read the finalized result
    import numpy as np
    rng = np.random.default_rng(cfg.seed + 1)
    prompt = (rng.integers(0, vocab, size=12).astype(np.int32)
              if vocab else None)
    live = server.submit(prompt, input_len=12, gen_len=min(10, cfg.max_gen),
                         max_gen=cfg.max_gen,
                         arrival=min(cfg.duration / 2, 1.0))
    streamed = list(itertools.islice(live.tokens(), 6))
    print(f"[serve] interactive rid={live.rid} streamed "
          f"{len(streamed)} tokens: {streamed}")
    live.result()

    metrics = server.drain(cfg.duration)
    _export_trace(cfg, server)
    print(json.dumps(dataclasses.asdict(metrics), indent=2))
    if server.core.predictor is not None:
        print(f"[serve] predictor={server.core.predictor.name} "
              f"calibration scale={server.core.calibrator.scale:.2f} "
              f"coverage={server.core.calibrator.empirical_coverage():.2f}")
    done = [h for h in handles if h.done]
    print(f"[serve] completed {len(done)}/{len(trace)}; "
          f"TTFT mean {metrics.ttft_mean:.3f}s, "
          f"p99 latency {metrics.p99_response:.3f}s, "
          f"reprefill {metrics.reprefill_tokens} tokens")
    if done:
        print(f"[serve] sample output ({done[0].rid}): "
              f"{done[0].output_tokens[:12]}")
    if not done or not live.done:
        print("[serve] FAILED: no completed requests", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
