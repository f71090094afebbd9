"""Bring-up smoke test: the serving path on a TPU at llama3.2-1b's published
widths (16 layers, d_model 2048, 32/8 heads of 64, d_ff 8192, vocab 128256,
bf16), with random weights and traffic made from ``--seed``.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four workers, one per chip

One chip runs these phases in order, in this one process:

  device    require a TPU; print its kind, the device count, JAX's version
  serve     build the server the launcher builds (SliceServer -> SchedulerCore
            -> RealBackend -> persistent paged StaticEngine, Eq. 3/4
            estimator fitted here), then check the served model's bf16
            logits against a float32 reference of the same weights and
            serve 8 requests: every request gets exactly its gen_len
            tokens, nothing is re-prefilled, a shared 256-token prefix is
            served from resident pages, and the pool's free pages come back
  kernels   each of the four paged Pallas kernels, compiled, against its
            jnp oracle in ``kernels/ref.py``

``--chips 4`` runs only the four-chip phase: four workers, each engine on
its own chip, against the same requests served by one worker.

Any failed check raises, so the script exits non-zero.  Every line before
the last is a bring-up observation, not a benchmark number.  The last line
is the JSON result with the device JAX reports.  The persistent
compilation cache is kept where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import itertools
import json
import pathlib
import sys
import time
from typing import Callable, Dict, List, Tuple

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import ops  # noqa: E402
from repro.kvcache.paged import PagedKVCache  # noqa: E402
from repro.launch.serve import build_server, use_compile_cache  # noqa: E402
from repro.models import transformer as tfm  # noqa: E402
from repro.serving import ServingConfig, SliceServer  # noqa: E402

GiB = 2 ** 30
SLICE = 16              # tokens per slice: every request spans >= 3 slices
PREFIX = 256            # tokens two requests share
ROPE_THETA = 500000.0   # llama3.2-1b's


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def note(*parts: object) -> None:
    print("[chip_smoke]", *parts, flush=True)


class CompileClock:
    """Seconds JAX spent in backend compilation (a persistent-cache hit is
    counted at its load time) and the number of cache hits, read from
    ``jax.monitoring``; ``phase`` splits a phase's wall time into compile
    and run seconds."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_: object) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_: object) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def phase(self, name: str, fn: Callable, *args, **kwargs):
        c0, h0, t0 = self.seconds, self.cache_hits, time.perf_counter()
        out = fn(*args, **kwargs)
        wall = time.perf_counter() - t0
        comp = self.seconds - c0
        note(f"phase {name}: wall {wall:.2f}s = compile {comp:.2f}s + "
             f"run {wall - comp:.2f}s; persistent-cache hits "
             f"{self.cache_hits - h0}")
        return out


# ---------------------------------------------------------------------------
# configuration and traffic
# ---------------------------------------------------------------------------
def serving_config(seed: int, workers: int, reduced: bool = False,
                   m_available: float = 4 * GiB) -> ServingConfig:
    """The launcher's real backend: SCLS over persistent paged KV with
    prefix sharing; ``m_available`` bytes of KV budget per worker."""
    return ServingConfig(backend="real", arch="llama3.2-1b", reduced=reduced,
                         strategy="scls", kv_layout="paged",
                         kv_retain="request", prefix_sharing=True,
                         workers=workers, slice_len=SLICE, max_gen=64,
                         gamma=0.25, m_available=m_available, seed=seed)


def make_requests(seed: int, vocab: int) -> List[Tuple[np.ndarray, int]]:
    """8 seeded (prompt, gen_len) pairs: prompts of 32-512 tokens, gen_len
    48-64; requests 0 and 1 share a 256-token prefix."""
    rng = np.random.default_rng(seed + 1)

    def toks(n: int) -> np.ndarray:
        return rng.integers(2, vocab, size=n).astype(np.int32)

    prefix = toks(PREFIX)
    prompts = [np.concatenate([prefix, toks(40)]),
               np.concatenate([prefix, toks(100)])]
    prompts += [toks(n) for n in (512, 384, 200, 140, 77, 32)]
    gens = rng.integers(48, 65, size=len(prompts))
    return [(p, int(g)) for p, g in zip(prompts, gens)]


def submit(server: SliceServer, req: Tuple[np.ndarray, int],
           arrival: float = None):
    prompt, gen = req
    return server.submit(prompt, input_len=len(prompt), gen_len=gen,
                         max_gen=64, arrival=arrival)


def check_completed(handles, requests) -> None:
    for h, (_, gen) in zip(handles, requests):
        check(h.done, f"request {h.rid} did not complete")
        check(len(h.request.output_tokens) == gen,
              f"request {h.rid}: {len(h.request.output_tokens)} tokens, "
              f"gen_len {gen}")


def pool_bytes(eng) -> int:
    return int(eng._k_pages.nbytes + eng._v_pages.nbytes)


def param_bytes(params) -> int:
    return int(sum(x.nbytes for x in jax.tree_util.tree_leaves(params)))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def numerics_phase(eng, seed: int, prompt_len: int = 128,
                   n_decode: int = 3) -> Dict[str, float]:
    """The served model's logits (bf16 params, paged prefill, then decode
    steps through the page cache) against the same weights cast to
    float32 in a plain full forward at ``highest`` matmul precision, at
    every position from the prompt's last token on."""
    model, params = eng.model, eng.params
    cfg = model.cfg
    rng = np.random.default_rng(seed + 2)
    T = prompt_len + n_decode
    toks = rng.integers(2, cfg.vocab_size, size=(1, T)).astype(np.int32)
    pg = eng.page_tokens
    nb = -(-T // pg)
    pool = (cfg.n_layers, nb + 1, pg) + eng._k_pages.shape[3:]
    cache = PagedKVCache(jnp.zeros(pool, cfg.dtype, device=eng.device),
                         jnp.zeros(pool, cfg.dtype, device=eng.device),
                         eng._put(np.arange(1, nb + 1, dtype=np.int32)[None]),
                         eng._put(np.full((1, nb * pg), -1, np.int32)),
                         eng._put(np.zeros((1,), np.int32)))
    prefill = jax.jit(lambda p, t, n, c: tfm.prefill_paged(p, cfg, t, n, c))
    decode = jax.jit(lambda p, c, t, q: tfm.decode_step_paged(p, cfg, c, t,
                                                              q, q))
    logits, cache = prefill(params, eng._put(toks[:, :prompt_len]),
                            eng._put(np.array([prompt_len], np.int32)), cache)
    served = [logits]
    for i in range(n_decode):
        pos = prompt_len + i
        logits, cache = decode(params, cache, eng._put(toks[:, pos]),
                               eng._put(np.array([pos], np.int32)))
        served.append(logits)
    served = np.asarray(jnp.concatenate(served).astype(jnp.float32))

    params32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                      params)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: tfm.forward(p, cfg, t))(
            params32, eng._put(toks))
        ref = np.asarray(ref[0, prompt_len - 1:])
    del params32
    err = np.abs(served - ref)
    scale = float(ref.std())
    out = dict(positions=n_decode + 1, ref_std=scale,
               max_abs_err=float(err.max()), mean_abs_err=float(err.mean()),
               max_err_over_std=float(err.max()) / scale,
               mean_err_over_std=float(err.mean()) / scale)
    note("numerics (bf16 served vs float32 reference):", json.dumps(out))
    # Tolerances, relative to the spread of the reference logits.  bf16
    # keeps 8 significant bits (relative rounding <= 2^-9 per value), and
    # its errors compound over 16 layers of matmuls, norms and residual
    # adds, so the mean error is expected near 1% of the spread and the
    # worst of ~128k logits a few times that.  A computation carried in a
    # lower precision than bf16 (8-bit floats keep 3-4 bits) is off by
    # well over 5% on average and fails the mean bound; a wrong mask,
    # position or cache slot moves logits by a large part of the spread
    # and fails the max bound.
    check(out["mean_err_over_std"] <= 0.05,
          f"mean |logit error| {out['mean_err_over_std']:.4f} of the "
          f"reference spread exceeds 0.05")
    check(out["max_err_over_std"] <= 0.35,
          f"max |logit error| {out['max_err_over_std']:.4f} of the "
          f"reference spread exceeds 0.35")
    return out


def serve_phase(server: SliceServer, requests) -> dict:
    """Serve ``requests`` through ``SliceServer.submit``: request 0 is
    streamed through ``tokens()``, and request 1, which shares its
    256-token prefix, is submitted only after request 0's first slice,
    so that it finds those pages resident."""
    eng = server.core.backend.engines[0]
    free0 = eng.allocator.free_blocks
    handles = {i: submit(server, r) for i, r in enumerate(requests) if i != 1}
    stream = handles[0].tokens()
    streamed = list(itertools.islice(stream, SLICE))
    handles[1] = submit(server, requests[1])
    streamed += list(stream)
    metrics = server.drain()
    order = [handles[i] for i in range(len(requests))]
    check_completed(order, requests)
    check(streamed == list(order[0].request.output_tokens),
          "streamed tokens differ from the request's final output")
    check(metrics.n_completed == len(requests),
          f"{metrics.n_completed}/{len(requests)} requests completed")
    check(metrics.reprefill_tokens == 0,
          f"reprefill_tokens {metrics.reprefill_tokens} != 0")
    check(metrics.prefix_hit_tokens > 0, "no prompt token was served from "
          "a shared prefix page")
    check(eng.allocator.free_blocks == free0,
          f"free pages {eng.allocator.free_blocks} after drain, {free0} "
          f"before serving")
    note("served RunMetrics:", json.dumps(dataclasses.asdict(metrics)))
    note(f"pool: {eng.allocator.n_pages} pages of {eng.page_tokens} tokens "
         f"({pool_bytes(eng) / GiB:.3f} GiB K+V), free {free0} before and "
         f"{eng.allocator.free_blocks} after; streamed {len(streamed)} "
         f"tokens of request {order[0].rid}")
    return dataclasses.asdict(metrics)


def kernel_phase(seed: int, B: int = 8, Hq: int = 32, Hkv: int = 8,
                 D: int = 64, pg: int = 16, nb: int = 32, T: int = 512,
                 dtype=jnp.bfloat16) -> Dict[str, float]:
    """The four paged Pallas kernels (compiled on the TPU; the CPU runs
    them in interpret mode) against their jnp oracles at the served
    widths, the prefill writes at the longest prompt served."""
    key = jax.random.PRNGKey(seed + 3)
    rng = np.random.default_rng(seed + 3)
    ks = iter(jax.random.split(key, 16))

    def normal(shape):
        return jax.random.normal(next(ks), shape, dtype)

    def disjoint_tables(n_blocks):
        pages = rng.permutation(np.arange(1, B * n_blocks + 1))
        return pages.reshape(B, n_blocks).astype(np.int32)

    errs: Dict[str, float] = {}
    # bf16 attention outputs are convex mixes of unit-scale V: the kernel
    # accumulates in float32, the oracle rounds the softmax weights to
    # bf16 first, so they differ by a few bf16 steps at magnitude 1
    # (2^-8 each); 2e-2 is the repo's bf16 kernel tolerance
    att_tol = 2e-2

    # --- decode: paged attention, and the fused RoPE + append + attention
    P = B * nb + 1
    kp, vp = normal((P, pg, Hkv, D)), normal((P, pg, Hkv, D))
    bt = disjoint_tables(nb)
    fill = rng.integers(1, nb * pg, size=B)  # tokens resident per row
    slot_pos = np.where(np.arange(nb * pg)[None] <= fill[:, None],
                        np.arange(nb * pg)[None], -1).astype(np.int32)
    q = normal((B, Hq, D))
    q_pos = jnp.asarray(fill, jnp.int32)  # the new token's slot == position
    args = (q, kp, vp, jnp.asarray(bt), jnp.asarray(slot_pos), q_pos)
    got = ops.paged_decode_attention(*args, impl="pallas")
    want = ops.paged_decode_attention(*args, impl="xla")
    errs["paged_decode_attention"] = _max_err(got, want)
    check(errs["paged_decode_attention"] <= att_tol,
          f"paged_decode_attention off by {errs['paged_decode_attention']}")

    kn, vn = normal((B, Hkv, D)), normal((B, Hkv, D))
    args = (q, kn, vn, jnp.asarray(bt), jnp.asarray(slot_pos), q_pos, q_pos,
            kp, vp)
    o_p, k_p, v_p = ops.fused_rope_decode_append(*args, theta=ROPE_THETA,
                                                 impl="pallas")
    o_x, k_x, v_x = ops.fused_rope_decode_append(*args, theta=ROPE_THETA,
                                                 impl="xla")
    page, off = bt[np.arange(B), fill // pg], fill % pg
    errs["fused_rope_decode_append"] = _max_err(o_p, o_x)
    check(errs["fused_rope_decode_append"] <= att_tol,
          f"fused_rope_decode_append off by "
          f"{errs['fused_rope_decode_append']}")
    _check_rotated(k_p[page, off], k_x[page, off], "fused decode append K")
    check(np.array_equal(np.asarray(v_p[page, off]),
                         np.asarray(v_x[page, off])),
          "fused decode append V differs")

    # --- prefill writes at T tokens: rows of T, and shorter left-padded
    nbw = T // pg + 1
    Pw = B * nbw + 1
    lens = rng.integers(1, T + 1, size=B)
    lens[0] = T
    positions = np.where(np.arange(T)[None] < T - lens[:, None], -1,
                         np.arange(T)[None] - (T - lens[:, None]))
    k_new, v_new = normal((B, T, Hkv, D)), normal((B, T, Hkv, D))
    kpool, vpool = normal((Pw, pg, Hkv, D)), normal((Pw, pg, Hkv, D))
    btw = disjoint_tables(nbw)
    args = (k_new, v_new, jnp.asarray(positions, jnp.int32),
            jnp.asarray(btw), kpool, vpool)
    outs = {impl: ops.paged_prefill_write(*args, impl=impl)
            for impl in ("pallas", "xla")}
    for b in range(B):  # written tokens land at slot == position, exactly
        for i, name in ((0, "K"), (1, "V")):
            g, w = (_row(outs[impl][i], btw[b])[:lens[b]]
                    for impl in ("pallas", "xla"))
            check(np.array_equal(g, w), f"paged_prefill_write {name} row {b}")
    errs["paged_prefill_write"] = 0.0

    outs = {impl: ops.fused_rope_prefill_write(*args, theta=ROPE_THETA,
                                               impl=impl)
            for impl in ("pallas", "xla")}
    worst = 0.0
    for b in range(B):
        g, w = (_row(outs[impl][0], btw[b])[:lens[b]]
                for impl in ("pallas", "xla"))
        worst = max(worst, _check_rotated(g, w, f"fused prefill K row {b}"))
        g, w = (_row(outs[impl][1], btw[b])[:lens[b]]
                for impl in ("pallas", "xla"))
        check(np.array_equal(g, w), f"fused_rope_prefill_write V row {b}")
    errs["fused_rope_prefill_write"] = worst
    note("kernels vs oracles (max abs error):", json.dumps(errs))
    return errs


def _max_err(a, b) -> float:
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def _row(pages, table) -> np.ndarray:
    """A row's logical window gathered from a (P,pg,Hkv,D) pool."""
    x = np.asarray(pages.astype(jnp.float32))[table]
    return x.reshape((-1,) + x.shape[2:])


def _check_rotated(got, want, what: str) -> float:
    """RoPE-rotated K: both sides rotate in float32 by the same cos/sin
    tables (``kernels.ref.rope_cos_sin``) and round to bf16, so only the
    order of the float32 multiply-adds differs, which may flip a rounding:
    allow two bf16 steps (2^-7 relative each)."""
    got = np.asarray(jnp.asarray(got).astype(jnp.float32))
    want = np.asarray(jnp.asarray(want).astype(jnp.float32))
    err = np.abs(got - want)
    bad = err > 2 ** -6 * np.abs(want) + 1e-3
    if bad.any():
        at = tuple(int(i) for i in np.argwhere(bad)[0])
        raise SmokeFailure(f"{what}: {int(bad.sum())} values off, first at "
                           f"{at}: got {got[at]}, want {want[at]}; max "
                           f"error {err.max()}")
    return float(err.max())


def four_chip_phase(seed: int, n_chips: int = 4, reduced: bool = False,
                    m_available: float = 4 * GiB) -> dict:
    """``workers=4``, each engine on its own chip, against the same requests
    served by one worker.  Arrivals are one schedule interval apart, so
    each arrives while earlier workers are busy."""
    devices = jax.devices()
    check(len(devices) >= n_chips, f"{len(devices)} devices, need {n_chips}")
    cfg = serving_config(seed, n_chips, reduced, m_available)
    server, vocab = build_server(cfg)
    engines = server.core.backend.engines
    check([e.device for e in engines] == devices[:n_chips],
          "engines are not one per device")
    for e in engines:
        for x in (e._k_pages, *jax.tree_util.tree_leaves(e.params)):
            check(x.devices() == {e.device},
                  f"engine array on {x.devices()}, not {e.device}")
    requests = make_requests(seed, vocab)
    handles = [submit(server, r, arrival=i * cfg.gamma)
               for i, r in enumerate(requests)]
    m4 = server.drain()
    check_completed(handles, requests)
    used = sorted({int(e[1]) for e in server.core.batch_log})
    check(used == list(range(n_chips)), f"batches went to workers {used}")
    note(f"{n_chips} workers: {m4.n_completed} completed, dispatches per "
         f"worker", [sum(1 for e in server.core.batch_log if e[1] == w)
                     for w in range(n_chips)])
    need = param_bytes(engines[0].params) + pool_bytes(engines[0])
    for d in devices[:n_chips]:
        stats = d.memory_stats() or {}
        in_use = stats.get("bytes_in_use")
        note(f"{d}: bytes_in_use {in_use} peak "
             f"{stats.get('peak_bytes_in_use')}; params + pool {need}")
        if in_use is not None:  # the CPU reports no memory stats
            check(in_use >= need, f"{d} holds {in_use} bytes < params + "
                  f"pool {need}")

    # one fixed batch on each engine: the same tokens on every chip
    fixed = [p for p, _ in requests[2:6]]
    outs = []
    for w, e in enumerate(engines):
        rids = [10 ** 9 + 10 * w + i for i in range(len(fixed))]
        res = e.serve_batch_paged(fixed, SLICE, rids,
                                  forced_gen_lens=[SLICE] * len(fixed))
        outs.append([r["tokens"] for r in res.results])
        for rid in rids:
            e.release_request(rid)
    check(all(o == outs[0] for o in outs),
          "the fixed batch gave different tokens on different chips")
    del server, engines, handles, e
    gc.collect()

    server, _ = build_server(serving_config(seed, 1, reduced, m_available))
    handles = [submit(server, r, arrival=i * cfg.gamma)
               for i, r in enumerate(requests)]
    m1 = server.drain()
    check_completed(handles, requests)
    note(f"1 worker: {m1.n_completed} completed; makespan {m1.makespan:.3f}"
         f" vs {m4.makespan:.3f} s virtual with {n_chips} workers")
    return dict(workers4=dataclasses.asdict(m4),
                workers1=dataclasses.asdict(m1))


# ---------------------------------------------------------------------------
def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] FAILED: JAX found no TPU (platform "
              f"{dev.platform!r})", file=sys.stderr)
        raise SystemExit(1)
    note(f"device {dev.device_kind}, {len(devices)} device(s), jax "
         f"{jax.__version__}")
    note(f"compile cache: {use_compile_cache()}")
    clock = CompileClock()

    if args.chips == 4:
        clock.phase("four-chip", four_chip_phase, args.seed)
    else:
        cfg = serving_config(args.seed, workers=1)
        server, vocab = clock.phase("build_server", build_server, cfg)
        eng = server.core.backend.engines[0]
        c = eng.model.cfg
        note(f"model {c.name}: {c.n_layers} layers, d_model {c.d_model}, "
             f"heads {c.n_heads}/{c.n_kv_heads} x {c.head_dim}, d_ff "
             f"{c.d_ff}, vocab {c.vocab_size}, {jnp.dtype(c.dtype).name}; "
             f"params {param_bytes(eng.params) / GiB:.3f} GiB")
        clock.phase("numerics", numerics_phase, eng, args.seed)
        clock.phase("serve", serve_phase, server, make_requests(args.seed,
                                                                vocab))
        clock.phase("kernels", kernel_phase, args.seed)
    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        note(f"{d}: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    note(f"compile seconds {clock.seconds:.2f}, persistent-cache hits "
         f"{clock.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
