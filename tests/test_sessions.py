"""Multi-turn sessions + COW prefix sharing (PR 7), cross-layer:
Session/aio composition, golden-placement equivalence with the sharing
machinery armed, token exactness through the engine and the HTTP chat
endpoint, and allocator hygiene on cancel/close."""
import asyncio
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.serving import ServingConfig, default_sim_environment

POOL_TOKENS = 512
PAGE_TOKENS = 8


@pytest.fixture(scope="module")
def sim_env():
    return default_sim_environment("hf")


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


def _real_server(model, est, params, prefix_sharing=True, workers=1):
    from repro.engine.static_engine import StaticEngine
    cfg = ServingConfig(strategy="scls", backend="real", workers=workers,
                        kv_layout="paged", kv_retain="request",
                        page_tokens=PAGE_TOKENS, slice_len=8, max_gen=8,
                        gamma=0.25, mem_bucket=8,
                        prefix_sharing=prefix_sharing)
    delta = model.kv_bytes_per_token()
    pool_pages = POOL_TOKENS // PAGE_TOKENS
    mem = cfg.memory_estimator(
        delta, m_available=pool_pages * PAGE_TOKENS * delta / cfg.zeta + 1)
    assert mem.total_blocks == pool_pages
    engines = [StaticEngine(model, params, eos_id=1, len_bucket=8,
                            kv_layout="paged", page_tokens=PAGE_TOKENS,
                            kv_pool_tokens=POOL_TOKENS,
                            prefix_sharing=prefix_sharing)
               for _ in range(workers)]
    return cfg.build_real(engines, est, mem)


# ---------------------------------------------------------------------------
# golden-equivalence guard: the sharing machinery must not move a single
# batch on the sim goldens (no shareable prefixes exist there)
# ---------------------------------------------------------------------------
def test_golden_dispatch_bit_exact_with_affinity_hook_armed():
    """PR 3's golden dispatch log is reproduced bit-for-bit with the PR 7
    retention-affinity hook *armed* (``affinity_fn`` set, returning None
    for every batch — the sim backend's truthful answer: nothing resident)
    and full observability on: placement is untouched and no
    ``prefix_share`` audit records appear."""
    import copy
    import os
    from repro.cluster.simulator import ClusterSimulator
    from repro.cluster.trace import CODEFUSE, generate_trace
    from repro.core.estimator import a100_llama13b_profile
    from repro.core.memory import (A100_80GB_AVAILABLE,
                                   AnalyticMemoryEstimator, LLAMA2_13B_DELTA)
    from repro.core.schedulers import make_strategy
    from repro.obs import Observability
    from repro.serving import fitted_estimator
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "golden_batch_compositions.json")) as f:
        g = json.load(f)
    args = g["scenario_args"]
    want = next(r for r in g["runs"]
                if r["strategy"] == "scls" and r["noise_sigma"] == 0.05)
    true_lat = a100_llama13b_profile()
    est = fitted_estimator(true_lat, seed=0)
    mem = AnalyticMemoryEstimator(delta_bytes=LLAMA2_13B_DELTA,
                                  m_available=A100_80GB_AVAILABLE, zeta=0.9)
    trace = generate_trace(args["rate"], args["duration"], CODEFUSE,
                           seed=args["trace_seed"])
    s = make_strategy("scls", slice_len=args["slice_len"],
                      fixed_batch_size=args["fixed_batch_size"],
                      gamma=args["gamma"], max_parallel=args["max_parallel"])
    sim = ClusterSimulator(s, args["workers"], true_lat, est, mem,
                           noise_sigma=want["noise_sigma"],
                           seed=args["sim_seed"])
    sim.core.obs = Observability.standard()
    sim.core.obs.attach(sim.core)
    calls = []

    def affinity(batch):
        calls.append(len(batch.requests))
        return None  # nothing resident on a sim backend, ever

    sim.core.offloader.affinity_fn = affinity
    res = sim.run(copy.deepcopy(trace), args["duration"])
    assert res.metrics.n_completed == want["n_completed"]
    assert sim.batch_log == want["batch_log"]          # bit-exact placement
    assert calls, "the armed hook was never consulted"
    assert sim.core.obs.audit.query(kind="prefix_share") == []
    assert res.metrics.prefix_hit_tokens == 0
    assert res.metrics.shared_blocks == 0


# ---------------------------------------------------------------------------
# Session composition on the sim backend
# ---------------------------------------------------------------------------
def test_session_sim_accumulates_history_and_survives_mid_flight_turn(sim_env):
    true_lat, est, mem = sim_env
    cfg = ServingConfig(strategy="scls", workers=2, max_gen=32)

    async def main():
        server = cfg.build_sim(true_lat, est, mem).aio
        async with server:
            async with server.session(max_gen=8) as s:
                h1 = await s.submit_turn(input_len=10, gen_len=5)
                await h1.result()
                # history folds in lazily, at the *next* submit_turn
                assert s.history_len == 0
                h2 = await s.submit_turn(input_len=4, gen_len=3)
                assert s.history_len == 15             # 10 prompt + 5 out
                # turn 3 while turn 2 is still in flight: submit_turn
                # awaits it internally before composing the prompt
                h3 = await s.submit_turn(input_len=6, gen_len=2)
                r3 = await h3.result()
            assert h2.request.input_len == 10 + 5 + 4
            assert h3.request.input_len == 19 + 3 + 6
            assert r3.session_id == h2.request.session_id == s.session_id
            assert s.n_turns == 3
            with pytest.raises(RuntimeError):
                await s.submit_turn(input_len=1)       # closed
            m = await server.close()
        return m

    m = asyncio.run(main())
    assert m.n_completed == 3
    assert m.prefix_hit_tokens == 0                    # sim: no KV to share


def test_session_sim_cancelled_turn_leaves_history_untouched(sim_env):
    true_lat, est, mem = sim_env
    cfg = ServingConfig(strategy="scls", workers=1, max_gen=64)

    async def main():
        server = cfg.build_sim(true_lat, est, mem).aio
        async with server:
            s = server.session()
            h1 = await s.submit_turn(input_len=8, gen_len=4)
            await h1.result()
            h2 = await s.submit_turn(input_len=100, gen_len=50)
            h2.cancel()
            await h2.result()
            assert h2.cancelled
            h3 = await s.submit_turn(input_len=5, gen_len=2)
            await h3.result()
            await s.close()
            m = await server.close()
        return h3, m

    h3, m = asyncio.run(main())
    # the cancelled turn contributed nothing: turn 3 = turn-1 history + 5
    assert h3.request.input_len == 8 + 4 + 5


# ---------------------------------------------------------------------------
# real backend: cross-layer token exactness + allocator hygiene
# ---------------------------------------------------------------------------
def _turn_prompts(vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, vocab, size=n).astype(np.int32)
            for n in (24, 12, 9)]


def test_real_session_three_turns_token_exact_and_shares(real_env):
    """Satellite acceptance: a 3-turn Session on the retain-mode paged
    backend produces the exact token stream of (a) the same turns with
    sharing disabled and (b) a single-shot submission of the concatenated
    final prompt — while actually serving the history from shared pages
    (prefix_hit_tokens > 0) and draining back to the page baseline."""
    arch, model, params, est = real_env
    turns = _turn_prompts(arch.vocab_size)

    async def run_session(prefix_sharing):
        server = _real_server(model, est, params, prefix_sharing).aio
        alloc = server.core.backend.allocators[0]
        baseline = alloc.free_blocks
        outs, final_prompt = [], None
        async with server:
            s = server.session(max_gen=6)
            for t in turns:
                h = await s.submit_turn(t, gen_len=4)
                await h.result()
                outs.append(list(h.output_tokens))
            final_prompt = np.asarray(h.request.prompt)
            await s.close()
            assert alloc.free_blocks == baseline       # anchor dropped
            assert not alloc.owners()
            m = await server.close()
        return outs, final_prompt, m

    async def run_single(prompt):
        server = _real_server(model, est, params, True).aio
        async with server:
            h = server.submit(prompt, gen_len=4, max_gen=6)
            await h.result()
            out = list(h.output_tokens)
            await server.close()
        return out

    outs_on, prompt_on, m_on = asyncio.run(run_session(True))
    outs_off, prompt_off, m_off = asyncio.run(run_session(False))
    assert outs_on == outs_off                         # sharing is invisible
    np.testing.assert_array_equal(prompt_on, prompt_off)
    assert m_on.prefix_hit_tokens > 0                  # ...but real
    assert m_on.shared_blocks > 0
    assert m_on.reprefill_tokens == 0
    assert m_off.prefix_hit_tokens == 0
    # single-shot of the concatenated conversation == turn 3
    assert asyncio.run(run_single(prompt_on)) == outs_on[2]


def test_real_session_turn_submitted_mid_slice_is_exact(real_env):
    """A turn submitted while the previous one is mid-slice must neither
    corrupt history nor change tokens: submit_turn awaits the in-flight
    turn, and the joined prefix serves the same stream."""
    arch, model, params, est = real_env
    turns = _turn_prompts(arch.vocab_size, seed=1)

    async def main():
        server = _real_server(model, est, params, True).aio
        async with server:
            s = server.session(max_gen=6)
            h1 = await s.submit_turn(turns[0], gen_len=4)
            # do NOT await h1: turn 2 goes in while turn 1 is in flight
            h2 = await s.submit_turn(turns[1], gen_len=4)
            await h2.result()
            assert h1.done
            expected = np.concatenate(
                [turns[0], np.asarray(h1.output_tokens, np.int32), turns[1]])
            np.testing.assert_array_equal(np.asarray(h2.request.prompt),
                                          expected)
            out2 = list(h2.output_tokens)
            await s.close()
            m = await server.close()
        return out2, m

    out2, m = asyncio.run(main())
    assert len(out2) == 4
    assert m.prefix_hit_tokens > 0


def test_real_session_cancel_mid_conversation_restores_baseline(real_env):
    """Cancel (and EOS) mid-conversation: the cancelled turn's envelope,
    the anchored prefix pages, and every shared reference all drain back
    to the allocator's free-block baseline on close."""
    arch, model, params, est = real_env
    turns = _turn_prompts(arch.vocab_size, seed=2)

    async def main():
        server = _real_server(model, est, params, True).aio
        alloc = server.core.backend.allocators[0]
        baseline = alloc.free_blocks
        async with server:
            s = server.session(max_gen=8)
            h1 = await s.submit_turn(turns[0], gen_len=6)
            await h1.result()
            h2 = await s.submit_turn(turns[1], gen_len=8)
            h2.cancel()
            await h2.result()
            assert h2.cancelled
            # the anchor still holds turn 1's pages (session is alive)
            assert alloc.used_blocks > 0
            h3 = await s.submit_turn(turns[2], gen_len=2)
            await h3.result()
            # cancelled turn absent from history
            assert h3.request.input_len == len(turns[0]) + 6 + len(turns[2])
            await s.close()
            assert alloc.free_blocks == baseline
            assert not alloc.owners()
            await server.close()

    asyncio.run(main())


def test_real_affinity_keeps_turns_on_anchor_worker(real_env):
    """Regression for the MaxMin retention-affinity tiebreak: with two
    workers and the Eq. 11 minimum nudged *away* from the anchor worker,
    the armed affinity hook keeps the next turn where its prefix pages
    live (prefix hit, no re-prefill of history) while the plain policy
    moves it and pays the full prefill — with identical tokens either
    way, and the load imbalance the override tolerates bounded by
    epsilon * est_time."""
    arch, model, params, est = real_env
    turns = _turn_prompts(arch.vocab_size, seed=3)

    async def run(affinity):
        server = _real_server(model, est, params, True, workers=2).aio
        async with server:
            off = server.core.offloader
            assert off.affinity_fn is not None         # wired by the core
            if not affinity:
                off.affinity_fn = None
            s = server.session(max_gen=6)
            h1 = await s.submit_turn(turns[0], gen_len=4)
            await h1.result()
            anchor_wid, _ = server.core.backend._session_anchor[s.session_id]
            # nudge: the other worker becomes the Eq. 11 minimum, so a
            # residency-blind placement moves turn 2 off the anchor.  The
            # override tolerates epsilon * est_time, and the fitted
            # est_time of turn 2 is ~0.02 s on a CPU (0.005 s of
            # tolerance), so the nudge stays far below it whatever the fit
            off.loads = {w: (1e-4 if w == anchor_wid else 0.0)
                         for w in off.loads}
            h2 = await s.submit_turn(turns[1], gen_len=4)
            await h2.result()
            outs = (list(h1.output_tokens), list(h2.output_tokens))
            await s.close()
            m = await server.close()
        return outs, m

    outs_on, m_on = asyncio.run(run(True))
    outs_off, m_off = asyncio.run(run(False))
    assert outs_on == outs_off                         # placement-invariant
    assert m_on.prefix_hit_tokens > 0                  # stayed on the anchor
    assert m_off.prefix_hit_tokens == 0                # moved: full prefill


# ---------------------------------------------------------------------------
# HTTP chat endpoint
# ---------------------------------------------------------------------------
def _post(url, path, body):
    req = urllib.request.Request(url + path, json.dumps(body).encode(),
                                 {"Content-Type": "application/json"})
    return json.loads(urllib.request.urlopen(req).read())


def test_http_chat_completions_sim(sim_env):
    from repro.serving import HTTPFrontend
    true_lat, est, mem = sim_env
    server = ServingConfig(strategy="scls", workers=2, max_gen=16,
                           slice_len=8).build_sim(true_lat, est, mem)
    with HTTPFrontend(server.aio, vocab_size=512) as front:
        msgs = [{"role": "user", "content": "hello there"}]
        r = _post(front.url, "/v1/chat/completions",
                  dict(messages=msgs, max_tokens=6, session=7))
        assert r["object"] == "chat.completion"
        assert r["choices"][0]["message"]["role"] == "assistant"
        assert r["choices"][0]["finish_reason"] in ("stop", "length")
        assert r["session"] == 7
        assert r["usage"]["completion_tokens"] > 0
        # streaming: chat.completion.chunk frames, terminated by [DONE]
        req = urllib.request.Request(
            front.url + "/v1/chat/completions",
            json.dumps(dict(messages=msgs, max_tokens=4,
                            stream=True)).encode(),
            {"Content-Type": "application/json"})
        lines = [ln for ln in
                 urllib.request.urlopen(req).read().decode().splitlines()
                 if ln.startswith("data: ")]
        assert lines[-1] == "data: [DONE]"
        first = json.loads(lines[0][len("data: "):])
        assert first["object"] == "chat.completion.chunk"
        assert "content" in first["choices"][0]["delta"]
        # session release is an explicit DELETE
        dreq = urllib.request.Request(front.url + "/v1/sessions/7",
                                      method="DELETE")
        assert json.loads(urllib.request.urlopen(dreq).read())["released"]
        # malformed requests are 400s, not 500s
        for body in (dict(messages=[]),
                     dict(messages=[{"role": "user"}]),
                     dict(messages=msgs, session=0),
                     dict(messages=msgs, max_tokens=0)):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(front.url, "/v1/chat/completions", body)
            assert ei.value.code == 400


def test_http_chat_multi_turn_real_token_exact(real_env):
    """3 chat turns over HTTP with the ``session`` extension == one-shot
    POST of the full message list: identical assistant text, and the
    server-side metrics show the history was served from shared pages."""
    from repro.serving import HTTPFrontend
    arch, model, params, est = real_env
    server = _real_server(model, est, params, True)
    with HTTPFrontend(server.aio, vocab_size=arch.vocab_size) as front:
        msgs = []
        replies = []
        for content in ("alpha bravo charlie", "delta echo", "foxtrot"):
            msgs.append({"role": "user", "content": content})
            r = _post(front.url, "/v1/chat/completions",
                      dict(messages=msgs, max_tokens=4, session=1))
            reply = r["choices"][0]["message"]["content"]
            replies.append(reply)
            msgs.append({"role": "assistant", "content": reply})
        # one-shot replay of the whole conversation, no session
        oneshot = _post(front.url, "/v1/chat/completions",
                        dict(messages=msgs[:-1], max_tokens=4))
        assert oneshot["choices"][0]["message"]["content"] == replies[-1]
        m = json.loads(urllib.request.urlopen(
            front.url + "/metrics.json").read())
        assert m["prefix_hit_tokens"] > 0
        assert m["n_completed"] == 4
        dreq = urllib.request.Request(front.url + "/v1/sessions/1",
                                      method="DELETE")
        urllib.request.urlopen(dreq)
        alloc = server.core.backend.allocators[0]
        assert not alloc.owners()


def test_chat_tokenizer_round_trip_and_template_prefix_stability():
    from repro.serving.tokenizer import (ByteTokenizer, HashTokenizer,
                                         for_vocab, render_chat)
    bt = for_vocab(512)
    assert isinstance(bt, ByteTokenizer) and bt.invertible
    text = "hello été"                       # multi-byte UTF-8
    assert bt.decode(bt.encode(text)) == text
    assert min(bt.encode(text)) >= 2                   # never pad/EOS ids
    # reserved + out-of-range ids carry no text
    assert bt.decode([0, 1, 300] + bt.encode("ok")) == "ok"
    ht = for_vocab(64)
    assert isinstance(ht, HashTokenizer) and not ht.invertible
    assert ht.encode("a b") == ht.encode("a  b")       # stable
    assert for_vocab(0) is None
    with pytest.raises(ValueError):
        ByteTokenizer(100)
    # appending a message extends the rendered prompt character-for-
    # character (the prefix-sharing contract)
    msgs = [{"role": "user", "content": "hi"}]
    r1 = render_chat(msgs)
    msgs += [{"role": "assistant", "content": "yo"},
             {"role": "user", "content": "more"}]
    r2 = render_chat(msgs)
    assert r2.startswith(r1[:-len("<|assistant|>\n")])
    assert r2.startswith(render_chat(msgs[:2], add_generation_prompt=False))
    with pytest.raises(ValueError):
        render_chat([{"role": "", "content": "x"}])
    with pytest.raises(ValueError):
        render_chat([{"role": "user", "content": 3}])
