"""DeepSeek-V2 on the persistent paged engine against its plain reference.

The program (``models/moe.py``, ``models/mla.py`` through
``StaticEngine(kv_layout="paged")``) is compared on seeded random weights
with ``chipbench/reference_deepseek_v2.py``, the same file the chip
benchmark's ``correct`` check loads, at a reduced size that keeps every
mechanism: a dense layer 0, MoE layers with shared experts and a share of
the routed experts, latent attention with a decoupled rotary key, and
YaRN.  Both compute in float32 here, so their logits agree to float32
rounding; tolerances below say what else each one allows for.
"""
import dataclasses
import importlib.util
import math
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.engine.static_engine import StaticEngine
from repro.kvcache.paged import PagedKVCache
from repro.models import attention, mla, moe
from repro.models.common import (stack_layers, yarn_freqs, yarn_rope_mult,
                                 yarn_softmax_factor)
from repro.models.registry import get_model
from repro.obs.trace import Tracer

_REF = pathlib.Path(__file__).resolve().parent.parent / "chipbench" / \
    "reference_deepseek_v2.py"
_spec = importlib.util.spec_from_file_location("reference_deepseek_v2", _REF)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

#: every mechanism of the published model at a CPU size: 1 dense + 2 MoE
#: layers, 8 routed experts of which this chip holds experts 2..5, top-3
#: not renormalised, one shared expert, latent attention, YaRN
CFG = get_config("deepseek-v2-lite-16b", reduced=True).replace(
    n_layers=3, n_experts=8, top_k=3, n_experts_held=4, expert_offset=2,
    n_shared_experts=1)
KEY = jax.random.PRNGKey(7)
#: float32 program against the float32 reference: the sums run in other
#: orders (cache reads, blocked attention, grouped experts), which moves
#: logits of magnitude ~5 by a few 1e-6
ATOL = 1e-4


def model_section(cfg):
    """The ``model`` section of a configuration file for ``cfg``."""
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if isinstance(getattr(cfg, f.name), (bool, int, float, str))}


@pytest.fixture(scope="module")
def setup():
    model = get_model(CFG)
    return model, model.init(KEY)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        2, CFG.vocab_size, n).astype(np.int32)


def _pools(model, n_pages, pg):
    pk, pv = model.paged_pages(pg)
    return (jnp.zeros((CFG.n_layers, n_pages + 1) + pk, CFG.dtype),
            jnp.zeros((CFG.n_layers, n_pages + 1) + pv, CFG.dtype))


@pytest.mark.parametrize("n_prompt", [37, 2051])
def test_paged_logits_match_reference(setup, n_prompt):
    """Prefill into the latent pages, then absorbed decode steps: every
    logit equals the reference's full forward at that position.  2051
    tokens take the chunked prefill (CHUNK_THRESHOLD = 2048)."""
    model, params = setup
    pg, steps = 16, 4
    nb = -(-(n_prompt + steps) // pg)
    kp, vp = _pools(model, nb, pg)
    bt = jnp.arange(1, nb + 1, dtype=jnp.int32)[None]
    W = nb * pg
    cache = PagedKVCache(kp, vp, bt, jnp.full((1, W), -1, jnp.int32),
                         jnp.zeros((1,), jnp.int32))
    toks = _prompt(n_prompt)
    logits, cache, _ = jax.jit(model.prefill_paged)(
        params, jnp.asarray(toks)[None], jnp.asarray([n_prompt]), cache)
    got = [np.asarray(logits[0])]
    seq = list(toks)
    step = jax.jit(model.decode_step_paged)
    for k in range(steps):
        cur = int(np.argmax(got[-1]))
        seq.append(cur)
        q = jnp.asarray([n_prompt + k], jnp.int32)
        logits, cache, _ = step(params, cache, jnp.asarray([cur], jnp.int32),
                                q, q, jnp.zeros((1,), bool))
        got.append(np.asarray(logits[0]))
    # padding after the end cannot reach earlier positions (causal)
    if len(seq) > ref.Q_BLOCK:
        seq += [0] * (-len(seq) % ref.Q_BLOCK)
    want = np.asarray(ref.logits(params, jnp.asarray(seq, jnp.int32),
                                 model_section(CFG)))
    want = want[n_prompt - 1:n_prompt + steps]
    np.testing.assert_allclose(np.stack(got), want, atol=ATOL, rtol=0)


def test_serve_batch_paged_matches_reference(setup):
    """Two slices through ``serve_batch_paged``: the second resumes each
    row from its retained latent pages.  Every served token is the
    reference's best at its position to float32 rounding (the served
    token's logit lies at most 1e-4 below the best, as ``correct`` judges
    the chip run)."""
    model, params = setup
    eng = StaticEngine(model, params, kv_layout="paged", page_tokens=16,
                       kv_pool_tokens=512, len_bucket=16)
    prompts = [_prompt(n, seed=n) for n in (41, 17, 29)]
    rids, S = [1, 2, 3], 5
    first = eng.serve_batch_paged(prompts, S, rids, forced_gen_lens=[9] * 3)
    prev = [r["tokens"] for r in first.results]
    second = eng.serve_batch_paged(prompts, S, rids, forced_gen_lens=[4] * 3,
                                   already_generated=prev)
    assert second.reprefill_tokens == 0  # resumed from pages
    for p, a, b in zip(prompts, first.results, second.results):
        seq = np.concatenate([p, a["tokens"], b["tokens"]]).astype(np.int32)
        sc = ref.score(params, model_section(CFG), seq, len(p), bucket=64)
        assert np.max(sc["best"] - sc["served"]) <= ATOL


def test_absorbed_decode_equals_expanded(setup):
    """The absorbed decode step (``mla_decode_paged``) gives the expanded
    form's attention output over the same pages: k_up folded into the
    query and v_up applied after the weighted sum change only the order
    of float32 sums."""
    model, params = setup
    p = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["attn"])
    B, pg, nb = 2, 16, 3
    kp, vp = _pools(model, B * nb, pg)
    kp, vp = kp[0], vp[0]  # one layer
    bt = jnp.arange(1, B * nb + 1, dtype=jnp.int32).reshape(B, nb)
    lengths = jnp.asarray([30, 11])
    x = jax.random.normal(KEY, (B, 40, CFG.d_model))
    pos = jnp.where(jnp.arange(40)[None] < 40 - lengths[:, None], -1,
                    jnp.arange(40)[None] - (40 - lengths[:, None]))
    _, kp, vp = mla.mla_prefill_paged(p, x, pos, CFG, kp, vp, bt,
                                      attention.prefill_mask(pos, None), None)
    xd = jax.random.normal(jax.random.fold_in(KEY, 1), (B, 1, CFG.d_model))
    W = nb * pg
    slot_pos = jnp.where(jnp.arange(W)[None] <= lengths[:, None],
                         jnp.arange(W)[None], -1)
    out, kp2, vp2 = mla.mla_decode_paged(p, xd, lengths, kp, vp, bt,
                                         slot_pos, lengths, CFG, None)
    ckv_w = mla._read_pages(kp2, bt, None, CFG.kv_lora_rank)
    kr_w = mla._read_pages(vp2, bt, None, CFG.qk_rope_head_dim)
    q = mla._q_proj(p, xd, lengths[:, None], CFG)
    mask = attention.decode_mask(lengths, slot_pos, None)
    want = mla._expand_attend(p, q, ckv_w, kr_w, mask, CFG)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def _layer_params(n_experts=8, n_held=8, seed=3):
    cfg = CFG.replace(n_experts=n_experts, n_experts_held=n_held,
                      expert_offset=0)
    return cfg, moe.init_moe_layer(jax.random.PRNGKey(seed), cfg)


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_expert_shares_sum_to_uncut_layer(norm_topk_prob):
    """Summed over the n_experts / n_experts_held shares, each share's
    layer output with the shared experts taken out once per extra share
    equals the uncut layer (all experts held), in the program and in the
    reference alike."""
    cfg, p = _layer_params()
    cfg = cfg.replace(norm_topk_prob=norm_topk_prob)
    x = jax.random.normal(KEY, (23, cfg.d_model))
    whole, hits = moe.moe_serve(p, x, cfg)
    assert int(hits.sum()) == 23 * cfg.top_k
    m = model_section(cfg)
    whole_ref = ref._moe(x, p, m, "f32")
    np.testing.assert_allclose(np.asarray(whole), np.asarray(whole_ref),
                               atol=1e-5)
    held, shares = 2, cfg.n_experts // 2
    shared = moe.mlp_apply(p["shared"], x, cfg.act)
    total = -(shares - 1) * shared
    total_ref = -(shares - 1) * shared
    for s in range(shares):
        cs = cfg.replace(n_experts_held=held, expert_offset=s * held)
        ps = dict(p, experts=jax.tree_util.tree_map(
            lambda a: a[s * held:(s + 1) * held], p["experts"]))
        total = total + moe.moe_serve(ps, x, cs)[0]
        total_ref = total_ref + ref._moe(x, ps, model_section(cs), "f32")
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=1e-5)
    np.testing.assert_allclose(np.asarray(total_ref), np.asarray(whole_ref),
                               atol=1e-5)


@pytest.mark.parametrize("n_tokens", [40, 5000])
def test_no_token_dropped_when_all_pick_one_expert(n_tokens):
    """A router that sends every token to expert 0: each token still gets
    expert 0's full weighted output (no capacity, nothing dropped), also
    past one block of ``moe.SERVE_BLOCK`` tokens."""
    cfg, p = _layer_params(n_held=4)
    cfg = cfg.replace(n_experts_held=4)
    w = p["router"]["w"]
    # expert 0's logit is 6 for every token; the others' are about N(0, 1)
    p = dict(p, router={"w": w.at[:, 0].set(0.0).at[0, 0].set(6.0)})
    x = jax.random.normal(KEY, (n_tokens, cfg.d_model)).at[:, 0].set(1.0)
    weights, idx = moe.route(p, x, cfg)
    assert bool(jnp.all(idx[:, 0] == 0))
    y, hits = moe.moe_serve(p, x, cfg)
    assert int(hits[0]) == n_tokens
    want = ref._moe(x, p, model_section(cfg), "f32")
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4)
    assert bool(jnp.all(weights.sum(-1) < 0.9999))  # not renormalised


def test_yarn_frequencies_and_softmax_factor():
    """DeepSeek-V2-Lite's YaRN, by hand: over the 32 rotary pairs the
    ramp runs from pair floor(10.47) = 10 to ceil(22.5) = 23, pairs below
    keep theta^(-2i/64), pairs above are divided by 40; cos and sin are
    not scaled (mscale = mscale_all_dim); the softmax scale gains
    (0.1 x 0.707 x ln 40 + 1)^2 = 1.5896."""
    cfg = get_config("deepseek-v2-lite-16b")
    f = np.asarray(yarn_freqs(64, cfg), np.float64)
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(f, base * (1 - ramp) + base / 40 * ramp,
                               rtol=1e-6)
    np.testing.assert_allclose(f[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], base[23:] / 40, rtol=1e-6)
    assert yarn_rope_mult(cfg) == 1.0
    assert yarn_softmax_factor(cfg) == pytest.approx(1.5896, abs=1e-4)
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * 1.589627,
                                                   rel=1e-5)
    m = model_section(cfg)
    np.testing.assert_allclose(ref.yarn_freqs(m, 64), f, rtol=1e-6)
    assert ref.softmax_scale(m) == pytest.approx(mla.softmax_scale(cfg))


def test_mixtral_routing_unchanged():
    """Mixtral keeps its renormalised top-2: the weights of each token
    sum to 1, and the training layer (capacity 4.0 drops nothing here)
    and the serving layer both give the renormalised mixture."""
    cfg = get_config("mixtral-8x22b", reduced=True)
    assert cfg.norm_topk_prob
    p = moe.init_moe_layer(KEY, cfg)
    x = jax.random.normal(KEY, (2, 9, cfg.d_model))
    w, idx = moe.route(p, x.reshape(-1, cfg.d_model), cfg)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    want = ref._moe(x.reshape(-1, cfg.d_model), p, model_section(cfg), "f32")
    train, _ = moe.moe_apply(p, x, cfg)
    serve, _ = moe.moe_serve(p, x.reshape(-1, cfg.d_model), cfg)
    np.testing.assert_allclose(np.asarray(train).reshape(-1, cfg.d_model),
                               np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(serve), np.asarray(want), atol=1e-5)


def _moe_stack(n_layers=4, seed=5):
    """A stack of ``n_layers`` MoE layers in bf16, as served, whose
    router, for an input whose first feature is 1, always picks held
    expert 0 (logit +5) and never held expert 3 (logit -30)."""
    cfg = CFG.replace(dtype=jnp.bfloat16)
    stacked = stack_layers(lambda k: moe.init_moe_layer(k, cfg),
                           jax.random.PRNGKey(seed), n_layers)
    w = stacked["router"]["w"]
    for e, logit in ((0, 5.0), (3, -30.0)):
        e += cfg.expert_offset
        w = w.at[:, :, e].set(0.0).at[:, 0, e].set(logit)
    stacked["router"]["w"] = w
    return cfg, stacked


@pytest.mark.parametrize("n_tokens", [1, 4, 16, moe.SERVE_BLOCK + 9])
@pytest.mark.parametrize("layer", [0, 2, 3])
def test_whole_stack_layer_matches_per_layer(layer, n_tokens):
    """The serving layer on the whole (L·n, ...) expert stack at MoE
    layer ``layer`` (first, middle, last) gives the per-layer layer's
    output on that layer's (n, ...) slice, to bf16 rounding, and the
    same hits: decode-sized inputs with some rows done, and a prefill
    past one ``SERVE_BLOCK``; held expert 3 is never routed to, so its
    group is empty in both."""
    cfg, stacked = _moe_stack()
    x = jax.random.normal(jax.random.fold_in(KEY, n_tokens),
                          (n_tokens, cfg.d_model)).at[:, 0].set(1.0)
    x = x.astype(cfg.dtype)
    valid = jnp.arange(n_tokens) % 3 != 2  # some rows done
    one = jax.tree_util.tree_map(lambda a: a[layer], stacked)
    want, want_hits = moe.moe_serve(one, x, cfg, valid)
    rest, experts = moe.expert_stack({"moe": stacked})
    p = dict(jax.tree_util.tree_map(lambda a: a[layer], rest["moe"]),
             experts=experts)
    assert experts["gate"]["w"].shape[0] == 4 * cfg.experts_held
    got, hits = jax.jit(moe.moe_serve, static_argnums=2)(p, x, cfg, valid,
                                                        layer)
    np.testing.assert_array_equal(np.asarray(hits), np.asarray(want_hits))
    assert int(hits[3]) == 0
    assert int(hits.sum()) > 0
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2 ** -7, atol=2 ** -7)


def _eqns(jaxpr, name):
    """Every ``name`` equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, name)


def test_decode_scan_closes_over_the_expert_stack(setup):
    """The paged decode step's MoE layer scan takes the routed experts
    as loop constants, the whole (L·n, ...) stack, and not as scanned
    (L, n, ...) inputs that each layer would slice and copy; each ragged
    dot runs on that stack, its rows (one row's top_k = 3 pairs here)
    padded to a multiple of 8, which a TPU runs with its kernel."""
    model, params = setup
    pg, nb = 16, 2
    kp, vp = _pools(model, nb, pg)
    cache = PagedKVCache(kp, vp, jnp.arange(1, nb + 1, dtype=jnp.int32)[None],
                         jnp.full((1, nb * pg), -1, jnp.int32),
                         jnp.zeros((1,), jnp.int32))
    q = jnp.zeros((1,), jnp.int32)
    jaxpr = jax.make_jaxpr(model.decode_step_paged)(
        params, cache, q, q, q, jnp.zeros((1,), bool)).jaxpr
    layer_shapes = {a.shape for a in jax.tree_util.tree_leaves(
        params["layers"]["moe"]["experts"])}
    stack_shapes = {(s[0] * s[1],) + s[2:] for s in layer_shapes}
    n_moe = CFG.n_layers - CFG.first_dense_layers
    scans = [e for e in _eqns(jaxpr, "scan") if e.params["length"] == n_moe]
    assert scans
    for eqn in scans:
        nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
        consts = {v.aval.shape for v in eqn.invars[:nc]}
        xs = {v.aval.shape for v in eqn.invars[nc + nk:]}
        assert stack_shapes <= consts
        assert not layer_shapes & xs
    dots = list(_eqns(jaxpr, "ragged_dot_general"))
    assert len(dots) == 3
    for eqn in dots:
        lhs, rhs = (v.aval.shape for v in eqn.invars[:2])
        assert rhs in stack_shapes and lhs[0] == 8


def _reference_routes(params, seq):
    """The held experts the reference routes each position of ``seq`` to,
    per MoE layer: a list over layers of sets per position."""
    m = model_section(CFG)
    h = params["embed"]["table"][jnp.asarray(seq)].astype(jnp.float32)
    for key, is_moe in (("dense_layers", False), ("layers", True)):
        n = jax.tree_util.tree_leaves(params[key])[0].shape[0]
        out = []
        for i in range(n):
            lp = jax.tree_util.tree_map(lambda a: a[i], params[key])
            h2 = h + ref._attention(ref._rms(h, lp["ln_attn"], m["norm_eps"]),
                                    lp["attn"], m, "f32")
            if is_moe:
                x = ref._rms(h2, lp["ln_mlp"], m["norm_eps"])
                _, idx = ref.route(x, lp["moe"]["router"]["w"], m)
                off, n_held = CFG.expert_offset, CFG.experts_held
                out.append([{int(e) for e in row if off <= e < off + n_held}
                            for row in np.asarray(idx)])
            h = ref._layer(h, lp, m, "f32", is_moe)
    return out


def test_expert_counts_match_reference_router(setup):
    """The decode program's ``expert_reads`` (per step and MoE layer, the
    held experts a live row was routed to) and ``expert_rows`` ((live
    row, held expert) pairs), and the prefill's ``expert_tokens``, equal
    counts made from the reference router over the served sequences."""
    model, params = setup
    eng = StaticEngine(model, params, kv_layout="paged", page_tokens=16,
                       kv_pool_tokens=512, len_bucket=16)
    tr = Tracer()
    tr.set_clock(time.perf_counter, host=True)
    eng.tracer = tr
    prompts = [_prompt(n, seed=n) for n in (23, 14, 31)]
    gen = [6, 3, 5]
    res = eng.serve_batch_paged(prompts, 8, [1, 2, 3], forced_gen_lens=gen)
    routes = [_reference_routes(params, np.concatenate(
        [p, r["tokens"]]).astype(np.int32)) for p, r in zip(prompts, res.results)]
    reads = rows = 0
    # decode step k feeds token k at position len(prompt) + k; a row is
    # live while it still owes tokens after this one (k + 1 < gen) and
    # has not produced the end-of-sequence token (1)
    toks = [r["tokens"] for r in res.results]
    for k in range(res.steps):
        live = [b for b in range(3) if k + 1 < gen[b] and 1 not in toks[b][:k + 1]]
        for layer in range(CFG.n_layers - CFG.first_dense_layers):
            hit = [routes[b][layer][len(prompts[b]) + k] for b in live]
            reads += len(set().union(*hit)) if hit else 0
            rows += sum(len(s) for s in hit)
    tokens = sum(len(s) for b, p in enumerate(prompts)
                 for layer in routes[b] for s in layer[:len(p)])
    ev = {e["name"]: e["args"] for e in tr.to_dict()["traceEvents"]
          if e.get("cat") == "span"}
    assert res.expert_reads == reads > 0
    assert ev["engine.decode"]["expert_reads"] == reads
    assert ev["engine.decode"]["expert_rows"] == rows
    assert ev["engine.prefill"]["expert_tokens"] == tokens


def _decode_flops(model, params, W, B=2, pg=16):
    """XLA's FLOP count of one layer's decode step over a window of W."""
    p = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["attn"])
    nb = W // pg
    kp, vp = (a[0] for a in _pools(model, B * nb, pg))
    bt = jnp.arange(1, B * nb + 1, dtype=jnp.int32).reshape(B, nb)
    q = jnp.zeros((B,), jnp.int32)
    x = jnp.zeros((B, 1, CFG.d_model))
    c = jax.jit(lambda *a: mla.mla_decode_paged(*a, CFG, None)).lower(
        p, x, q, kp, vp, bt, jnp.zeros((B, W), jnp.int32), q).compile()
    ca = c.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    return ca["flops"]


def test_decode_cost_is_the_absorbed_form(setup):
    """The decode step's XLA cost on the CPU grows by about 4·H·(r+dr)
    FLOPs per layer per added cached token per row (scores against the
    latent and the rotary key, and the latent weighted sum), plus the
    mask and softmax's few per head: the absorbed form.  Re-expanding
    each cached latent (the expanded form) would add 4·r·H·(dn+dv)."""
    model, params = setup
    B, dW = 2, 64
    grow = (_decode_flops(model, params, 64 + dW, B)
            - _decode_flops(model, params, 64, B)) / (B * dW)
    H, r, dr = CFG.n_heads, CFG.kv_lora_rank, CFG.qk_rope_head_dim
    exact = 2 * H * (r + dr) + 2 * H * r  # the three einsums, as counted
    expanded = 4 * r * H * (CFG.qk_nope_head_dim + CFG.v_head_dim)
    assert exact <= grow <= 1.25 * 4 * H * (r + dr) < 0.1 * expanded


def test_yarn_changes_the_rotary_part():
    """The MLA rotary part follows the config's YaRN factor: factor 1 is
    plain RoPE, factor 40 slows the pairs past the ramp."""
    cfg = CFG
    x = jnp.ones((1, 3, 1, cfg.qk_rope_head_dim))
    pos = jnp.arange(3)[None]
    plain = mla._rope(x, pos, cfg.replace(rope_scaling_factor=1.0))
    yarn = mla._rope(x, pos, cfg)
    assert not np.allclose(np.asarray(plain), np.asarray(yarn))
    assert math.isclose(yarn_softmax_factor(cfg.replace(
        rope_scaling_factor=1.0)), 1.0)


def test_retain_request_serves_deepseek_end_to_end(setup):
    """The normal served path, SliceServer -> SchedulerCore -> RealBackend
    -> StaticEngine(kv_layout="paged"), with kv_retain="request": three
    requests over three or more slices each resume from their latent
    pages (no re-prefill), get exactly their lengths, serve the
    reference's best token at every position (to float32 rounding, as
    above), and leave the pool as they found it."""
    from repro.core.estimator import LatencyCoeffs, ServingTimeEstimator
    from repro.serving import ServingConfig
    model, params = setup
    scfg = ServingConfig(strategy="scls", backend="real", kv_layout="paged",
                         page_tokens=16, kv_retain="request", slice_len=6,
                         max_gen=32, gamma=0.25, m_available=64e6,
                         mem_bucket=8, workers=1)
    mem = scfg.memory_estimator(model.kv_bytes_per_token())
    eng = StaticEngine(model, params, eos_id=1, len_bucket=16,
                       kv_layout="paged", page_tokens=16,
                       kv_pool_tokens=mem.total_blocks * 16)
    est = ServingTimeEstimator(LatencyCoeffs(1e-6, 1e-3, 1e-5, 1e-3),
                               LatencyCoeffs(1e-7, 1e-4, 1e-6, 1e-3))
    server = scfg.build_real([eng], est, mem)
    free = eng.allocator.free_blocks
    prompts = [_prompt(n, seed=n) for n in (21, 35, 12)]
    gens = (19, 13, 22)
    hs = [server.submit(p, gen_len=g, max_gen=32, arrival=0.1 * i)
          for i, (p, g) in enumerate(zip(prompts, gens))]
    m = server.drain()
    assert all(h.done for h in hs)
    assert min(h.request.n_schedules for h in hs) >= 3
    assert m.reprefill_tokens == 0
    assert eng.allocator.free_blocks == free
    for p, g, h in zip(prompts, gens, hs):
        out = np.asarray(h.request.output_tokens, np.int32)
        assert len(out) == g
        sc = ref.score(params, model_section(CFG),
                       np.concatenate([p, out]), len(p), bucket=64)
        assert np.max(sc["best"] - sc["served"]) <= ATOL


@pytest.mark.parametrize("arch", ["mamba2-130m", "recurrentgemma-9b",
                                  "mixtral-8x22b"])
def test_paged_engine_refuses_families_without_entry_points(arch):
    """A model with no paged entry points (state-space, recurrent, and
    sparse experts without latent attention) is refused by the paged
    engine with an error that says which families it serves."""
    model = get_model(get_config(arch, reduced=True))
    assert model.paged_pages is None
    with pytest.raises(ValueError, match="no paged entry points"):
        StaticEngine(model, None, kv_layout="paged", page_tokens=16,
                     kv_pool_tokens=64)
