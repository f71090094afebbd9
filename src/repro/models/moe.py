"""Mixture-of-Experts layers + MoE decoder models.

Covers both assigned MoE archs:
  * mixtral-8x22b  — GQA attention (SWA 4096) + 8 routed experts, top-2
  * deepseek-v2-lite — MLA attention + (2 shared + 64 routed, top-6) experts,
    first layer dense (arXiv:2405.04434)

Two routed-expert layers share one router (a float32 softmax over all
``n_experts``, greedy top-k, renormalised only with ``norm_topk_prob``):

  * training (``moe_apply``) uses the MaxText/Mesh-TF style *dropping*
    dispatch: tokens are reshaped into groups, and within each group a
    capacity-bounded one-hot dispatch/combine einsum moves tokens to
    experts.  Fully static-shaped, with a load-balance aux loss;
  * serving (``moe_serve``) drops no token: the (token, expert) pairs are
    grouped by expert and run through ``jax.lax.ragged_dot``.  The served
    layer scans read the routed experts from the whole (L·n_held, ...)
    stack by MoE layer index (``expert_stack``), the other layers' groups
    empty, so a step streams only the experts its tokens hit and copies
    no layer's experts out of the stack.

Either layer holds only experts ``[expert_offset, expert_offset +
n_experts_held)`` (expert parallelism, one chip's share): it routes over
all of them and computes its own experts' part; tokens routed to absent
experts get nothing here.  Shared experts are added once.

The paged entry points (``prefill_paged``, ``prefill_tail_paged``,
``decode_step_paged``) serve the MLA variant from the persistent paged
engine's latent pools; their third output counts expert work for the
engine's spans (``expert_tokens``; ``expert_reads``, ``expert_rows``).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mla
from repro.models.attention import KVCache
from repro.models.common import (ModelConfig, Params, activate, dense_param,
                                 embed_apply, init_embed, init_mlp, init_rms,
                                 mlp_apply, rms_norm, scan_layers,
                                 stack_layers)
from repro.models.transformer import _logits, make_positions

MOE_GROUP = 1024  # dispatch group size (tokens); decode uses one group
SERVE_BLOCK = 4096  # tokens per block of the serving layer


# ---------------------------------------------------------------------------
# MoE layer
# ---------------------------------------------------------------------------
def init_moe_layer(key, cfg: ModelConfig) -> Params:
    kr, ke, ks = jax.random.split(key, 3)
    E, dff = cfg.n_experts, cfg.d_ff_expert

    def one_expert(k):
        return init_mlp(k, cfg.d_model, dff, cfg.dtype)

    p = {
        # the router scores every expert, held here or not
        "router": dense_param(kr, cfg.d_model, E, jnp.float32),
        "experts": stack_layers(one_expert, ke, cfg.experts_held),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks, cfg.d_model, dff * cfg.n_shared_experts, cfg.dtype)
    return p


def router_probs(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """float32 softmax over all experts of x (..., d); the logits at full
    float32 precision (a TPU's default rounds float32 matmul inputs to
    bfloat16), since near-ties between experts flip on rounding."""
    logits = jnp.dot(x.astype(jnp.float32),
                     p["router"]["w"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return jax.nn.softmax(logits, axis=-1)


def route(p: Params, x: jnp.ndarray, cfg: ModelConfig
          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (N,d) -> (weights (N,K) float32, experts (N,K) int32): greedy
    top-k of the router's softmax, renormalised only if
    ``norm_topk_prob``."""
    w, idx = jax.lax.top_k(router_probs(p, x), cfg.top_k)
    if cfg.norm_topk_prob:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w, idx


def expert_stack(layers: Params) -> Tuple[Params, Params]:
    """A MoE layer stack split for the serving layer: (the stack without
    its routed experts, the experts with the leading (L, n) dims merged
    into one (L·n, ...) stack).  Merging leading dims is a bitcast; a
    layer scan that closes over the stack as a loop constant hands the
    ragged dot the parameter itself, where a scanned (n, d, f) slice
    would be copied out of the stack for it at every layer and step."""
    moe_p = dict(layers["moe"])
    stack = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]),
                         moe_p.pop("experts"))
    return dict(layers, moe=moe_p), stack


def _moe_block(p: Params, x: jnp.ndarray, cfg: ModelConfig,
               valid: jnp.ndarray, layer) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``moe_serve`` of one block of tokens x (N,d), valid (N,)."""
    N, d = x.shape
    K, n = cfg.top_k, cfg.experts_held
    w, idx = route(p, x, cfg)
    local = idx - cfg.expert_offset
    held = (local >= 0) & (local < n) & valid[:, None]
    # a counting sort of the (token, k) pairs by expert; pairs for absent
    # experts (and pads) sort last, past every group
    key = jnp.where(held, local, n).reshape(-1)
    onehot = jax.nn.one_hot(key, n + 1, dtype=jnp.int32)  # (N·K, n+1)
    counts = onehot.sum(0)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    dest = (jnp.cumsum(counts) - counts)[key] + rank
    # the sorted rows padded to a multiple of 8 (past every group): a TPU
    # lowers a ragged dot of other row counts to a dense product over
    # every group of the stack, not to its kernel that skips empty groups
    M = -(-N * K // 8) * 8
    tok = jnp.zeros((M,), key.dtype).at[dest].set(jnp.arange(N * K)) // K
    hits = counts[:n]
    e = p["experts"]
    # this layer's n groups within the whole stack; the others are empty
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros((e["gate"]["w"].shape[0],), hits.dtype), hits,
        (layer * n,))
    xs = x[tok]
    g = jax.lax.ragged_dot(xs, e["gate"]["w"], groups,
                           preferred_element_type=x.dtype)
    u = jax.lax.ragged_dot(xs, e["up"]["w"], groups,
                           preferred_element_type=x.dtype)
    o = jax.lax.ragged_dot(activate(g, cfg.act) * u, e["down"]["w"], groups,
                           preferred_element_type=x.dtype)
    # back to (token, k) order (``dest`` inverts the sort); rows past the
    # last group hold nothing defined: select, not multiply
    o = o[dest].reshape(N, K, d).astype(jnp.float32)
    y = jnp.sum(jnp.where(held[..., None], o * w[..., None], 0.0), axis=1)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], x, cfg.act).astype(jnp.float32)
    return y.astype(x.dtype), hits


def moe_serve(p: Params, x: jnp.ndarray, cfg: ModelConfig,
              valid: Optional[jnp.ndarray] = None, layer=0
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The serving routed-expert layer: no capacity, no dropped token.

    x (N,d); ``valid`` (N,) bool marks real tokens (None: all).  Each
    (token, k) pair routed to a held expert is sorted by expert and run
    through the held experts with ``jax.lax.ragged_dot``; the weighted
    outputs are summed back per token, and the shared experts are added
    once.  ``p["experts"]`` is one layer's (n_held, ...) experts, or the
    whole (L·n_held, ...) stack of ``expert_stack`` with ``layer`` the
    MoE layer's index in it: the ragged dot then runs on the whole stack
    with every other layer's groups empty.  More than ``SERVE_BLOCK``
    tokens (a long prefill) run a block at a time, which bounds the
    (token, k) temporaries.  Returns (y (N,d), hits (n_held,) int32: the
    valid tokens routed to each held expert)."""
    N, d = x.shape
    if valid is None:
        valid = jnp.ones((N,), bool)
    if N <= SERVE_BLOCK:
        return _moe_block(p, x, cfg, valid, layer)
    pad = (-N) % SERVE_BLOCK
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, SERVE_BLOCK, d)
    vb = jnp.pad(valid, (0, pad)).reshape(-1, SERVE_BLOCK)
    y, hits = jax.lax.map(lambda a: _moe_block(p, a[0], cfg, a[1], layer),
                          (xb, vb))
    return y.reshape(-1, d)[:N], hits.sum(0)


def _moe_serve_3d(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                  valid: Optional[jnp.ndarray] = None, layer=0):
    """``moe_serve`` of x (B,T,d), valid (B,T)."""
    B, T, d = x.shape
    y, hits = moe_serve(p, x.reshape(B * T, d), cfg,
                        None if valid is None else valid.reshape(-1), layer)
    return y.reshape(B, T, d), hits


def _dispatch(probs: jnp.ndarray, top_k: int, capacity: int,
              renorm: bool = True):
    """probs (g,E) -> (dispatch (g,E,C) bool-ish, combine (g,E,C) float)."""
    g, E = probs.shape
    top_p, top_i = jax.lax.top_k(probs, top_k)  # (g,K)
    if renorm:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(top_i, E, dtype=jnp.float32)  # (g,K,E)
    # priority: earlier tokens first, k-slots of one token in order
    flat = onehot.reshape(g * top_k, E)
    pos = jnp.cumsum(flat, axis=0) - flat  # (gK,E) position within expert
    keep = (pos < capacity) * flat
    disp_slot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32) * keep[..., None]
    disp = disp_slot.reshape(g, top_k, E, capacity)
    dispatch = disp.sum(1)  # (g,E,C)
    combine = jnp.einsum("gkec,gk->gec", disp, top_p)
    return dispatch, combine


def moe_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B,T,d) -> (y (B,T,d), aux_loss scalar)."""
    B, T, d = x.shape
    g_total = B * T
    xf = x.reshape(g_total, d)
    group = min(MOE_GROUP, g_total)
    pad = (-g_total) % group
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    n_groups = xf.shape[0] // group
    xg = xf.reshape(n_groups, group, d)

    probs = router_probs(p, xg)  # (n,gr,E)
    E = cfg.n_experts
    cap = max(1, int(group * cfg.top_k * cfg.capacity_factor / E))
    held = slice(cfg.expert_offset, cfg.expert_offset + cfg.experts_held)

    def one_group(args):
        xg_g, probs_g = args  # (gr,d), (gr,E)
        dispatch, combine = _dispatch(probs_g, cfg.top_k, cap,
                                      cfg.norm_topk_prob)
        xe = jnp.einsum("gec,gd->ecd", dispatch[:, held].astype(x.dtype), xg_g)

        def run_expert(ep, xe_e):  # xe_e (C,d)
            return mlp_apply(ep, xe_e, cfg.act)

        he = jax.vmap(run_expert)(p["experts"], xe)  # (E_held,C,d)
        y_g = jnp.einsum("gec,ecd->gd", combine[:, held].astype(x.dtype), he)
        frac_g = dispatch.sum(axis=(0, 2)) / (group * cfg.top_k)  # (E,)
        return y_g, frac_g

    if n_groups == 1:
        y, frac = one_group((xg[0], probs[0]))
        y, frac = y[None], frac[None]
    else:
        # sequential over groups: bounds live dispatch/einsum memory to one
        # group regardless of token count (1M tokens at train_4k)
        y, frac = jax.lax.map(one_group, (xg, probs))
    y = y.reshape(-1, d)
    if pad:
        y = y[:g_total]
    y = y.reshape(B, T, d)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], x, cfg.act)
    # load-balance aux loss (Switch-style): E * sum_e f_e * P_e
    imp = probs.mean(axis=1)  # (n,E)
    aux = E * jnp.mean(jnp.sum(frac * imp, axis=-1))
    return y, aux


# ---------------------------------------------------------------------------
# MoE decoder model (mixtral path: GQA; deepseek path: MLA)
# ---------------------------------------------------------------------------
class MoECache(NamedTuple):
    kv: object  # KVCache (GQA) or mla.MLACache
    dense_kv: object  # same type, for the first dense layers (or None-like)


def _init_block(key, cfg: ModelConfig, moe: bool) -> Params:
    ka, km = jax.random.split(key)
    p = {
        "attn": (mla.init_mla(ka, cfg) if cfg.use_mla else attn.init_attention(ka, cfg)),
        "ln_attn": init_rms(cfg.d_model, cfg.dtype),
        "ln_mlp": init_rms(cfg.d_model, cfg.dtype),
    }
    if moe:
        p["moe"] = init_moe_layer(km, cfg)
    else:
        p["mlp"] = init_mlp(km, cfg.d_model, cfg.d_ff, cfg.dtype)
    return p


def init(key, cfg: ModelConfig) -> Params:
    ke, kd, kl, ku = jax.random.split(key, 4)
    nf = cfg.first_dense_layers
    params = {
        "embed": init_embed(ke, cfg.vocab_size, cfg.d_model, cfg.dtype),
        "layers": stack_layers(lambda k: _init_block(k, cfg, True), kl, cfg.n_layers - nf),
        "ln_f": init_rms(cfg.d_model, cfg.dtype),
    }
    if nf:
        params["dense_layers"] = stack_layers(lambda k: _init_block(k, cfg, False), kd, nf)
    if not cfg.tie_embeddings:
        params["unembed"] = dense_param(ku, cfg.d_model, cfg.vocab_size, cfg.dtype)
    return params


def _attn_fwd(layer, x, positions, cfg, window, mask):
    if cfg.use_mla:
        return mla.mla_forward(layer["attn"], x, positions, cfg, window, mask)
    return attn.attention_forward(layer["attn"], x, positions, cfg, window, mask)


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            lengths: Optional[jnp.ndarray] = None,
            window: Optional[int] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (logits, aux_loss)."""
    window = window if window is not None else cfg.sliding_window
    B, T = tokens.shape
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    positions = make_positions(tokens, lengths)
    mask = (None if T >= attn.CHUNK_THRESHOLD
            else attn.prefill_mask(positions, window))
    h = embed_apply(params["embed"], tokens, cfg)

    def dense_body(carry, layer):
        a = _attn_fwd(layer, rms_norm(carry, layer["ln_attn"], cfg.norm_eps),
                      positions, cfg, window, mask)
        h2 = carry + a
        m = mlp_apply(layer["mlp"], rms_norm(h2, layer["ln_mlp"], cfg.norm_eps), cfg.act)
        return h2 + m, None

    def moe_body(carry, layer):
        a = _attn_fwd(layer, rms_norm(carry, layer["ln_attn"], cfg.norm_eps),
                      positions, cfg, window, mask)
        h2 = carry + a
        m, aux = moe_apply(layer["moe"], rms_norm(h2, layer["ln_mlp"], cfg.norm_eps), cfg)
        return h2 + m, aux

    if cfg.first_dense_layers:
        h, _ = scan_layers(dense_body, h, params["dense_layers"], remat=cfg.remat)
    h, auxs = scan_layers(moe_body, h, params["layers"], remat=cfg.remat)
    return _logits(params, cfg, h), jnp.mean(auxs)


def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            lengths: jnp.ndarray, cache_window: int,
            window: Optional[int] = None) -> Tuple[jnp.ndarray, MoECache]:
    window = window if window is not None else cfg.sliding_window
    positions = make_positions(tokens, lengths)
    T = positions.shape[1]
    mask = (None if T >= attn.CHUNK_THRESHOLD
            else attn.prefill_mask(positions, window))
    h = embed_apply(params["embed"], tokens, cfg)
    # SWA archs only ever need `window` ring slots; full attention needs L_i+S
    Wc = cache_window if window is None else min(cache_window, window)

    def body(experts):
        def go(carry, layer, l=None):
            x = rms_norm(carry, layer["ln_attn"], cfg.norm_eps)
            if cfg.use_mla:
                a, c1, c2 = mla.mla_prefill(layer["attn"], x, positions, cfg,
                                            window, Wc, mask)
            else:
                a, c1, c2 = attn.attention_prefill(layer["attn"], x, positions,
                                                   cfg, window, Wc, mask=mask)
            h2 = carry + a
            xm = rms_norm(h2, layer["ln_mlp"], cfg.norm_eps)
            if experts is None:
                m = mlp_apply(layer["mlp"], xm, cfg.act)
            else:
                m, _ = _moe_serve_3d(dict(layer["moe"], experts=experts),
                                     xm, cfg, layer=l)
            return h2 + m, (c1, c2)
        return go

    if cfg.first_dense_layers:
        h, (dk, dv) = scan_layers(body(None), h, params["dense_layers"])
    layers, experts = expert_stack(params["layers"])
    n_moe = cfg.n_layers - cfg.first_dense_layers
    h, (k_all, v_all) = scan_layers(body(experts), h, layers,
                                    jnp.arange(n_moe))
    logits = _logits(params, cfg, h[:, -1:])[:, 0]

    def mk_cache(k, v):
        common = dict(
            slot_pos=attn.prefill_slot_pos(positions, Wc),
            write_idx=jnp.asarray(T if Wc >= T else Wc, jnp.int32),
            lengths=lengths.astype(jnp.int32))
        if cfg.use_mla:
            return mla.MLACache(ckv=k, kr=v, **common)
        return KVCache(k=k, v=v, **common)

    dense_cache = mk_cache(dk, dv) if cfg.first_dense_layers else None
    return logits, MoECache(kv=mk_cache(k_all, v_all), dense_kv=dense_cache)


def decode_step(params: Params, cfg: ModelConfig, cache: MoECache,
                tokens: jnp.ndarray, step: jnp.ndarray,
                window: Optional[int] = None) -> Tuple[jnp.ndarray, MoECache]:
    window = window if window is not None else cfg.sliding_window
    kvc = cache.kv
    q_pos = kvc.lengths + step
    slot = attn.decode_slot(kvc) if not cfg.use_mla else mla.decode_slot(kvc)
    slot_pos = (attn.decode_slot_pos(kvc, q_pos) if not cfg.use_mla
                else mla.decode_slot_pos(kvc, q_pos))
    h = embed_apply(params["embed"], tokens[:, None], cfg)

    def body(experts):
        def go(carry, layer, c1, c2, l=None):
            x = rms_norm(carry, layer["ln_attn"], cfg.norm_eps)
            if cfg.use_mla:
                a, c1, c2 = mla.mla_decode(layer["attn"], x, q_pos, c1, c2,
                                           slot_pos, slot, cfg, window)
            else:
                a, c1, c2 = attn.attention_decode(layer["attn"], x, q_pos, c1, c2,
                                                  slot_pos, slot, cfg, window)
            h2 = carry + a
            xm = rms_norm(h2, layer["ln_mlp"], cfg.norm_eps)
            if experts is None:
                m = mlp_apply(layer["mlp"], xm, cfg.act)
            else:
                m, _ = _moe_serve_3d(dict(layer["moe"], experts=experts),
                                     xm, cfg, layer=l)
            return h2 + m, (c1, c2)
        return go

    if cfg.first_dense_layers:
        dc = cache.dense_kv
        d1, d2 = (dc.ckv, dc.kr) if cfg.use_mla else (dc.k, dc.v)
        h, (nd1, nd2) = scan_layers(body(None), h, params["dense_layers"], d1, d2)
        if cfg.use_mla:
            new_dense = dc._replace(ckv=nd1, kr=nd2, slot_pos=slot_pos,
                                    write_idx=dc.write_idx + 1)
        else:
            new_dense = dc._replace(k=nd1, v=nd2, slot_pos=slot_pos,
                                    write_idx=dc.write_idx + 1)
    else:
        new_dense = cache.dense_kv

    c1, c2 = (kvc.ckv, kvc.kr) if cfg.use_mla else (kvc.k, kvc.v)
    layers, experts = expert_stack(params["layers"])
    n_moe = cfg.n_layers - cfg.first_dense_layers
    h, (n1, n2) = scan_layers(body(experts), h, layers, c1, c2,
                              jnp.arange(n_moe))
    logits = _logits(params, cfg, h)[:, 0]
    if cfg.use_mla:
        new_kv = kvc._replace(ckv=n1, kr=n2, slot_pos=slot_pos,
                              write_idx=kvc.write_idx + 1)
    else:
        new_kv = kvc._replace(k=n1, v=n2, slot_pos=slot_pos,
                              write_idx=kvc.write_idx + 1)
    return logits, MoECache(kv=new_kv, dense_kv=new_dense)


# ---------------------------------------------------------------------------
# persistent paged serving (MLA): one latent pool over all layers
# ---------------------------------------------------------------------------
def _scan_paged(body, h: jnp.ndarray, layers: Params, first: int,
                k_pages: jnp.ndarray, v_pages: jnp.ndarray, counts):
    """lax.scan of ``body(h, layer, l, k_pages, v_pages) -> (h, k_pages,
    v_pages, counts)`` over a layer stack whose pool layers start at
    ``first``, carrying the whole pools (updated in place at index l, as
    ``transformer._scan_paged`` does) and summing the per-layer counts."""
    def step(carry, xs):
        h, kp, vp, acc = carry
        h, kp, vp, c = body(h, *xs, kp, vp)
        return (h, kp, vp, jax.tree.map(jnp.add, acc, c)), None

    n = jax.tree_util.tree_leaves(layers)[0].shape[0]
    (h, k_pages, v_pages, counts), _ = jax.lax.scan(
        step, (h, k_pages, v_pages, counts),
        (layers, jnp.arange(first, first + n)))
    return h, k_pages, v_pages, counts


def _paged_stack(params: Params, cfg: ModelConfig, h, k_pages, v_pages,
                 attend, mlp_counts, counts0):
    """The dense layers then the MoE layers over one pool of
    ``n_layers`` layers: ``attend(layer, x, l, kp, vp) -> (a, kp, vp)``
    is the attention of one layer, ``mlp_counts(p, x, l) -> (m, counts)``
    the MoE layer ``l`` (counted from the first MoE layer) and the counts
    it adds; ``p["experts"]`` is the whole stack (``expert_stack``)."""
    nf = cfg.first_dense_layers

    def body(experts):
        def go(carry, layer, li, kp, vp):
            with jax.named_scope("attention"):
                a, kp, vp = attend(layer["attn"],
                                   rms_norm(carry, layer["ln_attn"],
                                            cfg.norm_eps), li, kp, vp)
            h2 = carry + a
            with jax.named_scope("mlp"):
                xm = rms_norm(h2, layer["ln_mlp"], cfg.norm_eps)
                if experts is None:
                    m, c = mlp_apply(layer["mlp"], xm, cfg.act), counts0
                else:
                    m, c = mlp_counts(dict(layer["moe"], experts=experts),
                                      xm, li - nf)
            return h2 + m, kp, vp, c
        return go

    counts = counts0
    if nf:
        h, k_pages, v_pages, counts = _scan_paged(
            body(None), h, params["dense_layers"], 0, k_pages, v_pages,
            counts)
    layers, experts = expert_stack(params["layers"])
    return _scan_paged(body(experts), h, layers, nf, k_pages, v_pages,
                       counts)


def _prefill_counts(cfg: ModelConfig, positions, block_table):
    # a row that owns no page is the batch's padding: its tokens count
    # nothing (their outputs are dropped)
    valid = (positions >= 0) & (block_table[:, :1] > 0)

    def f(p, xm, l):
        m, hits = _moe_serve_3d(p, xm, cfg, valid, l)
        return m, {"expert_tokens": jnp.sum(hits)}
    return f


def _full_slot_pos(cache, lengths):
    # the window from slot_pos: a packed pool's pages have fewer rows
    # than slots (``mla.page_shape``)
    slots = jnp.arange(cache.slot_pos.shape[1], dtype=jnp.int32)[None]
    return jnp.where(slots < lengths[:, None], slots, -1)


def prefill_paged(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  lengths: jnp.ndarray, cache):
    """Prefill of left-padded rows straight into the latent pools
    (``cache``: a ``kvcache.paged.PagedKVCache`` whose k/v pools hold
    c_kv and the rope key): the MLA twin of
    ``transformer.prefill_paged``, expanded form, chunked at
    CHUNK_THRESHOLD tokens.  Returns (last logits, cache,
    {"expert_tokens": (real token, held expert) pairs over all MoE
    layers})."""
    positions = make_positions(tokens, lengths)
    T = positions.shape[1]
    mask = None if T >= attn.CHUNK_THRESHOLD else attn.prefill_mask(positions, None)
    bt = cache.block_table

    def attend(p, x, li, kp, vp):
        return mla.mla_prefill_paged(p, x, positions, cfg, kp, vp, bt, mask, li)

    h = embed_apply(params["embed"], tokens, cfg)
    zero = {"expert_tokens": jnp.zeros((), jnp.int32)}
    h, kp, vp, counts = _paged_stack(params, cfg, h, cache.k_pages,
                                     cache.v_pages, attend,
                                     _prefill_counts(cfg, positions, bt), zero)
    logits = _logits(params, cfg, h[:, -1:])[:, 0]
    return logits, cache._replace(k_pages=kp, v_pages=vp,
                                  slot_pos=_full_slot_pos(cache, lengths),
                                  lengths=lengths.astype(jnp.int32)), counts


def prefill_tail_paged(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                       start: jnp.ndarray, lengths: jnp.ndarray, cache):
    """Prefill of each row's novel tail (tokens ``start..lengths-1``,
    left-padded) whose head is already in the pools: the MLA twin of
    ``transformer.prefill_tail_paged``."""
    base = make_positions(tokens, lengths - start)
    positions = jnp.where(base >= 0, base + start[:, None], -1)
    slot_pos = _full_slot_pos(cache, lengths)
    bt = cache.block_table

    def attend(p, x, li, kp, vp):
        return mla.mla_prefill_tail_paged(p, x, positions, cfg, kp, vp, bt,
                                          slot_pos, li)

    h = embed_apply(params["embed"], tokens, cfg)
    zero = {"expert_tokens": jnp.zeros((), jnp.int32)}
    h, kp, vp, counts = _paged_stack(params, cfg, h, cache.k_pages,
                                     cache.v_pages, attend,
                                     _prefill_counts(cfg, positions, bt), zero)
    logits = _logits(params, cfg, h[:, -1:])[:, 0]
    return logits, cache._replace(k_pages=kp, v_pages=vp, slot_pos=slot_pos,
                                  lengths=lengths.astype(jnp.int32)), counts


def decode_step_paged(params: Params, cfg: ModelConfig, cache,
                      tokens: jnp.ndarray, q_pos: jnp.ndarray,
                      slots: jnp.ndarray, done: jnp.ndarray):
    """One absorbed-form decode step over the latent pools, per-row
    positions and write slots (``transformer.decode_step_paged``'s
    convention).  ``done`` (B,) marks the rows that produce no more
    tokens (padding, or finished); the others are live.
    Returns (logits, cache, {"expert_reads": held experts at least one
    live row was routed to, summed over the MoE layers; "expert_rows":
    (live row, held expert) pairs})."""
    oh = jax.nn.one_hot(slots, cache.slot_pos.shape[1], dtype=jnp.int32)
    slot_pos = cache.slot_pos * (1 - oh) + q_pos[:, None].astype(jnp.int32) * oh
    bt = cache.block_table

    def attend(p, x, li, kp, vp):
        return mla.mla_decode_paged(p, x, q_pos, kp, vp, bt, slot_pos, slots,
                                    cfg, li)

    def moe_counts(p, xm, l):
        m, hits = _moe_serve_3d(p, xm, cfg, ~done[:, None], l)
        return m, {"expert_reads": jnp.sum(hits > 0).astype(jnp.int32),
                   "expert_rows": jnp.sum(hits)}

    h = embed_apply(params["embed"], tokens[:, None], cfg)
    zero = {"expert_reads": jnp.zeros((), jnp.int32),
            "expert_rows": jnp.zeros((), jnp.int32)}
    h, kp, vp, counts = _paged_stack(params, cfg, h, cache.k_pages,
                                     cache.v_pages, attend, moe_counts, zero)
    with jax.named_scope("head"):
        logits = _logits(params, cfg, h)[:, 0]
    return logits, cache._replace(k_pages=kp, v_pages=vp,
                                  slot_pos=slot_pos), counts
