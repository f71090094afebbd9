"""Plain reference of DeepSeek-V2(-Lite) with one chip's share of the
routed experts: full-sequence causal forward in float32 at ``highest``
matmul precision, one layer at a time, in straightforward ``jax.numpy``.

It keeps the contract of ``chipbench/reference.py`` (``score``, the
``fp8`` control, nothing of the program imported, the benchmark's own
weights read by the names of the program's parameter tree) for the
configurations whose ``check.reference`` names this file.

This module reads ``embed.table``; per layer (``dense_layers`` first, then
``layers``) ``attn.{wq,w_dkv,k_up,v_up,wo}.w``, ``attn.ckv_norm``,
``ln_attn``, ``ln_mlp``; in a dense layer ``mlp.{gate,up,down}.w``, in an
MoE layer ``moe.router.w`` (d, n_experts), ``moe.experts.{gate,up,down}.w``
(the held experts stacked) and ``moe.shared.{gate,up,down}.w``; ``ln_f``;
``unembed.w``.  Norm weights are stored as the RMSNorm scale minus one.

The layer, as published (arXiv:2405.04434 and Hugging Face
``modeling_deepseek.py``), with no query compression (``q_lora_rank``
null):

    q = W_q x  (per head: q_nope of dn, q_rope of dr)
    [c, k_r] = W_dkv x;  c = RMSNorm(c)  (the latent, r wide)
    k = [W_uk c  (per head),  RoPE(k_r)  (one rotary key for all heads)]
    v = W_uv c  (per head)
    h += W_o · softmax(q · kᵀ · s + causal) · v,  x = RMSNorm(h)

with RoPE(q_rope) too, the rotary frequencies under YaRN (the ramp between
the beta_fast and beta_slow correction dims, cos and sin times
mscale / mscale_all_dim) and s = (dn + dr)^-0.5 · mscale(all dims)², then

    h += W_down · (silu(W_gate x) ⊙ W_up x)            (dense layers)
    h += Σ_{e in top-k, e held} p_e · expert_e(x) + shared(x)   (MoE)

where p is a float32 softmax of the router over all ``n_experts``, the
top-k are chosen greedily and renormalised only if ``norm_topk_prob``, and
only the experts ``[expert_offset, expert_offset + n_experts_held)`` held
on this chip contribute (the chip's share of an expert-parallel layer);
the shared experts are one SwiGLU.  Logits are ``W_unembed ·
RMSNorm(h)``.  The rotary dims rotate in halves (``rotate_half``) where
the published code interleaves pairs: with seeded weights that is a
permutation of weight columns.  Attention runs in blocks of query rows,
so no (T, T) score matrix of every head is held at once.

``precision="fp8"`` is the control: every matmul's inputs are rounded to
float8 e4m3 with one scale per output channel (weights) and per token
(activations), as an fp8 serving path would, and accumulated in float32.
"""
from __future__ import annotations

import functools
import math
from typing import Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
VOCAB_BLOCK = 32768
Q_BLOCK = 256


def _fp8(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x: jnp.ndarray, w: jnp.ndarray, precision: str) -> jnp.ndarray:
    """x (T, d_in) @ w (d_in, d_out) in float32."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _fp8(x, axis=1), _fp8(w, axis=0)
    return jnp.dot(x, w, precision=HI)


def _rms(x: jnp.ndarray, w_minus_one: jnp.ndarray, eps: float) -> jnp.ndarray:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + w_minus_one.astype(jnp.float32))


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(m: Mapping, dim: int) -> np.ndarray:
    """Inverse rotary frequencies (dim/2,) under YaRN."""
    theta, f = m["rope_theta"], m.get("rope_scaling_factor", 1.0)
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if f <= 1:
        return base

    def corr(n_rot):
        return (dim * math.log(m["rope_original_max_pos"]
                               / (n_rot * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(corr(m["rope_beta_fast"])), 0)
    hi = min(math.ceil(corr(m["rope_beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    return base / f * ramp + base * (1.0 - ramp)


def softmax_scale(m: Mapping) -> float:
    """(dn + dr)^-0.5 times YaRN's mscale(all dims)²."""
    s = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    if m.get("rope_mscale_all_dim"):
        s *= _mscale(m["rope_scaling_factor"], m["rope_mscale_all_dim"]) ** 2
    return s


def _rope(x: jnp.ndarray, m: Mapping) -> jnp.ndarray:
    """x (T, H, D): rotate the halves of each head at positions 0..T-1."""
    T, _, D = x.shape
    f = m.get("rope_scaling_factor", 1.0)
    mult = (_mscale(f, m.get("rope_mscale", 1.0))
            / _mscale(f, m.get("rope_mscale_all_dim", 0.0)))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(
        yarn_freqs(m, D), jnp.float32)[None]
    cos, sin = jnp.cos(ang)[:, None] * mult, jnp.sin(ang)[:, None] * mult
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(h, a, m: Mapping, precision: str):
    T = h.shape[0]
    H, dn, dr = m["n_heads"], m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    dv, r = m["v_head_dim"], m["kv_lora_rank"]
    x = h
    q = _mm(x, a["wq"]["w"], precision).reshape(T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], m)], -1)
    ckr = _mm(x, a["w_dkv"]["w"], precision)
    c = _rms(ckr[:, :r], a["ckv_norm"], m["norm_eps"])
    kr = _rope(ckr[:, None, r:], m)
    kn = _mm(c, a["k_up"]["w"], precision).reshape(T, H, dn)
    v = _mm(c, a["v_up"]["w"], precision).reshape(T, H, dv)
    k = jnp.concatenate([kn, jnp.broadcast_to(kr, (T, H, dr))], -1)
    scale = softmax_scale(m)
    bq = min(Q_BLOCK, T)
    keys = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=0)
        s = jnp.einsum("qhd,shd->hqs", qb, k, precision=HI) * scale
        rows = i * bq + jnp.arange(bq)
        s = jnp.where((keys[None, :] <= rows[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(s, axis=-1), v,
                          precision=HI)

    o = jax.lax.map(block, jnp.arange(T // bq)).reshape(T, H * dv)
    return _mm(o, a["wo"]["w"], precision)


def _swiglu(x, p, precision: str):
    g = _mm(x, p["gate"]["w"], precision)
    u = _mm(x, p["up"]["w"], precision)
    return _mm(jax.nn.silu(g) * u, p["down"]["w"], precision)


def route(x, router_w, m: Mapping, precision: str = "f32"):
    """The router: (weights (T, K), experts (T, K)) of x (T, d)."""
    probs = jax.nn.softmax(_mm(x, router_w, precision), axis=-1)
    w, idx = jax.lax.top_k(probs, m["top_k"])
    if m.get("norm_topk_prob", True):
        w = w / w.sum(-1, keepdims=True)
    return w, idx


def _moe(x, p, m: Mapping, precision: str):
    w, idx = route(x, p["router"]["w"], m, precision)
    off = m.get("expert_offset", 0)
    n = m.get("n_experts_held") or m["n_experts"]
    y = _swiglu(x, p["shared"], precision) if "shared" in p else 0.0
    for j in range(n):
        gate = jnp.sum(jnp.where(idx == off + j, w, 0.0), axis=-1)
        ej = jax.tree_util.tree_map(lambda t: t[j], p["experts"])
        y = y + gate[:, None] * _swiglu(x, ej, precision)
    return y


def _layer(h, p, m: Mapping, precision: str, moe: bool):
    h = h + _attention(_rms(h, p["ln_attn"], m["norm_eps"]), p["attn"], m,
                       precision)
    x = _rms(h, p["ln_mlp"], m["norm_eps"])
    if moe:
        return h + _moe(x, p["moe"], m, precision)
    return h + _swiglu(x, p["mlp"], precision)


def hidden(params, tokens, m: Mapping, precision: str = "f32"):
    """The final normed hidden states of ``tokens`` (T,) (T a multiple of
    ``Q_BLOCK`` or below it)."""
    h = params["embed"]["table"][tokens].astype(jnp.float32)
    for key, moe in (("dense_layers", False), ("layers", True)):
        if key not in params:
            continue

        def body(h, layer, moe=moe):
            return _layer(h, layer, m, precision, moe), None

        h, _ = jax.lax.scan(body, h, params[key])
    return _rms(h, params["ln_f"], m["norm_eps"])


def logits(params, tokens, m: Mapping, precision: str = "f32"):
    """Full logits (T, vocab) of ``tokens``: for small sizes (tests)."""
    h = hidden(params, tokens, m, precision)
    return _mm(h, params["unembed"]["w"], precision)


@functools.partial(jax.jit, static_argnames=("m_items", "precision"))
def _score(params, tokens, served, alt, m_items: Tuple, precision: str):
    """Per position of ``tokens``: the best logit, its token, and the
    logits of the tokens ``served`` and ``alt`` name there."""
    m = dict(m_items)
    h = hidden(params, tokens, m, precision)
    w = params["unembed"]["w"]
    # the head in blocks of the vocabulary, so that no float32 copy of the
    # whole head matrix or of all the logits is held at once
    T = h.shape[0]
    best = jnp.full((T,), -jnp.inf, jnp.float32)
    top = jnp.zeros((T,), jnp.int32)
    got = {"served": jnp.zeros((T,), jnp.float32),
           "alt": jnp.zeros((T,), jnp.float32)}
    for lo in range(0, w.shape[1], VOCAB_BLOCK):
        lg = _mm(h, w[:, lo:lo + VOCAB_BLOCK], precision)
        hi = lo + lg.shape[1]
        bmax = lg.max(axis=1)
        top = jnp.where(bmax > best, jnp.argmax(lg, axis=1) + lo, top)
        best = jnp.maximum(best, bmax)
        for key, t in (("served", served), ("alt", alt)):
            v = jnp.take_along_axis(lg, jnp.clip(t - lo, 0, hi - lo - 1)
                                    [:, None], axis=1)[:, 0]
            got[key] = jnp.where((t >= lo) & (t < hi), v, got[key])
    return best, top, got["served"], got["alt"]


def score(params, m: Mapping, seq: np.ndarray, first: int,
          alt: np.ndarray = None, precision: str = "f32",
          bucket: int = 256) -> dict:
    """Score the tokens ``seq[first:]`` against this model's predictions
    at positions ``first - 1 .. len(seq) - 2`` (``chipbench/reference.py``'s
    contract).  The sequence is right-padded to a multiple of ``bucket``;
    padding after the end cannot reach earlier positions under the causal
    mask, and few lengths compile."""
    seq = np.asarray(seq, np.int32)
    n = len(seq)
    T = -(-n // bucket) * bucket
    toks = np.zeros((T,), np.int32)
    toks[:n] = seq
    nxt = np.zeros((T,), np.int32)
    nxt[:n - 1] = seq[1:]
    other = np.zeros((T,), np.int32)
    if alt is not None:
        other[first - 1:n - 1] = alt
    m_items = tuple(sorted((k, v) for k, v in m.items()
                           if isinstance(v, (int, float, str, bool))))
    out = _score(params, jnp.asarray(toks), jnp.asarray(nxt),
                 jnp.asarray(other), m_items, precision)
    sl = slice(first - 1, n - 1)
    best, top, served, alt_l = (np.asarray(x)[sl] for x in out)
    return dict(best=best, top=top, served=served, alt=alt_l)
