"""Plain reference of the dense decoders the benchmark serves (Qwen1.5,
Mistral): full-sequence causal forward in float32 at ``highest`` matmul
precision, one layer at a time, in straightforward ``jax.numpy``.

A configuration names its plain reference by the path of a module in
``check.reference`` (``chipbench/spec.py``); this one is the default.
Every such module keeps to one contract:

- ``score(params, m, seq, first, alt=None, precision="f32")`` scores the
  tokens ``seq[first:]`` against the model's predictions at positions
  ``first - 1 .. len(seq) - 2`` and returns a dict of NumPy arrays with
  one entry per scored token: ``best``, the highest logit there; ``top``,
  the token that has it; ``served``, the logit of the token in ``seq``;
  ``alt``, the logit of ``alt``'s token there (``alt`` gives one token
  per scored position, or is ``None``).  ``m`` is the configuration's
  ``model`` section.  ``chipbench/check.py`` compares ``best - served``.
- ``precision="f32"`` computes in float32 with every matmul at
  ``jax.lax.Precision.HIGHEST``; ``precision="fp8"`` is the control, the
  same forward with every matmul's inputs rounded to the nearest
  precision below the configuration's (float8 e4m3 for bfloat16), which
  has to come out not ``correct``.
- It imports nothing of the program and takes nothing the program made.
  It reads the benchmark's own weights (``chipbench/weights.py``, made
  from the seed) by the names of the program's parameter tree.
- It fits on the chip beside nothing else: the check runs it once the
  program's state is freed, one layer or one block of rows at a time.

This module reads ``embed.table``; per layer ``attn.{wq,wk,wv,wo}.{w,b}``,
``mlp.{gate,up,down}.w``, ``ln_attn``, ``ln_mlp``; ``ln_f``;
``unembed.w``.  Norm weights are stored as the RMSNorm scale minus one.

The layer, as published for both families (Hugging Face
``Qwen2``/``Mistral`` decoder layers):

    h += W_o · softmax(RoPE(W_q x + b_q) · RoPE(W_k x + b_k)ᵀ / √D
                       + causal) · (W_v x + b_v),   x = RMSNorm(h)
    h += W_down · (silu(W_gate x) ⊙ W_up x),          x = RMSNorm(h)

with the key/value heads shared by groups of query heads, RoPE rotating
the two halves of each head (``rotate_half``), and logits
``W_unembed · RMSNorm(h)``.

``precision="fp8"`` is the control: every matmul's inputs are rounded to
float8 e4m3 with one scale per output channel (weights) and per token
(activations), as an fp8 serving path would, and accumulated in float32.
"""
from __future__ import annotations

import functools
from typing import Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
VOCAB_BLOCK = 32768


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


def _rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x (T, H, D): rotate the halves of each head by position · θ^(-2i/D)."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float64) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(h, p, m: Mapping, precision: str):
    T = h.shape[0]
    Hq, Hkv, D = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    a = p["attn"]

    def proj(name, x):
        y = _mm(x, a[name]["w"], precision)
        if "b" in a[name]:
            y = y + a[name]["b"].astype(jnp.float32)
        return y

    x = _rms(h, p["ln_attn"], m["norm_eps"])
    q = _rope(proj("wq", x).reshape(T, Hq, D), m["rope_theta"])
    k = _rope(proj("wk", x).reshape(T, Hkv, D), m["rope_theta"])
    v = proj("wv", x).reshape(T, Hkv, D)
    k = jnp.repeat(k, Hq // Hkv, axis=1)
    v = jnp.repeat(v, Hq // Hkv, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HI) * D ** -0.5
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hts,shd->thd", pr, v, precision=HI).reshape(T, Hq * D)
    h = h + _mm(o, a["wo"]["w"], precision)
    x = _rms(h, p["ln_mlp"], m["norm_eps"])
    mp = p["mlp"]
    g = _mm(x, mp["gate"]["w"], precision)
    u = _mm(x, mp["up"]["w"], precision)
    return h + _mm(jax.nn.silu(g) * u, mp["down"]["w"], precision)


_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
         "d_ff", "vocab_size", "rope_theta", "norm_eps")


@functools.partial(jax.jit, static_argnames=("m_items", "precision"))
def _score(params, tokens, served, alt, m_items: Tuple, precision: str):
    """Per position of ``tokens``: the best logit, its token, and the
    logits of the tokens ``served`` and ``alt`` name there."""
    m = dict(m_items)
    h = params["embed"]["table"][tokens].astype(jnp.float32)

    def body(h, layer):
        return _layer(h, layer, m, precision), None

    h, _ = jax.lax.scan(body, h, params["layers"])
    h = _rms(h, params["ln_f"], m["norm_eps"])
    w = (params["unembed"]["w"] if "unembed" in params
         else params["embed"]["table"].T)
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
    at positions ``first - 1 .. len(seq) - 2``: per token, the best logit
    (``best``), the token that has it (``top``), the logit of the token
    in ``seq`` (``served``) and of ``alt`` (another choice per token).
    The sequence is right-padded to a multiple of ``bucket``; padding
    after the end cannot reach earlier positions under the causal mask,
    and few lengths compile."""
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
    m_items = tuple((k, m[k]) for k in _KEYS)
    out = _score(params, jnp.asarray(toks), jnp.asarray(nxt),
                 jnp.asarray(other), m_items, precision)
    sl = slice(first - 1, n - 1)
    best, top, served, alt_l = (np.asarray(x)[sl] for x in out)
    return dict(best=best, top=top, served=served, alt=alt_l)
