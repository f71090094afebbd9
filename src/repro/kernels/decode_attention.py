"""Batched GQA decode attention over a (ring) KV cache — Pallas TPU kernel.

τ_decode in Eq. 4 is dominated by streaming the KV cache from HBM (one
query token per request, arithmetic intensity ≈ 1); the kernel therefore
blocks over the cache axis with a running softmax so each (bw, d) KV tile
is touched exactly once, and processes all G = Hq/Hkv query heads of one
kv head per tile to amortize the stream (the G×D query block sits in VMEM
for the whole sweep).

Grid: (B, Hkv, nw) with the cache-block axis sequential; masking comes from
``slot_pos`` (absolute position per cache slot; -1 = empty), which makes
full, windowed, and ring caches all look identical to the kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(q_pos_ref, slot_pos_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, window: Optional[int],
            nw: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = q_pos_ref[0]         # () int32
    slot_pos = slot_pos_ref[0, :]  # (bw,)
    q = q_ref[0, 0].astype(jnp.float32)   # (G, D)
    k = k_ref[0, :, 0].astype(jnp.float32)  # (bw, D)
    v = v_ref[0, :, 0].astype(jnp.float32)  # (bw, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (G,bw)
    mask = (slot_pos >= 0) & (slot_pos <= q_pos)
    if window is not None:
        mask = mask & (q_pos - slot_pos < window)
    s = jnp.where(mask[None, :], s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == nw - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / l_scr[...][:, None]).astype(o_ref.dtype)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     slot_pos: jnp.ndarray, q_pos: jnp.ndarray,
                     window: Optional[int] = None, scale: Optional[float] = None,
                     block_w: int = 512, interpret: bool = False) -> jnp.ndarray:
    """q (B,Hq,D); k/v_cache (B,W,Hkv,D); slot_pos (B,W); q_pos (B,).
    Returns (B,Hq,D)."""
    B, Hq, D = q.shape
    W, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bw = min(block_w, W)
    assert W % bw == 0, "cache width must divide block_w"
    nw = W // bw

    qg = q.reshape(B, Hkv, G, D)
    kernel = functools.partial(_kernel, scale=scale, window=window, nw=nw)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, nw),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, j: (b,)),              # q_pos
            pl.BlockSpec((1, bw), lambda b, h, j: (b, j)),         # slot_pos
            pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, bw, 1, D), lambda b, h, j: (b, j, h, 0)),
            pl.BlockSpec((1, bw, 1, D), lambda b, h, j: (b, j, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_pos.astype(jnp.int32), slot_pos.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(B, Hq, D)
