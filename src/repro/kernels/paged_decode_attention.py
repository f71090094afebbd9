"""Batched GQA decode attention over a *paged* KV cache — Pallas TPU kernel.

Same roofline as ``kernels.decode_attention`` (τ_decode in Eq. 4 is
dominated by streaming the cache from HBM), but K/V live in a shared page
pool instead of per-row contiguous regions: logical block j of row b is
physical page ``block_table[b, j]``.  The block table is passed as a
*scalar-prefetch* operand (``pltpu.PrefetchScalarGridSpec``) so the page
indirection happens in the BlockSpec index maps — each (pg, Hkv, D) K/V
page is DMA'd straight from its physical page, touched exactly once, and
folded into a running softmax per KV head.  No (B, W) contiguous gather is
ever materialized.

Grid: (B, nb) with the page axis sequential.  A page block spans every KV
head, so its last two dims equal the pool's (Hkv, D) — the TPU tiling rule
— and all G = Hq/Hkv query heads of each KV head ride along.  ``q_pos`` is
a scalar-prefetch operand; ``slot_pos`` is viewed as (B, nb, 1, pg) so one
page's slots form a block whose last two dims equal the array's.  Masking
comes from ``slot_pos`` over *logical* slots (absolute position per slot,
-1 = empty) — the same convention as the dense and ring caches, so the
null-page padding of short rows (block id 0) is masked rather than
special-cased and full/ring/paged layouts look identical to the kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _softmax_fold(q, k, v, mask, m_scr, l_scr, acc_scr, h: int,
                  scale: float) -> None:
    """Fold one page of KV head ``h`` into the running softmax state.

    q (G, D) f32; k/v (pg, D) f32; mask (1, pg) bool; the scratch refs hold
    per-head running max / sum (Hkv, G, 1) and accumulator (Hkv, G, D)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)                      # (G, pg)
    m_prev = m_scr[h]                                    # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[h] = m_new


def _kernel(bt_ref, q_pos_ref, slot_pos_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, window: Optional[int],
            nb: int, n_kv: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = q_pos_ref[b]              # () int32
    slot_pos = slot_pos_ref[0, 0]     # (1, pg) — logical slots of page j
    mask = (slot_pos >= 0) & (slot_pos <= q_pos)
    if window is not None:
        mask = mask & (q_pos - slot_pos < window)
    for h in range(n_kv):
        _softmax_fold(q_ref[0, h].astype(jnp.float32),
                      k_ref[0, :, h, :].astype(jnp.float32),  # via bt_ref
                      v_ref[0, :, h, :].astype(jnp.float32),
                      mask, m_scr, l_scr, acc_scr, h, scale)

    @pl.when(j == nb - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def paged_decode_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, block_table: jnp.ndarray,
                           slot_pos: jnp.ndarray, q_pos: jnp.ndarray,
                           window: Optional[int] = None,
                           scale: Optional[float] = None,
                           interpret: bool = False) -> jnp.ndarray:
    """q (B,Hq,D); k/v_pages (P,pg,Hkv,D); block_table (B,nb) int32 physical
    page per logical block (0 = null page, fully masked via slot_pos);
    slot_pos (B,nb·pg); q_pos (B,).  Returns (B,Hq,D)."""
    B, Hq, D = q.shape
    pg, Hkv = k_pages.shape[1], k_pages.shape[2]
    nb = block_table.shape[1]
    assert slot_pos.shape == (B, nb * pg), (slot_pos.shape, (B, nb * pg))
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5

    qg = q.reshape(B, Hkv, G, D)
    sp = slot_pos.astype(jnp.int32).reshape(B, nb, 1, pg)
    kernel = functools.partial(_kernel, scale=scale, window=window, nb=nb,
                               n_kv=Hkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_table feeds the K/V index maps
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, 1, 1, pg), lambda b, j, bt, qp: (b, j, 0, 0)),
            pl.BlockSpec((1, Hkv, G, D), lambda b, j, bt, qp: (b, 0, 0, 0)),
            pl.BlockSpec((1, pg, Hkv, D),
                         lambda b, j, bt, qp: (bt[b, j], 0, 0, 0)),  # k page
            pl.BlockSpec((1, pg, Hkv, D),
                         lambda b, j, bt, qp: (bt[b, j], 0, 0, 0)),  # v page
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, D),
                               lambda b, j, bt, qp: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table.astype(jnp.int32), q_pos.astype(jnp.int32), sp, qg,
      k_pages, v_pages)
    return out.reshape(B, Hq, D)
