"""Flash attention for the prefill phase — Pallas TPU kernel.

The static-batching prefill is the compute hot spot SCLS schedules around
(T_prefill in Eq. 3 — recomputed at every reschedule), so it gets a proper
TPU kernel: blockwise causal attention with running-softmax accumulation.

TPU adaptation (DESIGN.md §4): Q/K tiles are (128, head_dim) MXU-aligned;
the grid is (B, Hq, nq, nk) with the trailing kv-block axis sequential so
the (bq, d) fp32 accumulator + (bq,) running max/sum live in VMEM scratch
across kv steps.  Left-pad masking and sliding windows are folded into the
block mask via per-token positions; fully-masked kv blocks are skipped
(block-level causal early-out).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(pos_q_ref, pos_k_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, window: Optional[int],
            bq: int, bk: int, nk: int):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos_q = pos_q_ref[0, :]  # (bq,)
    pos_k = pos_k_ref[0, :]  # (bk,)
    # block-level early out: the whole kv block is strictly after every query
    block_live = jnp.min(pos_k) <= jnp.max(pos_q)

    @pl.when(block_live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = (pos_k[None, :] >= 0) & (pos_k[None, :] <= pos_q[:, None])
        if window is not None:
            mask = mask & (pos_q[:, None] - pos_k[None, :] < window)
        # allow self-slot for fully-padded query rows (avoids 0/0)
        qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        ki = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = mask | (qi == ki)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / l_scr[...][:, None]).astype(o_ref.dtype)


def flash_prefill(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  positions: jnp.ndarray, window: Optional[int] = None,
                  scale: Optional[float] = None, block_q: int = 128,
                  block_k: int = 128, interpret: bool = False) -> jnp.ndarray:
    """q (B,T,Hq,D); k/v (B,T,Hkv,D); positions (B,T). Returns (B,T,Hq,D)."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bq = min(block_q, T)
    bk = min(block_k, T)
    assert T % bq == 0 and T % bk == 0, "T must divide the block sizes"
    nq, nk = T // bq, T // bk

    # layout: (B, H, T, D) blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_kernel, scale=scale, window=window,
                               bq=bq, bk=bk, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq), lambda b, h, i, j: (b, i)),       # pos_q
            pl.BlockSpec((1, bk), lambda b, h, i, j: (b, j)),       # pos_k
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, T, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # running max
            pltpu.VMEM((bq,), jnp.float32),      # running denom
            pltpu.VMEM((bq, D), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(positions.astype(jnp.int32), positions.astype(jnp.int32), qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
