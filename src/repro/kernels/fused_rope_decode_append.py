"""Fused RoPE + page append + paged decode attention — Pallas TPU kernel.

The unfused decode step is three passes: rotate the new q/k token in
plain jnp, scatter the rotated k (and v) into its page slot with an XLA
scatter, then launch ``kernels.paged_decode_attention`` to stream every
page back out of HBM.  This kernel does all of it in ONE launch: each
(row, page) grid step rotates the new token in-register (by the cos/sin
of its angle at ``q_pos``, ``kernels.ref.rope_cos_sin``), injects it into
the current page's K/V tile *before* scoring (so attention sees the post-write state —
exactly the unfused ordering), folds the tile into the running softmax,
and DMA's the modified tile back through ``input_output_aliases``.  The
new token's K/V thus lands in the pool as a side effect of the attention
stream it was already paying for.  Blocks are laid out as in
``kernels.paged_decode_attention``: a page block spans every KV head, and
``slot_pos`` is viewed as (B, nb, 1, pg).

Pages of different rows are disjoint by the allocator contract, so the
per-(b,j) aliased page writes never collide — except on the null page 0
shared by short rows' unowned blocks, whose contents are never observable
(masked by ``slot_pos``), same discipline as the write kernel.  The jnp
oracle is ``kernels.ref.fused_rope_decode_append_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_decode_attention import NEG_INF, _softmax_fold
from repro.kernels.ref import rope_cos_sin


def _rope(x, cos, sin):
    """Half-rotation RoPE of x (..., D) f32 by cos/sin (..., D/2),
    broadcast against x's halves — identical arithmetic to
    ``models.common.apply_rope``."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _kernel(bt_ref, slot_ref, q_pos_ref, slot_pos_ref, q_ref, kn_ref, vn_ref,
            cos_ref, sin_ref, k_in, v_in, ko_ref, vo_ref, o_ref, m_scr,
            l_scr, acc_scr, *, scale: float, window: Optional[int], nb: int,
            pg: int, n_kv: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = q_pos_ref[b]              # () int32 — absolute position of the token
    slot = slot_ref[b]                # () int32 — its destination logical slot
    slot_pos = slot_pos_ref[0, 0]     # (1, pg) — logical slots of page j

    cos, sin = cos_ref[0], sin_ref[0]  # (1, D/2) — the angles at q_pos
    knr = _rope(kn_ref[0].astype(jnp.float32), cos, sin)  # (Hkv, D) new K

    # inject the rotated new token into this page's tile iff it lives here,
    # BEFORE scoring — attention reads the post-append cache state
    row = jax.lax.broadcasted_iota(jnp.int32, (pg, 1, 1), 0)
    hit = (row == slot % pg) & (j == slot // pg)      # (pg, 1, 1)
    ko_ref[0] = jnp.where(hit, knr.astype(ko_ref.dtype)[None], k_in[0])
    vo_ref[0] = jnp.where(hit, vn_ref[0].astype(vo_ref.dtype)[None], v_in[0])

    mask = (slot_pos >= 0) & (slot_pos <= q_pos)
    if window is not None:
        mask = mask & (q_pos - slot_pos < window)
    for h in range(n_kv):
        _softmax_fold(_rope(q_ref[0, h].astype(jnp.float32), cos, sin),
                      ko_ref[0, :, h, :].astype(jnp.float32),
                      vo_ref[0, :, h, :].astype(jnp.float32),
                      mask, m_scr, l_scr, acc_scr, h, scale)

    @pl.when(j == nb - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def fused_rope_decode_append(q: jnp.ndarray, k_new: jnp.ndarray,
                             v_new: jnp.ndarray, block_table: jnp.ndarray,
                             slot_pos: jnp.ndarray, slots: jnp.ndarray,
                             q_pos: jnp.ndarray, k_pages: jnp.ndarray,
                             v_pages: jnp.ndarray, theta: float = 10000.0,
                             window: Optional[int] = None,
                             scale: Optional[float] = None,
                             interpret: bool = False):
    """q (B,Hq,D) and k/v_new (B,Hkv,D) *unrotated* new-token projections;
    block_table (B,nb); slot_pos (B,nb·pg) already marking the new token's
    slot (it must attend to itself); slots (B,) destination logical slot;
    q_pos (B,) absolute position (== slots in the compact layout);
    k/v_pages (P,pg,Hkv,D).  Returns (out (B,Hq,D), k_pages, v_pages)."""
    B, Hq, D = q.shape
    pg, Hkv = k_pages.shape[1], k_pages.shape[2]
    nb = block_table.shape[1]
    assert slot_pos.shape == (B, nb * pg), (slot_pos.shape, (B, nb * pg))
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5

    qg = q.reshape(B, Hkv, G, D)
    sp = slot_pos.astype(jnp.int32).reshape(B, nb, 1, pg)
    cos, sin = (t.reshape(B, 1, D // 2)
                for t in rope_cos_sin(q_pos, D, theta))
    kernel = functools.partial(_kernel, scale=scale, window=window, nb=nb,
                               pg=pg, n_kv=Hkv)
    page = lambda b, j, bt, sl, qp: (bt[b, j], 0, 0, 0)  # noqa: E731
    row = lambda b, j, bt, sl, qp: (b, 0, 0, 0)  # noqa: E731
    row3 = lambda b, j, bt, sl, qp: (b, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # block_table + slots + q_pos
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, 1, 1, pg), lambda b, j, bt, sl, qp: (b, j, 0, 0)),
            pl.BlockSpec((1, Hkv, G, D), row),
            pl.BlockSpec((1, Hkv, D), row3),
            pl.BlockSpec((1, Hkv, D), row3),
            pl.BlockSpec((1, 1, D // 2), row3),  # cos at q_pos
            pl.BlockSpec((1, 1, D // 2), row3),  # sin at q_pos
            # aliased pool inputs: read-modify-write of the whole page
            pl.BlockSpec((1, pg, Hkv, D), page),
            pl.BlockSpec((1, pg, Hkv, D), page),
        ],
        out_specs=[
            pl.BlockSpec((1, pg, Hkv, D), page),
            pl.BlockSpec((1, pg, Hkv, D), page),
            pl.BlockSpec((1, Hkv, G, D), row),
        ],
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
        ],
    )
    out_k, out_v, out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype)],
        # operand indices count the scalar-prefetch args: (bt, slots, q_pos,
        # slot_pos, q, k_new, v_new, cos, sin, k_pages, v_pages) -> pools
        # are 9 and 10
        input_output_aliases={9: 0, 10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table.astype(jnp.int32), slots.astype(jnp.int32),
      q_pos.astype(jnp.int32), sp, qg, k_new, v_new, cos, sin, k_pages,
      v_pages)
    return out.reshape(B, Hq, D), out_k, out_v
