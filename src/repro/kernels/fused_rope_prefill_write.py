"""Fused RoPE + prefill K/V page-pool scatter — Pallas TPU kernel.

The unfused persistent-paged prefill makes two passes over K: rotate in
plain jnp (materializing a rotated-K tensor the size of the prompt), then
call ``kernels.paged_prefill_write`` to copy it into pages.  This kernel
folds both into ONE pass: each (row, logical block) grid step loads the
raw projected K tile, rotates it in-register at its *destination slot*
positions (compact paged layout: logical slot == absolute position, so
the rotation is read from a cos/sin table block indexed by the grid
alone: ``kernels.ref.rope_cos_sin``, the oracle's own values), and DMA's
the rotated K plus the untouched V straight into their physical pages via
``input_output_aliases`` — no rotated-K tensor ever exists in HBM.

Addressing: token destined for logical slot ``s`` of row ``b`` sits at
padded input index ``shift_b + s`` where ``shift_b = pad_b - start_b``
(``start_b`` = the row's first novel slot: 0 for a full prefill, the
resident-prefix length for a shared-prefix tail).  Slots below
``start_b`` belong to retained/shared pages and are passed through from
the aliased pool input unchanged.  The Pallas path requires ``start_b``
to be page-aligned (the engine shares whole pages only — PR 7 contract);
the jnp oracle ``kernels.ref.fused_rope_prefill_write_ref`` handles
arbitrary offsets.  Tail slots past the row's real length copy garbage
into the last owned page (or null page 0) — masked by ``slot_pos``
until overwritten, never observable, same discipline as the unfused
write kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_rope_decode_append import _rope
from repro.kernels.ref import rope_cos_sin


def _kernel(bt_ref, shift_ref, start_ref, k_ref, v_ref, cos_ref, sin_ref,
            k_in, v_in, ko_ref, vo_ref, *, pg: int, rd_max: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    base = j * pg  # first logical slot of this block == absolute position
    # tokens for slots [base, base+pg) sit at padded indices shift_b + slot;
    # fully-passthrough blocks (below start) may index before the buffer —
    # clamp; their loaded data is discarded by the novel mask below
    rd = jnp.clip(shift_ref[b] + base, 0, rd_max)
    k = k_ref[0, pl.ds(rd, pg)].astype(jnp.float32)  # (pg, Hkv, D)
    v = v_ref[0, pl.ds(rd, pg)]                      # (pg, Hkv, D)
    # cos/sin (pg, 1, D/2) at the destination slots (== absolute positions
    # in the compact paged layout)
    kr = _rope(k, cos_ref[...], sin_ref[...])

    slot = base + jax.lax.broadcasted_iota(jnp.int32, (pg, 1, 1), 0)
    novel = slot >= start_ref[b]  # (pg, 1, 1)
    ko_ref[0] = jnp.where(novel, kr.astype(ko_ref.dtype), k_in[0])
    vo_ref[0] = jnp.where(novel, v, v_in[0])


def fused_rope_prefill_write(k_new: jnp.ndarray, v_new: jnp.ndarray,
                             shift: jnp.ndarray, start: jnp.ndarray,
                             block_table: jnp.ndarray, k_pages: jnp.ndarray,
                             v_pages: jnp.ndarray, theta: float = 10000.0,
                             interpret: bool = False):
    """k/v_new (B,T,Hkv,D) left-padded *unrotated* prefill K/V;
    shift (B,) int32 = ``pad - start`` (read offset: slot ``s`` reads
    padded index ``shift + s``); start (B,) int32 first novel slot
    (page-aligned; slots below it are preserved from the pool);
    block_table (B,nb); k/v_pages (P,pg,Hkv,D).  Rotates K at its
    destination position in-register and returns the updated
    (k_pages, v_pages) in one pass."""
    B, T, Hkv, D = k_new.shape
    P, pg = k_pages.shape[0], k_pages.shape[1]
    nb = block_table.shape[1]
    # reads span shift_b + slot with slot < nb*pg and shift_b <= T, so pad
    # the token axis like the unfused kernel to keep every load in bounds
    overhang = nb * pg
    kp = jnp.pad(k_new, ((0, 0), (0, overhang), (0, 0), (0, 0)))
    vp = jnp.pad(v_new, ((0, 0), (0, overhang), (0, 0), (0, 0)))
    Tp = T + overhang

    # the rotation tables of every logical slot, viewed (nb*pg, 1, D/2) so
    # a block of pg slots keeps the array's last two dims
    cos, sin = (t.reshape(nb * pg, 1, D // 2)
                for t in rope_cos_sin(jnp.arange(nb * pg), D, theta))
    kernel = functools.partial(_kernel, pg=pg, rd_max=Tp - pg)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # block_table + shift + start
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, Tp, Hkv, D),
                         lambda b, j, bt, sh, st: (b, 0, 0, 0)),
            pl.BlockSpec((1, Tp, Hkv, D),
                         lambda b, j, bt, sh, st: (b, 0, 0, 0)),
            pl.BlockSpec((pg, 1, D // 2), lambda b, j, bt, sh, st: (j, 0, 0)),
            pl.BlockSpec((pg, 1, D // 2), lambda b, j, bt, sh, st: (j, 0, 0)),
            # aliased pool inputs: read for passthrough of non-novel slots
            pl.BlockSpec((1, pg, Hkv, D),
                         lambda b, j, bt, sh, st: (bt[b, j], 0, 0, 0)),
            pl.BlockSpec((1, pg, Hkv, D),
                         lambda b, j, bt, sh, st: (bt[b, j], 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, pg, Hkv, D),
                         lambda b, j, bt, sh, st: (bt[b, j], 0, 0, 0)),
            pl.BlockSpec((1, pg, Hkv, D),
                         lambda b, j, bt, sh, st: (bt[b, j], 0, 0, 0)),
        ],
        scratch_shapes=[],
    )
    out_k, out_v = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        # operand indices count the scalar-prefetch args: (bt, shift, start,
        # k, v, cos, sin, k_pages, v_pages) -> pools are operands 7 and 8
        input_output_aliases={7: 0, 8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table.astype(jnp.int32), shift.astype(jnp.int32),
      start.astype(jnp.int32), kp, vp, cos, sin, k_pages, v_pages)
    return out_k, out_v
