"""Prefill K/V page-pool scatter — Pallas TPU kernel.

The persistent-paged serving path (`engine.static_engine`, kv_retain=
"request") keeps K/V in a shared page pool across slices, so prefill must
land its K/V *in pages* rather than in a per-batch contiguous buffer.
This kernel is the write half of that path: the page-gather twin of
``kernels.paged_decode_attention`` — one grid step per (row, logical
block), with the block table and each row's left-pad offset as
scalar-prefetch operands so the physical destination page is resolved in
the output BlockSpec index map and each (pg, Hkv, D) page is DMA'd exactly
once.  The page pools are updated *in place* via ``input_output_aliases``
(no copy of a pool that is most of HBM).

Masking discipline: tokens of logical block j of row b live at padded
input positions ``pad_b + j·pg .. pad_b + (j+1)·pg - 1`` (left padding),
so a block copy past the row's real length writes garbage into the tail
of its last owned page (or, for blocks past the row's page list, into the
null page 0) — both are unreachable, because readers mask by ``slot_pos``
and decode overwrites a slot before ever unmasking it.  The pure-jnp
oracle is ``kernels.ref.paged_prefill_write_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(bt_ref, pad_ref, k_ref, v_ref, _ko_alias, _vo_alias,
            ko_ref, vo_ref, *, pg: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    start = pad_ref[b] + j * pg  # row's tokens start after its left pad
    ko_ref[...] = k_ref[:, pl.ds(start, pg)]
    vo_ref[...] = v_ref[:, pl.ds(start, pg)]


def paged_prefill_write(k_new: jnp.ndarray, v_new: jnp.ndarray,
                        pad: jnp.ndarray, block_table: jnp.ndarray,
                        k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                        interpret: bool = False):
    """k/v_new (B,T,Hkv,D) left-padded prefill K/V; pad (B,) int32 left-pad
    width per row (= T - len); block_table (B,nb); k/v_pages (P,pg,Hkv,D).
    Token at padded index ``pad_b + s`` lands in page
    ``block_table[b, s // pg]`` at offset ``s % pg``.  Returns the updated
    (k_pages, v_pages)."""
    B, T, Hkv, D = k_new.shape
    P, pg = k_pages.shape[0], k_pages.shape[1]
    nb = block_table.shape[1]
    # block reads start at pad_b + j*pg with pad_b <= T, so the last block
    # can read up to T + nb*pg (its tail slots are masked garbage); pad the
    # token axis so every read stays in bounds
    overhang = nb * pg
    kp = jnp.pad(k_new, ((0, 0), (0, overhang), (0, 0), (0, 0)))
    vp = jnp.pad(v_new, ((0, 0), (0, overhang), (0, 0), (0, 0)))
    Tp = T + overhang

    kernel = functools.partial(_kernel, pg=pg)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_table + pad feed the index maps
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, Tp, Hkv, D), lambda b, j, bt, pad: (b, 0, 0, 0)),
            pl.BlockSpec((1, Tp, Hkv, D), lambda b, j, bt, pad: (b, 0, 0, 0)),
            # aliased pool inputs: same tile the kernel writes (never read)
            pl.BlockSpec((1, pg, Hkv, D),
                         lambda b, j, bt, pad: (bt[b, j], 0, 0, 0)),
            pl.BlockSpec((1, pg, Hkv, D),
                         lambda b, j, bt, pad: (bt[b, j], 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, pg, Hkv, D),
                         lambda b, j, bt, pad: (bt[b, j], 0, 0, 0)),
            pl.BlockSpec((1, pg, Hkv, D),
                         lambda b, j, bt, pad: (bt[b, j], 0, 0, 0)),
        ],
        scratch_shapes=[],
    )
    out_k, out_v = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        # operand indices count the scalar-prefetch args: (bt, pad, k, v,
        # k_pages, v_pages) -> pools are operands 4 and 5
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table.astype(jnp.int32), pad.astype(jnp.int32), kp, vp,
      k_pages, v_pages)
    return out_k, out_v
