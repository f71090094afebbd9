"""Device-side paged KV cache: a shared page pool + per-row block tables.

Layout (cf. vLLM's PagedAttention, adapted to the repo's slot_pos
convention):

  * ``k_pages``/``v_pages`` — (L, P, pg, Hkv, D), or (L, P, pg, Hkv·D) as
    ``StaticEngine`` stores them lane-dense (``kernels.ref.pool_view``):
    P physical pages of ``pg`` token slots each, shared by all batch rows
    (page 0 is the null page, see ``kvcache.allocator``);
  * ``block_table`` — (B, nb) int32: logical block j of row b lives in
    physical page ``block_table[b, j]`` (0 = unused → null page);
  * ``slot_pos`` — (B, nb·pg) int32: absolute position stored in each
    *logical* slot, -1 = empty — the exact masking convention of the dense
    ``models.attention.KVCache``, so full, ring, and paged caches all look
    identical to the attention math and the Pallas kernels.

A row's logical cache is the gather ``k_pages[block_table[b]]`` reshaped
to (nb·pg, Hkv, D); the Pallas kernel streams that gather page by page
through scalar-prefetched block tables instead of materializing it.
"""
from __future__ import annotations

from typing import Any, List, NamedTuple, Tuple

import jax.numpy as jnp
import numpy as np


class PagedKVCache(NamedTuple):
    """Per-model paged KV cache; k/v carry a leading layer axis."""

    k_pages: jnp.ndarray     # (L, P, pg, Hkv, D) or (L, P, pg, Hkv·D)
    v_pages: jnp.ndarray     # (L, P, pg, Hkv, D) or (L, P, pg, Hkv·D)
    block_table: jnp.ndarray  # (B, nb) int32 physical page per logical block
    slot_pos: jnp.ndarray    # (B, nb·pg) int32 absolute position, -1 empty
    lengths: jnp.ndarray     # (B,) int32 real (unpadded) input lengths

    @property
    def page_tokens(self) -> int:
        return self.k_pages.shape[2]

    @property
    def window(self) -> int:
        """Logical cache width per row (matches dense ``KVCache.window``)."""
        return self.block_table.shape[1] * self.k_pages.shape[2]

    @property
    def n_pages(self) -> int:
        """Physical pages including the null page."""
        return self.k_pages.shape[1]


def init_paged_kv_cache(n_layers: int, batch: int, n_pages: int,
                        page_tokens: int, max_blocks_per_row: int,
                        n_kv: int, head_dim: int, dtype: Any) -> PagedKVCache:
    """``n_pages`` usable pages; one extra null page (id 0) is added."""
    P = n_pages + 1
    return PagedKVCache(
        k_pages=jnp.zeros((n_layers, P, page_tokens, n_kv, head_dim), dtype),
        v_pages=jnp.zeros((n_layers, P, page_tokens, n_kv, head_dim), dtype),
        block_table=jnp.zeros((batch, max_blocks_per_row), jnp.int32),
        slot_pos=jnp.full((batch, max_blocks_per_row * page_tokens), -1,
                          jnp.int32),
        lengths=jnp.zeros((batch,), jnp.int32),
    )


def write_prefill_pages(cache: PagedKVCache, row: int, page_ids: List[int],
                        k: jnp.ndarray, v: jnp.ndarray,
                        prefill_slot_pos: jnp.ndarray, length: int
                        ) -> PagedKVCache:
    """Scatter one request's prefill K/V (L, T, Hkv, D) into its pages.

    ``page_ids`` are the allocator's pages for this row (first block first);
    T must fit in them.  ``prefill_slot_pos`` (T,) carries the absolute
    position per prefill slot (pads -1), exactly as the dense prefill
    produces it.
    """
    L, _, pg, Hkv, D = cache.k_pages.shape
    T = k.shape[1]
    n_used = len(page_ids)
    pad = n_used * pg - T
    if pad < 0:
        raise ValueError(f"{T} prefill slots exceed {n_used} pages of {pg}")
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    ids = jnp.asarray(page_ids, jnp.int32)
    nb = cache.block_table.shape[1]
    bt_row = np.zeros((nb,), np.int32)
    bt_row[:n_used] = page_ids
    sp_row = np.full((nb * pg,), -1, np.int32)
    sp_row[:T] = np.asarray(prefill_slot_pos, np.int32)
    return cache._replace(
        k_pages=cache.k_pages.at[:, ids].set(
            kp.reshape(L, n_used, pg, Hkv, D)),
        v_pages=cache.v_pages.at[:, ids].set(
            vp.reshape(L, n_used, pg, Hkv, D)),
        block_table=cache.block_table.at[row].set(jnp.asarray(bt_row)),
        slot_pos=cache.slot_pos.at[row].set(jnp.asarray(sp_row)),
        lengths=cache.lengths.at[row].set(length),
    )


def append_prefill(cache: PagedKVCache, row: int, page_ids: List[int],
                   k: jnp.ndarray, v: jnp.ndarray, start: int, n_new: int
                   ) -> PagedKVCache:
    """Append K/V for tokens ``[start, start + n_new)`` of one row.

    The *compact* layout (logical slot == absolute position, no pad
    slots) used by the persistent-paged engine: ``page_ids`` is the row's
    full page list (first block first), ``k``/``v`` are (L, n_new, Hkv, D)
    for just the new tokens.  Slots before ``start`` (the retained
    prefix) are untouched; slot_pos/lengths/block_table are refreshed for
    the row.  This is the host-side twin of the batched in-graph path
    (``models.transformer.prefill_paged``) — used for single-row delta
    prefills and as the reference in tests.
    """
    L, _, pg, Hkv, D = cache.k_pages.shape
    n_total = start + n_new
    if n_total > len(page_ids) * pg:
        raise ValueError(f"{n_total} slots exceed {len(page_ids)} pages "
                         f"of {pg}")
    nb = cache.block_table.shape[1]
    if len(page_ids) > nb:
        raise ValueError(f"{len(page_ids)} pages exceed the {nb}-block table")
    k_pages, v_pages = cache.k_pages, cache.v_pages
    for t in range(n_new):
        slot = start + t
        page, off = page_ids[slot // pg], slot % pg
        k_pages = k_pages.at[:, page, off].set(k[:, t])
        v_pages = v_pages.at[:, page, off].set(v[:, t])
    bt_row = np.zeros((nb,), np.int32)
    bt_row[:len(page_ids)] = page_ids
    sp_row = np.full((nb * pg,), -1, np.int32)
    sp_row[:n_total] = np.arange(n_total)
    return cache._replace(
        k_pages=k_pages, v_pages=v_pages,
        block_table=cache.block_table.at[row].set(jnp.asarray(bt_row)),
        slot_pos=cache.slot_pos.at[row].set(jnp.asarray(sp_row)),
        lengths=cache.lengths.at[row].set(n_total),
    )


def batch_block_table(pages_per_row: List[List[int]], n_blocks: int
                      ) -> np.ndarray:
    """Assemble a (B, nb) block table from per-row page lists (padded with
    the null page) — how the persistent engine remaps each batch member's
    retained pages into the dispatched batch's table."""
    B = len(pages_per_row)
    bt = np.zeros((B, n_blocks), np.int32)
    for b, pages in enumerate(pages_per_row):
        if len(pages) > n_blocks:
            raise ValueError(f"row {b}: {len(pages)} pages exceed the "
                             f"{n_blocks}-block table")
        bt[b, :len(pages)] = pages
    return bt


def batch_slot_pos(lengths: List[int], n_blocks: int, page_tokens: int
                   ) -> np.ndarray:
    """(B, nb·pg) slot_pos for the compact layout: slot s of row b holds
    absolute position s for s < lengths[b], -1 (masked) beyond."""
    W = n_blocks * page_tokens
    slots = np.arange(W, dtype=np.int32)[None]
    lens = np.asarray(lengths, np.int32)[:, None]
    return np.where(slots < lens, slots, -1).astype(np.int32)


def clear_row(cache: PagedKVCache, row: int) -> PagedKVCache:
    """Evict a row: point its blocks at the null page and mask every slot.

    The page contents are left dirty — once unmapped and masked they are
    unreachable, and the allocator will hand the pages to a new owner whose
    prefill overwrites them.
    """
    return cache._replace(
        block_table=cache.block_table.at[row].set(0),
        slot_pos=cache.slot_pos.at[row].set(-1),
    )


def gather_row(cache: PagedKVCache, row: int) -> Tuple[np.ndarray, np.ndarray]:
    """Materialize row's logical (L, nb·pg, Hkv, D) K/V — debug/test helper."""
    L, _, pg, Hkv, D = cache.k_pages.shape
    bt = np.asarray(cache.block_table[row])
    k = np.asarray(cache.k_pages[:, bt]).reshape(L, -1, Hkv, D)
    v = np.asarray(cache.v_pages[:, bt]).reshape(L, -1, Hkv, D)
    return k, v
