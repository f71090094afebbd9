"""Attention layers with KV caches for static-batch serving.

Cache design (see DESIGN.md §6):
  * static batching left-pads the batch to ``L_i`` (bucketed), so all requests
    share cache slot indices: slot ``j`` is written by global step ``j`` for
    every batch row.  Real positions differ per row (left padding), so we keep
    ``slot_pos`` (B, W) with the absolute position stored in each slot
    (-1 = empty / pad).
  * the cache has exactly ``W = L_i + S`` slots for slice-level serving — the
    paper's memory model Eq. (5) — or ``W = window`` as a ring buffer for
    sliding-window attention (long-context decode).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import (ModelConfig, Params, apply_rope, dense_param,
                                 dense_apply)

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


class KVCache(NamedTuple):
    """Per-model KV cache; k/v carry a leading layer axis."""

    k: jnp.ndarray  # (L, B, W, Hkv, D)
    v: jnp.ndarray  # (L, B, W, Hkv, D)
    slot_pos: jnp.ndarray  # (B, W) int32 absolute position per slot, -1 empty
    write_idx: jnp.ndarray  # () int32 — next global slot counter
    lengths: jnp.ndarray  # (B,) int32 — real (unpadded) input lengths

    @property
    def window(self) -> int:
        return self.k.shape[2]


def init_kv_cache(n_layers: int, batch: int, window: int, n_kv: int, head_dim: int,
                  dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((n_layers, batch, window, n_kv, head_dim), dtype),
        v=jnp.zeros((n_layers, batch, window, n_kv, head_dim), dtype),
        slot_pos=jnp.full((batch, window), -1, jnp.int32),
        write_idx=jnp.zeros((), jnp.int32),
        lengths=jnp.zeros((batch,), jnp.int32),
    )


# ---------------------------------------------------------------------------
# core attention math (jnp reference; Pallas kernels mirror this in kernels/)
# ---------------------------------------------------------------------------
def gqa_attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
               mask: jnp.ndarray, scale: float) -> jnp.ndarray:
    """q (B,T,Hq,D), k/v (B,S,Hkv,D), mask (B,1,T,S) bool -> (B,T,Hq,D)."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qr = q.reshape(B, T, Hkv, G, D)
    # f32 accumulation WITHOUT materializing f32 copies of K/V (the cache
    # can be tens of GB; astype would double-buffer it — §Perf iteration C2)
    scores = jnp.einsum("bthgd,bshd->bhgts", qr, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, :, None], scores, NEG_INF)  # (B,1,1,T,S) bcast
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhgts,bshd->bthgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, T, Hq, v.shape[-1]).astype(q.dtype)  # Dv may differ (MLA)


def prefill_mask(positions: jnp.ndarray, window: Optional[int]) -> jnp.ndarray:
    """Causal mask over left-padded prefill. positions (B,T) with pads < 0."""
    pq = positions[:, :, None]  # (B,T,1)
    pk = positions[:, None, :]  # (B,1,S)
    m = (pk >= 0) & (pk <= pq)
    if window is not None:
        m = m & (pq - pk < window)
    # pad query rows would be fully masked -> allow the diagonal to avoid NaN
    T = positions.shape[1]
    m = m | jnp.eye(T, dtype=bool)[None]
    return m[:, None]  # (B,1,T,S)


def decode_mask(q_pos: jnp.ndarray, slot_pos: jnp.ndarray,
                window: Optional[int]) -> jnp.ndarray:
    """q_pos (B,), slot_pos (B,W) -> (B,1,1,W)."""
    m = (slot_pos >= 0) & (slot_pos <= q_pos[:, None])
    if window is not None:
        m = m & (q_pos[:, None] - slot_pos < window)
    return m[:, None, None]


# ---------------------------------------------------------------------------
# chunked attention (XLA fallback for long sequences)
#
# Materializing (B,·,T,S) scores at T=4k–32k would blow HBM; the q axis is
# scanned in blocks of `block_q`, with masks rebuilt per block from positions
# (never materialized at (T,S)).  The Pallas flash kernel replaces this on
# real TPU runs; this path is what the dry-run lowers (DESIGN.md §4).
# ---------------------------------------------------------------------------
CHUNK_THRESHOLD = 2048  # use the chunked path at or above this many tokens
_DEFAULT_BLOCK_Q = 512


def _chunk_mask(pq: jnp.ndarray, pk: jnp.ndarray, window: Optional[int],
                prefix_len: int, valid_q=None, valid_k=None) -> jnp.ndarray:
    """pq (B,bq), pk (B,S) -> (B,bq,S) bool."""
    pqe, pke = pq[:, :, None], pk[:, None, :]
    if valid_k is not None:  # bidirectional (encoder / cross-attention)
        m = jnp.broadcast_to(valid_k[:, None, :], pqe.shape[:2] + (pk.shape[1],))
        if valid_q is not None:
            m = m | (~valid_q[:, :, None] & ~valid_k[:, None, :])
        return m
    m = (pke >= 0) & (pke <= pqe)
    if window is not None:
        m = m & (pqe - pke < window)
    if prefix_len:
        m = m | ((pke >= 0) & (pke < prefix_len) & (pqe >= 0))
    return m | ((pqe < 0) & (pke < 0))  # pads attend pads (NaN guard)


def gqa_attend_chunked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       scale: float, pos_q: jnp.ndarray, pos_k: jnp.ndarray,
                       window: Optional[int], prefix_len: int = 0,
                       valid_q=None, valid_k=None,
                       block_q: int = _DEFAULT_BLOCK_Q) -> jnp.ndarray:
    """Scan over q blocks; full K/V per block. Shapes as gqa_attend."""
    B, T, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    bq = min(block_q, T)
    while T % bq:
        bq //= 2
    nq = T // bq
    qr = q.reshape(B, nq, bq, Hkv, G, D).transpose(1, 0, 2, 3, 4, 5)
    pqr = pos_q.reshape(B, nq, bq).transpose(1, 0, 2)
    vqr = (valid_q.reshape(B, nq, bq).transpose(1, 0, 2)
           if valid_q is not None else None)

    def chunk(_, xs):
        if vqr is None:
            qc, pqc = xs
            vq = None
        else:
            qc, pqc, vq = xs
        s = jnp.einsum("bqhgd,bshd->bhgqs", qc, k,
                       preferred_element_type=jnp.float32) * scale
        m = _chunk_mask(pqc, pos_k, window, prefix_len, vq, valid_k)
        s = jnp.where(m[:, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqs,bshd->bqhgd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return None, o

    xs = (qr, pqr) if vqr is None else (qr, pqr, vqr)
    _, o = jax.lax.scan(chunk, None, xs)
    o = o.transpose(1, 0, 2, 3, 4, 5).reshape(B, T, Hq, v.shape[-1])  # Dv != Dq (MLA)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# attention layer
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    D, Hq, Hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    return {
        "wq": dense_param(kq, cfg.d_model, Hq * D, cfg.dtype, bias=cfg.qkv_bias),
        "wk": dense_param(kk, cfg.d_model, Hkv * D, cfg.dtype, bias=cfg.qkv_bias),
        "wv": dense_param(kv, cfg.d_model, Hkv * D, cfg.dtype, bias=cfg.qkv_bias),
        "wo": dense_param(ko, Hq * D, cfg.d_model, cfg.dtype),
    }


def _qkv(p: Params, x: jnp.ndarray, cfg: ModelConfig):
    B, T, _ = x.shape
    q = dense_apply(p["wq"], x).reshape(B, T, cfg.n_heads, cfg.head_dim)
    k = dense_apply(p["wk"], x).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    v = dense_apply(p["wv"], x).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def attention_forward(p: Params, x: jnp.ndarray, positions: jnp.ndarray,
                      cfg: ModelConfig, window: Optional[int],
                      mask: Optional[jnp.ndarray] = None,
                      prefix_len: int = 0,
                      valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Full-sequence (train / prefill without cache return) attention.

    Long sequences (T >= CHUNK_THRESHOLD) take the q-blocked path and build
    masks per block from ``positions`` / ``prefix_len`` / ``valid`` —
    callers should pass ``mask=None`` there."""
    q, k, v = _qkv(p, x, cfg)
    rp = jnp.maximum(positions, 0)
    q = apply_rope(q, rp, cfg.rope_theta)
    k = apply_rope(k, rp, cfg.rope_theta)
    scale = cfg.head_dim ** -0.5
    if x.shape[1] >= CHUNK_THRESHOLD:
        o = gqa_attend_chunked(q, k, v, scale, positions, positions, window,
                               prefix_len, valid_q=valid, valid_k=valid)
    else:
        if mask is None:
            mask = prefill_mask(positions, window)
        o = gqa_attend(q, k, v, mask, scale)
    return dense_apply(p["wo"], o.reshape(x.shape[0], x.shape[1], -1))


def attention_prefill(p: Params, x: jnp.ndarray, positions: jnp.ndarray,
                      cfg: ModelConfig, window: Optional[int], cache_window: int,
                      mask: Optional[jnp.ndarray] = None, prefix_len: int = 0,
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Prefill that also returns per-layer (k_cache, v_cache) of width W."""
    B, T, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    rp = jnp.maximum(positions, 0)
    q = apply_rope(q, rp, cfg.rope_theta)
    k = apply_rope(k, rp, cfg.rope_theta)
    scale = cfg.head_dim ** -0.5
    if T >= CHUNK_THRESHOLD:
        o = gqa_attend_chunked(q, k, v, scale, positions, positions, window,
                               prefix_len)
    else:
        if mask is None:
            mask = prefill_mask(positions, window)
        o = gqa_attend(q, k, v, mask, scale)
    out = dense_apply(p["wo"], o.reshape(B, T, -1))
    W = cache_window
    if W >= T:
        pad = [(0, 0), (0, W - T), (0, 0), (0, 0)]
        kc, vc = jnp.pad(k, pad), jnp.pad(v, pad)
    else:  # ring: keep the last W entries (window-limited decode)
        kc, vc = k[:, T - W:], v[:, T - W:]
    return out, kc, vc


def attention_prefill_paged(p: Params, x: jnp.ndarray, positions: jnp.ndarray,
                            cfg: ModelConfig, window: Optional[int],
                            k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                            block_table: jnp.ndarray,
                            mask: Optional[jnp.ndarray] = None,
                            impl: str = "unfused", layer=None,
                            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Prefill that lands K/V in the paged pool (``repro.kvcache``).

    The attention math is ``attention_prefill``'s exactly; instead of a
    padded dense (B, W) cache, each valid token's K/V is written to page
    ``block_table[b, pos // pg]`` at offset ``pos % pg`` via
    ``kernels.ops.paged_prefill_write`` (pads land in the null page).
    Mirrors ``attention_decode_paged`` so prefill and decode both read
    and write the same persistent page pool.  Returns
    (out, k_pages, v_pages).  ``layer`` selects one layer of stacked
    pools in any ``kernels.ref.pool_view`` layout (see
    ``kernels.ops.paged_decode_attention``).

    ``impl="fused"`` routes K through
    ``kernels.ops.fused_rope_prefill_write`` — RoPE applied in-register
    while the pages are written, no rotated-K tensor in HBM — and the
    queries attend against the rotated K/V gathered back from the pages
    (the read attention pays anyway).  ``"unfused"`` is the correctness
    baseline.  Long prompts (T >= CHUNK_THRESHOLD) always take the
    unfused chunked path.
    """
    from repro.kernels import ops as kernel_ops  # deferred: keep models importable without kernels
    from repro.kernels import ref as kernel_ref
    assert impl in ("unfused", "fused"), impl
    B, T, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    rp = jnp.maximum(positions, 0)
    q = apply_rope(q, rp, cfg.rope_theta)
    scale = cfg.head_dim ** -0.5
    if impl == "fused" and T < CHUNK_THRESHOLD:
        # one pass over K: rotate in-register + write pages; attention
        # reads the rotated K/V back through the block tables (slot ==
        # position in the compact layout)
        k_pages, v_pages = kernel_ops.fused_rope_prefill_write(
            k, v, positions, block_table, k_pages, v_pages,
            theta=cfg.rope_theta, layer=layer)
        kw, vw = kernel_ref.gather_pages(k_pages, v_pages, block_table,
                                         cfg.head_dim, layer)
        W = kw.shape[1]
        lengths = jnp.sum(positions >= 0, axis=1)
        slots = jnp.arange(W, dtype=jnp.int32)[None]
        pk = jnp.where(slots < lengths[:, None], slots, -1)[:, None, :]
        pq = positions[:, :, None]
        m = (pk >= 0) & (pk <= pq)
        if window is not None:
            m = m & (pq - pk < window)
        # pad query rows would be fully masked -> attend slot 0 (NaN guard)
        m = m | ((pq < 0) & (jnp.arange(W)[None, None, :] == 0))
        o = gqa_attend(q, kw, vw, m[:, None], scale)
        out = dense_apply(p["wo"], o.reshape(B, T, -1))
        return out, k_pages, v_pages
    k = apply_rope(k, rp, cfg.rope_theta)
    if T >= CHUNK_THRESHOLD:
        o = gqa_attend_chunked(q, k, v, scale, positions, positions, window)
    else:
        if mask is None:
            mask = prefill_mask(positions, window)
        o = gqa_attend(q, k, v, mask, scale)
    out = dense_apply(p["wo"], o.reshape(B, T, -1))
    k_pages, v_pages = kernel_ops.paged_prefill_write(
        k, v, positions, block_table, k_pages, v_pages, layer=layer)
    return out, k_pages, v_pages


def attention_prefill_tail_paged(p: Params, x: jnp.ndarray,
                                 positions: jnp.ndarray, cfg: ModelConfig,
                                 window: Optional[int],
                                 k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                                 block_table: jnp.ndarray,
                                 slot_pos: jnp.ndarray,
                                 impl: str = "unfused", layer=None,
                                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Tail prefill over a paged pool whose head KV is already resident.

    The cross-request prefix-sharing path: ``x`` (B,T,d) holds only each
    row's *novel tail* tokens (left-padded; pad positions < 0) while the
    shared/retained prefix KV is reachable through ``block_table``.
    ``positions`` are absolute (prefix_len .. total_len-1) and double as
    the compact-layout destination slots; ``slot_pos`` (B, nb·pg) covers
    the full logical window *including* the tail slots.  Tail K/V is
    scattered into the pages first, then each tail query attends to the
    gathered full window under the ``slot_pos <= q_pos`` causal mask —
    intra-tail causality falls out of the same comparison, so one pass
    covers prefix attention and tail self-attention.  Shared prefix pages
    are only read: tail writes land at positions past the shared head by
    construction (the engine shares full pages only).

    ``impl="fused"`` fuses the tail K rotation into the page write
    (``kernels.ops.fused_rope_prefill_write``); the gathered-window
    attention below is shared by both impls.
    """
    from repro.kernels import ops as kernel_ops  # deferred: keep models importable without kernels
    from repro.kernels import ref as kernel_ref
    assert impl in ("unfused", "fused"), impl
    B, T, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    rp = jnp.maximum(positions, 0)
    q = apply_rope(q, rp, cfg.rope_theta)
    if impl == "fused":
        k_pages, v_pages = kernel_ops.fused_rope_prefill_write(
            k, v, positions, block_table, k_pages, v_pages,
            theta=cfg.rope_theta, layer=layer)
    else:
        k = apply_rope(k, rp, cfg.rope_theta)
        k_pages, v_pages = kernel_ops.paged_prefill_write(
            k, v, positions, block_table, k_pages, v_pages, layer=layer)
    kw, vw = kernel_ref.gather_pages(k_pages, v_pages, block_table,
                                     cfg.head_dim, layer)
    pq = positions[:, :, None]  # (B,T,1)
    pk = slot_pos[:, None, :]   # (B,1,S)
    m = (pk >= 0) & (pk <= pq)
    if window is not None:
        m = m & (pq - pk < window)
    # pad query rows would be fully masked -> attend slot 0 to avoid NaN
    # (their output is discarded; slot 0 always holds position 0 here)
    m = m | ((pq < 0) & (jnp.arange(kw.shape[1])[None, None, :] == 0))
    o = gqa_attend(q, kw, vw, m[:, None], cfg.head_dim ** -0.5)
    out = dense_apply(p["wo"], o.reshape(B, T, -1))
    return out, k_pages, v_pages


def attention_decode(p: Params, x: jnp.ndarray, q_pos: jnp.ndarray,
                     k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     slot_pos: jnp.ndarray, slot: jnp.ndarray,
                     cfg: ModelConfig, window: Optional[int]
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token decode. x (B,1,d); k/v_cache (B,W,Hkv,D); slot () int32.

    Returns (out, new_k_cache, new_v_cache).  ``slot_pos`` must already
    include the *current* token position at ``slot`` (the model driver
    updates it once, shared across layers).
    """
    B = x.shape[0]
    q, k, v = _qkv(p, x, cfg)
    q = apply_rope(q, q_pos[:, None], cfg.rope_theta)
    k = apply_rope(k, q_pos[:, None], cfg.rope_theta)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, slot, axis=1)
    mask = decode_mask(q_pos, slot_pos, window)
    o = gqa_attend(q, k_cache, v_cache, mask, cfg.head_dim ** -0.5)
    out = dense_apply(p["wo"], o.reshape(B, 1, -1))
    return out, k_cache, v_cache


def attention_decode_rowslots(p: Params, x: jnp.ndarray, q_pos: jnp.ndarray,
                              k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                              slot_pos: jnp.ndarray, slots: jnp.ndarray,
                              cfg: ModelConfig, window: Optional[int]
                              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Decode with *per-row* write slots (continuous batching: each slot of
    the engine is at a different position).  slots (B,) int32."""
    B = x.shape[0]
    W = k_cache.shape[1]
    q, k, v = _qkv(p, x, cfg)
    q = apply_rope(q, q_pos[:, None], cfg.rope_theta)
    k = apply_rope(k, q_pos[:, None], cfg.rope_theta)
    oh = jax.nn.one_hot(slots, W, dtype=k_cache.dtype)[:, :, None, None]  # (B,W,1,1)
    k_cache = k_cache * (1 - oh) + k * oh
    v_cache = v_cache * (1 - oh) + v * oh
    mask = decode_mask(q_pos, slot_pos, window)
    o = gqa_attend(q, k_cache, v_cache, mask, cfg.head_dim ** -0.5)
    out = dense_apply(p["wo"], o.reshape(B, 1, -1))
    return out, k_cache, v_cache


def attention_decode_paged(p: Params, x: jnp.ndarray, q_pos: jnp.ndarray,
                           k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                           block_table: jnp.ndarray, slot_pos: jnp.ndarray,
                           slots: jnp.ndarray, cfg: ModelConfig,
                           window: Optional[int], impl: str = "unfused",
                           layer=None,
                           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Decode over a paged KV cache (``repro.kvcache``) with per-row slots.

    x (B,1,d); k/v_pages (P,pg,Hkv,D) shared page pool (or stacked pools,
    ``kernels.ref.pool_view``, read and written at ``layer``);
    block_table (B,nb) physical page per logical block; slot_pos (B,nb·pg) over *logical*
    slots (must already include the current token position at ``slots``,
    like the dense drivers); slots (B,) logical write slots.  The write
    scatters one token into page ``block_table[b, slots[b]//pg]``; rows
    whose blocks all point at the null page (inactive engine rows) write
    there harmlessly.  Attention goes through
    ``kernels.ops.paged_decode_attention`` — pure-jnp gather on CPU, the
    Pallas page-streaming kernel on TPU — so the engine's paged path runs
    the kernel end to end.

    ``impl="fused"`` hands the *unrotated* q/k/v to
    ``kernels.ops.fused_rope_decode_append`` — one launch rotates the new
    token, appends its K/V to the page slot, and streams the running
    softmax; ``"unfused"`` (jnp rope + XLA scatter + attention kernel) is
    the correctness baseline.
    """
    from repro.kernels import ops as kernel_ops  # deferred: keep models importable without kernels
    from repro.kernels import ref as kernel_ref
    assert impl in ("unfused", "fused"), impl
    B = x.shape[0]
    at, pg, rec = kernel_ref.pool_view(k_pages, layer)
    q, k, v = _qkv(p, x, cfg)
    if impl == "fused":
        o, k_pages, v_pages = kernel_ops.fused_rope_decode_append(
            q[:, 0], k[:, 0], v[:, 0], block_table, slot_pos, slots, q_pos,
            k_pages, v_pages, theta=cfg.rope_theta, window=window,
            layer=layer)
        out = dense_apply(p["wo"], o.reshape(B, 1, -1))
        return out, k_pages, v_pages
    q = apply_rope(q, q_pos[:, None], cfg.rope_theta)
    k = apply_rope(k, q_pos[:, None], cfg.rope_theta)
    pages = jnp.take_along_axis(block_table, (slots // pg)[:, None], axis=1)[:, 0]
    at = at + (pages, slots % pg)
    k_pages = k_pages.at[at].set(k[:, 0].reshape((B,) + rec))
    v_pages = v_pages.at[at].set(v[:, 0].reshape((B,) + rec))
    o = kernel_ops.paged_decode_attention(q[:, 0], k_pages, v_pages,
                                          block_table, slot_pos, q_pos,
                                          window=window, layer=layer)
    out = dense_apply(p["wo"], o.reshape(B, 1, -1))
    return out, k_pages, v_pages


# ---------------------------------------------------------------------------
# cache bookkeeping shared by all attention archs
# ---------------------------------------------------------------------------
def prefill_slot_pos(positions: jnp.ndarray, cache_window: int) -> jnp.ndarray:
    """slot_pos after prefill of T (possibly > W, ring) left-padded tokens."""
    B, T = positions.shape
    W = cache_window
    if W >= T:
        pad = jnp.full((B, W - T), -1, jnp.int32)
        return jnp.concatenate([positions.astype(jnp.int32), pad], axis=1)
    return positions[:, T - W:].astype(jnp.int32)


def decode_slot(cache: KVCache) -> jnp.ndarray:
    """Ring slot for the next decode write."""
    return jnp.remainder(cache.write_idx, cache.window)


def decode_slot_pos(cache: KVCache, q_pos: jnp.ndarray) -> jnp.ndarray:
    slot = decode_slot(cache)
    return jax.lax.dynamic_update_slice_in_dim(
        cache.slot_pos, q_pos[:, None].astype(jnp.int32), slot, axis=1)
