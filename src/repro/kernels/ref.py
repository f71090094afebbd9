"""Pure-jnp oracles for the Pallas kernels (the allclose ground truth)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def pool_view(pages: jnp.ndarray, layer=None) -> tuple:
    """(index prefix, page_tokens, slot record shape) of a page pool.

    ``layer`` None: one layer (P,pg,*rec); else layer ``layer`` of stacked
    pools (L,P,pg,*rec).  A slot record is (Hkv,D), or (Hkv·D,) for a pool
    stored lane-dense as the serving engine does (the TPU's (8, 128)
    tiles leave half their lanes empty over a minor dim of 64).  Folding the
    layer into the same gather/scatter as the pages keeps a layer-scanned
    update in place; slicing the layer out first would copy it."""
    at = () if layer is None else (layer,)
    return at, pages.shape[len(at) + 1], pages.shape[len(at) + 2:]


def flash_prefill_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      positions: jnp.ndarray, window: Optional[int] = None,
                      scale: Optional[float] = None) -> jnp.ndarray:
    """Causal + left-pad-masked GQA attention.

    q (B,T,Hq,D); k/v (B,T,Hkv,D); positions (B,T) with pads < 0.
    """
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    pq = positions[:, :, None]
    pk = positions[:, None, :]
    mask = (pk >= 0) & (pk <= pq)
    if window is not None:
        mask = mask & (pq - pk < window)
    mask = mask | jnp.eye(T, dtype=bool)[None]
    qr = q.reshape(B, T, Hkv, G, D)
    s = jnp.einsum("bthgd,bshd->bhgts", qr, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgts,bshd->bthgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, T, Hq, D).astype(q.dtype)


def paged_prefill_write_ref(k_new: jnp.ndarray, v_new: jnp.ndarray,
                            dest_slot: jnp.ndarray, block_table: jnp.ndarray,
                            k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                            layer=None):
    """Scatter prefill K/V into a paged KV pool through block tables.

    k/v_new (B,T,Hkv,D); dest_slot (B,T) int32 — the *logical* cache slot
    each token lands in (< 0 = pad, routed to the null page 0 whose slots
    are permanently masked); block_table (B,nb); k/v_pages (P,pg,Hkv,D).
    Token (b,t) is written to page ``block_table[b, dest_slot//pg]`` at
    offset ``dest_slot % pg``.  Returns the updated (k_pages, v_pages) —
    the paged twin of ``attention_prefill``'s dense cache build.  With
    ``layer``, the pools are stacked (``pool_view``) and only that layer is
    written.
    """
    B, T, Hkv, D = k_new.shape
    at, pg, rec = pool_view(k_pages, layer)
    nb = block_table.shape[1]
    valid = dest_slot >= 0
    slot = jnp.clip(dest_slot, 0, nb * pg - 1)
    page = jnp.take_along_axis(block_table, slot // pg, axis=1)
    page = jnp.where(valid, page, 0).reshape(-1)   # pads -> null page
    off = jnp.where(valid, slot % pg, 0).reshape(-1)
    at = at + (page, off)
    k_pages = k_pages.at[at].set(k_new.reshape((B * T,) + rec))
    v_pages = v_pages.at[at].set(v_new.reshape((B * T,) + rec))
    return k_pages, v_pages


def rope_cos_sin(positions: jnp.ndarray, head_dim: int,
                 theta: float) -> tuple:
    """(cos, sin) of the RoPE angles at ``positions``, each
    positions.shape + (head_dim/2,) float32.  The fused Pallas kernels
    take these tables rather than evaluating the transcendentals
    in-kernel, so their rotation uses the very values of this oracle."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(ang), jnp.sin(ang)


def _rope_ref(x: jnp.ndarray, positions: jnp.ndarray,
              theta: float) -> jnp.ndarray:
    """Llama half-rotation RoPE — arithmetic twin of
    ``models.common.apply_rope`` kept local so the oracle module stays
    free of model-package imports.  x (..., T, H, D); positions (..., T)."""
    cos, sin = rope_cos_sin(positions, x.shape[-1], theta)
    cos, sin = cos[..., None, :], sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def fused_rope_prefill_write_ref(k_new: jnp.ndarray, v_new: jnp.ndarray,
                                 positions: jnp.ndarray,
                                 block_table: jnp.ndarray,
                                 k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                                 theta: float = 10000.0, layer=None):
    """Rotate prefill K at its absolute positions, then scatter K/V into
    the paged pool — the one-pass fused kernel's ground truth.

    k/v_new (B,T,Hkv,D) *unrotated*; positions (B,T) (pads < 0, real
    tokens at their absolute position == destination logical slot);
    block_table (B,nb); k/v_pages (P,pg,Hkv,D).  Returns the updated
    (k_pages, v_pages); V is written unrotated."""
    kr = _rope_ref(k_new, jnp.maximum(positions, 0), theta)
    return paged_prefill_write_ref(kr, v_new, positions, block_table,
                                   k_pages, v_pages, layer=layer)


def fused_rope_decode_append_ref(q: jnp.ndarray, k_new: jnp.ndarray,
                                 v_new: jnp.ndarray, block_table: jnp.ndarray,
                                 slot_pos: jnp.ndarray, slots: jnp.ndarray,
                                 q_pos: jnp.ndarray, k_pages: jnp.ndarray,
                                 v_pages: jnp.ndarray, theta: float = 10000.0,
                                 window: Optional[int] = None,
                                 scale: Optional[float] = None, layer=None):
    """Rotate the new q/k token at ``q_pos``, append its K/V to page slot
    ``slots``, then run paged decode attention over the post-append pool —
    the fused decode kernel's ground truth.

    q (B,Hq,D) and k/v_new (B,Hkv,D) *unrotated*; slot_pos (B,nb·pg)
    already marks the new token's slot (it attends to itself); slots (B,)
    destination logical slot; q_pos (B,).  Returns
    (out (B,Hq,D), k_pages, v_pages)."""
    qr = _rope_ref(q[:, None], q_pos[:, None], theta)[:, 0]
    knr = _rope_ref(k_new[:, None], q_pos[:, None], theta)[:, 0]
    at, pg, rec = pool_view(k_pages, layer)
    page = jnp.take_along_axis(block_table, (slots // pg)[:, None],
                               axis=1)[:, 0]
    at = at + (page, slots % pg)
    B = q.shape[0]
    k_pages = k_pages.at[at].set(knr.astype(k_pages.dtype).reshape((B,) + rec))
    v_pages = v_pages.at[at].set(v_new.astype(v_pages.dtype).reshape((B,) + rec))
    out = paged_decode_attention_ref(qr, k_pages, v_pages, block_table,
                                     slot_pos, q_pos, window=window,
                                     scale=scale, layer=layer)
    return out, k_pages, v_pages


def paged_decode_attention_ref(q: jnp.ndarray, k_pages: jnp.ndarray,
                               v_pages: jnp.ndarray, block_table: jnp.ndarray,
                               slot_pos: jnp.ndarray, q_pos: jnp.ndarray,
                               window: Optional[int] = None,
                               scale: Optional[float] = None,
                               layer=None) -> jnp.ndarray:
    """Single-token GQA decode over a *paged* KV cache.

    q (B,Hq,D); k/v_pages (P,pg,Hkv,D), or stacked pools read at
    ``layer`` (``pool_view``); block_table (B,nb) physical page per
    logical block; slot_pos (B,nb·pg) (-1 empty); q_pos (B,).
    Materializes the per-row gather the Pallas kernel streams page by page.
    """
    B, _, D = q.shape
    k_cache, v_cache = gather_pages(k_pages, v_pages, block_table, D, layer)
    return decode_attention_ref(q, k_cache, v_cache, slot_pos, q_pos,
                                window=window, scale=scale)


def gather_pages(k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                 block_table: jnp.ndarray, head_dim: int, layer=None):
    """Each row's logical window (B, nb·pg, Hkv, D) read through its block
    table (``pool_view`` layouts), in one gather per pool."""
    at, pg, rec = pool_view(k_pages, layer)
    B, nb = block_table.shape
    Hkv = math.prod(rec) // head_dim
    at = at + (block_table,)
    return (k_pages[at].reshape(B, nb * pg, Hkv, head_dim),
            v_pages[at].reshape(B, nb * pg, Hkv, -1))


def decode_attention_ref(q: jnp.ndarray, k_cache: jnp.ndarray,
                         v_cache: jnp.ndarray, slot_pos: jnp.ndarray,
                         q_pos: jnp.ndarray, window: Optional[int] = None,
                         scale: Optional[float] = None) -> jnp.ndarray:
    """Single-token GQA decode over a (ring) KV cache.

    q (B,Hq,D); k/v_cache (B,W,Hkv,D); slot_pos (B,W) (-1 empty);
    q_pos (B,).  Returns (B,Hq,D).
    """
    B, Hq, D = q.shape
    Hkv = k_cache.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    mask = (slot_pos >= 0) & (slot_pos <= q_pos[:, None])
    if window is not None:
        mask = mask & (q_pos[:, None] - slot_pos < window)
    qr = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bwhd->bhgw", qr, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgw,bwhd->bhgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Hq, D).astype(q.dtype)


def ssd_scan_ref(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                 B: jnp.ndarray, C: jnp.ndarray, Q: int,
                 init_state: Optional[jnp.ndarray] = None):
    """Chunked SSD scan (Mamba-2, arXiv:2405.21060) — the ``ssd_scan``
    kernel's oracle and the XLA dispatch path.

    x (B,T,H,P); dt (B,T,H) >=0 (0 at pads); A (H,) negative; B,C (B,T,G,N).
    Returns (y (B,T,H,P), final_state (B,H,P,N)).  T % Q must be 0.
    """
    Bsz, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    nc = T // Q
    rep = H // G
    xc = x.reshape(Bsz, nc, Q, H, P)
    dtc = dt.reshape(Bsz, nc, Q, H)
    Bc = B.reshape(Bsz, nc, Q, G, N)
    Cc = C.reshape(Bsz, nc, Q, G, N)

    log_a = dtc * A  # (B,nc,Q,H), <= 0
    cum = jnp.cumsum(log_a, axis=2)  # inclusive cumsum within chunk
    # intra-chunk (attention-like): y[t] += sum_{s<=t} (C_t.B_s) e^{cum_t-cum_s} dt_s x_s
    CB = jnp.einsum("bcqgn,bckgn->bcgqk", Cc, Bc)  # (B,nc,G,Q,Q)
    CB = jnp.repeat(CB, rep, axis=2)  # (B,nc,H,Q,Q)
    decay = jnp.exp(cum[:, :, :, None, :] - cum[:, :, None, :, :])  # (B,nc,Q,Q,H) t,s
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    w = CB * jnp.transpose(decay, (0, 1, 4, 2, 3)) * causal[None, None, None]
    w = w * dtc.transpose(0, 1, 3, 2)[:, :, :, None, :]  # dt_s
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", w, xc)
    # chunk states: S_c = sum_s e^{cum_end - cum_s} dt_s B_s (x) x_s
    seg = jnp.exp(cum[:, :, -1:, :] - cum) * dtc  # (B,nc,Q,H)
    Bh = jnp.repeat(Bc, rep, axis=3)  # (B,nc,Q,H,N)
    S = jnp.einsum("bcqh,bcqhn,bcqhp->bchpn", seg, Bh, xc)
    # inter-chunk recurrence
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (B,nc,H)
    if init_state is None:
        init_state = jnp.zeros((Bsz, H, P, N), S.dtype)

    def step(h, xs):
        dec, s = xs  # dec (B,H), s (B,H,P,N)
        h_new = h * dec[:, :, None, None] + s
        return h_new, h  # emit state *entering* the chunk

    final, h_in = jax.lax.scan(step, init_state,
                               (chunk_decay.transpose(1, 0, 2), S.transpose(1, 0, 2, 3, 4)))
    h_in = h_in.transpose(1, 0, 2, 3, 4)  # (B,nc,H,P,N)
    # inter-chunk contribution: y[t] += C_t . (e^{cum_t} * h_in)
    Ch = jnp.repeat(Cc, rep, axis=3)  # (B,nc,Q,H,N)
    y_inter = jnp.einsum("bcqhn,bchpn->bcqhp", Ch, h_in) * jnp.exp(cum)[..., None]
    y = (y_intra + y_inter).reshape(Bsz, T, H, P)
    return y, final
