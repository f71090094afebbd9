"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

KV is compressed to a per-token latent ``c_kv`` (kv_lora_rank) plus one
shared rope key (qk_rope_head_dim) — the cache stores only those, giving a
~20x smaller Δ (bytes/token) than naive GQA for the assigned config.

Two forms of the same attention:

  * expanded (prefill): k_nope/v are re-expanded from the latent via the
    up projections and attended as ordinary heads — compute-bound, and
    the chunked path takes prompts of CHUNK_THRESHOLD tokens and more;
  * absorbed (paged decode): ``k_up`` is folded into the query
    (``q_lat = q_nope · W_UKᵀ``, width kv_lora_rank) and ``v_up`` is
    applied after the weighted sum, so a step reads each cached token's
    latent and rope key once and never forms per-token K or V.

The rotary part uses YaRN when the config scales it
(``common.yarn_freqs``), and the softmax scale carries YaRN's mscale².
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.common import (ModelConfig, Params, apply_rope, dense_apply,
                                 dense_param, init_rms, rms_norm,
                                 yarn_freqs, yarn_rope_mult,
                                 yarn_softmax_factor)


class MLACache(NamedTuple):
    ckv: jnp.ndarray  # (L, B, W, r)
    kr: jnp.ndarray   # (L, B, W, dr)
    slot_pos: jnp.ndarray
    write_idx: jnp.ndarray
    lengths: jnp.ndarray

    @property
    def window(self) -> int:
        return self.ckv.shape[2]


def decode_slot(cache: MLACache) -> jnp.ndarray:
    return jnp.remainder(cache.write_idx, cache.window)


def decode_slot_pos(cache: MLACache, q_pos: jnp.ndarray) -> jnp.ndarray:
    slot = decode_slot(cache)
    return jax.lax.dynamic_update_slice_in_dim(
        cache.slot_pos, q_pos[:, None].astype(jnp.int32), slot, axis=1)


def init_mla(key, cfg: ModelConfig) -> Params:
    kq, kd, ku, kv, ko = jax.random.split(key, 5)
    H = cfg.n_heads
    dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim, cfg.kv_lora_rank)
    return {
        "wq": dense_param(kq, cfg.d_model, H * (dn + dr), cfg.dtype),
        "w_dkv": dense_param(kd, cfg.d_model, r + dr, cfg.dtype),
        "ckv_norm": init_rms(r, cfg.dtype),
        "k_up": dense_param(ku, r, H * dn, cfg.dtype),
        "v_up": dense_param(kv, r, H * dv, cfg.dtype),
        "wo": dense_param(ko, H * dv, cfg.d_model, cfg.dtype),
    }


def _rope(x: jnp.ndarray, positions: jnp.ndarray, cfg: ModelConfig):
    """Rotate the rope part x (B,T,H,dr) at ``positions`` (pads < 0)."""
    return apply_rope(x, jnp.maximum(positions, 0), cfg.rope_theta,
                      freqs=yarn_freqs(x.shape[-1], cfg),
                      mult=yarn_rope_mult(cfg))


def softmax_scale(cfg: ModelConfig) -> float:
    """(dn + dr)^-0.5 times YaRN's mscale²."""
    return ((cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
            * yarn_softmax_factor(cfg))


def _q_proj(p: Params, x: jnp.ndarray, positions: jnp.ndarray, cfg: ModelConfig):
    B, T, _ = x.shape
    H, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = dense_apply(p["wq"], x).reshape(B, T, H, dn + dr)
    qn, qr = q[..., :dn], q[..., dn:]
    qr = _rope(qr, positions, cfg)
    return jnp.concatenate([qn, qr], axis=-1)  # (B,T,H,dn+dr)


def _compress(p: Params, x: jnp.ndarray, positions: jnp.ndarray, cfg: ModelConfig):
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dkv = dense_apply(p["w_dkv"], x)
    ckv = rms_norm(dkv[..., :r], p["ckv_norm"], cfg.norm_eps)
    kr = dkv[..., r:][:, :, None, :]  # (B,T,1,dr) one shared rope head
    kr = _rope(kr, positions, cfg)[:, :, 0]
    return ckv, kr  # (B,T,r), (B,T,dr)


def _expand_attend(p: Params, q: jnp.ndarray, ckv: jnp.ndarray, kr: jnp.ndarray,
                   mask, cfg: ModelConfig, positions=None,
                   window=None, key_positions=None) -> jnp.ndarray:
    """q (B,T,H,dn+dr); ckv (B,S,r); kr (B,S,dr); mask (B,1,T,S) or None
    (None -> q-chunked path with per-block masks from ``positions`` and
    ``key_positions``, by default the same)."""
    B, T, H, _ = q.shape
    S = ckv.shape[1]
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    kn = dense_apply(p["k_up"], ckv).reshape(B, S, H, dn)
    v = dense_apply(p["v_up"], ckv).reshape(B, S, H, dv)
    k = jnp.concatenate([kn, jnp.broadcast_to(kr[:, :, None, :], (B, S, H, kr.shape[-1]))],
                        axis=-1)
    scale = softmax_scale(cfg)
    if mask is None:
        kp = positions if key_positions is None else key_positions
        o = attn.gqa_attend_chunked(q, k, v, scale, positions, kp, window)
    else:
        o = attn.gqa_attend(q, k, v, mask, scale)  # H == Hkv here
    return dense_apply(p["wo"], o.reshape(B, T, H * dv))


def mla_forward(p: Params, x: jnp.ndarray, positions: jnp.ndarray,
                cfg: ModelConfig, window: Optional[int],
                mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    if mask is None and x.shape[1] < attn.CHUNK_THRESHOLD:
        mask = attn.prefill_mask(positions, window)
    q = _q_proj(p, x, positions, cfg)
    ckv, kr = _compress(p, x, positions, cfg)
    return _expand_attend(p, q, ckv, kr, mask, cfg, positions, window)


def mla_prefill(p: Params, x: jnp.ndarray, positions: jnp.ndarray,
                cfg: ModelConfig, window: Optional[int], cache_window: int,
                mask: Optional[jnp.ndarray] = None):
    B, T, _ = x.shape
    if mask is None and T < attn.CHUNK_THRESHOLD:
        mask = attn.prefill_mask(positions, window)
    q = _q_proj(p, x, positions, cfg)
    ckv, kr = _compress(p, x, positions, cfg)
    out = _expand_attend(p, q, ckv, kr, mask, cfg, positions, window)
    W = cache_window
    if W >= T:
        ckv_c = jnp.pad(ckv, ((0, 0), (0, W - T), (0, 0)))
        kr_c = jnp.pad(kr, ((0, 0), (0, W - T), (0, 0)))
    else:
        ckv_c, kr_c = ckv[:, T - W:], kr[:, T - W:]
    return out, ckv_c, kr_c


def mla_decode(p: Params, x: jnp.ndarray, q_pos: jnp.ndarray,
               ckv_cache: jnp.ndarray, kr_cache: jnp.ndarray,
               slot_pos: jnp.ndarray, slot: jnp.ndarray,
               cfg: ModelConfig, window: Optional[int]):
    """x (B,1,d); ckv_cache (B,W,r); kr_cache (B,W,dr)."""
    q = _q_proj(p, x, q_pos[:, None], cfg)
    ckv, kr = _compress(p, x, q_pos[:, None], cfg)
    ckv_cache = jax.lax.dynamic_update_slice_in_dim(ckv_cache, ckv, slot, axis=1)
    kr_cache = jax.lax.dynamic_update_slice_in_dim(kr_cache, kr, slot, axis=1)
    mask = attn.decode_mask(q_pos, slot_pos, window)
    out = _expand_attend(p, q, ckv_cache, kr_cache, mask, cfg)
    return out, ckv_cache, kr_cache


# ---------------------------------------------------------------------------
# paged latent pool (persistent paged StaticEngine)
# ---------------------------------------------------------------------------
# Two stacked pools, of c_kv and of the rotary key, addressed like the dense
# engine's K/V pools (``kernels.ref.pool_view``): logical slot == absolute
# position, page 0 the null page.  A page is (rows, lanes): a record
# narrower than the TPU's 128 lanes that divides them (the 64-wide rotary
# key) is packed ``lanes // width`` slots to a row, since a minor dim of 64
# is padded to 128 on the chip and the compiler then spends minutes on each
# 64-wide scatter into it.
LANES = 128


def page_shape(page_tokens: int, width: int) -> Tuple[int, int]:
    """(rows, lanes) of one page of ``width``-wide slot records."""
    per = LANES // width if width < LANES and LANES % width == 0 else 1
    if page_tokens % per:
        per = 1
    return page_tokens // per, width * per


def paged_pages(cfg: ModelConfig, page_tokens: int):
    """The page shapes of the c_kv pool and the rotary-key pool."""
    return (page_shape(page_tokens, cfg.kv_lora_rank),
            page_shape(page_tokens, cfg.qk_rope_head_dim))


def _slot_index(pages, dest, block_table, layer, per: int):
    """Index of the row holding logical slot ``dest`` (B,T) (< 0: the
    null page) and the slot's place in that row."""
    at = () if layer is None else (layer,)
    pg = pages.shape[-2] * per
    nb = block_table.shape[1]
    valid = dest >= 0
    slot = jnp.clip(dest, 0, nb * pg - 1)
    page = jnp.where(valid, jnp.take_along_axis(block_table, slot // pg, axis=1), 0)
    off = jnp.where(valid, slot % pg, 0)
    return at + (page.reshape(-1), (off // per).reshape(-1)), off % per


def _write_pages(pages: jnp.ndarray, vals: jnp.ndarray, dest: jnp.ndarray,
                 block_table: jnp.ndarray, layer) -> jnp.ndarray:
    """Scatter vals (B,T,w) to logical slots dest (B,T) of layer
    ``layer``; slots < 0 (pads) go to the null page.  The slots of a row
    are consecutive along T (a prefill: positions run on from the row's
    first, which starts a packed row, as slot 0 and every page do), so a
    packed row is written whole from the token at its first slot and the
    ones after it."""
    B, T, w = vals.shape
    per = pages.shape[-1] // w
    vals = vals.astype(pages.dtype)
    if per > 1:
        vals = jnp.concatenate(
            [vals] + [jnp.pad(vals[:, j:], ((0, 0), (0, j), (0, 0)))
                      for j in range(1, per)], axis=-1)
        dest = jnp.where(dest % per == 0, dest, -1)
    at, _ = _slot_index(pages, dest, block_table, layer, per)
    return pages.at[at].set(vals.reshape(B * T, -1))


def _append_slot(pages: jnp.ndarray, vals: jnp.ndarray, slots: jnp.ndarray,
                 block_table: jnp.ndarray, layer) -> jnp.ndarray:
    """Write one record per row, vals (B,w), at logical slot ``slots``
    (B,) of layer ``layer`` (decode): a packed row is read, the slot's
    part replaced, and the row written back whole."""
    B, w = vals.shape
    per = pages.shape[-1] // w
    at, part = _slot_index(pages, slots[:, None], block_table, layer, per)
    vals = vals.astype(pages.dtype)
    if per > 1:
        old = pages[at].reshape(B, per, w)
        vals = jnp.where((jnp.arange(per)[None] == part)[..., None],
                         vals[:, None], old).reshape(B, -1)
    return pages.at[at].set(vals)


def _read_pages(pages: jnp.ndarray, block_table: jnp.ndarray, layer,
                width: int) -> jnp.ndarray:
    """Each row's logical window (B, nb·pg, width) of layer ``layer``."""
    at = () if layer is None else (layer,)
    B = block_table.shape[0]
    return pages[at + (block_table,)].reshape(B, -1, width)


def mla_prefill_paged(p: Params, x: jnp.ndarray, positions: jnp.ndarray,
                      cfg: ModelConfig, ckv_pages: jnp.ndarray,
                      kr_pages: jnp.ndarray, block_table: jnp.ndarray,
                      mask: Optional[jnp.ndarray], layer):
    """Expanded-form prefill of left-padded x (B,T,d) that writes c_kv
    and the roped shared key of each real token into the pools at slot
    == position.  ``mask`` None: the chunked path (T >= CHUNK_THRESHOLD).
    Returns (out, ckv_pages, kr_pages)."""
    q = _q_proj(p, x, positions, cfg)
    ckv, kr = _compress(p, x, positions, cfg)
    out = _expand_attend(p, q, ckv, kr, mask, cfg, positions)
    ckv_pages = _write_pages(ckv_pages, ckv, positions, block_table, layer)
    kr_pages = _write_pages(kr_pages, kr, positions, block_table, layer)
    return out, ckv_pages, kr_pages


def mla_prefill_tail_paged(p: Params, x: jnp.ndarray, positions: jnp.ndarray,
                           cfg: ModelConfig, ckv_pages: jnp.ndarray,
                           kr_pages: jnp.ndarray, block_table: jnp.ndarray,
                           slot_pos: jnp.ndarray, layer):
    """Expanded-form prefill of each row's novel tail (absolute
    ``positions``, pads < 0) over a window whose head is already in the
    pools: the tail's latents are written first, then its queries attend
    to the whole gathered window under ``slot_pos <= position``."""
    T = x.shape[1]
    q = _q_proj(p, x, positions, cfg)
    ckv, kr = _compress(p, x, positions, cfg)
    ckv_pages = _write_pages(ckv_pages, ckv, positions, block_table, layer)
    kr_pages = _write_pages(kr_pages, kr, positions, block_table, layer)
    ckv_w = _read_pages(ckv_pages, block_table, layer, cfg.kv_lora_rank)
    kr_w = _read_pages(kr_pages, block_table, layer, cfg.qk_rope_head_dim)
    if T >= attn.CHUNK_THRESHOLD:
        out = _expand_attend(p, q, ckv_w, kr_w, None, cfg, positions,
                             key_positions=slot_pos)
    else:
        pq = positions[:, :, None]
        pk = slot_pos[:, None, :]
        m = (pk >= 0) & (pk <= pq)
        # pad query rows would be fully masked -> attend slot 0 (NaN guard)
        m = m | ((pq < 0) & (jnp.arange(ckv_w.shape[1])[None, None] == 0))
        out = _expand_attend(p, q, ckv_w, kr_w, m[:, None], cfg)
    return out, ckv_pages, kr_pages


def mla_decode_paged(p: Params, x: jnp.ndarray, q_pos: jnp.ndarray,
                     ckv_pages: jnp.ndarray, kr_pages: jnp.ndarray,
                     block_table: jnp.ndarray, slot_pos: jnp.ndarray,
                     slots: jnp.ndarray, cfg: ModelConfig, layer):
    """Absorbed-form decode of x (B,1,d) at ``q_pos`` (B,): append the
    token's latent and rope key at logical slot ``slots``, then

        scores = (q_nope · W_UKᵀ) · c_kvᵀ + q_rope · k_ropeᵀ   (per head)
        out    = W_o · ((softmax(scores · scale) · c_kv) · W_UV)

    over the gathered window; no per-token K or V is formed.  ``slot_pos``
    already marks the new token's slot."""
    B = x.shape[0]
    H, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    dt = x.dtype
    pos = q_pos[:, None]
    q = _q_proj(p, x, pos, cfg)[:, 0]  # (B,H,dn+dr)
    ckv, kr = _compress(p, x, pos, cfg)  # (B,1,r), (B,1,dr)
    with jax.named_scope("kv_append"):
        ckv_pages = _append_slot(ckv_pages, ckv[:, 0], slots, block_table,
                                 layer)
        kr_pages = _append_slot(kr_pages, kr[:, 0], slots, block_table,
                                layer)
    with jax.named_scope("kv_gather"):
        ckv_w = _read_pages(ckv_pages, block_table, layer, r)  # (B,W,r)
        kr_w = _read_pages(kr_pages, block_table, layer,
                           cfg.qk_rope_head_dim)              # (B,W,dr)
    k_up = p["k_up"]["w"].reshape(r, H, dn)
    v_up = p["v_up"]["w"].reshape(r, H, dv)
    q_lat = jnp.einsum("bhn,rhn->bhr", q[..., :dn], k_up,
                       preferred_element_type=jnp.float32).astype(dt)
    s = (jnp.einsum("bhr,bwr->bhw", q_lat, ckv_w,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,bwd->bhw", q[..., dn:], kr_w,
                      preferred_element_type=jnp.float32))
    s = s * softmax_scale(cfg)
    mask = (slot_pos >= 0) & (slot_pos <= q_pos[:, None])
    s = jnp.where(mask[:, None], s, attn.NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhw,bwr->bhr", pr.astype(dt), ckv_w,
                       preferred_element_type=jnp.float32).astype(dt)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, v_up,
                   preferred_element_type=jnp.float32).astype(dt)
    out = dense_apply(p["wo"], o.reshape(B, 1, H * dv))
    return out, ckv_pages, kr_pages
