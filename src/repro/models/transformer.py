"""Decoder-only transformer LM (dense archs + PaliGemma-style prefix-VLM).

API (used by the engine, the trainer, and the dry-run):
  init(key, cfg)                                   -> params
  forward(params, cfg, tokens, positions, ...)     -> logits (B,T,V)
  prefill(params, cfg, tokens, lengths, ...)       -> (last_logits, KVCache)
  decode_step(params, cfg, cache, tokens)          -> (logits, KVCache)

Layers are stacked and consumed with lax.scan (HLO is O(1) in depth).
Left-padding convention: ``positions[b, t] < 0`` marks pad tokens.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.attention import KVCache
from repro.kvcache.paged import PagedKVCache
from repro.models.common import (ModelConfig, Params, embed_apply, init_embed,
                                 init_mlp, init_rms, mlp_apply, rms_norm,
                                 scan_layers, stack_layers, unembed_apply,
                                 dense_param, dense_apply)


def init_block(key, cfg: ModelConfig) -> Params:
    ka, km = jax.random.split(key)
    return {
        "attn": attn.init_attention(ka, cfg),
        "mlp": init_mlp(km, cfg.d_model, cfg.d_ff, cfg.dtype),
        "ln_attn": init_rms(cfg.d_model, cfg.dtype),
        "ln_mlp": init_rms(cfg.d_model, cfg.dtype),
    }


def init(key, cfg: ModelConfig) -> Params:
    ke, kl, ku = jax.random.split(key, 3)
    params = {
        "embed": init_embed(ke, cfg.vocab_size, cfg.d_model, cfg.dtype),
        "layers": stack_layers(lambda k: init_block(k, cfg), kl, cfg.n_layers),
        "ln_f": init_rms(cfg.d_model, cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_param(ku, cfg.d_model, cfg.vocab_size, cfg.dtype)
    return params


def _logits(params: Params, cfg: ModelConfig, h: jnp.ndarray) -> jnp.ndarray:
    h = rms_norm(h, params["ln_f"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return unembed_apply(params["embed"], h)
    return dense_apply(params["unembed"], h)


def _block_fwd(layer: Params, h, positions, cfg, window, mask, prefix_len=0):
    a = attn.attention_forward(layer["attn"], rms_norm(h, layer["ln_attn"], cfg.norm_eps),
                               positions, cfg, window, mask, prefix_len=prefix_len)
    h = h + a
    m = mlp_apply(layer["mlp"], rms_norm(h, layer["ln_mlp"], cfg.norm_eps), cfg.act)
    return h + m


def make_positions(tokens: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """Left-padded position ids: pads get -1, real tokens 0..len-1."""
    B, T = tokens.shape
    idx = jnp.arange(T)[None]
    return jnp.where(idx < T - lengths[:, None], -1, idx - (T - lengths[:, None]))


def _mask_with_prefix(positions: jnp.ndarray, window: Optional[int],
                      prefix_len: int) -> jnp.ndarray:
    m = attn.prefill_mask(positions, window)
    if prefix_len:
        pk = positions[:, None, :]
        pq = positions[:, :, None]
        bidir = (pk >= 0) & (pk < prefix_len) & (pq >= 0)
        m = m | bidir[:, None]
    return m


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            positions: Optional[jnp.ndarray] = None,
            prefix_embeds: Optional[jnp.ndarray] = None,
            window: Optional[int] = None) -> jnp.ndarray:
    """Full-sequence forward (training).  For VLM, ``prefix_embeds``
    (B,P,d) is prepended and ``tokens`` covers only the text part."""
    window = window if window is not None else cfg.sliding_window
    h = embed_apply(params["embed"], tokens, cfg)
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
    B, T, _ = h.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    big = T >= attn.CHUNK_THRESHOLD
    mask = None if big else _mask_with_prefix(positions, window, cfg.n_prefix_tokens)

    def body(carry, layer):
        return _block_fwd(layer, carry, positions, cfg, window, mask,
                          cfg.n_prefix_tokens), None

    h, _ = scan_layers(body, h, params["layers"], remat=cfg.remat)
    return _logits(params, cfg, h)


# ---------------------------------------------------------------------------
# serving path
# ---------------------------------------------------------------------------
def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            lengths: jnp.ndarray, cache_window: int,
            prefix_embeds: Optional[jnp.ndarray] = None,
            window: Optional[int] = None) -> Tuple[jnp.ndarray, KVCache]:
    """Run the prefill phase and build the KV cache (width ``cache_window``)."""
    window = window if window is not None else cfg.sliding_window
    positions = make_positions(tokens, lengths)
    h = embed_apply(params["embed"], tokens, cfg)
    if prefix_embeds is not None:
        P = prefix_embeds.shape[1]
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
        positions = jnp.concatenate(
            [jnp.broadcast_to(jnp.arange(P)[None], (h.shape[0], P)),
             jnp.where(positions >= 0, positions + P, -1)], axis=1)
        lengths = lengths + P
    B, T = positions.shape
    big = T >= attn.CHUNK_THRESHOLD
    mask = None if big else _mask_with_prefix(positions, window, cfg.n_prefix_tokens)

    def body(carry, layer):
        x = rms_norm(carry, layer["ln_attn"], cfg.norm_eps)
        a, kc, vc = attn.attention_prefill(layer["attn"], x, positions, cfg,
                                           window, cache_window, mask=mask,
                                           prefix_len=cfg.n_prefix_tokens)
        h2 = carry + a
        m = mlp_apply(layer["mlp"], rms_norm(h2, layer["ln_mlp"], cfg.norm_eps), cfg.act)
        return h2 + m, (kc, vc)

    h, (k_all, v_all) = scan_layers(body, h, params["layers"])
    logits = _logits(params, cfg, h[:, -1:, :])
    cache = KVCache(
        k=k_all, v=v_all,
        slot_pos=attn.prefill_slot_pos(positions, cache_window),
        write_idx=jnp.asarray(T if cache_window >= T else cache_window, jnp.int32),
        lengths=lengths.astype(jnp.int32),
    )
    return logits[:, 0], cache


def _scan_paged(body, h: jnp.ndarray, layers: Params, k_pages: jnp.ndarray,
                v_pages: jnp.ndarray):
    """lax.scan of ``body(h, layer_params, l, k_pages, v_pages)`` over the
    layers, carrying the whole stacked page pools: each layer
    gathers and scatters its pages at index ``l`` of the pools, so the
    update happens in place (pools threaded through scan xs/ys would be
    copied whole on every call)."""
    def step(carry, xs):
        return body(carry[0], *xs, carry[1], carry[2]), None

    n = jax.tree_util.tree_leaves(layers)[0].shape[0]
    (h, k_pages, v_pages), _ = jax.lax.scan(
        step, (h, k_pages, v_pages), (layers, jnp.arange(n)))
    return h, k_pages, v_pages


def prefill_paged(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  lengths: jnp.ndarray, cache: PagedKVCache,
                  window: Optional[int] = None, attn_impl: str = "unfused"
                  ) -> Tuple[jnp.ndarray, PagedKVCache]:
    """Prefill that writes K/V straight into the paged pool.

    ``attn_impl="fused"`` selects the fused RoPE+page-write kernel path
    (``attention.attention_prefill_paged``); ``"unfused"`` (default) is
    the correctness baseline.

    The paged twin of ``prefill``: same left-padded attention math, but
    per-layer K/V land in ``cache.k_pages``/``v_pages`` through the
    per-row block tables (``attention.attention_prefill_paged`` →
    ``kernels.ops.paged_prefill_write``) instead of a transient dense
    (B, W) buffer — so prefix KV survives the slice boundary and a
    resumed slice never re-prefills (``engine.static_engine``, paper
    §3.3).  Layout: logical slot == absolute position (no pad slots);
    ``slot_pos``/``lengths`` of the prefilled rows are refreshed
    accordingly.  Token-only dense archs (no ``prefix_embeds``).
    """
    window = window if window is not None else cfg.sliding_window
    positions = make_positions(tokens, lengths)
    h = embed_apply(params["embed"], tokens, cfg)
    B, T = positions.shape
    big = T >= attn.CHUNK_THRESHOLD
    mask = None if big else attn.prefill_mask(positions, window)

    def body(carry, layer, li, kp, vp):
        x = rms_norm(carry, layer["ln_attn"], cfg.norm_eps)
        a, kp, vp = attn.attention_prefill_paged(
            layer["attn"], x, positions, cfg, window, kp, vp,
            cache.block_table, mask=mask, impl=attn_impl, layer=li)
        h2 = carry + a
        m = mlp_apply(layer["mlp"], rms_norm(h2, layer["ln_mlp"], cfg.norm_eps), cfg.act)
        return h2 + m, kp, vp

    h, k_all, v_all = _scan_paged(body, h, params["layers"],
                                  cache.k_pages, cache.v_pages)
    logits = _logits(params, cfg, h[:, -1:, :])
    W = cache.window
    slots = jnp.arange(W, dtype=jnp.int32)[None]
    slot_pos = jnp.where(slots < lengths[:, None], slots, -1)
    return logits[:, 0], cache._replace(k_pages=k_all, v_pages=v_all,
                                        slot_pos=slot_pos,
                                        lengths=lengths.astype(jnp.int32))


def prefill_tail_paged(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                       start: jnp.ndarray, lengths: jnp.ndarray,
                       cache: PagedKVCache, window: Optional[int] = None,
                       attn_impl: str = "unfused"
                       ) -> Tuple[jnp.ndarray, PagedKVCache]:
    """Prefill only the novel *tail* of rows whose prefix KV is resident.

    The cross-request prefix-sharing twin of ``prefill_paged``: each row's
    leading ``start[b]`` tokens already live in pages reachable through
    ``cache.block_table`` (shared or retained from another request), so
    ``tokens`` holds only the left-padded tail and the per-token work
    drops from O(total) to O(tail).  Tail K/V is written at absolute
    slots ``start..lengths-1`` (compact layout, slot == position) and the
    tail queries attend to the full gathered window — see
    ``attention.attention_prefill_tail_paged``.  Returns the next-token
    logits of each row's last tail token and the refreshed cache
    (``slot_pos``/``lengths`` cover the full logical stream).
    """
    window = window if window is not None else cfg.sliding_window
    tail = lengths - start
    base = make_positions(tokens, tail)
    positions = jnp.where(base >= 0, base + start[:, None], -1)
    h = embed_apply(params["embed"], tokens, cfg)
    W = cache.window
    slots = jnp.arange(W, dtype=jnp.int32)[None]
    slot_pos = jnp.where(slots < lengths[:, None], slots, -1)

    def body(carry, layer, li, kp, vp):
        x = rms_norm(carry, layer["ln_attn"], cfg.norm_eps)
        a, kp, vp = attn.attention_prefill_tail_paged(
            layer["attn"], x, positions, cfg, window, kp, vp,
            cache.block_table, slot_pos, impl=attn_impl, layer=li)
        h2 = carry + a
        m = mlp_apply(layer["mlp"], rms_norm(h2, layer["ln_mlp"], cfg.norm_eps), cfg.act)
        return h2 + m, kp, vp

    h, k_all, v_all = _scan_paged(body, h, params["layers"],
                                  cache.k_pages, cache.v_pages)
    logits = _logits(params, cfg, h[:, -1:, :])
    return logits[:, 0], cache._replace(k_pages=k_all, v_pages=v_all,
                                        slot_pos=slot_pos,
                                        lengths=lengths.astype(jnp.int32))


def decode_step(params: Params, cfg: ModelConfig, cache: KVCache,
                tokens: jnp.ndarray, step: jnp.ndarray,
                window: Optional[int] = None) -> Tuple[jnp.ndarray, KVCache]:
    """One decode iteration. tokens (B,) int32; step () int32 (0-based)."""
    window = window if window is not None else cfg.sliding_window
    q_pos = cache.lengths + step  # (B,)
    slot = attn.decode_slot(cache)
    slot_pos = attn.decode_slot_pos(cache, q_pos)
    h = embed_apply(params["embed"], tokens[:, None], cfg)

    def body(carry, layer, kc, vc):
        x = rms_norm(carry, layer["ln_attn"], cfg.norm_eps)
        a, kc, vc = attn.attention_decode(layer["attn"], x, q_pos, kc, vc,
                                          slot_pos, slot, cfg, window)
        h2 = carry + a
        m = mlp_apply(layer["mlp"], rms_norm(h2, layer["ln_mlp"], cfg.norm_eps), cfg.act)
        return h2 + m, (kc, vc)

    h, (k_all, v_all) = scan_layers(body, h, params["layers"], cache.k, cache.v)
    logits = _logits(params, cfg, h)[:, 0]
    return logits, cache._replace(k=k_all, v=v_all, slot_pos=slot_pos,
                                  write_idx=cache.write_idx + 1)


def decode_step_rowslots(params: Params, cfg: ModelConfig, cache: KVCache,
                         tokens: jnp.ndarray, q_pos: jnp.ndarray,
                         slots: jnp.ndarray, window: Optional[int] = None
                         ) -> Tuple[jnp.ndarray, KVCache]:
    """Continuous-batching decode: per-row positions/write slots.

    ``q_pos``/``slots`` (B,) — caller (ContinuousEngine) tracks per-slot
    progress.  ``slot_pos`` rows are updated via scatter."""
    window = window if window is not None else cfg.sliding_window
    W = cache.window
    oh = jax.nn.one_hot(slots, W, dtype=jnp.int32)
    slot_pos = cache.slot_pos * (1 - oh) + q_pos[:, None].astype(jnp.int32) * oh
    h = embed_apply(params["embed"], tokens[:, None], cfg)

    def body(carry, layer, kc, vc):
        x = rms_norm(carry, layer["ln_attn"], cfg.norm_eps)
        a, kc, vc = attn.attention_decode_rowslots(
            layer["attn"], x, q_pos, kc, vc, slot_pos, slots, cfg, window)
        h2 = carry + a
        m = mlp_apply(layer["mlp"], rms_norm(h2, layer["ln_mlp"], cfg.norm_eps), cfg.act)
        return h2 + m, (kc, vc)

    h, (k_all, v_all) = scan_layers(body, h, params["layers"], cache.k, cache.v)
    logits = _logits(params, cfg, h)[:, 0]
    return logits, cache._replace(k=k_all, v=v_all, slot_pos=slot_pos)


def decode_step_paged(params: Params, cfg: ModelConfig, cache: PagedKVCache,
                      tokens: jnp.ndarray, q_pos: jnp.ndarray,
                      slots: jnp.ndarray, window: Optional[int] = None,
                      attn_impl: str = "unfused"
                      ) -> Tuple[jnp.ndarray, PagedKVCache]:
    """Continuous-batching decode over the paged cache (``repro.kvcache``).

    Mirrors ``decode_step_rowslots`` — per-row positions and write slots —
    but K/V live in a shared page pool reached through per-row block
    tables, so a row only occupies the pages its ``(L_i + S)`` envelope
    reserved.  ``slots`` index *logical* row slots; the page indirection
    happens inside the attention layer.
    """
    window = window if window is not None else cfg.sliding_window
    W = cache.window
    oh = jax.nn.one_hot(slots, W, dtype=jnp.int32)
    slot_pos = cache.slot_pos * (1 - oh) + q_pos[:, None].astype(jnp.int32) * oh
    h = embed_apply(params["embed"], tokens[:, None], cfg)

    def body(carry, layer, li, kp, vp):
        x = rms_norm(carry, layer["ln_attn"], cfg.norm_eps)
        a, kp, vp = attn.attention_decode_paged(
            layer["attn"], x, q_pos, kp, vp, cache.block_table, slot_pos,
            slots, cfg, window, impl=attn_impl, layer=li)
        h2 = carry + a
        m = mlp_apply(layer["mlp"], rms_norm(h2, layer["ln_mlp"], cfg.norm_eps), cfg.act)
        return h2 + m, kp, vp

    h, k_all, v_all = _scan_paged(body, h, params["layers"],
                                  cache.k_pages, cache.v_pages)
    logits = _logits(params, cfg, h)[:, 0]
    return logits, cache._replace(k_pages=k_all, v_pages=v_all,
                                  slot_pos=slot_pos)
