"""Unified model interface over all architecture families.

``get_model(cfg)`` returns a ``Model`` with five pure functions sharing one
calling convention, so the engine / trainer / dry-run never dispatch on the
family themselves:

  init(key)                                      -> params
  loss(params, batch)                            -> scalar
  prefill(params, batch, cache_window)           -> (last_logits, cache)
  decode_step(params, cache, tokens, step)       -> (logits, cache)
  kv_bytes_per_token(n_model_shards)             -> float  (Δ in Eq. 5)

and, for the families the persistent paged engine serves (dense, and MoE
with MLA), three paged entry points over a ``kvcache.paged.PagedKVCache``
whose two stacked pools (L, P) + ``paged_pages(page_tokens)`` hold K and V
(dense) or c_kv and the rotary key (MLA):

  prefill_paged(params, tokens, lengths, cache, attn_impl)
                                                 -> (last_logits, cache, counts)
  prefill_tail_paged(params, tokens, start, lengths, cache, attn_impl)
                                                 -> (last_logits, cache, counts)
  decode_step_paged(params, cache, tokens, q_pos, slots, done, attn_impl)
                                                 -> (logits, cache, counts)

``counts`` is a dict of int32 scalars the engine puts on its spans (empty
for dense; ``decode_counts`` names the decode step's).  Other families
leave them None and the paged engine refuses them.

``batch`` is a dict: tokens (B,T) int32, lengths (B,) int32, and optionally
labels / loss_mask (train), src_embeds or prefix_embeds (audio / vlm stubs).
``window_override`` lets the long_500k shape force a sliding window on
otherwise full-attention archs (DESIGN.md §5).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import encdec, mamba2, mla, moe, rglru, transformer
from repro.models.common import ModelConfig, softmax_xent


class Model(NamedTuple):
    cfg: ModelConfig
    init: Callable
    loss: Callable
    prefill: Callable
    decode_step: Callable
    kv_bytes_per_token: Callable
    prefill_paged: Optional[Callable] = None
    prefill_tail_paged: Optional[Callable] = None
    decode_step_paged: Optional[Callable] = None
    #: page_tokens -> the page shapes (rows, lanes) of the paged
    #: engine's two pools
    paged_pages: Optional[Callable] = None
    #: names of the counts ``decode_step_paged`` returns
    decode_counts: Tuple[str, ...] = ()


def _dtype_bytes(cfg: ModelConfig) -> int:
    return jnp.dtype(cfg.dtype).itemsize


def _loss_mask(batch: Dict[str, Any]) -> Optional[jnp.ndarray]:
    if "loss_mask" in batch:
        return batch["loss_mask"]
    return None


# ---------------------------------------------------------------------------
# per-family adapters
# ---------------------------------------------------------------------------
def _dense(cfg: ModelConfig) -> Model:
    def loss(params, batch):
        window = batch.get("window_override", cfg.sliding_window)
        logits = transformer.forward(params, cfg, batch["tokens"],
                                     prefix_embeds=batch.get("prefix_embeds"),
                                     window=window)
        if cfg.n_prefix_tokens and "prefix_embeds" in batch:
            logits = logits[:, batch["prefix_embeds"].shape[1]:]
        return softmax_xent(logits[:, :-1], batch["tokens"][:, 1:], _loss_mask(batch))

    def prefill(params, batch, cache_window, window=None):
        return transformer.prefill(params, cfg, batch["tokens"], batch["lengths"],
                                   cache_window,
                                   prefix_embeds=batch.get("prefix_embeds"),
                                   window=window)

    def decode_step(params, cache, tokens, step, window=None):
        return transformer.decode_step(params, cfg, cache, tokens, step, window=window)

    def kv_bytes(n_shards=1):
        per_tok = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * _dtype_bytes(cfg)
        shard = min(n_shards, cfg.n_kv_heads)  # MQA replicates KV on model axis
        return per_tok / shard

    def prefill_paged(params, tokens, lengths, cache, attn_impl="unfused"):
        logits, cache = transformer.prefill_paged(params, cfg, tokens, lengths,
                                                  cache, attn_impl=attn_impl)
        return logits, cache, {}

    def prefill_tail_paged(params, tokens, start, lengths, cache,
                           attn_impl="unfused"):
        logits, cache = transformer.prefill_tail_paged(
            params, cfg, tokens, start, lengths, cache, attn_impl=attn_impl)
        return logits, cache, {}

    def decode_step_paged(params, cache, tokens, q_pos, slots, done,
                          attn_impl="unfused"):
        logits, cache = transformer.decode_step_paged(
            params, cfg, cache, tokens, q_pos, slots, attn_impl=attn_impl)
        return logits, cache, {}

    paged = {}
    if cfg.family == "dense":  # token-only: no frontend embeddings
        rec = cfg.n_kv_heads * cfg.head_dim
        paged = dict(prefill_paged=prefill_paged,
                     prefill_tail_paged=prefill_tail_paged,
                     decode_step_paged=decode_step_paged,
                     paged_pages=lambda pg: ((pg, rec), (pg, rec)))
    return Model(cfg, lambda k: transformer.init(k, cfg), loss, prefill,
                 decode_step, kv_bytes, **paged)


def _ssm(cfg: ModelConfig) -> Model:
    def loss(params, batch):
        logits = mamba2.forward(params, cfg, batch["tokens"])
        return softmax_xent(logits[:, :-1], batch["tokens"][:, 1:], _loss_mask(batch))

    def prefill(params, batch, cache_window, window=None):
        return mamba2.prefill(params, cfg, batch["tokens"], batch["lengths"])

    def decode_step(params, cache, tokens, step, window=None):
        return mamba2.decode_step(params, cfg, cache, tokens, step)

    def kv_bytes(n_shards=1):
        # constant-size state, amortized over the slice: report the marginal
        # per-token cost as 0 and expose the fixed state separately.
        return 0.0

    return Model(cfg, lambda k: mamba2.init(k, cfg), loss, prefill,
                 decode_step, kv_bytes)


def _hybrid(cfg: ModelConfig) -> Model:
    def loss(params, batch):
        logits = rglru.forward(params, cfg, batch["tokens"])
        return softmax_xent(logits[:, :-1], batch["tokens"][:, 1:], _loss_mask(batch))

    def prefill(params, batch, cache_window, window=None):
        return rglru.prefill(params, cfg, batch["tokens"], batch["lengths"], cache_window)

    def decode_step(params, cache, tokens, step, window=None):
        return rglru.decode_step(params, cfg, cache, tokens, step)

    def kv_bytes(n_shards=1):
        n_attn = cfg.n_layers // 3
        per_tok = 2 * n_attn * cfg.n_kv_heads * cfg.head_dim * _dtype_bytes(cfg)
        return per_tok / min(n_shards, cfg.n_kv_heads)

    return Model(cfg, lambda k: rglru.init(k, cfg), loss, prefill,
                 decode_step, kv_bytes)


def _moe(cfg: ModelConfig) -> Model:
    def loss(params, batch):
        window = batch.get("window_override", cfg.sliding_window)
        logits, aux = moe.forward(params, cfg, batch["tokens"],
                                  batch.get("lengths"), window=window)
        xent = softmax_xent(logits[:, :-1], batch["tokens"][:, 1:], _loss_mask(batch))
        return xent + cfg.router_aux_coef * aux

    def prefill(params, batch, cache_window, window=None):
        return moe.prefill(params, cfg, batch["tokens"], batch["lengths"],
                           cache_window, window=window)

    def decode_step(params, cache, tokens, step, window=None):
        return moe.decode_step(params, cfg, cache, tokens, step, window=window)

    def kv_bytes(n_shards=1):
        b = _dtype_bytes(cfg)
        if cfg.use_mla:  # latent + shared rope key, replicated across heads
            return cfg.n_layers * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * b
        per_tok = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * b
        return per_tok / min(n_shards, cfg.n_kv_heads)

    def unfused(attn_impl):
        if attn_impl != "unfused":
            raise ValueError(f"{cfg.name}: latent attention has no "
                             f"{attn_impl!r} kernels")

    def prefill_paged(params, tokens, lengths, cache, attn_impl="unfused"):
        unfused(attn_impl)
        return moe.prefill_paged(params, cfg, tokens, lengths, cache)

    def prefill_tail_paged(params, tokens, start, lengths, cache,
                           attn_impl="unfused"):
        unfused(attn_impl)
        return moe.prefill_tail_paged(params, cfg, tokens, start, lengths,
                                      cache)

    def decode_step_paged(params, cache, tokens, q_pos, slots, done,
                          attn_impl="unfused"):
        unfused(attn_impl)
        return moe.decode_step_paged(params, cfg, cache, tokens, q_pos,
                                     slots, done)

    paged = {}
    if cfg.use_mla:
        paged = dict(prefill_paged=prefill_paged,
                     prefill_tail_paged=prefill_tail_paged,
                     decode_step_paged=decode_step_paged,
                     paged_pages=lambda pg: mla.paged_pages(cfg, pg),
                     decode_counts=("expert_reads", "expert_rows"))
    return Model(cfg, lambda k: moe.init(k, cfg), loss, prefill,
                 decode_step, kv_bytes, **paged)


def _encdec(cfg: ModelConfig) -> Model:
    def loss(params, batch):
        logits = encdec.forward(params, cfg, batch["src_embeds"], batch["tokens"],
                                batch.get("src_valid"))
        return softmax_xent(logits[:, :-1], batch["tokens"][:, 1:], _loss_mask(batch))

    def prefill(params, batch, cache_window, window=None):
        return encdec.prefill(params, cfg, batch["src_embeds"], batch["tokens"],
                              batch["lengths"], cache_window,
                              batch.get("src_valid"), window=window)

    def decode_step(params, cache, tokens, step, window=None):
        return encdec.decode_step(params, cfg, cache, tokens, step, window=window)

    def kv_bytes(n_shards=1):
        # decoder self-attention cache only (cross-KV is per-schedule constant)
        per_tok = 2 * cfg.n_dec_layers * cfg.n_kv_heads * cfg.head_dim * _dtype_bytes(cfg)
        return per_tok / min(n_shards, cfg.n_kv_heads)

    return Model(cfg, lambda k: encdec.init(k, cfg), loss, prefill,
                 decode_step, kv_bytes)


_FAMILIES = {
    "dense": _dense,
    "vlm": _dense,  # prefix-LM rides the dense path (prefix_embeds in batch)
    "ssm": _ssm,
    "hybrid": _hybrid,
    "moe": _moe,
    "encdec": _encdec,
}


def get_model(cfg: ModelConfig) -> Model:
    return _FAMILIES[cfg.family](cfg)
