"""Shared building blocks for the model zoo.

Everything is raw JAX: parameters are pytrees (nested dicts of jnp arrays),
modules are pairs of ``init_*`` / pure-apply functions.  Layer stacks are
stored with a leading ``layer`` axis and consumed with ``lax.scan`` so the
traced HLO is O(1) in depth (critical for the 512-device dry-run compiles).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config type for every assigned architecture family."""

    name: str = "model"
    family: str = "dense"  # dense | ssm | encdec | vlm | moe | hybrid
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 64
    d_ff: int = 512
    vocab_size: int = 1024
    rope_theta: float = 10000.0
    # YaRN rotary scaling (arXiv:2309.00071, deepseek-v2's rope_scaling);
    # a factor of 1 is plain RoPE
    rope_scaling_factor: float = 1.0
    rope_original_max_pos: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    norm_eps: float = 1e-6
    act: str = "silu"  # silu (SwiGLU) | gelu (GeGLU)
    qkv_bias: bool = False  # qwen1.5
    tie_embeddings: bool = True
    scale_embed: bool = False  # gemma: embeddings * sqrt(d_model)
    # sliding window attention (None = full causal).  ``long_context_window``
    # is the window substituted when the long_500k shape is requested for an
    # arch whose base attention is full-causal (see DESIGN.md §5).
    sliding_window: Optional[int] = None
    long_context_window: int = 8192
    # --- MoE ---
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 2
    d_ff_expert: int = 0
    capacity_factor: float = 1.25
    first_dense_layers: int = 0  # deepseek-v2: layer 0 is a dense MLP
    router_aux_coef: float = 0.01
    # renormalise the top-k router weights to sum to 1 (mixtral); deepseek-v2
    # keeps the softmax probabilities as they are
    norm_topk_prob: bool = True
    # expert parallelism: this layer holds experts [expert_offset,
    # expert_offset + n_experts_held) of the n_experts the router scores
    # (0 = all of them); tokens routed elsewhere get nothing from here
    n_experts_held: int = 0
    expert_offset: int = 0
    # --- MLA (deepseek-v2) ---
    use_mla: bool = False
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # --- SSM (mamba2 / SSD) ---
    ssm_state: int = 128
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv_width: int = 4
    ssm_chunk: int = 128
    ssm_n_groups: int = 1
    # --- hybrid (recurrentgemma) ---
    rg_pattern: Tuple[str, ...] = ("rec", "rec", "attn")
    rg_lru_width: int = 0  # 0 -> d_model
    local_window: int = 2048
    # --- enc-dec (seamless) ---
    n_enc_layers: int = 0
    n_dec_layers: int = 0
    # --- vlm (paligemma) ---
    n_prefix_tokens: int = 0  # SigLIP patch count; embeddings come pre-computed
    # --- training memory policy ---
    remat: bool = False  # per-layer activation checkpointing in lax.scan
    # --- numerics ---
    dtype: Any = jnp.float32

    @property
    def n_rep(self) -> int:
        return self.n_heads // max(self.n_kv_heads, 1)

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    @property
    def d_lru(self) -> int:
        return self.rg_lru_width or self.d_model

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_n_heads(self) -> int:
        return self.ssm_d_inner // self.ssm_head_dim

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------
def normal_init(key, shape, dtype, scale: float = 0.02):
    return (scale * jax.random.normal(key, shape)).astype(dtype)


def dense_param(key, d_in: int, d_out: int, dtype, bias: bool = False) -> Params:
    kw, kb = jax.random.split(key)
    p = {"w": normal_init(kw, (d_in, d_out), dtype, scale=d_in ** -0.5)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense_apply(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------
def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + weight.astype(jnp.float32))).astype(dt)


def init_rms(d: int, dtype) -> jnp.ndarray:
    # stored as (scale - 1) like gemma/llama "weight + 1" convention simplified:
    # we keep zeros and add 1 inside rms_norm.
    return jnp.zeros((d,), dtype)


def activate(x: jnp.ndarray, act: str) -> jnp.ndarray:
    if act == "silu":
        return jax.nn.silu(x)
    if act == "gelu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown act {act}")


def init_mlp(key, d_model: int, d_ff: int, dtype) -> Params:
    kg, ku, kd = jax.random.split(key, 3)
    return {
        "gate": dense_param(kg, d_model, d_ff, dtype),
        "up": dense_param(ku, d_model, d_ff, dtype),
        "down": dense_param(kd, d_ff, d_model, dtype),
    }


def mlp_apply(p: Params, x: jnp.ndarray, act: str) -> jnp.ndarray:
    return dense_apply(p["down"], activate(dense_apply(p["gate"], x), act) * dense_apply(p["up"], x))


# ---------------------------------------------------------------------------
# rotary position embeddings (llama-style half rotation)
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               freqs: Optional[jnp.ndarray] = None,
               mult: float = 1.0) -> jnp.ndarray:
    """x: (..., T, H, D); positions: broadcastable to (..., T).  ``freqs``
    (D/2,) overrides the plain inverse frequencies (YaRN) and ``mult``
    scales cos and sin."""
    d = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(d, theta)  # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., T, D/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]  # (...,T,1,D/2)
    if mult != 1.0:
        cos, sin = cos * mult, sin * mult
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term, 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_dim(n_rot: float, dim: int, theta: float, max_pos: int) -> float:
    """The rotary pair index whose wavelength fits ``n_rot`` turns in
    ``max_pos`` positions."""
    return (dim * math.log(max_pos / (n_rot * 2 * math.pi))
            / (2 * math.log(theta)))


def yarn_freqs(dim: int, cfg: ModelConfig) -> jnp.ndarray:
    """Inverse rotary frequencies (dim/2,) under YaRN: pairs below the
    ``beta_fast`` correction dim keep theta^(-2i/dim), pairs above the
    ``beta_slow`` one are divided by the factor, and a linear ramp
    blends the pairs in between (deepseek-v2's
    ``DeepseekV2YarnRotaryEmbedding``)."""
    base = 1.0 / (cfg.rope_theta ** (np.arange(0, dim, 2) / dim))
    f = cfg.rope_scaling_factor
    if f <= 1:
        return jnp.asarray(base, jnp.float32)
    lo = max(math.floor(_yarn_dim(cfg.rope_beta_fast, dim, cfg.rope_theta,
                                  cfg.rope_original_max_pos)), 0)
    hi = min(math.ceil(_yarn_dim(cfg.rope_beta_slow, dim, cfg.rope_theta,
                                 cfg.rope_original_max_pos)), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    return jnp.asarray(base / f * ramp + base * (1.0 - ramp), jnp.float32)


def yarn_rope_mult(cfg: ModelConfig) -> float:
    """The factor on YaRN's cos and sin: mscale over mscale_all_dim."""
    f = cfg.rope_scaling_factor
    return (yarn_mscale(f, cfg.rope_mscale)
            / yarn_mscale(f, cfg.rope_mscale_all_dim))


def yarn_softmax_factor(cfg: ModelConfig) -> float:
    """The factor on the attention softmax scale: mscale(all dims)^2 (1
    without YaRN, or with ``rope_mscale_all_dim`` 0)."""
    return yarn_mscale(cfg.rope_scaling_factor, cfg.rope_mscale_all_dim) ** 2


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------
def init_embed(key, vocab: int, d_model: int, dtype) -> Params:
    return {"table": normal_init(key, (vocab, d_model), dtype)}


def embed_apply(p: Params, tokens: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    h = jnp.take(p["table"], tokens, axis=0)
    if cfg.scale_embed:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    return h


def unembed_apply(p: Params, h: jnp.ndarray) -> jnp.ndarray:
    return h @ p["table"].T


# ---------------------------------------------------------------------------
# layer stacking helpers
# ---------------------------------------------------------------------------
def stack_layers(init_one: Callable[[jax.Array], Params], key, n: int) -> Params:
    """Initialize n layers and stack each leaf along a leading axis."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_one)(keys)


# Megatron-SP-style activation sequence sharding (launch sets this before
# tracing a sharded train step; see EXPERIMENTS.md §Perf iteration 1).  When
# set, the residual stream carried between layers is constrained to this
# PartitionSpec — GSPMD then keeps pointwise ops sequence-sharded and only
# gathers where attention genuinely needs the full sequence.
_ACTIVATION_SPEC = None


def set_activation_sharding(spec) -> None:
    global _ACTIVATION_SPEC
    _ACTIVATION_SPEC = spec


def _constrain(h):
    if _ACTIVATION_SPEC is not None and hasattr(h, "ndim") and h.ndim == 3:
        return jax.lax.with_sharding_constraint(h, _ACTIVATION_SPEC)
    return h


def scan_layers(body: Callable, h: jnp.ndarray, stacked: Params, *extra_xs,
                remat: bool = False):
    """lax.scan of ``body(h, per_layer_params, *per_layer_extras)``.

    body returns (new_h, per_layer_output or None).  ``remat=True`` wraps the
    body in jax.checkpoint (per-layer activation rematerialization for the
    training path).
    """

    def step(carry, xs):
        out, ys = body(_constrain(carry), *xs)
        return _constrain(out), ys

    if remat:
        step = jax.checkpoint(step)
    xs = (stacked,) + tuple(extra_xs)
    return jax.lax.scan(step, h, xs)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------
def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray, mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean next-token cross entropy. logits (B,T,V); labels (B,T) int."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
