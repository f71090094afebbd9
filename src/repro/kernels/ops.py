"""jit'd dispatch wrappers for the Pallas kernels.

``impl`` selects the backend:
  * "xla"       — the pure-jnp reference (default on CPU; also the oracle)
  * "pallas"    — the TPU kernel (compiled on TPU, interpret-executed on
                  the CPU; any other backend is an error, never a silent
                  interpret run)

``set_default_impl`` flips the global default (the engines and models call
through these wrappers, so one switch moves the whole serving stack onto
the kernels).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_prefill import flash_prefill as _prefill_pallas
from repro.kernels.fused_rope_decode_append import (
    fused_rope_decode_append as _fused_decode_pallas)
from repro.kernels.fused_rope_prefill_write import (
    fused_rope_prefill_write as _fused_write_pallas)
from repro.kernels.paged_decode_attention import (
    paged_decode_attention as _paged_decode_pallas)
from repro.kernels.paged_prefill_write import (
    paged_prefill_write as _paged_write_pallas)
from repro.kernels.ssd_scan import ssd_scan as _ssd_pallas

_DEFAULT_IMPL = "xla"


def set_default_impl(impl: str) -> None:
    global _DEFAULT_IMPL
    assert impl in ("xla", "pallas")
    _DEFAULT_IMPL = impl


def get_default_impl() -> str:
    return _DEFAULT_IMPL


def _layer_pools(k_pages, v_pages, layer, head_dim: int):
    """The (P,pg,Hkv,D) pools a Pallas kernel takes: ``layer`` of stacked
    pools in any ``ref.pool_view`` layout, or the pools themselves when
    ``layer`` is None."""
    if layer is None:
        return k_pages, v_pages
    P, pg = k_pages.shape[1:3]
    return (k_pages[layer].reshape(P, pg, -1, head_dim),
            v_pages[layer].reshape(P, pg, -1, head_dim))


def _one_layer(fn, layer, k_pages, v_pages, head_dim: int):
    """Run a page-writing Pallas kernel ``fn(k_pool, v_pool)`` on one layer
    (``_layer_pools``); returns ``fn``'s outputs with the written pools,
    put back into the stacked pools, in its last two."""
    if layer is None:
        return fn(k_pages, v_pages)
    *rest, kl, vl = fn(*_layer_pools(k_pages, v_pages, layer, head_dim))
    return (*rest,
            k_pages.at[layer].set(kl.reshape(k_pages.shape[1:])),
            v_pages.at[layer].set(vl.reshape(v_pages.shape[1:])))


def _interpret() -> bool:
    """Compiled on the TPU, interpreted on the CPU (tests); any other
    backend raises rather than hiding behind the interpreter."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels target the TPU (compiled) or the "
                       f"CPU (interpret mode); backend {backend!r} is "
                       f"neither")


@partial(jax.jit, static_argnames=("window", "impl", "block_q", "block_k"))
def prefill_attention(q, k, v, positions, window: Optional[int] = None,
                      impl: Optional[str] = None, block_q: int = 128,
                      block_k: int = 128):
    """Causal/pad-masked GQA prefill attention. q (B,T,Hq,D) -> (B,T,Hq,D)."""
    impl = impl or _DEFAULT_IMPL
    if impl == "pallas":
        T = q.shape[1]
        bq = min(block_q, T)
        bk = min(block_k, T)
        while T % bq:
            bq //= 2
        while T % bk:
            bk //= 2
        return _prefill_pallas(q, k, v, positions, window=window,
                               block_q=bq, block_k=bk, interpret=_interpret())
    return ref.flash_prefill_ref(q, k, v, positions, window=window)


@partial(jax.jit, static_argnames=("window", "impl", "block_w"))
def decode_gqa_attention(q, k_cache, v_cache, slot_pos, q_pos,
                         window: Optional[int] = None,
                         impl: Optional[str] = None, block_w: int = 512):
    """Single-token GQA decode attention. q (B,Hq,D) -> (B,Hq,D)."""
    impl = impl or _DEFAULT_IMPL
    if impl == "pallas":
        W = k_cache.shape[1]
        bw = min(block_w, W)
        while W % bw:
            bw //= 2
        return _decode_pallas(q, k_cache, v_cache, slot_pos, q_pos,
                              window=window, block_w=bw, interpret=_interpret())
    return ref.decode_attention_ref(q, k_cache, v_cache, slot_pos, q_pos,
                                    window=window)


@partial(jax.jit, static_argnames=("window", "impl"))
def paged_decode_attention(q, k_pages, v_pages, block_table, slot_pos, q_pos,
                           window: Optional[int] = None,
                           impl: Optional[str] = None, layer=None):
    """Single-token GQA decode over a paged KV cache. q (B,Hq,D) -> (B,Hq,D).

    The page size is the kernel's cache-block size (one grid step per
    page), so no block_w knob: pick ``page_tokens`` TPU-friendly instead.
    ``layer`` (traced int) selects one layer of stacked pools in any
    ``ref.pool_view`` layout; None means the pools are a single
    (P,pg,Hkv,D) layer — the same convention for every paged op below.
    """
    impl = impl or _DEFAULT_IMPL
    if impl == "pallas":
        k_pages, v_pages = _layer_pools(k_pages, v_pages, layer, q.shape[-1])
        return _paged_decode_pallas(q, k_pages, v_pages, block_table,
                                    slot_pos, q_pos, window=window,
                                    interpret=_interpret())
    return ref.paged_decode_attention_ref(q, k_pages, v_pages, block_table,
                                          slot_pos, q_pos, window=window,
                                          layer=layer)


@partial(jax.jit, static_argnames=("impl",))
def paged_prefill_write(k_new, v_new, positions, block_table, k_pages,
                        v_pages, impl: Optional[str] = None, layer=None):
    """Write prefill K/V into the paged pool through block tables.

    k/v_new (B,T,Hkv,D) in the repo's left-padded layout; positions (B,T)
    from ``models.transformer.make_positions`` (pads < 0, real tokens at
    their absolute position — which IS the destination logical slot in
    the persistent-paged layout); block_table (B,nb); k/v_pages
    (P,pg,Hkv,D).  Returns the updated (k_pages, v_pages); pads land in
    the null page.  Tail slots of a row's last owned page differ between
    impls (the Pallas kernel copies whole pages) but are masked by
    ``slot_pos`` until decode overwrites them — never observable.
    """
    impl = impl or _DEFAULT_IMPL
    if impl == "pallas":
        pad = jnp.sum(positions < 0, axis=1).astype(jnp.int32)
        return _one_layer(
            lambda kp, vp: _paged_write_pallas(k_new, v_new, pad,
                                               block_table, kp, vp,
                                               interpret=_interpret()),
            layer, k_pages, v_pages, k_new.shape[-1])
    return ref.paged_prefill_write_ref(k_new, v_new, positions, block_table,
                                       k_pages, v_pages, layer=layer)


@partial(jax.jit, static_argnames=("theta", "impl"))
def fused_rope_prefill_write(k_new, v_new, positions, block_table, k_pages,
                             v_pages, theta: float = 10000.0,
                             impl: Optional[str] = None, layer=None):
    """Rotate prefill K at its absolute positions AND write K/V into the
    paged pool in one pass.

    k/v_new (B,T,Hkv,D) left-padded *unrotated* projections; positions
    (B,T) from ``models.transformer.make_positions`` (pads < 0, real
    tokens at their absolute position == destination logical slot);
    block_table (B,nb); k/v_pages (P,pg,Hkv,D).  Returns the updated
    (k_pages, v_pages) — V unrotated, K rotated at its slot.  Slots below
    a row's first real position (a shared-prefix tail) are preserved; the
    Pallas path requires that first position to be page-aligned (the
    engine shares whole pages only).  Tail slots of a row's last owned
    page differ between impls (the Pallas kernel copies whole pages) but
    are masked by ``slot_pos`` — never observable."""
    impl = impl or _DEFAULT_IMPL
    if impl == "pallas":
        T = positions.shape[1]
        pad = jnp.sum(positions < 0, axis=1).astype(jnp.int32)
        n_real = T - pad
        start = jnp.maximum(
            jnp.where(n_real > 0,
                      jnp.max(positions, axis=1).astype(jnp.int32)
                      - n_real + 1, 0), 0)
        return _one_layer(
            lambda kp, vp: _fused_write_pallas(k_new, v_new, pad - start,
                                               start, block_table, kp, vp,
                                               theta=theta,
                                               interpret=_interpret()),
            layer, k_pages, v_pages, k_new.shape[-1])
    return ref.fused_rope_prefill_write_ref(k_new, v_new, positions,
                                            block_table, k_pages, v_pages,
                                            theta=theta, layer=layer)


@partial(jax.jit, static_argnames=("theta", "window", "impl"))
def fused_rope_decode_append(q, k_new, v_new, block_table, slot_pos, slots,
                             q_pos, k_pages, v_pages, theta: float = 10000.0,
                             window: Optional[int] = None,
                             impl: Optional[str] = None, layer=None):
    """Rotate the new q/k token, append its K/V to its page slot, and run
    paged decode attention — all in one launch.

    q (B,Hq,D) and k/v_new (B,Hkv,D) *unrotated*; block_table (B,nb);
    slot_pos (B,nb·pg) already marking the new token's slot; slots (B,)
    destination logical slot; q_pos (B,) absolute position.  Returns
    (out (B,Hq,D), k_pages, v_pages)."""
    impl = impl or _DEFAULT_IMPL
    if impl == "pallas":
        return _one_layer(
            lambda kp, vp: _fused_decode_pallas(q, k_new, v_new, block_table,
                                                slot_pos, slots, q_pos, kp,
                                                vp, theta=theta,
                                                window=window,
                                                interpret=_interpret()),
            layer, k_pages, v_pages, q.shape[-1])
    return ref.fused_rope_decode_append_ref(q, k_new, v_new, block_table,
                                            slot_pos, slots, q_pos,
                                            k_pages, v_pages, theta=theta,
                                            window=window, layer=layer)


@partial(jax.jit, static_argnames=("chunk", "impl"))
def ssd_chunked_scan(x, dt, A, B, C, chunk: int = 128,
                     impl: Optional[str] = None):
    """Mamba-2 SSD scan. x (B,T,H,P); B/C (B,T,G,N) -> (y, final_state)."""
    impl = impl or _DEFAULT_IMPL
    H = x.shape[2]
    G = B.shape[2]
    if impl == "pallas":
        Bh = jnp.broadcast_to(B[:, :, :1], B.shape[:2] + (H, B.shape[-1]))             if G == 1 else jnp.repeat(B, H // G, axis=2)
        Ch = jnp.broadcast_to(C[:, :, :1], C.shape[:2] + (H, C.shape[-1]))             if G == 1 else jnp.repeat(C, H // G, axis=2)
        return _ssd_pallas(x, dt, A, Bh, Ch, chunk, interpret=_interpret())
    from repro.kernels.ref import ssd_scan_ref
    return ssd_scan_ref(x, dt, A, B, C, chunk)
