"""Mamba-2 SSD chunked scan — Pallas TPU kernel.

The SSD mixer is the whole compute of the attention-free arch
(mamba2-130m), and its chunked formulation maps cleanly onto TPU tiles:
per (batch, head) the grid walks chunks sequentially, carrying the (P, N)
state in VMEM scratch; within a chunk everything is (Q, ·) matmuls on the
MXU (Q = 128 aligns with the 128-lane register file):

  y[t] = Σ_{s<=t} (C_t·B_s) e^{cum_t - cum_s} dt_s x_s   (intra, tril-masked)
       + C_t · (e^{cum_t} ⊙ state_in)                     (inter)
  state_out = e^{cum_Q} state_in + Σ_s e^{cum_Q - cum_s} dt_s B_s ⊗ x_s

Numerics follow ref.ssd_scan_ref (the oracle) exactly: fp32
throughout the recurrence, single-group B/C shared across heads is handled
by the caller broadcasting (this kernel takes per-head B/C blocks).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_out_ref,
            state_scr, *, nc: int, Q: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    A = a_ref[0]  # scalar (this head's A, negative)
    x = x_ref[0, 0, :, 0].astype(jnp.float32)    # (Q, P)
    dt = dt_ref[0, 0, :, 0].astype(jnp.float32)  # (Q,)
    B = b_ref[0, 0, :, 0].astype(jnp.float32)    # (Q, N)
    C = c_ref[0, 0, :, 0].astype(jnp.float32)    # (Q, N)

    log_a = dt * A                               # (Q,) <= 0
    cum = jnp.cumsum(log_a)                      # inclusive
    # intra-chunk: G[t,s] = (C_t.B_s) e^{cum_t-cum_s} dt_s, s<=t
    CB = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    decay = jnp.exp(cum[:, None] - cum[None, :])
    tril = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    G = jnp.where(tril, CB * decay * dt[None, :], 0.0)
    y = jax.lax.dot_general(G, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q,P)
    # inter-chunk: y[t] += e^{cum_t} C_t . state_in  (state (P,N))
    state = state_scr[...]
    y += jnp.exp(cum)[:, None] * jax.lax.dot_general(
        C, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # (Q,N)x(P,N) -> (Q,P)
    y_ref[0, 0, :, 0] = y.astype(y_ref.dtype)
    # state update: e^{cum_Q} state + Σ_s w_s x_s (x) B_s,  w = e^{cum_Q-cum} dt
    w = jnp.exp(cum[Q - 1] - cum) * dt                    # (Q,)
    upd = jax.lax.dot_general(x * w[:, None], B, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P,N)
    state = state * jnp.exp(cum[Q - 1]) + upd
    state_scr[...] = state

    @pl.when(c_idx == nc - 1)
    def _final():
        state_out_ref[0, 0] = state.astype(state_out_ref.dtype)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             B: jnp.ndarray, C: jnp.ndarray, chunk: int,
             interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B,T,H,P); dt (B,T,H); A (H,); B/C (B,T,H,N) (caller broadcasts
    groups to heads).  Returns (y (B,T,H,P), final_state (B,H,P,N))."""
    Bsz, T, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, T)
    assert T % Q == 0, "T must divide the chunk size"
    nc = T // Q
    xc = x.reshape(Bsz, nc, Q, H, P)
    dtc = dt.reshape(Bsz, nc, Q, H)
    Bc = B.reshape(Bsz, nc, Q, H, N)
    Cc = C.reshape(Bsz, nc, Q, H, N)

    kernel = functools.partial(_kernel, nc=nc, Q=Q)
    y, state = pl.pallas_call(
        kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, c: (h,)),                  # A
            pl.BlockSpec((1, 1, Q, 1, P), lambda b, h, c: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h, c: (b, c, 0, h)),  # dt
            pl.BlockSpec((1, 1, Q, 1, N), lambda b, h, c: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, Q, 1, N), lambda b, h, c: (b, c, 0, h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, 1, P), lambda b, h, c: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, nc, Q, H, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(A.astype(jnp.float32), xc, dtc, Bc, Cc)
    return y.reshape(Bsz, T, H, P), state
