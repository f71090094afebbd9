"""Ahead-of-time compiles for a described TPU v5e, at llama3.2-1b's widths.

Nothing runs: the TPU compiler, which is installed with jax, compiles for a
chip that is described and not attached, and raises what the chip's
compiler would (tiling, VMEM or HBM limits).  Covered: the four paged
Pallas kernels of the serving path, the prefill writes at the longest
prompt ``chip_smoke.py`` serves and at the longest that fits VMEM, and the
served paged prefill/decode programs, whose page pools must be updated in
place.  The topology and everything built from it live in the fixtures of
this one file, never at import (only one process may load the TPU
library at a time).
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.fused_rope_decode_append import fused_rope_decode_append
from repro.kernels.fused_rope_prefill_write import fused_rope_prefill_write
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.kernels.paged_prefill_write import paged_prefill_write
from repro.kvcache.paged import PagedKVCache
from repro.models import transformer as tfm
from repro.models.registry import get_model

CFG = get_config("llama3.2-1b", reduced=False)
B, PG, NB = 8, 16, 32            # batch, page tokens, blocks per row
HQ, HKV, D = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
THETA = CFG.rope_theta
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory on one described v5e chip, with JAX's
    persistent compilation cache off (entries compiled for a described
    chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype=BF16: jax.ShapeDtypeStruct(shape, dtype,
                                                         sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_paged_decode_attention_compiles(spec):
    P = B * NB + 1
    _compile(partial(paged_decode_attention, interpret=False),
             spec((B, HQ, D)), spec((P, PG, HKV, D)), spec((P, PG, HKV, D)),
             spec((B, NB), I32), spec((B, NB * PG), I32), spec((B,), I32))


def test_fused_rope_decode_append_compiles(spec):
    P = B * NB + 1
    _compile(partial(fused_rope_decode_append, theta=THETA,
                     interpret=False),
             spec((B, HQ, D)), spec((B, HKV, D)), spec((B, HKV, D)),
             spec((B, NB), I32), spec((B, NB * PG), I32), spec((B,), I32),
             spec((B,), I32), spec((P, PG, HKV, D)), spec((P, PG, HKV, D)))


# 512: chip_smoke's longest prompt.  1536: the longest prompt whose whole
# padded row still fits VMEM (2048 is refused; each grid step stages the
# row, see ROADMAP Queue 1 item 5)
@pytest.mark.parametrize("T", [512, 1536])
def test_paged_prefill_write_compiles(spec, T):
    nb = T // PG + 1
    P = B * nb + 1
    _compile(partial(paged_prefill_write, interpret=False),
             spec((B, T, HKV, D)), spec((B, T, HKV, D)), spec((B,), I32),
             spec((B, nb), I32), spec((P, PG, HKV, D)), spec((P, PG, HKV, D)))


@pytest.mark.parametrize("T", [512, 1536])
def test_fused_rope_prefill_write_compiles(spec, T):
    nb = T // PG + 1
    P = B * nb + 1
    _compile(partial(fused_rope_prefill_write, theta=THETA, interpret=False),
             spec((B, T, HKV, D)), spec((B, T, HKV, D)), spec((B,), I32),
             spec((B,), I32), spec((B, nb), I32), spec((P, PG, HKV, D)),
             spec((P, PG, HKV, D)))


@pytest.fixture(scope="module")
def served(spec):
    """Full-width params and 1 GiB K and V page pools in the lane-dense
    layout ``StaticEngine`` allocates, as shapes on the described chip."""
    model = get_model(CFG)
    params = jax.tree_util.tree_map(
        lambda s: spec(s.shape, s.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    F = HKV * D
    P = 2 ** 30 // (CFG.n_layers * PG * F * 2)
    pool = spec((CFG.n_layers, P, PG, F))
    return params, pool


def _in_place(compiled, pool) -> None:
    """Both pools are donated and aliased to the outputs, and the program's
    temporaries (activations, a few hundred MB) leave no room for a copy
    of a pool."""
    m = compiled.memory_analysis()
    pool_bytes = pool.size * pool.dtype.itemsize
    assert m.alias_size_in_bytes >= 2 * pool_bytes
    assert m.temp_size_in_bytes < pool_bytes // 2


def test_served_paged_prefill_updates_pool_in_place(spec, served):
    params, pool = served
    T = 512

    def prefill(p, toks, lens, kp, vp, bt):
        cache = PagedKVCache(kp, vp, bt, jnp.full((B, NB * PG), -1, I32),
                             jnp.zeros((B,), I32))
        logits, cache = tfm.prefill_paged(p, CFG, toks, lens, cache)
        return logits, cache.k_pages, cache.v_pages

    compiled = jax.jit(prefill, donate_argnums=(3, 4)).lower(
        params, spec((B, T), I32), spec((B,), I32), pool, pool,
        spec((B, NB), I32)).compile()
    _in_place(compiled, pool)


def test_served_paged_decode_updates_pool_in_place(spec, served):
    params, pool = served

    def decode(p, kp, vp, bt, sp, toks, q_pos):
        cache = PagedKVCache(kp, vp, bt, sp, q_pos)
        logits, cache = tfm.decode_step_paged(p, CFG, cache, toks, q_pos,
                                              q_pos)
        return logits, cache.k_pages, cache.v_pages

    compiled = jax.jit(decode, donate_argnums=(1, 2)).lower(
        params, pool, pool, spec((B, NB), I32), spec((B, NB * PG), I32),
        spec((B,), I32), spec((B,), I32)).compile()
    _in_place(compiled, pool)
