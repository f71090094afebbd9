"""Pallas kernel validation: shape/dtype sweeps against the jnp oracles
(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import optional_hypothesis

given, settings, st = optional_hypothesis()

from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.kernels.ref import (decode_attention_ref, flash_prefill_ref,
                               paged_decode_attention_ref)

KEY = jax.random.PRNGKey(0)


def _qkv(B, T, Hq, Hkv, D, dtype=jnp.float32, key=KEY):
    q = jax.random.normal(key, (B, T, Hq, D), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, Hkv, D), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, Hkv, D), dtype)
    return q, k, v


def _positions(B, T, lengths):
    idx = jnp.arange(T)[None]
    L = jnp.asarray(lengths)[:, None]
    return jnp.where(idx < T - L, -1, idx - (T - L)).astype(jnp.int32)


ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("B,T,Hq,Hkv,D", [
    (1, 16, 1, 1, 8),
    (2, 32, 4, 2, 16),
    (2, 32, 4, 1, 32),     # MQA
    (1, 64, 8, 8, 16),     # MHA
    (3, 24, 6, 2, 64),     # non-pow2 batch, T%8==0
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 8])
def test_flash_prefill_sweep(B, T, Hq, Hkv, D, dtype, window):
    q, k, v = _qkv(B, T, Hq, Hkv, D, dtype)
    lengths = [T] + [max(1, T - 5)] * (B - 1)
    pos = _positions(B, T, lengths)
    out = flash_prefill(q, k, v, pos, window=window, block_q=8, block_k=8,
                        interpret=True)
    ref = flash_prefill_ref(q, k, v, pos, window=window)
    valid = (pos >= 0)[..., None, None]
    np.testing.assert_allclose(
        np.asarray((out * valid).astype(jnp.float32)),
        np.asarray((ref * valid).astype(jnp.float32)), atol=ATOL[dtype])


@pytest.mark.parametrize("B,W,Hq,Hkv,D", [
    (1, 8, 1, 1, 8),
    (2, 24, 8, 2, 16),
    (4, 16, 4, 1, 32),     # MQA
    (2, 64, 4, 4, 64),     # MHA, long cache
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 6])
def test_decode_attention_sweep(B, W, Hq, Hkv, D, dtype, window):
    kc = jax.random.normal(KEY, (B, W, Hkv, D), dtype)
    vc = jax.random.normal(jax.random.fold_in(KEY, 1), (B, W, Hkv, D), dtype)
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Hq, D), dtype)
    rng = np.random.default_rng(0)
    slot_pos = np.full((B, W), -1, np.int32)
    q_pos = []
    for b in range(B):
        fill = rng.integers(1, W + 1)
        slot_pos[b, :fill] = np.arange(fill)
        q_pos.append(fill)
    out = decode_attention(q, kc, vc, jnp.asarray(slot_pos), jnp.asarray(q_pos),
                           window=window, block_w=8, interpret=True)
    ref = decode_attention_ref(q, kc, vc, jnp.asarray(slot_pos),
                               jnp.asarray(q_pos), window=window)
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                               np.asarray(ref.astype(jnp.float32)),
                               atol=ATOL[dtype])


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 3), st.sampled_from([8, 16, 32]),
       st.sampled_from([(4, 2), (2, 1), (4, 4)]), st.sampled_from([8, 16]))
def test_flash_prefill_property(B, T, heads, D):
    """Random shapes: kernel == oracle on all real-token rows."""
    Hq, Hkv = heads
    q, k, v = _qkv(B, T, Hq, Hkv, D)
    lengths = [T - (i % T) for i in range(B)]
    pos = _positions(B, T, lengths)
    out = flash_prefill(q, k, v, pos, block_q=8, block_k=8, interpret=True)
    ref = flash_prefill_ref(q, k, v, pos)
    valid = (pos >= 0)[..., None, None]
    np.testing.assert_allclose(np.asarray(out * valid), np.asarray(ref * valid),
                               atol=5e-5)


def test_ring_cache_decode_kernel():
    """Ring layout (wrapped positions) must be handled purely via slot_pos."""
    B, W, H, D = 1, 8, 2, 16
    kc = jax.random.normal(KEY, (B, W, H, D))
    vc = jax.random.normal(jax.random.fold_in(KEY, 1), (B, W, H, D))
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (B, 4, D))
    # cache holds positions 5..12 wrapped: slot i has position (5+i) rotated
    slot_pos = jnp.asarray(np.roll(np.arange(5, 13), 3)[None].astype(np.int32))
    q_pos = jnp.array([12])
    out = decode_attention(q, kc, vc, slot_pos, q_pos, window=6, block_w=4,
                           interpret=True)
    ref = decode_attention_ref(q, kc, vc, slot_pos, q_pos, window=6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def _paged_setup(B, nb, pg, Hkv, D, P, dtype=jnp.float32, seed=0):
    """Random page pool + block tables: each row fills a random number of
    logical slots, mapped to shuffled non-null pages; unused table entries
    stay at the null page (0) and are masked via slot_pos = -1."""
    kp = jax.random.normal(KEY, (P, pg, Hkv, D), dtype)
    vp = jax.random.normal(jax.random.fold_in(KEY, 1), (P, pg, Hkv, D), dtype)
    rng = np.random.default_rng(seed)
    bt = np.zeros((B, nb), np.int32)
    slot_pos = np.full((B, nb * pg), -1, np.int32)
    q_pos = []
    for b in range(B):
        fill = int(rng.integers(1, nb * pg + 1))
        n_used = -(-fill // pg)
        bt[b, :n_used] = rng.choice(np.arange(1, P), size=n_used, replace=False)
        slot_pos[b, :fill] = np.arange(fill)
        q_pos.append(fill - 1)
    return kp, vp, jnp.asarray(bt), jnp.asarray(slot_pos), jnp.asarray(q_pos)


@pytest.mark.parametrize("B,nb,pg,Hq,Hkv,D", [
    (1, 2, 8, 1, 1, 8),
    (2, 3, 8, 4, 2, 16),
    (4, 2, 8, 4, 1, 32),   # MQA
    (2, 4, 16, 4, 4, 64),  # MHA, long cache
    (3, 3, 8, 6, 2, 16),   # non-pow2 batch
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 6])
def test_paged_decode_attention_sweep(B, nb, pg, Hq, Hkv, D, dtype, window):
    P = B * nb + 1  # enough distinct pages for every row + the null page
    kp, vp, bt, slot_pos, q_pos = _paged_setup(B, nb, pg, Hkv, D, P, dtype)
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Hq, D), dtype)
    out = paged_decode_attention(q, kp, vp, bt, slot_pos, q_pos,
                                 window=window, interpret=True)
    ref = paged_decode_attention_ref(q, kp, vp, bt, slot_pos, q_pos,
                                     window=window)
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                               np.asarray(ref.astype(jnp.float32)),
                               atol=ATOL[dtype])


def test_paged_equals_dense_on_gathered_cache():
    """The paged kernel over scattered pages == the dense kernel over the
    materialized gather: paging is pure layout, never math."""
    B, nb, pg, Hq, Hkv, D = 2, 3, 8, 4, 2, 16
    P = B * nb + 1
    kp, vp, bt, slot_pos, q_pos = _paged_setup(B, nb, pg, Hkv, D, P)
    q = jax.random.normal(jax.random.fold_in(KEY, 3), (B, Hq, D))
    paged = paged_decode_attention(q, kp, vp, bt, slot_pos, q_pos,
                                   interpret=True)
    kc = kp[bt].reshape(B, nb * pg, Hkv, D)
    vc = vp[bt].reshape(B, nb * pg, Hkv, D)
    dense = decode_attention(q, kc, vc, slot_pos, q_pos, block_w=pg,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense), atol=2e-5)


def test_paged_decode_ring_positions():
    """Wrapped (ring) positions must be handled purely via slot_pos, as in
    the dense kernel — the block table stays oblivious."""
    B, nb, pg, H, D = 1, 2, 4, 2, 16
    P = 4
    kp = jax.random.normal(KEY, (P, pg, H, D))
    vp = jax.random.normal(jax.random.fold_in(KEY, 1), (P, pg, H, D))
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (B, 4, D))
    bt = jnp.asarray([[2, 1]], jnp.int32)
    # cache holds positions 5..12 wrapped across the two pages
    slot_pos = jnp.asarray(np.roll(np.arange(5, 13), 3)[None].astype(np.int32))
    q_pos = jnp.array([12])
    out = paged_decode_attention(q, kp, vp, bt, slot_pos, q_pos, window=6,
                                 interpret=True)
    ref = paged_decode_attention_ref(q, kp, vp, bt, slot_pos, q_pos, window=6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                               ("tpu", False),
                                               ("gpu", None)])
def test_interpret_mode_only_on_the_cpu(monkeypatch, backend, interpret):
    """Kernels compile on the TPU and are interpreted on the CPU; any other
    backend is an error, never a silent interpret run."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="neither"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret


def test_paged_ops_dispatch_xla_equals_pallas():
    B, nb, pg, Hkv, D = 2, 2, 8, 2, 16
    P = B * nb + 1
    kp, vp, bt, slot_pos, q_pos = _paged_setup(B, nb, pg, Hkv, D, P)
    q = jax.random.normal(jax.random.fold_in(KEY, 4), (B, 4, D))
    a = ops.paged_decode_attention(q, kp, vp, bt, slot_pos, q_pos, impl="xla")
    b = ops.paged_decode_attention(q, kp, vp, bt, slot_pos, q_pos,
                                   impl="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_ops_dispatch_xla_equals_pallas():
    q, k, v = _qkv(2, 16, 4, 2, 16)
    pos = _positions(2, 16, [16, 10])
    a = ops.prefill_attention(q, k, v, pos, impl="xla")
    b = ops.prefill_attention(q, k, v, pos, impl="pallas", block_q=8, block_k=8)
    valid = (pos >= 0)[..., None, None]
    np.testing.assert_allclose(np.asarray(a * valid), np.asarray(b * valid), atol=2e-5)

    kc = jax.random.normal(KEY, (2, 16, 2, 16))
    vc = jax.random.normal(jax.random.fold_in(KEY, 5), (2, 16, 2, 16))
    qd = jax.random.normal(jax.random.fold_in(KEY, 6), (2, 4, 16))
    slot_pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16)).astype(jnp.int32)
    q_pos = jnp.array([15, 15])
    a = ops.decode_gqa_attention(qd, kc, vc, slot_pos, q_pos, impl="xla")
    b = ops.decode_gqa_attention(qd, kc, vc, slot_pos, q_pos, impl="pallas", block_w=8)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize("B,T,H,P,N,Q", [
    (1, 8, 1, 4, 4, 4),
    (2, 24, 3, 8, 4, 8),
    (1, 32, 2, 16, 8, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_ssd_scan_sweep(B, T, H, P, N, Q, dtype):
    """SSD Pallas kernel vs the jnp chunked oracle (and hence, transitively,
    vs the exact recurrence — see test_models.py)."""
    from repro.kernels.ssd_scan import ssd_scan
    from repro.models.mamba2 import _ssd_chunked
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (B, T, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (B, T, H), dtype))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (H,)) * 0.2)
    Bm = jax.random.normal(jax.random.fold_in(key, 3), (B, T, 1, N), dtype)
    Cm = jax.random.normal(jax.random.fold_in(key, 4), (B, T, 1, N), dtype)
    y_ref, st_ref = _ssd_chunked(x, dt, A, Bm, Cm, Q)
    Bh = jnp.broadcast_to(Bm, (B, T, H, N))
    Ch = jnp.broadcast_to(Cm, (B, T, H, N))
    y, st = ssd_scan(x, dt, A, Bh, Ch, Q, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref), atol=1e-5)


def test_ssd_ops_dispatch():
    from repro.kernels import ops
    key = jax.random.PRNGKey(2)
    B, T, H, P, N = 1, 16, 2, 8, 4
    x = jax.random.normal(key, (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (B, T, H)))
    A = -jnp.ones((H,))
    Bm = jax.random.normal(jax.random.fold_in(key, 2), (B, T, 1, N))
    Cm = jax.random.normal(jax.random.fold_in(key, 3), (B, T, 1, N))
    y1, s1 = ops.ssd_chunked_scan(x, dt, A, Bm, Cm, chunk=8, impl="xla")
    y2, s2 = ops.ssd_chunked_scan(x, dt, A, Bm, Cm, chunk=8, impl="pallas")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-5)


# ---------------------------------------------------------------------------
# paged prefill write (persistent paged StaticEngine storage)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,T,pg,Hkv,D", [
    (1, 16, 8, 1, 8),
    (2, 24, 8, 2, 16),
    (3, 12, 4, 2, 8),   # non-pow2 batch, partial last page
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_prefill_write_matches_ref_on_observable_slots(B, T, pg, Hkv,
                                                             D, dtype):
    """Pallas and jnp impls agree on every slot a reader can reach (valid
    slot_pos); tail slots of a partial page are masked garbage by
    contract and excluded."""
    rng = np.random.default_rng(0)
    lens = rng.integers(1, T + 1, size=B)
    lens[0] = T  # always one full row
    positions = _positions(B, T, lens)
    nb = -(-T // pg) + 1  # one spare block per row (decode capacity)
    P = B * nb + 1
    k_new = jax.random.normal(KEY, (B, T, Hkv, D), dtype)
    v_new = jax.random.normal(jax.random.fold_in(KEY, 1), (B, T, Hkv, D), dtype)
    bt = np.zeros((B, nb), np.int32)
    perm = rng.permutation(np.arange(1, P))
    for b in range(B):
        bt[b] = perm[b * nb:(b + 1) * nb]
    pool = jax.random.normal(jax.random.fold_in(KEY, 2), (P, pg, Hkv, D), dtype)
    outs = {}
    for impl in ("xla", "pallas"):
        outs[impl] = ops.paged_prefill_write(
            k_new, v_new, positions, jnp.asarray(bt), pool, pool, impl=impl)
    for b in range(B):
        ln = int(lens[b])
        for impl in ("xla", "pallas"):
            kk, vv = outs[impl]
            gk = np.asarray(kk)[bt[b]].reshape(nb * pg, Hkv, D)
            gv = np.asarray(vv)[bt[b]].reshape(nb * pg, Hkv, D)
            # written tokens land at slot == position, bit-exact
            np.testing.assert_array_equal(
                gk[:ln], np.asarray(k_new)[b, T - ln:])
            np.testing.assert_array_equal(
                gv[:ln], np.asarray(v_new)[b, T - ln:])


def test_paged_prefill_write_ref_leaves_unmapped_pages_untouched():
    """The jnp oracle routes pads to the null page and never touches pages
    outside the block tables."""
    from repro.kernels.ref import paged_prefill_write_ref
    B, T, pg, Hkv, D, P = 1, 8, 4, 1, 4, 5
    k_new = jnp.ones((B, T, Hkv, D))
    positions = _positions(B, T, [6])
    bt = jnp.asarray([[2, 3]], jnp.int32)
    pool = jnp.full((P, pg, Hkv, D), 7.0)
    kk, _ = paged_prefill_write_ref(k_new, k_new, positions, bt, pool, pool)
    kk = np.asarray(kk)
    np.testing.assert_array_equal(kk[1], np.full((pg, Hkv, D), 7.0))
    np.testing.assert_array_equal(kk[4], np.full((pg, Hkv, D), 7.0))
    np.testing.assert_array_equal(kk[2], np.ones((pg, Hkv, D)))
    np.testing.assert_array_equal(kk[3, :2], np.ones((2, Hkv, D)))
    # pads hit only the null page
    assert (kk[3, 2:] == 7.0).all()


# ---------------------------------------------------------------------------
# fused RoPE + paged-KV kernels (PR 10)
# ---------------------------------------------------------------------------
def _fused_write_setup(B, T, pg, Hkv, D, dtype=jnp.float32, seed=0,
                       starts=None):
    """Left-padded unrotated prefill K/V + disjoint block tables.  With
    ``starts`` (page-aligned), row b's first ``starts[b]`` slots play
    resident/shared pages whose contents must be preserved."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(1, T + 1, size=B)
    lens[0] = T
    starts = [0] * B if starts is None else list(starts)
    # positions: row b covers absolute slots starts[b] .. starts[b]+len-1
    idx = np.arange(T)[None]
    L = np.asarray(lens)[:, None]
    pos = np.where(idx < T - L, -1,
                   idx - (T - L) + np.asarray(starts)[:, None]).astype(np.int32)
    nb = -(-(T + max(starts)) // pg) + 1
    P = B * nb + 1
    k_new = jax.random.normal(KEY, (B, T, Hkv, D), dtype)
    v_new = jax.random.normal(jax.random.fold_in(KEY, 1), (B, T, Hkv, D), dtype)
    bt = np.zeros((B, nb), np.int32)
    perm = rng.permutation(np.arange(1, P))
    for b in range(B):
        bt[b] = perm[b * nb:(b + 1) * nb]
    pool = jax.random.normal(jax.random.fold_in(KEY, 2), (P, pg, Hkv, D), dtype)
    return (k_new, v_new, jnp.asarray(pos), jnp.asarray(bt), pool,
            [int(x) for x in lens], starts, nb)


@pytest.mark.parametrize("B,T,pg,Hkv,D", [
    (1, 16, 8, 1, 8),
    (2, 24, 8, 2, 16),
    (3, 12, 4, 2, 8),   # non-pow2 batch, partial last page
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_rope_prefill_write_matches_oracle(B, T, pg, Hkv, D, dtype):
    """ONE Pallas pass == rope-then-write oracle on every observable slot
    (rotated K within atol; V bit-exact — the kernel never touches V math)."""
    k_new, v_new, pos, bt, pool, lens, _, nb = _fused_write_setup(
        B, T, pg, Hkv, D, dtype)
    outs = {impl: ops.fused_rope_prefill_write(k_new, v_new, pos, bt,
                                               pool, pool, impl=impl)
            for impl in ("xla", "pallas")}
    for b in range(B):
        ln = lens[b]
        gk = {i: np.asarray(o[0])[np.asarray(bt)[b]].reshape(nb * pg, Hkv, D)
              for i, o in outs.items()}
        gv = {i: np.asarray(o[1])[np.asarray(bt)[b]].reshape(nb * pg, Hkv, D)
              for i, o in outs.items()}
        np.testing.assert_allclose(
            gk["pallas"][:ln].astype(np.float32),
            gk["xla"][:ln].astype(np.float32), atol=ATOL[dtype])
        np.testing.assert_array_equal(gv["pallas"][:ln], gv["xla"][:ln])


def test_fused_prefill_write_tail_preserves_resident_pages():
    """Shared-prefix tail (page-aligned start > 0): slots below start are
    passed through BIT-EXACT from the aliased pool input; novel slots
    match the oracle."""
    B, T, pg, Hkv, D = 2, 16, 8, 2, 16
    starts = [8, 0]  # row 0 resumes after one resident page
    k_new, v_new, pos, bt, pool, lens, starts, nb = _fused_write_setup(
        B, T, pg, Hkv, D, starts=starts, seed=3)
    kx, vx = ops.fused_rope_prefill_write(k_new, v_new, pos, bt, pool, pool,
                                          impl="xla")
    kp, vp = ops.fused_rope_prefill_write(k_new, v_new, pos, bt, pool, pool,
                                          impl="pallas")
    bt_np = np.asarray(bt)
    for b in range(B):
        st, ln = starts[b], lens[b]
        g = lambda arr: np.asarray(arr)[bt_np[b]].reshape(nb * pg, Hkv, D)
        # resident slots: exactly the pre-existing pool contents
        np.testing.assert_array_equal(g(kp)[:st], np.asarray(pool)[bt_np[b]]
                                      .reshape(nb * pg, Hkv, D)[:st])
        # novel slots: oracle agreement
        np.testing.assert_allclose(g(kp)[st:st + ln], g(kx)[st:st + ln],
                                   atol=2e-5)
        np.testing.assert_array_equal(g(vp)[st:st + ln], g(vx)[st:st + ln])


def test_fused_prefill_write_equals_unfused_two_pass():
    """Fused == apply_rope (jnp) + paged_prefill_write: the fusion changes
    pass count, never math."""
    from repro.models.common import apply_rope
    B, T, pg, Hkv, D = 2, 16, 8, 2, 16
    k_new, v_new, pos, bt, pool, lens, _, nb = _fused_write_setup(
        B, T, pg, Hkv, D, seed=5)
    fused = ops.fused_rope_prefill_write(k_new, v_new, pos, bt, pool, pool,
                                         impl="xla", theta=10000.0)
    k_rot = apply_rope(k_new, jnp.maximum(pos, 0), 10000.0)
    unfused = ops.paged_prefill_write(k_rot, v_new, pos, bt, pool, pool,
                                      impl="xla")
    for b in range(B):
        ln = lens[b]
        for f, u in zip(fused, unfused):
            gf = np.asarray(f)[np.asarray(bt)[b]].reshape(nb * pg, Hkv, D)
            gu = np.asarray(u)[np.asarray(bt)[b]].reshape(nb * pg, Hkv, D)
            np.testing.assert_allclose(gf[:ln], gu[:ln], atol=2e-5)


def _fused_decode_setup(B, nb, pg, Hq, Hkv, D, dtype=jnp.float32, seed=0):
    """Paged pool mid-decode: each row has ``fill`` tokens resident and a
    new token destined for slot ``fill`` (slot_pos already marks it — the
    token must attend to itself)."""
    P = B * nb + 1
    kp = jax.random.normal(KEY, (P, pg, Hkv, D), dtype)
    vp = jax.random.normal(jax.random.fold_in(KEY, 1), (P, pg, Hkv, D), dtype)
    rng = np.random.default_rng(seed)
    bt = np.zeros((B, nb), np.int32)
    slot_pos = np.full((B, nb * pg), -1, np.int32)
    slots = []
    # pages of different rows must be DISJOINT (allocator contract — the
    # fused kernel's aliased tile writes rely on it; only null page 0 is
    # shared, and only by unmapped blocks)
    perm = rng.permutation(np.arange(1, P))
    for b in range(B):
        fill = int(rng.integers(0, nb * pg))  # new token lands at slot fill
        n_used = -(-(fill + 1) // pg)
        bt[b, :n_used] = perm[b * nb:b * nb + n_used]
        slot_pos[b, :fill + 1] = np.arange(fill + 1)
        slots.append(fill)
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Hq, D), dtype)
    kn = jax.random.normal(jax.random.fold_in(KEY, 3), (B, Hkv, D), dtype)
    vn = jax.random.normal(jax.random.fold_in(KEY, 4), (B, Hkv, D), dtype)
    s = jnp.asarray(slots, jnp.int32)
    return (q, kn, vn, jnp.asarray(bt), jnp.asarray(slot_pos), s, s, kp, vp)


@pytest.mark.parametrize("B,nb,pg,Hq,Hkv,D", [
    (1, 2, 8, 1, 1, 8),
    (2, 3, 8, 4, 2, 16),
    (4, 2, 8, 4, 1, 32),   # MQA
    (3, 3, 8, 6, 2, 16),   # non-pow2 batch
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 6])
def test_fused_rope_decode_append_matches_oracle(B, nb, pg, Hq, Hkv, D,
                                                 dtype, window):
    args = _fused_decode_setup(B, nb, pg, Hq, Hkv, D, dtype)
    ox, kx, vx = ops.fused_rope_decode_append(*args, window=window,
                                              impl="xla")
    op_, kp_, vp_ = ops.fused_rope_decode_append(*args, window=window,
                                                 impl="pallas")
    np.testing.assert_allclose(np.asarray(op_.astype(jnp.float32)),
                               np.asarray(ox.astype(jnp.float32)),
                               atol=ATOL[dtype])
    # the appended token's K/V landed identically in the pool
    bt, slots = np.asarray(args[3]), np.asarray(args[5])
    for b in range(B):
        s = int(slots[b])
        page, off = int(bt[b, s // pg]), s % pg
        np.testing.assert_allclose(
            np.asarray(kp_)[page, off].astype(np.float32),
            np.asarray(kx)[page, off].astype(np.float32), atol=ATOL[dtype])
        np.testing.assert_array_equal(np.asarray(vp_)[page, off],
                                      np.asarray(vx)[page, off])


def test_fused_decode_append_equals_unfused_three_pass():
    """Fused == rope (jnp) + XLA scatter + paged_decode_attention: the
    single launch reproduces the three-pass pipeline's math."""
    from repro.models.common import apply_rope
    B, nb, pg, Hq, Hkv, D = 2, 3, 8, 4, 2, 16
    q, kn, vn, bt, slot_pos, slots, q_pos, kp, vp = _fused_decode_setup(
        B, nb, pg, Hq, Hkv, D, seed=4)
    fo, fk, fv = ops.fused_rope_decode_append(q, kn, vn, bt, slot_pos, slots,
                                              q_pos, kp, vp, impl="xla")
    qr = apply_rope(q[:, None], q_pos[:, None], 10000.0)[:, 0]
    kr = apply_rope(kn[:, None], q_pos[:, None], 10000.0)[:, 0]
    pages = bt[jnp.arange(B), slots // pg]
    uk = kp.at[pages, slots % pg].set(kr)
    uv = vp.at[pages, slots % pg].set(vn)
    uo = ops.paged_decode_attention(qr, uk, uv, bt, slot_pos, q_pos,
                                    impl="xla")
    np.testing.assert_allclose(np.asarray(fo), np.asarray(uo), atol=2e-5)
    np.testing.assert_allclose(np.asarray(fk), np.asarray(uk), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(uv))
