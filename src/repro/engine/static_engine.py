"""Static-batching inference engine (real JAX execution).

Semantics follow the paper's §2.4 exactly:
  * batched prompts are left-padded to the (bucketed) batch input length;
  * the batch runs prefill once, then decodes for at most ``slice_len``
    iterations (SCLS) or until every request has produced EOS — completed
    requests keep generating *invalid* tokens while others finish, just like
    HF/DS static batching (these are counted and discarded);
  * serving ends early only when ALL requests are done (paper's
    early-return case, measured in Fig. 14b/20b).

Shape discipline (TPU adaptation, DESIGN.md §8): batch size is bucketed to
the next power of two and input length to a multiple of ``len_bucket``, so
each (N, L) bucket hits one compiled executable.  The KV cache is allocated
at exactly ``L + slice_len`` slots — the paper's memory model Eq. (5).

``forced_gen_lens`` emulates known EOS positions so controlled experiments
can replay traces with ground-truth generation lengths while still doing
every real FLOP; pass None to rely on the model's own EOS.

Persistent paged storage (``kv_layout="paged"``): the engine owns a real
``repro.kvcache`` page pool and per-request page state that survives
across ``serve_batch_paged`` calls.  A resumed request remaps its
retained prefix pages into the dispatched batch's block table and decodes
straight from its stored next token — the paper's §3.3 re-prefill becomes
a page-table remap, and only evicted requests (memory pressure, worker
migration) fall back to the classic prompt+generated re-prefill.  Layout:
logical slot == absolute position (no pad slots), so the same pages read
identically in any batch composition.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.request import bucket_len
from repro.engine.sampling import greedy
from repro.models.registry import Model
from repro.obs.trace import NULL_TRACER, Tracer


def _pow2_bucket(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


#: decode-stage block tables are bucketed to multiples of this many blocks
#: so a growing batch does not recompile every slice
NB_BUCKET = 4


# Forced-length sentinel: a per-row forced length at/above this means "no
# emulated EOS — decode until the model's own EOS token".  Shared protocol
# with repro.serving.backends.RealBackend; fits int32 with headroom.
EOS_DRIVEN = 1 << 30


class _Resident:
    """Per-request page state retained across slices (paged engine)."""

    __slots__ = ("n_tokens", "next_token", "stamp")

    def __init__(self, n_tokens: int, next_token: int, stamp: int):
        self.n_tokens = n_tokens      # tokens whose K/V live in pages
        self.next_token = next_token  # precomputed first token of the resume
        self.stamp = stamp            # LRU clock for evict-on-pressure


class StaticEngine:
    def __init__(self, model: Model, params, eos_id: int = 1,
                 pad_id: int = 0, len_bucket: int = 16,
                 extra_inputs: Optional[Dict[str, np.ndarray]] = None,
                 kv_layout: str = "dense", page_tokens: int = 16,
                 kv_pool_tokens: Optional[int] = None,
                 prefix_sharing: bool = True, attn_impl: str = "unfused",
                 device: Optional[jax.Device] = None):
        self.model = model
        # the device this engine runs on: params, page pool and every input
        # are placed there (None = JAX's default device), so one process
        # drives one engine per chip
        self.device = device
        self.params = (params if device is None
                       else jax.device_put(params, device))
        # donated pool buffers are updated in place; the CPU ignores
        # donation and warns, so only donate on accelerators
        platform = (device.platform if device is not None
                    else jax.default_backend())
        self._donate = platform != "cpu"
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.len_bucket = len_bucket
        self.extra_inputs = extra_inputs or {}
        if attn_impl not in ("unfused", "fused"):
            raise ValueError(f"unknown attn_impl {attn_impl!r}")
        # "fused" routes the paged path through the fused RoPE+page-write /
        # RoPE+append+attention kernels; "unfused" is the baseline
        self.attn_impl = attn_impl
        #: program spans of the paged path (repro.obs); the serving
        #: backend binds its tracer here
        self.tracer: Tracer = NULL_TRACER
        self._compiled: Dict[Tuple[int, int, int], object] = {}
        self.compile_seconds = 0.0
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        self.kv_layout = kv_layout
        self.allocator = None
        self.prefix_sharing = prefix_sharing and kv_layout == "paged"
        if kv_layout == "paged":
            from repro.kvcache import PageAllocator, PrefixIndex  # deferred import cycle
            cfg = model.cfg
            if model.paged_pages is None:
                raise ValueError("persistent paged StaticEngine: no paged "
                                 f"entry points for {cfg.name!r} (family "
                                 f"{cfg.family!r}; dense and MoE with "
                                 "latent attention have them)")
            if self.extra_inputs:
                raise ValueError("persistent paged StaticEngine does not "
                                 "take frontend extra_inputs")
            if kv_pool_tokens is None or kv_pool_tokens <= 0:
                raise ValueError("kv_layout='paged' needs kv_pool_tokens "
                                 "(the engine-owned page pool size)")
            if kv_pool_tokens % page_tokens:
                raise ValueError(f"kv_pool_tokens {kv_pool_tokens} must be "
                                 f"a multiple of page_tokens {page_tokens}")
            self.page_tokens = page_tokens
            self.allocator = PageAllocator(kv_pool_tokens // page_tokens,
                                           page_tokens)
            P = self.allocator.n_pages + 1  # + null page 0
            # a slot's record is stored lane-dense (kernels.ref.pool_view):
            # K and V as one Hkv·D record each (dense; the TPU tiles the
            # last two dims by (8, 128), so a minor dim of head_dim = 64
            # fills half a tile's lanes, Hkv·D = 512 for llama3.2-1b tiles
            # exactly), or c_kv and the rotary key (latent attention,
            # ``models.mla.page_shape``)
            page_k, page_v = model.paged_pages(page_tokens)
            # filled on the device itself: jnp.zeros(device=...) fills on
            # the default device and copies, so every engine's pool would
            # pass through chip 0
            with jax.default_device(device):
                self._k_pages = jnp.zeros((cfg.n_layers, P) + page_k,
                                          cfg.dtype)
                self._v_pages = jnp.zeros((cfg.n_layers, P) + page_v,
                                          cfg.dtype)
            self._resident: Dict[int, _Resident] = {}
            self._prefix = PrefixIndex(page_tokens)
            self._stamp = 0
            self.n_evictions = 0
            from repro.kvcache.paged import PagedKVCache as _PKV

            def _cache(tokens, k_pages, v_pages, block_table):
                W = block_table.shape[1] * page_tokens
                return _PKV(k_pages, v_pages, block_table,
                            jnp.full((tokens.shape[0], W), -1, jnp.int32),
                            jnp.zeros((tokens.shape[0],), jnp.int32))

            def _prefill_paged(params, tokens, lengths, k_pages, v_pages,
                               block_table):
                logits, cache, counts = model.prefill_paged(
                    params, tokens, lengths,
                    _cache(tokens, k_pages, v_pages, block_table), attn_impl)
                return greedy(logits), cache.k_pages, cache.v_pages, counts

            def _prefill_tail(params, tokens, start, lengths, k_pages,
                              v_pages, block_table):
                logits, cache, counts = model.prefill_tail_paged(
                    params, tokens, start, lengths,
                    _cache(tokens, k_pages, v_pages, block_table), attn_impl)
                return greedy(logits), cache.k_pages, cache.v_pages, counts

            # donate the pool buffers so XLA updates them in place (the
            # pool is sized to most of HBM; without donation every call
            # would hold two full copies)
            donate = (3, 4) if self._donate else ()
            self._prefill_paged = jax.jit(_prefill_paged,
                                          donate_argnums=donate)
            donate_t = (4, 5) if self._donate else ()
            self._prefill_tail_paged = jax.jit(_prefill_tail,
                                               donate_argnums=donate_t)

    # ------------------------------------------------------------------
    def _serve_fn(self, slice_len: int):
        model, eos = self.model, self.eos_id

        @jax.jit
        def serve(params, tokens, lengths, forced, extra):
            B = tokens.shape[0]
            batch = {"tokens": tokens, "lengths": lengths, **extra}
            cache_window = tokens.shape[1] + slice_len
            if model.cfg.family == "vlm" and "prefix_embeds" in extra:
                cache_window += extra["prefix_embeds"].shape[1]
            last_logits, cache = model.prefill(params, batch, cache_window)
            tok0 = greedy(last_logits)

            def cond(state):
                step, _, _, done, _ = state
                return (step < slice_len) & ~jnp.all(done)

            def body(state):
                step, cur, cache, done, out = state
                out = jax.lax.dynamic_update_slice_in_dim(
                    out, cur[:, None], step, axis=1)
                gen_count = step + 1
                done = done | (cur == eos) | (gen_count >= forced)
                logits, cache = model.decode_step(params, cache, cur, step)
                nxt = greedy(logits)
                return step + 1, nxt, cache, done, out

            out = jnp.full((B, slice_len), -1, jnp.int32)
            done0 = jnp.zeros((B,), bool)
            step, _, _, done, out = jax.lax.while_loop(
                cond, body, (jnp.asarray(0, jnp.int32), tok0, cache, done0, out))
            return out, step, done

        return serve

    def _get_compiled(self, slice_len: int):
        key = slice_len
        if key not in self._compiled:
            self._compiled[key] = self._serve_fn(slice_len)
        return self._compiled[key]

    # ------------------------------------------------------------------
    # persistent paged path (kv_layout="paged")
    # ------------------------------------------------------------------
    def _serve_paged_fn(self, slice_len: int):
        from repro.kvcache.paged import PagedKVCache
        model, eos = self.model, self.eos_id
        attn_impl = self.attn_impl
        # pool buffers donated in place, as in _prefill_paged
        donate = (1, 2) if self._donate else ()

        @partial(jax.jit, donate_argnums=donate)
        def serve(params, k_pages, v_pages, block_table, slot_pos, row_len,
                  first_tok, forced):
            B = first_tok.shape[0]
            cache = PagedKVCache(k_pages, v_pages, block_table, slot_pos,
                                 row_len)

            def cond(state):
                step, _, _, done, _, _ = state
                return (step < slice_len) & ~jnp.all(done)

            def body(state):
                step, cur, cache, done, out, counts = state
                out = jax.lax.dynamic_update_slice_in_dim(
                    out, cur[:, None], step, axis=1)
                gen_count = step + 1
                done = done | (cur == eos) | (gen_count >= forced)
                q_pos = row_len + step  # compact layout: slot == position
                # rows done (padding, or finished) count no work
                logits, cache, c = model.decode_step_paged(
                    params, cache, cur, q_pos, q_pos, done, attn_impl)
                with jax.named_scope("sampling"):
                    nxt = greedy(logits)
                return (step + 1, nxt, cache, done, out,
                        jax.tree.map(jnp.add, counts, c))

            out = jnp.full((B, slice_len), -1, jnp.int32)
            done0 = jnp.zeros((B,), bool)
            counts0 = {k: jnp.zeros((), jnp.int32)
                       for k in model.decode_counts}
            step, nxt, cache, done, out, counts = jax.lax.while_loop(
                cond, body,
                (jnp.asarray(0, jnp.int32), first_tok, cache, done0, out,
                 counts0))
            return (out, step, done, nxt, cache.k_pages, cache.v_pages,
                    counts)

        return serve

    def _get_compiled_paged(self, slice_len: int):
        key = ("paged", slice_len)
        if key not in self._compiled:
            self._compiled[key] = self._serve_paged_fn(slice_len)
        return self._compiled[key]

    def _evict(self, rid: int) -> None:
        """Drop a request's retained pages — its next dispatch falls back
        to the classic §3.3 re-prefill (memory safety over retention)."""
        self._resident.pop(rid, None)
        self._prefix.remove(rid)
        self.allocator.release(rid, missing_ok=True)
        self.n_evictions += 1

    def _lru_parked(self, protected) -> Optional[int]:
        """Oldest resident request NOT in the currently dispatched batch."""
        victims = [(res.stamp, rid) for rid, res in self._resident.items()
                   if rid not in protected]
        return min(victims)[1] if victims else None

    def _extend_evicting(self, rid: int, need: int, protected) -> None:
        """``allocator.extend`` with the LRU evict-on-pressure loop;
        re-raises ``MemoryError`` when no parked victim remains."""
        while True:
            try:
                # grows the caller's reservation; serve_batch_paged unwinds
                # it on MemoryError and retention frees it later via
                # release_request/_evict
                self.allocator.extend(rid, need)  # repro: transfer(allocator-pairing) — caller-owned reservation
                return
            except MemoryError:
                victim = self._lru_parked(protected)
                if victim is None:
                    raise
                self._evict(victim)

    def release_request(self, rid: int) -> int:
        """Free a request's retained pages (finish / cancel / migration);
        an explicit no-op for unknown rids.  Returns pages freed."""
        if self.kv_layout != "paged":
            return 0
        self._resident.pop(rid, None)
        self._prefix.remove(rid)
        return self.allocator.release(rid, missing_ok=True)

    @property
    def retained_blocks(self) -> int:
        """Blocks currently held by retained/in-flight requests."""
        return self.allocator.used_blocks if self.allocator else 0

    def serve_batch_paged(self, prompts: Sequence[np.ndarray],
                          slice_len: int, rids: Sequence[int],
                          forced_gen_lens: Optional[Sequence[int]] = None,
                          already_generated: Optional[Sequence[Sequence[int]]] = None,
                          ) -> "ServeResult":
        """Serve one slice with persistent paged KV storage.

        Same §2.4 semantics and token stream as ``serve_batch``, but K/V
        live in the engine's page pool keyed by ``rids``:

          * a request whose pages are resident performs ZERO re-prefill —
            its retained prefix pages are remapped into the batch block
            table and decode resumes from its stored next token;
          * a non-resident request (first dispatch, evicted, migrated)
            prefills prompt + ``already_generated`` into freshly reserved
            pages — the classic §3.3 fallback, counted in
            ``ServeResult.reprefill_tokens``;
          * at slice end every surviving row is trimmed to exactly its
            resident tokens and retained; pages are freed only by
            ``release_request`` (finish/cancel) or evict-on-pressure.

        Memory safety is unchanged: each row's envelope is its exact
        ``resident + slice_len`` tokens (≤ the scheduler's Eq. 5 batch
        bound), and on pool pressure parked residents are evicted LRU —
        a ``MemoryError`` with no parked victim means the DP batcher
        violated its own no-OOM constraint, as in the slice-scoped mode.
        """
        if self.kv_layout != "paged":
            raise ValueError("serve_batch_paged needs kv_layout='paged'")
        pg = self.page_tokens
        B_raw = len(prompts)
        if len(rids) != B_raw:
            raise ValueError(f"{len(rids)} rids for {B_raw} prompts — page "
                             f"residency is keyed by rid, one per row")
        if B_raw == 0:
            raise ValueError("empty batch")
        eff: List[np.ndarray] = []
        prevs: List[list] = []
        for i, p in enumerate(prompts):
            prev = list(already_generated[i]) if already_generated else []
            prevs.append(prev)
            eff.append(np.concatenate([np.asarray(p, np.int32),
                                       np.asarray(prev, np.int32)])
                       if prev else np.asarray(p, np.int32))

        # --- capacity planning: extend residents, reserve the rest,
        # evicting parked requests LRU under pressure.  All-or-nothing:
        # if the batch cannot be satisfied even with every parked resident
        # evicted (the DP batcher violated its own bound), the rows already
        # granted in THIS call are unwound before re-raising — otherwise
        # their ownerless reservations would wedge the pool for those rids
        # (reserve would KeyError on retry, masking the real failure)
        tr = self.tracer
        with tr.span("engine.plan", rows=B_raw) as sp:
            evicted = self.n_evictions
            is_resident, shared_start, shared_blocks = self._plan(
                rids, eff, slice_len)
            sp.set(evictions=self.n_evictions - evicted)

        # --- stage A: paged prefill of the non-resident rows
        # (clock starts here, just before device work, mirroring
        # serve_batch — so retain-mode latency comparisons measure the
        # same quantity and exclude host-side allocator bookkeeping)
        t0 = time.perf_counter()
        t_prefill = 0.0  # every row resident -> no stage-A device call
        first = np.zeros((B_raw,), np.int32)
        row_len = np.zeros((B_raw,), np.int64)
        pads = [0] * B_raw
        reprefill = 0
        prefix_hit = sum(shared_start.values())
        pre_idx = [i for i in range(B_raw)
                   if not is_resident[i] and i not in shared_start]
        tail_idx = sorted(shared_start)
        L_pre = 0
        if pre_idx:
            max_eff = max(len(eff[i]) for i in pre_idx)
            L_pre = bucket_len(max_eff, self.len_bucket)
            Bp = _pow2_bucket(len(pre_idx))
            with tr.span("engine.prefill", rows=len(pre_idx), padded=Bp,
                         length=L_pre) as sp:
                toks = np.full((Bp, L_pre), self.pad_id, np.int32)
                lens = np.ones((Bp,), np.int32)
                nb_p = -(-L_pre // pg)
                btp = np.zeros((Bp, nb_p), np.int32)
                for s, i in enumerate(pre_idx):
                    e = eff[i]
                    toks[s, L_pre - len(e):] = e
                    lens[s] = len(e)
                    pages = self.allocator.pages_of(rids[i])
                    btp[s, :min(len(pages), nb_p)] = pages[:nb_p]
                    if prevs[i]:  # re-prefill beyond the first (§3.3)
                        reprefill += len(e)
                tok0, self._k_pages, self._v_pages, counts = \
                    self._prefill_paged(
                        self.params, self._put(toks), self._put(lens),
                        self._k_pages, self._v_pages, self._put(btp))
                tok0 = np.asarray(tok0)  # host transfer: blocks on stage A
                sp.set(**{k: int(v) for k, v in counts.items()})
                for s, i in enumerate(pre_idx):
                    first[i] = int(tok0[s])
                    row_len[i] = len(eff[i])
                    pads[i] = L_pre - len(eff[i])
        # --- stage A': tail-only prefill of the prefix-sharing rows — the
        # shared head is a page-table remap, only the novel tail runs
        if tail_idx:
            max_tail = max(len(eff[i]) - shared_start[i] for i in tail_idx)
            T_t = bucket_len(max_tail, self.len_bucket)
            Bt = _pow2_bucket(len(tail_idx))
            with tr.span("engine.prefill_tail", rows=len(tail_idx),
                         padded=Bt, length=T_t) as sp:
                toks_t = np.full((Bt, T_t), self.pad_id, np.int32)
                start_t = np.zeros((Bt,), np.int32)
                lens_t = np.zeros((Bt,), np.int32)
                nb_t = bucket_len(max(len(self.allocator.pages_of(rids[i]))
                                      for i in tail_idx), NB_BUCKET)
                btt = np.zeros((Bt, nb_t), np.int32)
                for s, i in enumerate(tail_idx):
                    e, st = eff[i], shared_start[i]
                    toks_t[s, T_t - (len(e) - st):] = e[st:]
                    start_t[s] = st
                    lens_t[s] = len(e)
                    pages = self.allocator.pages_of(rids[i])
                    btt[s, :min(len(pages), nb_t)] = pages[:nb_t]
                    if prevs[i]:  # only the tail re-runs on a reschedule
                        reprefill += len(e) - st
                tokt, self._k_pages, self._v_pages, counts = \
                    self._prefill_tail_paged(
                        self.params, self._put(toks_t), self._put(start_t),
                        self._put(lens_t), self._k_pages, self._v_pages,
                        self._put(btt))
                tokt = np.asarray(tokt)  # host transfer: blocks on stage A'
                sp.set(**{k: int(v) for k, v in counts.items()})
                for s, i in enumerate(tail_idx):
                    first[i] = int(tokt[s])
                    row_len[i] = len(eff[i])
                    pads[i] = T_t - (len(eff[i]) - shared_start[i])
        if pre_idx or tail_idx:
            t_prefill = time.perf_counter() - t0
        for i, rid in enumerate(rids):
            if is_resident[i]:
                res = self._resident[rid]
                first[i] = res.next_token
                row_len[i] = res.n_tokens

        # --- stage B: one decode slice over the whole batch through the
        # per-row block tables (remapped retained pages + fresh ones)
        from repro.kvcache.paged import batch_block_table, batch_slot_pos
        B = _pow2_bucket(B_raw)
        with tr.span("engine.decode", padded=B, rows=B_raw) as ph:
            max_pages = max(len(self.allocator.pages_of(r)) for r in rids)
            nb = bucket_len(max_pages, NB_BUCKET)
            pages_rows = [self.allocator.pages_of(r) for r in rids] \
                + [[] for _ in range(B - B_raw)]
            bt = batch_block_table(pages_rows, nb)
            lens_full = row_len.tolist() + [0] * (B - B_raw)
            slot_pos = batch_slot_pos(lens_full, nb, pg)
            first_full = np.concatenate(
                [first, np.full((B - B_raw,), self.pad_id, np.int32)])
            forced = self._forced_array(forced_gen_lens, B, B_raw)
            fn = self._get_compiled_paged(slice_len)
            out, steps, done, nxt, kp, vp, counts = fn(
                self.params, self._k_pages, self._v_pages, self._put(bt),
                self._put(slot_pos),
                self._put(np.asarray(lens_full, np.int32)),
                self._put(first_full), self._put(forced))
            self._k_pages, self._v_pages = kp, vp
            out = np.asarray(jax.block_until_ready(out))
            nxt = np.asarray(nxt)
            wall = time.perf_counter() - t0
            steps = int(steps)
            counts = {k: int(v) for k, v in counts.items()}
            ph.set(blocks=nb, steps=steps, **counts)

        # --- retention: trim every row to its resident tokens; pages are
        # freed only via release_request (finish/cancel) or eviction
        results = self._assemble_results(out, steps, done, forced_gen_lens,
                                         pads)
        with tr.span("engine.retain", rows=B_raw):
            for i, rid in enumerate(rids):
                new_len = int(row_len[i]) + steps
                self._stamp += 1
                self._resident[rid] = _Resident(new_len, int(nxt[i]),
                                                self._stamp)
                self.allocator.shrink(rid, new_len)
                if self.prefix_sharing:
                    # index the row's full pages for future prefix joins;
                    # the resident stream is prompt+generated so far
                    # followed by every token this slice fed the decoder
                    stream = np.concatenate([eff[i], out[i, :steps]])
                    self._prefix.insert(rid, stream,
                                        self.allocator.pages_of(rid))
        L_rep = bucket_len(int(max(row_len)), self.len_bucket)
        return ServeResult(results=results, steps=steps, wall_time=wall,
                           batch_input_len=max(L_pre, L_rep),
                           batch_size=B_raw,
                           early_return=steps < slice_len,
                           reprefill_tokens=reprefill,
                           prefill_time=t_prefill,
                           prefix_hit_tokens=prefix_hit,
                           shared_blocks=shared_blocks,
                           expert_reads=counts.get("expert_reads", 0))

    def _plan(self, rids: Sequence[int], eff: List[np.ndarray],
              slice_len: int) -> Tuple[List[bool], Dict[int, int], int]:
        """Capacity planning of one paged slice: returns each row's
        residency, the shared-prefix start of each prefix-joining row and
        the pages those joins took."""
        pg = self.page_tokens
        batch_set = set(rids)
        is_resident = []
        fresh: List[int] = []               # reserved this call, no residency
        grown: List[Tuple[int, int]] = []   # (rid, resident tokens before)
        shared_start: Dict[int, int] = {}   # row index -> shared prefix tokens
        shared_blocks = 0
        try:
            for i, rid in enumerate(rids):
                res = self._resident.get(rid)
                if res is not None and res.n_tokens != len(eff[i]):
                    # stale residency (token stream advanced elsewhere):
                    # fall back to a fresh prefill rather than serve bad KV
                    self._evict(rid)
                    res = None
                hit_pages: List[int] = []
                if res is None and self.prefix_sharing:
                    # cross-request prefix join: take references on another
                    # resident's full pages matching this prompt's head and
                    # prefill only the novel tail.  At least one tail token
                    # must remain to produce the next-token logits.
                    hit_pages, _ = self._prefix.lookup(eff[i])
                    n_hit = min(len(hit_pages), (len(eff[i]) - 1) // pg)
                    hit_pages = hit_pages[:n_hit]
                need = (res.n_tokens if res else len(eff[i])) + slice_len
                if res is None and hit_pages:
                    # share never allocates; the tail extension does, with
                    # its own evict-on-pressure loop.  On MemoryError the
                    # rid is already in ``fresh`` so the outer unwind drops
                    # its shared references too.
                    # retained past this call by design (kv_retain=
                    # "request"): freed by release_request/_evict; the
                    # except MemoryError arm below unwinds rows granted
                    # in THIS call
                    self.allocator.share(rid, hit_pages)  # repro: transfer(allocator-pairing) — retention owns it
                    fresh.append(rid)
                    self._extend_evicting(rid, need, batch_set)
                    shared_start[i] = len(hit_pages) * pg
                    shared_blocks += len(hit_pages)
                else:
                    while True:
                        try:
                            if res is not None:
                                # both arms retained by design (see the
                                # share above): freed via release_request/
                                # _evict, unwound by the except MemoryError
                                # arm below
                                if self.allocator.extend(rid, need):  # repro: transfer(allocator-pairing) — retention owns it
                                    grown.append((rid, res.n_tokens))
                            else:
                                self.allocator.reserve(rid, need)  # repro: transfer(allocator-pairing) — see above
                                fresh.append(rid)
                            break
                        except MemoryError:
                            victim = self._lru_parked(batch_set)
                            if victim is None:
                                raise
                            self._evict(victim)
                is_resident.append(res is not None)
        except MemoryError:
            for rid in fresh:
                self.allocator.release(rid, missing_ok=True)
            for rid, n_before in grown:
                if rid in self._resident:  # not evicted meanwhile
                    self.allocator.shrink(rid, n_before)
            raise
        return is_resident, shared_start, shared_blocks

    # ------------------------------------------------------------------
    def serve_batch(self, prompts: Sequence[np.ndarray], slice_len: int,
                    forced_gen_lens: Optional[Sequence[int]] = None,
                    already_generated: Optional[Sequence[Sequence[int]]] = None,
                    ) -> "ServeResult":
        """Serve one static batch for at most ``slice_len`` iterations.

        ``already_generated``: per-request previously generated tokens —
        SCLS reschedule re-prefills prompt+generated (paper §3.3 overhead).
        """
        B_raw = len(prompts)
        eff = []
        reprefill = 0
        for i, p in enumerate(prompts):
            prev = list(already_generated[i]) if already_generated else []
            if prev:  # §3.3: a reschedule re-prefills prompt + generated
                reprefill += len(p) + len(prev)
            eff.append(np.concatenate([np.asarray(p, np.int32),
                                       np.asarray(prev, np.int32)])
                       if prev else np.asarray(p, np.int32))
        lengths = np.array([len(e) for e in eff], np.int32)
        L = bucket_len(int(lengths.max()), self.len_bucket)
        B = _pow2_bucket(B_raw)
        tokens = np.full((B, L), self.pad_id, np.int32)
        for i, e in enumerate(eff):
            tokens[i, L - len(e):] = e  # left padding
        lengths_p = np.concatenate([lengths, np.ones(B - B_raw, np.int32)])
        forced = self._forced_array(forced_gen_lens, B, B_raw)
        extra = {k: self._put(self._pad_extra(v, B, B_raw))
                 for k, v in self.extra_inputs.items()}

        fn = self._get_compiled(slice_len)
        t0 = time.perf_counter()
        out, steps, done = fn(self.params, self._put(tokens),
                              self._put(lengths_p), self._put(forced), extra)
        out = np.asarray(jax.block_until_ready(out))
        wall = time.perf_counter() - t0
        steps = int(steps)
        results = self._assemble_results(
            out, steps, done, forced_gen_lens,
            [L - int(lengths[i]) for i in range(B_raw)])
        return ServeResult(results=results, steps=steps, wall_time=wall,
                           batch_input_len=L, batch_size=B_raw,
                           early_return=steps < slice_len,
                           reprefill_tokens=reprefill)

    def _forced_array(self, forced_gen_lens: Optional[Sequence[int]],
                      B: int, B_raw: int) -> np.ndarray:
        """Per-row forced lengths padded to the bucketed batch size (pad
        rows get 1 so they finish immediately); None → EOS-driven rows."""
        if forced_gen_lens is None:
            return np.full((B,), EOS_DRIVEN, np.int32)
        return np.concatenate([np.asarray(forced_gen_lens, np.int32),
                               np.ones(B - B_raw, np.int32)])

    def _assemble_results(self, out: np.ndarray, steps: int, done,
                          forced_gen_lens: Optional[Sequence[int]],
                          pads: Sequence[int]) -> List[dict]:
        """Per-row slice outcomes, shared verbatim by the dense and the
        persistent-paged paths (their token-exactness is pinned on it):
        a forced length below the sentinel emulates a known EOS position;
        the sentinel (or no forced list) means EOS-driven — the model's
        own EOS token ends the row."""
        results = []
        for i, pad in enumerate(pads):
            toks = out[i, :steps]
            f = (int(forced_gen_lens[i]) if forced_gen_lens is not None
                 else EOS_DRIVEN)
            if f < EOS_DRIVEN:
                n_valid = min(f, steps)
            else:
                eos_pos = np.where(toks == self.eos_id)[0]
                n_valid = int(eos_pos[0]) + 1 if len(eos_pos) else steps
            results.append(dict(tokens=toks[:n_valid].tolist(),
                                n_valid=n_valid,
                                finished=n_valid < steps or bool(done[i]),
                                invalid=steps - n_valid,
                                pad=pad))
        return results

    def _put(self, x) -> jax.Array:
        """Host array -> this engine's device."""
        return jax.device_put(x, self.device)

    @staticmethod
    def _pad_extra(v: np.ndarray, B: int, B_raw: int) -> np.ndarray:
        if v.shape[0] == B:
            return v
        return np.concatenate([v, np.repeat(v[-1:], B - B_raw, axis=0)],
                              axis=0)


class ServeResult:
    def __init__(self, results: List[dict], steps: int, wall_time: float,
                 batch_input_len: int, batch_size: int, early_return: bool,
                 reprefill_tokens: int = 0,
                 prefill_time: Optional[float] = None,
                 prefix_hit_tokens: int = 0, shared_blocks: int = 0,
                 expert_reads: int = 0):
        self.results = results
        self.steps = steps
        self.wall_time = wall_time
        self.batch_input_len = batch_input_len
        self.batch_size = batch_size
        self.early_return = early_return
        #: tokens prefilled beyond each request's FIRST prefill this call —
        #: the paper's §3.3 rescheduling overhead, 0 for resumed residents
        #: on the persistent paged path
        self.reprefill_tokens = reprefill_tokens
        #: measured wall seconds of the prefill stage, when it runs as a
        #: separate device call (serve_batch_paged stage A; 0.0 when every
        #: row resumed resident).  None on the fused dense path, where
        #: prefill and decode share one jit call and cannot be attributed
        #: separately.  Feeds the trace's prefill/decode sub-spans
        #: (repro.obs); never read by the scheduler.
        self.prefill_time = prefill_time
        #: prompt tokens satisfied by a cross-request prefix-page join
        #: this call (their prefill became a page-table remap), and the
        #: number of pages those joins took references on
        self.prefix_hit_tokens = prefix_hit_tokens
        self.shared_blocks = shared_blocks
        #: routed experts the decode program read: per step and MoE
        #: layer, the held experts at least one live row was routed to
        #: (0 for a model without routed experts)
        self.expert_reads = expert_reads
