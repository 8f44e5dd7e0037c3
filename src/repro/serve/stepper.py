"""Decode steppers: the jitted prefill/decode/spec cores per cache kind.

A stepper owns the *device* half of serving — the jitted entry points
(wrapped in :class:`.slots.TraceCounter` so ``metrics()`` can report
call/trace counts) and the persistent cache state they advance: the
dense ``(n_slots, max_len)`` cache block for :class:`DenseStepper`, the
page store + :class:`.pages.PagePool` + per-slot page tables for
:class:`PagedStepper`.  The engine's single serve loop drives whichever
stepper the engine was built with through one narrow interface:

* ``begin()`` — reset per-serve device state (dense allocates a fresh
  cache; the page store persists so the prefix index keeps paying off),
* ``admit_group`` / ``admit_single`` — bucketed batched admission and
  the exact-length fallback for models without ``prompt_len`` prefill,
* ``plain_step`` — one masked decode step (teacher-forcing chunked /
  prefix-hit prompt tails from the slot table's ``fill`` lists),
* ``spec_cycle`` + ``post_spec_slot`` / ``spec_rollback`` — one
  speculative draft+verify burst and its rejected-suffix rollback
  (dense: jitted length truncation; paged: returning exclusively-owned
  pages past the accepted depth),
* ``retire`` / ``fill_done`` — slot lifecycle hooks (paged: release
  page refs / publish finished prompt blocks to the prefix index).

Everything the two cache kinds *share* (emission, budgets, deadlines,
chunk bookkeeping, spec-depth policy) lives once, in the engine.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import SERVE_DECODE_RULES, SERVE_PREFILL_RULES, tree_hint
from . import instrument
from .cache_ops import copy_page, merge_slots, scatter_prefill_pages, write_slot
from .pages import PagePool, PagePressure, block_hashes
from .sampler import sample_tokens
from .slots import SlotTable, TraceCounter


class DenseStepper:
    """Jitted serving core over one dense ``(n_slots, max_len)`` cache."""

    kind = "dense"

    def __init__(self, engine):
        self.engine = engine
        self._prefill1 = TraceCounter(
            engine._jit(engine.model.prefill, SERVE_PREFILL_RULES),
            "prefill1", engine)
        self._prefill_admit = TraceCounter(
            engine._jit(self._prefill_admit_fn, SERVE_PREFILL_RULES),
            "prefill_admit", engine)
        self._admit_one = TraceCounter(
            engine._jit(self._admit_one_fn, SERVE_PREFILL_RULES),
            "admit_one", engine)
        self._decode = TraceCounter(
            engine._jit(self._decode_fn, SERVE_DECODE_RULES),
            "decode", engine)
        self.cache = None

    # -- lifecycle -----------------------------------------------------------
    def begin(self):
        eng = self.engine
        self.cache = eng._place(
            eng.model.init_cache(eng.n_slots, eng.max_len), eng._cache_axes)

    def retire(self, st: SlotTable, s: int):
        pass

    def preempt(self, st: SlotTable, s: int):
        """Release the slot for eviction-and-resume.  Dense KV is a
        fixed block per slot — nothing to hand back; the resume's
        teacher-forced prefill recomputes it exactly."""
        self.retire(st, s)

    def fill_done(self, st: SlotTable, s: int):
        pass

    # -- capacity (backpressure protocol; trivially satisfied dense) ---------
    def reserve_admit(self, counts):
        """Pre-own pages for a whole admission group before any slot
        binds (paged only) — a mid-group allocation failure must not
        leave half-bound slots behind."""
        return None

    def pages_needed(self, n_tokens: int):
        """Pages a sequence of ``n_tokens`` needs, or None when the
        cache kind has no page concept."""
        return None

    def fits_pool(self, n_pages: int) -> bool:
        return True

    def slot_overflows(self, st: SlotTable, s: int) -> bool:
        """True when the slot's own next token can never be allocated
        (its sequence exceeds the whole pool) — preempting it would
        livelock; the engine truncates instead."""
        return False

    # -- jitted bodies -------------------------------------------------------
    def _prefill_admit_fn(self, params, tokens, prompt_len, cache,
                          admit_mask, temps, top_k, top_p, key, slot_last):
        """Batched bucketed prefill + admission + first-token sampling.

        tokens (n_slots, bucket) is slot-aligned: row s is the prompt
        admitted into slot s (rows with admit_mask False are dummies).
        """
        eng = self.engine
        scratch = eng.model.init_cache(eng.n_slots, eng.max_len)
        logits, new = eng.model.prefill(params, tokens, scratch, prompt_len)
        merged = eng._hint_cache(merge_slots(cache, new, admit_mask))
        first = sample_tokens(eng._gathered(logits[:, 0]), temps, top_k,
                              key, top_p)
        slot_last = jnp.where(admit_mask, first, slot_last)
        return slot_last, merged

    def _admit_one_fn(self, params, tokens, cache, slot, temps, top_k,
                      top_p, key, slot_last):
        """Fallback admission: exact-length batch-1 prefill, written into
        the batched cache by one per-slot dynamic_update_index_in_dim op
        (slot is traced — a single compile serves every slot)."""
        eng = self.engine
        c1 = eng.model.init_cache(1, eng.max_len)
        logits, c1 = eng.model.prefill(params, tokens, c1)
        merged = eng._hint_cache(write_slot(cache, c1, slot))
        first = sample_tokens(eng._gathered(logits[:, 0]), temps, top_k,
                              key, top_p)
        slot_last = jax.lax.dynamic_update_index_in_dim(
            slot_last, first[0], slot, 0)
        return slot_last, merged

    def _decode_fn(self, params, cache, slot_last, active, temps, top_k,
                   top_p, key):
        """One decode step with inactive slots masked.

        Inactive slots still flow through the batched matmuls (shape
        stability) but their ``len`` is restored afterwards and their
        in-bounds scratch write lands at a position attention masks out —
        a dead slot's cache length can never pass ``max_len``."""
        eng = self.engine
        old_len = cache["len"]
        safe_len = jnp.where(active, old_len,
                             jnp.minimum(old_len, eng.max_len - 1))
        cache = dict(cache, len=safe_len)
        logits, cache = eng.model.decode_step(params, cache,
                                              slot_last[:, None])
        cache = dict(cache, len=jnp.where(active, cache["len"], old_len))
        cache = eng._hint_cache(cache)
        nxt = sample_tokens(eng._gathered(logits[:, 0]), temps, top_k,
                            key, top_p)
        nxt = jnp.where(active, nxt, slot_last)
        return nxt, cache

    # -- admission entry points ----------------------------------------------
    def admit_group(self, st: SlotTable, tokens, plen, admit_mask, group,
                    reserved=None):
        eng = self.engine
        st.slot_last, self.cache = self._prefill_admit(
            eng.params, jnp.asarray(tokens), jnp.asarray(plen),
            self.cache, jnp.asarray(admit_mask),
            *eng._policy_args(st.temps, st.top_k, st.top_p),
            eng._next_key(), st.slot_last)

    def admit_single(self, st: SlotTable, req, s: int, eff=None):
        eng = self.engine
        p = np.asarray(req.prompt if eff is None else eff, np.int32)
        st.slot_last, self.cache = self._admit_one(
            eng.params, jnp.asarray(p)[None],
            self.cache, jnp.asarray(s, jnp.int32),
            *eng._policy_args([req.temperature], [req.top_k], [req.top_p]),
            eng._next_key(), st.slot_last)

    # -- decode-loop entry points --------------------------------------------
    def plain_step(self, st: SlotTable):
        """One decode step in two traced phases: ``prepare`` builds the
        host->device arguments, ``dispatch`` is the jitted call."""
        eng = self.engine
        # outside prepare: with a slot filling its prompt this reads the
        # sampled tokens on the host, which after an admission waits for
        # the prefill on the device
        sl = st.input_tokens()
        with instrument.step_span(eng, "prepare"):
            if eng._spec is not None:
                # keep the independent draft's KV aligned through plain
                # fallback / fill steps (self-draft shares the cache)
                eng._spec.track_step(
                    jnp.asarray(sl),
                    np.where(st.active, st.slot_len,
                             np.minimum(st.slot_len, eng.max_len - 1)))
            args = (eng.params, self.cache, jnp.asarray(sl),
                    jnp.asarray(st.active),
                    *eng._policy_args(st.temps, st.top_k, st.top_p),
                    eng._next_key())
        with instrument.step_span(eng, "dispatch"):
            st.slot_last, self.cache = self._decode(*args)

    def spec_cycle(self, st: SlotTable, k_eff: int):
        eng = self.engine
        lens_safe = np.where(
            st.active, st.slot_len,
            np.minimum(st.slot_len, eng.max_len - (k_eff + 1)))
        out, n_acc, self.cache = eng._spec.run_cycle_dense(
            self.cache, jnp.asarray(lens_safe.astype(np.int32)),
            st.slot_last, jnp.asarray(st.active), st.temps, st.top_k,
            st.top_p, eng._next_key(), k_eff)
        return out, n_acc

    def post_spec_slot(self, st: SlotTable, s: int):
        pass

    def spec_rollback(self, st: SlotTable):
        """Republish host lengths after a burst — rejected suffixes roll
        back via one jitted length truncation."""
        self.cache = self.engine._truncate(
            self.cache, jnp.asarray(st.slot_len.astype(np.int32)))


class PagedStepper(DenseStepper):
    """Serving core over the paged KV cache (DESIGN.md §10).

    Inherits the dense jitted entry points — ``generate()`` and the
    trace-count metrics use them — and overrides the serve-loop hooks to
    run against the persistent page store.  The per-slot page ``table``
    maps logical to physical pages; retired rows point at the trash
    page so masked writes can never touch a live page.
    """

    kind = "paged"

    def __init__(self, engine, page_size: int, n_pages):
        super().__init__(engine)
        eng = engine
        self.page_size = page_size
        self.pages_per_slot = -(-eng.max_len // page_size)
        # default capacity guarantees admission can never deadlock:
        # every slot can hold a full max_len sequence (+1 trash page)
        self.n_pages = (int(n_pages) if n_pages
                        else 1 + eng.n_slots * self.pages_per_slot)
        self.pool = PagePool(self.n_pages, page_size,
                             faults=getattr(eng, "faults", None),
                             registry=eng.registry)
        # persistent across serve() calls so the prefix index keeps
        # paying off between bursts; with a mesh the page stores are
        # sharded on the head axis (page tables stay replicated)
        self._store_axes = (eng.model.paged_cache_axes()
                            if hasattr(eng.model, "paged_cache_axes")
                            else None)
        self.store = eng._place(
            eng.model.init_paged_cache(self.n_pages, page_size),
            self._store_axes)
        self.table = np.full((eng.n_slots, self.pages_per_slot),
                             PagePool.TRASH, np.int32)
        self._window = eng.model.cfg.sliding_window or None
        self._pages_live = eng.registry.counter("serve.attn_pages_live")
        self._pages_table = eng.registry.counter("serve.attn_pages_table")
        self._prefill_paged = TraceCounter(
            eng._jit(self._prefill_paged_fn, SERVE_PREFILL_RULES),
            "prefill_paged", eng)
        self._decode_paged = TraceCounter(
            eng._jit(self._decode_paged_fn, SERVE_DECODE_RULES),
            "decode_paged", eng)
        self._scatter_pages = eng._jit(scatter_prefill_pages,
                                       SERVE_DECODE_RULES)
        self._copy_page = eng._jit(copy_page, SERVE_DECODE_RULES)

    # -- lifecycle -----------------------------------------------------------
    def begin(self):
        pass    # page store persists; slot tables were released at retire

    def retire(self, st: SlotTable, s: int):
        """Release the slot's page refs (index-held pages survive for
        cross-request reuse)."""
        for j in range(self.pages_per_slot):
            if self.table[s, j] != PagePool.TRASH:
                self.pool.decref(int(self.table[s, j]))
                self.table[s, j] = PagePool.TRASH

    def preempt(self, st: SlotTable, s: int):
        """Backpressure eviction: publish every *full* KV block —
        prompt and generated tokens alike — to the prefix index under
        the effective-sequence hash chain, then release the slot's
        refs.  The index refs keep those pages alive, so the resume's
        prefix-hit admission maps them straight back and only the
        partial tail block recomputes.  (Under continued pressure the
        registered pages are index-only and evictable — publishing
        them can never wedge the pool.)"""
        req = st.req[s]
        ps = self.page_size
        nfull = int(st.slot_len[s]) // ps
        if nfull:
            eff = np.concatenate([
                np.asarray(req.prompt, np.int32),
                np.asarray(req.out_tokens or [], np.int32)])
            hs = block_hashes(eff[:nfull * ps], ps)
            for j in range(nfull):
                if self.table[s, j] != PagePool.TRASH:
                    self.pool.register(hs[j], int(self.table[s, j]))
        self.retire(st, s)

    def fill_done(self, st: SlotTable, s: int):
        self.register_prompt_pages(st, s)

    # -- capacity (backpressure protocol) ------------------------------------
    def _take_page(self, slot=None) -> int:
        p = self.pool.try_alloc()
        if p is None:
            raise PagePressure(slot)
        return p

    def reserve_admit(self, counts):
        """Allocate every page an admission group needs up front; on
        failure release the partial reservation and raise
        :class:`.pages.PagePressure` with nothing bound.  Admission
        pre-checks ``pool.available()``, so this only fails under an
        injected allocation fault."""
        got = []
        for c in counts:
            pages = []
            for _ in range(c):
                p = self.pool.try_alloc()
                if p is None:
                    for q in pages:
                        self.pool.decref(q)
                    for lst in got:
                        for q in lst:
                            self.pool.decref(q)
                    raise PagePressure(None, c)
                pages.append(p)
            got.append(pages)
        return got

    def pages_needed(self, n_tokens: int):
        return self.pool.pages_for(n_tokens)

    def fits_pool(self, n_pages: int) -> bool:
        return n_pages <= self.n_pages - 1

    def slot_overflows(self, st: SlotTable, s: int) -> bool:
        return not self.fits_pool(
            self.pool.pages_for(int(st.slot_len[s]) + 1))

    # -- jitted bodies -------------------------------------------------------
    def _hint_store(self, store):
        if self.engine.mesh is None or self._store_axes is None:
            return store
        return tree_hint(store, self._store_axes)

    def _prefill_paged_fn(self, params, tokens, prompt_len, admit_mask,
                          temps, top_k, top_p, key, slot_last):
        """Bucketed batched prefill for the paged path: fills a dense
        *scratch* cache sized to the bucket (padded up to a page
        multiple), samples first tokens, and returns the scratch for the
        host to scatter into freshly allocated pages.  Unlike the dense
        path there is no merge — the persistent cache is the page store.
        """
        eng = self.engine
        t = tokens.shape[1]
        s_pages = -(-t // self.page_size) * self.page_size
        scratch = eng.model.init_cache(eng.n_slots, s_pages)
        logits, new = eng.model.prefill(params, tokens, scratch, prompt_len)
        new = eng._hint_cache(new)
        first = sample_tokens(eng._gathered(logits[:, 0]), temps, top_k,
                              key, top_p)
        slot_last = jnp.where(admit_mask, first, slot_last)
        return slot_last, new

    def _decode_paged_fn(self, params, store, page_table, lens, slot_last,
                         active, temps, top_k, top_p, key):
        """One decode step against the page store.  ``lens`` is the
        host-managed per-slot valid length (already clamped for retired
        slots); retired slots' page-table rows point at the trash page,
        so their masked write can never touch a live page."""
        eng = self.engine
        logits, store = eng.model.decode_step_paged(
            params, store, slot_last[:, None], page_table, lens)
        store = self._hint_store(store)
        nxt = sample_tokens(eng._gathered(logits[:, 0]), temps, top_k,
                            key, top_p)
        nxt = jnp.where(active, nxt, slot_last)
        return nxt, store

    # -- page bookkeeping ----------------------------------------------------
    def ensure_writable(self, s: int, pos: int):
        """Make the page holding position ``pos`` safe for slot ``s`` to
        write: allocate if unmapped, copy-on-write if shared with
        another slot or the prefix index.  Exhaustion raises
        :class:`.pages.PagePressure` for the engine to relieve by
        preemption — never a terminal error on the serve path."""
        ps = self.page_size
        lp = pos // ps
        phys = int(self.table[s, lp])
        if phys == PagePool.TRASH:
            self.table[s, lp] = self._take_page(s)
            instrument.page_event(self.engine, "page_alloc", slot=s,
                                  block=lp)
        elif self.pool.is_shared(phys):
            fresh = self._take_page(s)
            self.store = self._copy_page(self.store, phys, fresh)
            self.pool.decref(phys)
            self.table[s, lp] = fresh
            self.pool.cow_copies += 1
            instrument.page_event(self.engine, "cow", slot=s, block=lp)

    def register_prompt_pages(self, st: SlotTable, s: int):
        """Publish the slot's hashed full blocks for future reuse (the
        index takes its own ref; partial tail blocks are never shared).
        ``st.hashes[s]`` covers the *effective* prompt — for a resumed
        request that includes previously emitted tokens, so its blocks
        re-register under the same chain they were published to at
        preemption."""
        for j in range(len(st.hashes[s])):
            self.pool.register(st.hashes[s][j], int(self.table[s, j]))

    # -- admission entry points ----------------------------------------------
    def admit_group(self, st: SlotTable, tokens, plen, admit_mask, group,
                    reserved=None):
        """Bucketed batched prefill into scratch, scattered into pages
        pre-owned by :meth:`reserve_admit` (``reserved``, one page list
        per group member in order).  ``st.slot_len`` already holds each
        slot's admitted length (== prompt length, or the first chunk of
        a chunked admission); chunked slots defer prefix-index
        registration to ``fill_done``."""
        eng = self.engine
        st.slot_last, scratch = self._prefill_paged(
            eng.params, jnp.asarray(tokens), jnp.asarray(plen),
            jnp.asarray(admit_mask),
            *eng._policy_args(st.temps, st.top_k, st.top_p),
            eng._next_key(), st.slot_last)
        b = tokens.shape[1]
        ps = self.page_size
        n_scratch_pages = -(-b // ps)
        targets = [s for _, s in group]
        all_ids = np.full((len(group), n_scratch_pages),
                          PagePool.TRASH, np.int32)
        for gi, (req, s) in enumerate(group):
            npages = -(-int(st.slot_len[s]) // ps)
            phys = (reserved[gi] if reserved is not None
                    else [self._take_page(s) for _ in range(npages)])
            assert len(phys) == npages
            all_ids[gi, :npages] = phys
            self.table[s, :npages] = phys
        self.store = self._scatter_pages(
            self.store, scratch,
            jnp.asarray(np.asarray(targets, np.int32)),
            jnp.asarray(all_ids))
        for req, s in group:
            if st.fill[s] is None:
                self.register_prompt_pages(st, s)

    def admit_single(self, st: SlotTable, req, s: int, eff=None):
        raise NotImplementedError(
            "paged serving requires prompt_len prefill")

    # -- decode-loop entry points --------------------------------------------
    def plain_step(self, st: SlotTable):
        """One decode step: ``prepare`` makes each active slot's next
        page writable and builds the arguments, ``dispatch`` is the
        jitted call."""
        eng = self.engine
        # outside prepare: with a slot filling its prompt this reads the
        # sampled tokens on the host, which after an admission waits for
        # the prefill on the device
        sl = st.input_tokens()
        with instrument.step_span(eng, "prepare"):
            lens = np.minimum(st.slot_len, eng.max_len - 1)  # retired slots
            for s in range(eng.n_slots):
                if not st.active[s]:
                    continue
                lens[s] = st.slot_len[s]
                self.ensure_writable(s, int(st.slot_len[s]))
            self._count_pages(lens)
            if eng._spec is not None:
                # align the independent draft's KV through fill / fallback
                # steps (it sees the same token stream)
                eng._spec.track_step(jnp.asarray(sl), lens)
            args = (eng.params, self.store, jnp.asarray(self.table),
                    jnp.asarray(lens.astype(np.int32)), jnp.asarray(sl),
                    jnp.asarray(st.active),
                    *eng._policy_args(st.temps, st.top_k, st.top_p),
                    eng._next_key())
        with instrument.step_span(eng, "dispatch"):
            st.slot_last, self.store = self._decode_paged(*args)

    def _count_pages(self, lens):
        """Pages the decode kernel walks this step, against the table it
        is handed: every slot from the first page its window reaches to
        the page holding the step's own token (``lens + 1`` positions)."""
        n = lens.astype(np.int64) + 1
        live = -(-n // self.page_size)
        if self._window is not None:
            live -= np.maximum(n - self._window, 0) // self.page_size
        self._pages_live.inc(int(live.sum()))
        self._pages_table.inc(self.table.size)

    def spec_cycle(self, st: SlotTable, k_eff: int):
        """Paged speculative cycle: pre-own the burst's pages (alloc /
        copy-on-write), then draft+verify in one jitted call."""
        eng = self.engine
        lens = np.minimum(st.slot_len, eng.max_len - (k_eff + 1))
        for s in range(eng.n_slots):
            if not st.active[s]:
                continue
            lens[s] = st.slot_len[s]
            for pos in range(int(st.slot_len[s]),
                             int(st.slot_len[s]) + k_eff + 1):
                self.ensure_writable(s, pos)
        out, n_acc, self.store = eng._spec.run_cycle_paged(
            self.store, jnp.asarray(self.table),
            jnp.asarray(lens.astype(np.int32)), st.slot_last,
            jnp.asarray(st.active), st.temps, st.top_k, st.top_p,
            eng._next_key(), k_eff)
        return out, n_acc

    def post_spec_slot(self, st: SlotTable, s: int):
        """Rejected-suffix rollback: pages wholly past the accepted
        depth were allocated (or COW'd) for this burst and are
        exclusively owned — shared prefix pages all sit below
        ``slot_len``."""
        ps = self.page_size
        trimmed = 0
        for j in range(self.pages_per_slot):
            phys = int(self.table[s, j])
            if phys != PagePool.TRASH and j * ps >= st.slot_len[s]:
                assert not self.pool.is_shared(phys)
                self.pool.decref(phys)
                self.table[s, j] = PagePool.TRASH
                trimmed += 1
        if trimmed:
            instrument.page_event(self.engine, "page_trim", slot=s,
                                  pages=trimmed)

    def spec_rollback(self, st: SlotTable):
        pass    # per-slot page trim happens in post_spec_slot
