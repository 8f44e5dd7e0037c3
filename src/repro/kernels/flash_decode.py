"""Pallas TPU kernel family: decode attention over dense and paged caches.

Decode attention is the hottest loop of the serving engine: every step,
every layer scores one query position against the whole KV cache.  The
pure-jnp path (now the oracle in :mod:`.ref`) upcasts the entire
``(B, S, KH, hd)`` cache to f32 score matrices in HBM and always pays
for ``max_len`` positions regardless of the slot's live length.  These
kernels fix both, in two shapes:

* **Dense caches: split-KV with a cross-split combine.**  The grid is
  ``(B, KH, n_splits)`` — each split covers ``bs`` consecutive cache
  positions, computes a local softmax ``(m, l, p·V)`` over its block,
  and the per-split partials are merged by an associative logsumexp
  combine (:func:`_combine`) outside the kernel.  ``cache_len`` is
  scalar-prefetched (SMEM): splits past a slot's live length skip all
  compute under ``pl.when``, and their BlockSpec index_map clamps to the
  last live block, so Pallas does not fetch them again.  HBM traffic
  tracks ``cache_len``; every split still costs a grid step.
* **Paged stores (`*_paged*`): one grid step per slot.**  The grid is
  ``(B,)``; the page table and lengths are scalar-prefetched and the
  stores stay in HBM.  Each slot loops (``fori_loop``) over blocks of
  its own live pages only — from the first page the sliding window can
  reach to the page holding its newest token — copying each block's
  pages straight out of the shared store into double-buffered VMEM
  (block ``i + 1`` is fetched while block ``i`` is scored) and running
  the softmax online in f32 scratch.  It writes the normalized output
  once per slot: no per-page grid steps, no split partials, no combine.
  Time as well as traffic tracks the live length, not ``max_len``.
* **GQA-grouped queries.**  q is reshaped ``(B, KH, G, hd)`` and scored
  against the *unrepeated* cache — the kernel-side analogue of the
  sharding rationale in the jnp oracle (repeating KV to q-heads forces
  an SPMD reshard that replicates the cache in f32).
* **int8 fold** (`*_q8`).  The per-(token, head) scales multiply the
  K/V rows inside the kernel (so the scores and the prob-weighted V sum
  carry them), and int8 codes never hit HBM as f32.

Scores, probabilities and the P·V sum are f32 in both shapes; a slot of
length 0 returns zeros.  Layouts are the caches' *native* ones —
``(B, KH, S, hd)`` dense, ``(P, KH, ps, hd)`` paged — so callers no
longer transpose the cache every step.  ``window`` applies the
hymba/local-attention sliding mask (positions
``[cache_len - window, cache_len)``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------

def _decode_body(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                 ks_ref=None, vs_ref=None, *, bs, window, scale):
    """One split: local softmax over ``bs`` cache positions.

    Writes the unnormalized partial ``(p @ V, m, l)``; dead splits (fully
    past ``cache_len`` / fully below the window) write the identity of
    the combine monoid ``(0, -inf, 0)`` without touching the MXU.
    """
    b = pl.program_id(0)
    s = pl.program_id(2)
    length = len_ref[b]
    start = s * bs
    run = start < length
    if window is not None:
        run = jnp.logical_and(run, start + bs > length - window)

    def live_mask(shape, axis):
        pos = start + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
        mask = pos < length
        if window is not None:
            mask = jnp.logical_and(mask, pos >= length - window)
        return mask

    @pl.when(run)
    def _live():
        q = q_ref[0, 0].astype(jnp.float32)               # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (bs, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        # int8 fold: the per-(token, head) scales are (bs, 1) columns, so
        # they multiply the K/V rows (the scores and the prob-weighted V
        # sum then carry them); Mosaic cannot transpose them to (1, bs)
        if ks_ref is not None:
            k = k * ks_ref[0, 0]
        if vs_ref is not None:
            v = v * vs_ref[0, 0]
        sc = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        mask = live_mask((1, bs), 1)                      # score columns
        sc = jnp.where(mask, sc, NEG_INF)
        m = jnp.max(sc, axis=-1, keepdims=True)           # (G, 1)
        p = jnp.exp(sc - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        # hard-zero masked prob columns and V rows: a partial last
        # block's out-of-bounds K/V region is undefined (NaN-filled in
        # interpret mode), and IEEE 0 * NaN = NaN would otherwise leak
        # through the V dot even though exp(-1e30 - m) underflows to 0.
        # The V-row mask is built in (bs, 1) directly: Mosaic cannot
        # transpose a boolean vector.
        p = jnp.where(mask, p, 0.0)
        v = jnp.where(live_mask((bs, 1), 0), v, 0.0)
        o_ref[0, 0, 0] = jnp.dot(p, v, preferred_element_type=jnp.float32)
        m_ref[0, 0, 0] = m
        l_ref[0, 0, 0] = l

    @pl.when(jnp.logical_not(run))
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)


def _dense_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
                  bs, window, scale):
    _decode_body(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                 bs=bs, window=window, scale=scale)


def _dense_q8_kernel(len_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                     o_ref, m_ref, l_ref, *, bs, window, scale):
    _decode_body(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                 ks_ref, vs_ref, bs=bs, window=window, scale=scale)


def _paged_kernel(table_ref, len_ref, q_ref, *refs, n_stores, ps, ppb,
                  window, scale):
    """One slot: every kv head against the slot's live pages.

    ``refs`` are the page stores in HBM (``k, v`` or ``k, k_scale, v,
    v_scale``), the ``(1, KH, G, hd)`` output block, then scratch: one
    double-buffered ``(2, ppb, KH, ps, ·)`` VMEM block per store, their
    DMA semaphores and the running ``(m, l, acc)`` of the online
    softmax.  Blocks of ``ppb`` pages from the first page the window can
    reach to the last live page are copied straight out of the page
    store, block ``i + 1`` while block ``i`` is scored; pages past the
    slot's length are never visited.
    """
    stores, o_ref = refs[:n_stores], refs[n_stores]
    bufs = refs[n_stores + 1:2 * n_stores + 1]
    sems, m_ref, l_ref, acc_ref = refs[2 * n_stores + 1:]
    kb, ksb, vb, vsb = bufs if n_stores == 4 else (bufs[0], None, bufs[1],
                                                   None)
    kh, hd = q_ref.shape[1], q_ref.shape[3]
    bt = ppb * ps                                     # tokens per block
    b = pl.program_id(0)
    length = len_ref[b]
    n_live = (length + ps - 1) // ps
    first = 0 if window is None else jnp.maximum(length - window, 0) // ps
    lo, hi = first // ppb, (n_live + ppb - 1) // ppb
    last_entry = table_ref.shape[1] - 1

    def dma(blk, slot, start):
        """Start (or wait for) the copies of block ``blk``'s live pages
        into buffer ``slot``."""
        for i in range(ppb):
            j = blk * ppb + i
            page = table_ref[b, jnp.minimum(j, last_entry)]

            @pl.when(jnp.logical_and(j >= first, j < n_live))
            def _():
                for n, (st, buf) in enumerate(zip(stores, bufs)):
                    src = st.at[page, :, :, pl.ds(0, buf.shape[-1])]
                    cp = pltpu.make_async_copy(src, buf.at[slot, i],
                                               sems.at[n, slot])
                    cp.start() if start else cp.wait()

    def live_mask(start, shape, axis):
        pos = start + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
        mask = pos < length
        if window is not None:
            mask = jnp.logical_and(mask, pos >= length - window)
        return mask

    def rows(buf, slot, h, scale_buf=None):
        x = buf[slot, :, h][..., :hd].astype(jnp.float32)  # (ppb, ps, hd)
        # int8 fold: the per-(token, head) scales are (ps, 1) columns,
        # multiplying the K/V rows (the scores and the prob-weighted V
        # sum then carry them)
        if scale_buf is not None:
            x = x * scale_buf[slot, :, h][..., :1]
        return x.reshape(bt, hd)

    def block(blk, carry):
        slot = (blk - lo) % 2

        @pl.when(blk + 1 < hi)
        def _():
            dma(blk + 1, 1 - slot, True)

        dma(blk, slot, False)
        start = blk * bt
        # score columns, and V rows built in (bt, 1) directly: Mosaic
        # cannot transpose a boolean vector
        cols = live_mask(start, (1, bt), 1)
        vrows = live_mask(start, (bt, 1), 0)
        for h in range(kh):
            q = q_ref[0, h].astype(jnp.float32)       # (G, hd)
            k = rows(kb, slot, h, ksb)
            v = rows(vb, slot, h, vsb)
            sc = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            sc = jnp.where(cols, sc, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # hard-zero masked prob columns and V rows: pages not copied
            # (past the length, below the window) leave stale VMEM, and a
            # live page's tail past the length holds whatever the trash
            # page or an earlier owner wrote; IEEE 0 * NaN = NaN would
            # otherwise leak through the V dot
            p = jnp.where(cols, jnp.exp(sc - m_new), 0.0)
            v = jnp.where(vrows, v, 0.0)
            m_ref[h] = m_new
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
        return carry

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(lo < hi)
    def _():
        dma(lo, 0, True)

    jax.lax.fori_loop(lo, hi, block, 0)
    # an empty slot (length 0) keeps acc = 0 and returns zeros
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
        o_ref.dtype)


# ---------------------------------------------------------------------------
# Cross-split combine + index maps
# ---------------------------------------------------------------------------

def _combine(o, m, l):
    """Merge per-split partials: ``(o_i, m_i, l_i)`` over the split axis.

    Standard flash-decoding reduction — with ``M = max_i m_i`` and
    ``w_i = exp(m_i - M)``: ``out = sum(w_i o_i) / sum(w_i l_i)``.  The
    per-split merge is associative, so split order (and dead splits,
    which contribute ``(0, -inf, 0)``) cannot change the result.
    """
    big_m = jnp.max(m, axis=2, keepdims=True)             # (B,KH,1,G,1)
    w = jnp.exp(m - big_m)
    l_tot = jnp.sum(w * l, axis=2)                        # (B,KH,G,1)
    acc = jnp.sum(w * o, axis=2)                          # (B,KH,G,hd)
    return acc / jnp.maximum(l_tot, 1e-30)


def _first_live(len_b, window, bs):
    """Index of the first split the sliding window can reach."""
    return jnp.maximum(len_b - window, 0) // bs


def _last_live(len_b, bs):
    """Index of the last live split (0 when the slot is empty)."""
    return jnp.maximum((len_b + bs - 1) // bs - 1, 0)


def _dense_kv_map(bs, window):
    """Clamp dead splits onto the nearest live block: consecutive grid
    steps with identical block indices are not re-fetched, so cache HBM
    traffic tracks ``cache_len``."""
    def imap(b, h, s, len_ref):
        hi = _last_live(len_ref[b], bs)
        idx = jnp.minimum(s, hi)
        if window is not None:
            lo = _first_live(len_ref[b], window, bs)
            idx = jnp.clip(s, lo, jnp.maximum(hi, lo))
        return (b, h, idx, 0)
    return imap


def _out_specs(g, hd):
    def omap(b, h, s, *scalar_refs):
        return (b, h, s, 0, 0)
    return [pl.BlockSpec((1, 1, 1, g, hd), omap),
            pl.BlockSpec((1, 1, 1, g, 1), omap),
            pl.BlockSpec((1, 1, 1, g, 1), omap)]


def _out_shapes(b, kh, ns, g, hd):
    return [jax.ShapeDtypeStruct((b, kh, ns, g, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, kh, ns, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kh, ns, g, 1), jnp.float32)]


_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("window", "bs", "interpret"))
def flash_decode_pallas(q, k_cache, v_cache, cache_len, *, window=None,
                        bs=128, interpret=True):
    """q: (B, 1, H, hd); caches: (B, KH, S, hd) *native* layout;
    cache_len: (B,) int32.  Returns (B, 1, H, hd)."""
    b, _, h, hd = q.shape
    kh, s = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    qg = q[:, 0].reshape(b, kh, g, hd)
    bs = min(bs, s)
    ns = -(-s // bs)
    lens = jnp.broadcast_to(cache_len, (b,)).astype(jnp.int32)
    kv = _dense_kv_map(bs, window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kh, ns),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda b_, h_, s_, lr: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), kv),
            pl.BlockSpec((1, 1, bs, hd), kv),
        ],
        out_specs=_out_specs(g, hd),
    )
    o, m, l = pl.pallas_call(
        functools.partial(_dense_kernel, bs=bs, window=window,
                          scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=_out_shapes(b, kh, ns, g, hd),
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="decode_attention",
    )(lens, qg, k_cache, v_cache)
    return _combine(o, m, l).reshape(b, 1, h, hd).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bs", "interpret"))
def flash_decode_q8_pallas(q, k_codes, k_scale, v_codes, v_scale, cache_len,
                           *, window=None, bs=128, interpret=True):
    """int8-KV variant: codes (B, KH, S, hd) int8, scales (B, KH, S, 1)
    f32, folded inside the kernel (codes never dequantize in HBM)."""
    b, _, h, hd = q.shape
    kh, s = k_codes.shape[1], k_codes.shape[2]
    g = h // kh
    qg = q[:, 0].reshape(b, kh, g, hd)
    bs = min(bs, s)
    ns = -(-s // bs)
    lens = jnp.broadcast_to(cache_len, (b,)).astype(jnp.int32)
    kv = _dense_kv_map(bs, window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kh, ns),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda b_, h_, s_, lr: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), kv),
            pl.BlockSpec((1, 1, bs, 1), kv),
            pl.BlockSpec((1, 1, bs, hd), kv),
            pl.BlockSpec((1, 1, bs, 1), kv),
        ],
        out_specs=_out_specs(g, hd),
    )
    o, m, l = pl.pallas_call(
        functools.partial(_dense_q8_kernel, bs=bs, window=window,
                          scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=_out_shapes(b, kh, ns, g, hd),
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="decode_attention",
    )(lens, qg, k_codes, k_scale, v_codes, v_scale)
    return _combine(o, m, l).reshape(b, 1, h, hd).astype(q.dtype)


def _lanes(d, interpret):
    """Width of a store row as a page copy moves it.  Mosaic lays a store
    out in HBM with its minor dim padded to a multiple of 128 lanes and
    refuses a copy of a narrower slice of it, so the copy takes each row
    whole, padding included, and the kernel reads its first ``d`` lanes;
    the interpreter holds the logical width."""
    return d if interpret else -(-d // 128) * 128


def _paged_call(q, stores, page_table, cache_len, window, interpret):
    """Grid over slots: each slot walks only its own live pages (see
    :func:`_paged_kernel`).  ``stores`` stay in HBM (``pl.ANY``); the
    page table and lengths are scalar-prefetched.  A block holds 256
    tokens of pages (fewer where the table is shorter): on one TPU v5e
    chip, at 32 slots of 320-1100 tokens, 8 kv heads, G 4 and hd 160, a
    call took 0.41 ms with 256, 0.55 ms with 128 and 0.91 ms with 64."""
    b, _, h, hd = q.shape
    kh, ps = stores[0].shape[1], stores[0].shape[2]
    g = h // kh
    qg = q[:, 0].reshape(b, kh, g, hd)
    ppb = max(1, min(256 // ps, page_table.shape[1]))
    lens = jnp.broadcast_to(cache_len, (b,)).astype(jnp.int32)
    table = page_table.astype(jnp.int32)
    slot_map = lambda b_, tr, lr: (b_, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, kh, g, hd), slot_map)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(stores),
        out_specs=pl.BlockSpec((1, kh, g, hd), slot_map),
        scratch_shapes=[pltpu.VMEM((2, ppb) + st.shape[1:-1]
                                   + (_lanes(st.shape[-1], interpret),),
                                   st.dtype) for st in stores]
        + [pltpu.SemaphoreType.DMA((len(stores), 2)),
           pltpu.VMEM((kh, g, 1), jnp.float32),
           pltpu.VMEM((kh, g, 1), jnp.float32),
           pltpu.VMEM((kh, g, hd), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, n_stores=len(stores), ps=ps,
                          ppb=ppb, window=window, scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="decode_attention",
    )(table, lens, qg, *stores)
    return out.reshape(b, 1, h, hd)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def flash_decode_paged_pallas(q, k_store, v_store, page_table, cache_len, *,
                              window=None, interpret=True):
    """Paged variant: stores (P, KH, ps, hd); page_table (B, NP) int32
    physical ids (unmapped entries point at the pinned trash page).
    One grid step per slot, looping over the slot's live pages."""
    return _paged_call(q, (k_store, v_store), page_table, cache_len,
                       window, interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def flash_decode_paged_q8_pallas(q, k_codes, k_scale, v_codes, v_scale,
                                 page_table, cache_len, *, window=None,
                                 interpret=True):
    """Paged int8-KV variant: scale stores (P, KH, ps, 1) are paged
    alongside the codes, copied by the same table and folded
    in-kernel."""
    return _paged_call(q, (k_codes, k_scale, v_codes, v_scale), page_table,
                       cache_len, window, interpret)
