"""Pallas TPU kernel family: split-KV (flash-decoding) decode attention.

Decode attention is the hottest loop of the serving engine: every step,
every layer scores one query position against the whole KV cache.  The
pure-jnp path (now the oracle in :mod:`.ref`) upcasts the entire
``(B, S, KH, hd)`` cache to f32 score matrices in HBM and always pays
for ``max_len`` positions regardless of the slot's live length.  These
kernels fix both:

* **Split-KV with a cross-split combine.**  The grid is
  ``(B, KH, n_splits)`` — each split covers ``bs`` consecutive cache
  positions, computes a local softmax ``(m, l, p·V)`` over its block,
  and the per-split partials are merged by an associative logsumexp
  combine (:func:`_combine`) outside the kernel.  Score matrices never
  round-trip HBM in f32; only the tiny ``(ns, G, hd)`` partials do.
* **Length-aware cost.**  ``cache_len`` is scalar-prefetched (SMEM).
  Splits past a slot's live length skip all compute under ``pl.when``,
  and their BlockSpec index_map clamps to the last live block — Pallas
  skips re-fetching a block whose indices match the previous grid step,
  so HBM traffic *and* FLOPs track ``cache_len``, not ``max_len``.
* **GQA-grouped queries.**  q is reshaped ``(B, KH, G, hd)`` and scored
  against the *unrepeated* cache — the kernel-side analogue of the
  sharding rationale in the jnp oracle (repeating KV to q-heads forces
  an SPMD reshard that replicates the cache in f32).
* **int8 fold** (`*_q8`).  The per-(token, head) scales multiply the
  K/V rows inside the kernel (so the scores and the prob-weighted V sum
  carry them), and int8 codes never hit HBM as f32.
* **In-kernel page gather** (`*_paged*`).  The page table is
  scalar-prefetched and the K/V index_maps read physical pages straight
  out of the shared page store — the dense-HBM ``gather_pages``
  round-trip is gone from the decode path.

Layouts are the caches' *native* ones — ``(B, KH, S, hd)`` dense,
``(P, KH, ps, hd)`` paged — so callers no longer transpose the cache
every step.  ``window`` applies the hymba/local-attention sliding mask
(positions ``[cache_len - window, cache_len)``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Kernel bodies (shared between the dense and paged variants: only the
# BlockSpec index maps differ — logical split positions are identical)
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


def _paged_kernel(table_ref, len_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, *, bs, window, scale):
    del table_ref  # consumed by the index maps
    _decode_body(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                 bs=bs, window=window, scale=scale)


def _paged_q8_kernel(table_ref, len_ref, q_ref, k_ref, ks_ref, v_ref,
                     vs_ref, o_ref, m_ref, l_ref, *, bs, window, scale):
    del table_ref
    _decode_body(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                 ks_ref, vs_ref, bs=bs, window=window, scale=scale)


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


def _paged_kv_map(ps, window):
    """Like :func:`_dense_kv_map` but the clamped *logical* block index
    goes through the scalar-prefetched page table — the kernel reads
    K/V pages directly from the shared page store."""
    def imap(b, h, s, table_ref, len_ref):
        hi = _last_live(len_ref[b], ps)
        idx = jnp.minimum(s, hi)
        if window is not None:
            lo = _first_live(len_ref[b], window, ps)
            idx = jnp.clip(s, lo, jnp.maximum(hi, lo))
        return (table_ref[b, idx], h, 0, 0)
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


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def flash_decode_paged_pallas(q, k_store, v_store, page_table, cache_len, *,
                              window=None, interpret=True):
    """Paged variant: stores (P, KH, ps, hd); page_table (B, NP) int32
    physical ids (unmapped entries point at the pinned trash page).
    One split per page; the table is scalar-prefetched so the K/V
    index_maps gather pages in-kernel."""
    b, _, h, hd = q.shape
    kh, ps = k_store.shape[1], k_store.shape[2]
    g = h // kh
    qg = q[:, 0].reshape(b, kh, g, hd)
    n_pages = page_table.shape[1]
    lens = jnp.broadcast_to(cache_len, (b,)).astype(jnp.int32)
    table = page_table.astype(jnp.int32)
    kv = _paged_kv_map(ps, window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kh, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd),
                         lambda b_, h_, s_, tr, lr: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd), kv),
            pl.BlockSpec((1, 1, ps, hd), kv),
        ],
        out_specs=_out_specs(g, hd),
    )
    o, m, l = pl.pallas_call(
        functools.partial(_paged_kernel, bs=ps, window=window,
                          scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=_out_shapes(b, kh, n_pages, g, hd),
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="decode_attention",
    )(table, lens, qg, k_store, v_store)
    return _combine(o, m, l).reshape(b, 1, h, hd).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def flash_decode_paged_q8_pallas(q, k_codes, k_scale, v_codes, v_scale,
                                 page_table, cache_len, *, window=None,
                                 interpret=True):
    """Paged int8-KV variant: scale stores (P, KH, ps, 1) are paged
    alongside the codes, gathered by the same table and folded
    in-kernel."""
    b, _, h, hd = q.shape
    kh, ps = k_codes.shape[1], k_codes.shape[2]
    g = h // kh
    qg = q[:, 0].reshape(b, kh, g, hd)
    n_pages = page_table.shape[1]
    lens = jnp.broadcast_to(cache_len, (b,)).astype(jnp.int32)
    table = page_table.astype(jnp.int32)
    kv = _paged_kv_map(ps, window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kh, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd),
                         lambda b_, h_, s_, tr, lr: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd), kv),
            pl.BlockSpec((1, 1, ps, 1), kv),
            pl.BlockSpec((1, 1, ps, hd), kv),
            pl.BlockSpec((1, 1, ps, 1), kv),
        ],
        out_specs=_out_specs(g, hd),
    )
    o, m, l = pl.pallas_call(
        functools.partial(_paged_q8_kernel, bs=ps, window=window,
                          scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=_out_shapes(b, kh, n_pages, g, hd),
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="decode_attention",
    )(table, lens, qg, k_codes, k_scale, v_codes, v_scale)
    return _combine(o, m, l).reshape(b, 1, h, hd).astype(q.dtype)
