"""Jit'd dispatch wrappers around the Pallas kernels.

On TPU the Pallas kernels run compiled; everywhere else (this CPU
container, the 512-device host dry-run) the pure-jnp reference path is
used so every caller — serving engine, dry-run, tests — shares one entry
point.  ``REPRO_KERNEL_MODE`` overrides: "ref" | "interpret" | "tpu".
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.quantizer import QuantizedTensor
from repro.dist.sharding import (active_mesh, active_rule, in_row_parallel,
                                 logical_to_spec, shard_hint)
from . import ref as ref_ops
from .flash_decode import (flash_decode_paged_pallas,
                           flash_decode_paged_q8_pallas,
                           flash_decode_pallas, flash_decode_q8_pallas)
from .quant_error import quant_error_pallas
from .quant_matmul import quant_matmul_pallas


def _mode() -> str:
    forced = os.environ.get("REPRO_KERNEL_MODE")
    if forced:
        return forced
    return "tpu" if jax.default_backend() == "tpu" else "ref"


def quant_matmul(x: jax.Array, qt: QuantizedTensor) -> jax.Array:
    """``(x / act_scale) @ dequant(qt)`` for arbitrary leading x dims."""
    mode = _mode()
    if mode == "ref" or not qt.packed or qt.spec.bits > 4:
        # Decode-serving layouts opt in (rules set "qin" to None) to a
        # constraint that moves weights cross-device in the packed uint8
        # domain instead of dequantized f32 (EXPERIMENTS.md §Perf iter 1).
        # Applied only on explicit opt-in: under default rules the
        # constraint pessimizes GSPMD's own dot partitioning (iter 1d).
        if qt.codes.ndim == 2 and active_rule("qin") is None:
            qt = QuantizedTensor(
                codes=shard_hint(qt.codes, "qin", "qout"),
                scale=shard_hint(qt.scale, "qgroups", "qout"),
                zero=shard_hint(qt.zero, "qgroups", "qout"),
                spec=qt.spec, n_in=qt.n_in, packed=qt.packed,
                act_scale=qt.act_scale)
        return ref_ops.quant_matmul_ref(x, qt)
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    if qt.act_scale is not None:
        x2 = x2 / qt.act_scale.astype(x2.dtype)
    # The kernel wrapper pads m and n up to the tiles it actually picks
    # and slices the result, so the dispatch passes shapes through
    # unchanged — the old pad-rows-to-min(128, m) here became redundant
    # (and it never covered the dimension that actually crashed: n_out
    # not a multiple of the 128 tile, e.g. hymba's d_model=1600).
    kernel = functools.partial(quant_matmul_pallas,
                               interpret=(mode != "tpu"))
    mesh = _model_mesh()
    if mesh is not None:
        kernel = _quant_matmul_shard_map(kernel, mesh, x2, qt)
    out = kernel(x2, qt.codes, qt.scale, qt.zero)
    return out.reshape(lead + (qt.codes.shape[-1],)).astype(x.dtype)


def _model_mesh():
    """The active mesh iff it has a "model" axis of size > 1."""
    mesh = active_mesh()
    if (isinstance(mesh, jax.sharding.Mesh)
            and dict(mesh.shape).get("model", 1) > 1):
        return mesh
    return None


def _quant_matmul_shard_map(kernel, mesh, x2, qt):
    """Run the dequant-matmul kernel per device: Mosaic kernels cannot be
    partitioned by GSPMD.  Column-parallel sites split the output
    columns over "model"; row-parallel sites (:func:`row_parallel`:
    ``wo``, ``w_down``) split the input channels — whole quant groups per
    device — and all-reduce the partial products.  A site whose dims do
    not divide runs replicated."""
    m = dict(mesh.shape)["model"]
    n = qt.codes.shape[-1]
    n_groups = qt.scale.shape[0]
    rows = _batch_entry(x2.shape[0], mesh)
    if in_row_parallel() and n_groups % m == 0:
        w = P("model", None)
        body = lambda *a: jax.lax.psum(kernel(*a), "model")
        in_specs = (P(rows, "model"), w, w, w)
        out_spec = P(rows, None)
    elif n % m == 0:
        w = P(None, "model")
        body = kernel
        in_specs = (P(rows, None), w, w, w)
        out_spec = P(rows, "model")
    else:
        body = kernel
        in_specs = (P(rows, None), P(), P(), P())
        out_spec = P(rows, None)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_spec, check_vma=False)


def quant_error_batch(w: jax.Array, scales: jax.Array, mean_sq: jax.Array,
                      spec) -> jax.Array:
    """Fused multi-candidate quant-error (α search inner loop)."""
    mode = _mode()
    if mode == "ref":
        return ref_ops.quant_error_ref(w, scales, mean_sq, spec)
    return quant_error_pallas(w, scales, mean_sq, spec,
                              interpret=(mode != "tpu"))


def quant_matmul_experts(x: jax.Array, qt: QuantizedTensor) -> jax.Array:
    """Per-expert dequant matmul: x (E, C, d) with qt codes (E, d[/2], f).

    Same grouped-dequant math as quant_matmul: the ref path is vmapped
    over the expert axis; the kernel path (interpret/tpu) unrolls the
    (static) expert axis into per-expert ``quant_matmul_pallas`` calls,
    so MoE serving consumes packed expert weights through the same
    dequant-GEMM kernel as the dense matmuls."""
    mode = _mode()
    if mode == "ref" or not qt.packed or qt.spec.bits > 4:
        def one(xe, codes, scale, zero, act):
            sub = QuantizedTensor(codes=codes, scale=scale, zero=zero,
                                  spec=qt.spec, n_in=qt.n_in,
                                  packed=qt.packed, act_scale=act)
            return ref_ops.quant_matmul_ref(xe, sub)

        if qt.act_scale is None:
            return jax.vmap(lambda xe, c, s, z: one(xe, c, s, z, None))(
                x, qt.codes, qt.scale, qt.zero)
        return jax.vmap(one)(x, qt.codes, qt.scale, qt.zero, qt.act_scale)

    outs = []
    for e in range(qt.codes.shape[0]):
        xe = x[e]
        if qt.act_scale is not None:
            xe = xe / qt.act_scale[e].astype(xe.dtype)
        outs.append(quant_matmul_pallas(xe, qt.codes[e], qt.scale[e],
                                        qt.zero[e],
                                        interpret=(mode != "tpu")))
    return jnp.stack(outs).astype(x.dtype)


# ---------------------------------------------------------------------------
# Decode attention (the serving engine's hottest loop).  All entry
# points take the caches' *native* layouts — dense (B, KH, S, hd),
# paged stores (P, KH, ps, hd) — q (B, 1, H, hd), cache_len (B,) int32.
# Ref mode transposes into the jnp oracles (bit-identical to the
# pre-kernel call sites); otherwise the flash-decode Pallas kernels run
# (interpret off-TPU): split-KV over dense caches, one grid step per slot
# over its live pages for the page store.
#
# When a real mesh with a non-trivial "model" axis is active and both
# head counts divide it, the whole family runs under a head-axis
# ``shard_map``: each device owns H/m query heads and KH/m KV heads, so
# decode attention and the in-kernel page copies stay device-local and
# the decode step needs no KV-cache collectives at all (attention is
# exactly parallel over heads — per-head softmax, no cross-head math);
# a device may hold a single KV head.
# Otherwise (no mesh, model=1, or non-dividing head counts) the local
# body runs directly and GSPMD handles whatever layout it was given.
# ---------------------------------------------------------------------------

def _tp_mesh(n_q_heads: int, n_kv_heads: int):
    """The active mesh iff head-axis shard_map is applicable, else None."""
    mesh = _model_mesh()
    if mesh is None:
        return None
    m = dict(mesh.shape)["model"]
    return None if n_q_heads % m or n_kv_heads % m else mesh


def _batch_entry(n: int, mesh):
    """PartitionSpec entry for a batch dim of size ``n`` (None / "data" /
    ("pod","data") ... depending on the mesh and divisibility)."""
    return logical_to_spec(("batch",), shape=(n,), mesh=mesh)[0]


def _decode_attention_local(q, k_cache, v_cache, cache_len, *, window, mode):
    if mode == "ref":
        return ref_ops.decode_attention_ref(
            q, k_cache.transpose(0, 2, 1, 3), v_cache.transpose(0, 2, 1, 3),
            cache_len, window=window)
    return flash_decode_pallas(q, k_cache, v_cache, cache_len,
                               window=window, interpret=(mode != "tpu"))


def _decode_attention_q8_local(q, k_codes, k_scale, v_codes, v_scale,
                               cache_len, *, window, mode):
    if mode == "ref":
        return ref_ops.decode_attention_q8_ref(
            q, k_codes.transpose(0, 2, 1, 3), k_scale.transpose(0, 2, 1, 3),
            v_codes.transpose(0, 2, 1, 3), v_scale.transpose(0, 2, 1, 3),
            cache_len, window=window)
    return flash_decode_q8_pallas(q, k_codes, k_scale, v_codes, v_scale,
                                  cache_len, window=window,
                                  interpret=(mode != "tpu"))


def _paged_decode_attention_local(q, k_store, v_store, page_table, cache_len,
                                  *, window, mode):
    if mode == "ref":
        return ref_ops.paged_decode_attention_ref(
            q, k_store, v_store, page_table, cache_len, window=window)
    return flash_decode_paged_pallas(q, k_store, v_store, page_table,
                                     cache_len, window=window,
                                     interpret=(mode != "tpu"))


def _paged_decode_attention_q8_local(q, k_codes, k_scale, v_codes, v_scale,
                                     page_table, cache_len, *, window, mode):
    if mode == "ref":
        return ref_ops.paged_decode_attention_q8_ref(
            q, k_codes, k_scale, v_codes, v_scale, page_table, cache_len,
            window=window)
    return flash_decode_paged_q8_pallas(q, k_codes, k_scale, v_codes,
                                        v_scale, page_table, cache_len,
                                        window=window,
                                        interpret=(mode != "tpu"))


def _dense_shard_map(body, mesh, q, n_kv: int):
    """Head-axis shard_map wrapper for dense-cache entries: q and the
    output shard heads (dim 2), every (B, KH, S, hd)-shaped cache operand
    shards KV heads (dim 1), lengths shard batch."""
    b = _batch_entry(q.shape[0], mesh)
    qspec = P(b, None, "model", None)
    kvspec = P(b, "model", None, None)
    n_caches = n_kv  # cache-layout operands between q and cache_len
    in_specs = (qspec,) + (kvspec,) * n_caches + (P(b),)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=qspec, check_vma=False)


def _paged_shard_map(body, mesh, q, n_stores: int):
    """Head-axis shard_map wrapper for paged entries: page stores
    (P, KH, ps, hd) shard KV heads (dim 1) with the page dim replicated;
    page tables replicate across "model" (each device gathers its own
    head slice through the same table)."""
    b = _batch_entry(q.shape[0], mesh)
    qspec = P(b, None, "model", None)
    store = P(None, "model", None, None)
    in_specs = (qspec,) + (store,) * n_stores + (P(b, None), P(b))
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=qspec, check_vma=False)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array, *, window=None) -> jax.Array:
    """Single-position attention against a (possibly longer) cache."""
    body = functools.partial(_decode_attention_local, window=window,
                             mode=_mode())
    mesh = _tp_mesh(q.shape[2], k_cache.shape[1])
    if mesh is not None:
        body = _dense_shard_map(body, mesh, q, 2)
    return body(q, k_cache, v_cache, cache_len)


def decode_attention_q8(q, k_codes, k_scale, v_codes, v_scale, cache_len, *,
                        window=None):
    """int8-KV decode attention; scales stay folded in the consumer."""
    body = functools.partial(_decode_attention_q8_local, window=window,
                             mode=_mode())
    mesh = _tp_mesh(q.shape[2], k_codes.shape[1])
    if mesh is not None:
        body = _dense_shard_map(body, mesh, q, 4)
    return body(q, k_codes, k_scale, v_codes, v_scale, cache_len)


def paged_decode_attention(q, k_store, v_store, page_table, cache_len, *,
                           window=None):
    """Decode attention against the shared page store via the table."""
    body = functools.partial(_paged_decode_attention_local, window=window,
                             mode=_mode())
    mesh = _tp_mesh(q.shape[2], k_store.shape[1])
    if mesh is not None:
        body = _paged_shard_map(body, mesh, q, 2)
    return body(q, k_store, v_store, page_table, cache_len)


def paged_decode_attention_q8(q, k_codes, k_scale, v_codes, v_scale,
                              page_table, cache_len, *, window=None):
    """Paged int8-KV decode attention (scales paged alongside codes)."""
    body = functools.partial(_paged_decode_attention_q8_local, window=window,
                             mode=_mode())
    mesh = _tp_mesh(q.shape[2], k_codes.shape[1])
    if mesh is not None:
        body = _paged_shard_map(body, mesh, q, 4)
    return body(q, k_codes, k_scale, v_codes, v_scale, page_table, cache_len)


# ---------------------------------------------------------------------------
# Verify attention (speculative decoding, DESIGN.md §12).  q carries T
# speculative positions per slot; position i attends keys at cache
# positions < base_len[b] + i + 1 (its own fresh entry included) —
# shifted-causal over the tail, length-masked below it.  Ref mode runs
# one fused masked einsum over all T positions (the cycle-cost win: one
# score/softmax pass per layer instead of T); kernel modes unroll T
# calls of the same flash-decode kernel the non-speculative loop runs,
# each position with its own cache_len — so per mode, verify row i
# computes exactly what the sequential decode step would.  T is a
# small static K+1, so either form stays one fused XLA program inside
# the engine's jitted cycle.
# ---------------------------------------------------------------------------

def _verify_attention_local(q, k_cache, v_cache, base_len, *, window, mode):
    if mode == "ref":
        return ref_ops.verify_attention_ref(
            q, k_cache.transpose(0, 2, 1, 3), v_cache.transpose(0, 2, 1, 3),
            base_len, window=window)
    outs = [_decode_attention_local(q[:, i:i + 1], k_cache, v_cache,
                                    base_len + i + 1, window=window,
                                    mode=mode)
            for i in range(q.shape[1])]
    return jnp.concatenate(outs, axis=1)


def _verify_attention_q8_local(q, k_codes, k_scale, v_codes, v_scale,
                               base_len, *, window, mode):
    if mode == "ref":
        return ref_ops.verify_attention_q8_ref(
            q, k_codes.transpose(0, 2, 1, 3), k_scale.transpose(0, 2, 1, 3),
            v_codes.transpose(0, 2, 1, 3), v_scale.transpose(0, 2, 1, 3),
            base_len, window=window)
    outs = [_decode_attention_q8_local(q[:, i:i + 1], k_codes, k_scale,
                                       v_codes, v_scale, base_len + i + 1,
                                       window=window, mode=mode)
            for i in range(q.shape[1])]
    return jnp.concatenate(outs, axis=1)


def _paged_verify_attention_local(q, k_store, v_store, page_table, base_len,
                                  *, window, mode):
    if mode == "ref":
        return ref_ops.paged_verify_attention_ref(
            q, k_store, v_store, page_table, base_len, window=window)
    outs = [_paged_decode_attention_local(q[:, i:i + 1], k_store, v_store,
                                          page_table, base_len + i + 1,
                                          window=window, mode=mode)
            for i in range(q.shape[1])]
    return jnp.concatenate(outs, axis=1)


def _paged_verify_attention_q8_local(q, k_codes, k_scale, v_codes, v_scale,
                                     page_table, base_len, *, window, mode):
    if mode == "ref":
        return ref_ops.paged_verify_attention_q8_ref(
            q, k_codes, k_scale, v_codes, v_scale, page_table, base_len,
            window=window)
    outs = [_paged_decode_attention_q8_local(q[:, i:i + 1], k_codes, k_scale,
                                             v_codes, v_scale, page_table,
                                             base_len + i + 1, window=window,
                                             mode=mode)
            for i in range(q.shape[1])]
    return jnp.concatenate(outs, axis=1)


def verify_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     base_len: jax.Array, *, window=None) -> jax.Array:
    """Multi-position decode attention: q (B, T, H, hd), dense caches in
    native (B, KH, S, hd) layout, base_len (B,) valid entries *before*
    the burst (the T fresh K/V entries are already written)."""
    body = functools.partial(_verify_attention_local, window=window,
                             mode=_mode())
    mesh = _tp_mesh(q.shape[2], k_cache.shape[1])
    if mesh is not None:
        # one shard_map around the whole burst — kernel modes unroll the
        # per-position loop *inside* it, never nesting shard_maps
        body = _dense_shard_map(body, mesh, q, 2)
    return body(q, k_cache, v_cache, base_len)


def verify_attention_q8(q, k_codes, k_scale, v_codes, v_scale, base_len, *,
                        window=None):
    """int8-KV variant of :func:`verify_attention`."""
    body = functools.partial(_verify_attention_q8_local, window=window,
                             mode=_mode())
    mesh = _tp_mesh(q.shape[2], k_codes.shape[1])
    if mesh is not None:
        body = _dense_shard_map(body, mesh, q, 4)
    return body(q, k_codes, k_scale, v_codes, v_scale, base_len)


def paged_verify_attention(q, k_store, v_store, page_table, base_len, *,
                           window=None):
    """:func:`verify_attention` against the shared page store."""
    body = functools.partial(_paged_verify_attention_local, window=window,
                             mode=_mode())
    mesh = _tp_mesh(q.shape[2], k_store.shape[1])
    if mesh is not None:
        body = _paged_shard_map(body, mesh, q, 2)
    return body(q, k_store, v_store, page_table, base_len)


def paged_verify_attention_q8(q, k_codes, k_scale, v_codes, v_scale,
                              page_table, base_len, *, window=None):
    """Paged int8-KV variant of :func:`verify_attention`."""
    body = functools.partial(_paged_verify_attention_q8_local, window=window,
                             mode=_mode())
    mesh = _tp_mesh(q.shape[2], k_codes.shape[1])
    if mesh is not None:
        body = _paged_shard_map(body, mesh, q, 4)
    return body(q, k_codes, k_scale, v_codes, v_scale, page_table, base_len)
