"""Pallas TPU kernel: causal flash attention (forward).

Motivation from the roofline iteration log (EXPERIMENTS.md §Perf): after
the sharding fixes, train/prefill cells are memory-term-bound and the
dominant bytes are the attention score matrices — a pure-jnp chunked
attention still round-trips (B, H, Tq, chunk) scores through HBM each
chunk.  This kernel keeps the running max / denominator / output
accumulator in VMEM scratch across the K-block loop, so score traffic
never leaves the chip: HBM bytes drop from O(T²) to O(T·hd).

Layout: GQA-grouped — q is (BKH, G, T, hd) against the *unrepeated*
k/v (BKH, T, hd), so the kernel streams each KV head's cache once for
all G query heads in its group instead of re-reading a head-repeated
copy (the prefill analogue of the decode-side GQA rationale: repeating
KV to q-heads replicates the cache and multiplies K/V HBM traffic by
G).  A 3-D q (BH, T, hd) is accepted as the G=1 / MHA layout.  Grid is
(BKH, nq, nk) with the K axis innermost ("arbitrary"); fully-future K
blocks are skipped under causal masking via pl.when, halving compute
for causal runs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, g: int, bq: int, bk: int, hd: int, scale: float,
            t_valid: int | None):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: skip blocks strictly in the future of every query in the tile
    run = True
    if causal:
        run = (ik * bk) <= (iq * bq + bq - 1)

    @pl.when(run)
    def _block():
        # (G, bq, hd) -> (G*bq, hd): all grouped query heads share this
        # KV head's k/v block, fetched once
        q = q_ref[0].astype(jnp.float32).reshape(g * bq, hd) * scale
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if t_valid is not None:
            # padded tail keys (t not on the block grid) must not attend
            kpos = ik * bk + jax.lax.broadcasted_iota(
                jnp.int32, (g * bq, bk), 1)
            s = jnp.where(kpos < t_valid, s, NEG_INF)
        if causal:
            # row r of the flattened (G, bq) tile is query position
            # iq*bq + r % bq (group index r // bq shares the position)
            r = jax.lax.broadcasted_iota(jnp.int32, (g * bq, bk), 0)
            qpos = iq * bq + r % bq
            kpos = ik * bk + jax.lax.broadcasted_iota(
                jnp.int32, (g * bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).reshape(g, bq, hd).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, bq: int = 128, bk: int = 128,
                           interpret: bool = True) -> jax.Array:
    """q: (BKH, G, T, hd) grouped GQA — or (BH, T, hd) for G=1/MHA —
    against unrepeated k/v (BKH, T, hd) with hd <= 128.  Returns q's
    shape."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    bkh, g, t, hd = q.shape
    assert k.shape[0] == bkh and k.shape[1] == t, (q.shape, k.shape)
    bq = min(bq, t)
    bk = min(bk, t)
    # t need not land on the block grid (odd prompt lengths): pad q/k/v
    # up to a common multiple of both block sizes and mask padded key
    # positions inside the kernel; padded query rows are sliced away.
    # When t already divides, t_valid stays None and the lowered kernel
    # is bit-identical to the unpadded build.
    step = bq * bk // math.gcd(bq, bk)
    t_pad = -(-t // step) * step
    t_valid = None
    if t_pad != t:
        pad = ((0, t_pad - t), (0, 0))
        q = jnp.pad(q, ((0, 0), (0, 0)) + pad)
        k = jnp.pad(k, ((0, 0),) + pad)
        v = jnp.pad(v, ((0, 0),) + pad)
        t_valid = t
    grid = (bkh, t_pad // bq, t_pad // bk)
    scale = hd ** -0.5
    out = pl.pallas_call(
        functools.partial(_kernel, causal=causal, g=g, bq=bq, bk=bk, hd=hd,
                          scale=scale, t_valid=t_valid),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, g, bq, hd), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, bq, hd), lambda b, i, j: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bkh, g, t_pad, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * bq, 1), jnp.float32),
            pltpu.VMEM((g * bq, 1), jnp.float32),
            pltpu.VMEM((g * bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="prefill_attention",
    )(q, k, v)
    out = out[:, :, :t]
    return out[:, 0] if squeeze else out


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True) -> jax.Array:
    """Pure-jnp oracle: full masked softmax attention."""
    bh, t, hd = q.shape
    s = jnp.einsum("btd,bsd->bts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * hd ** -0.5
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bts,bsd->btd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
