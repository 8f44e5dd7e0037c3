"""Pallas TPU kernel: fused quantization-error evaluation for the α search.

The calibration hot-spot: AWQ/FAQ grid-search evaluates, for every
candidate smoothing scale s_a,

    err[a] = Σ_ij  mean_sq_i · ( deq(Q(W·s_a))_ij / s_a,i  −  W_ij )²

A naive implementation materializes the fake-quantized weight in HBM per
grid point (|grid| × weight-sized traffic).  This kernel streams each W
block into VMEM **once per candidate** and performs
scale→quantize→dequantize→unscale→weighted-error in-register, emitting
only the (A,) error accumulators — turning an HBM-bound search into a
compute-bound one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantizer import QuantSpec


def _kernel(w_ref, s_ref, msq_ref, out_ref, *, g: int, spec: QuantSpec):
    kk = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when((kk == 0) & (j == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[...].astype(jnp.float32)        # (bk, bn)
    s = s_ref[...].astype(jnp.float32)        # (1, bk)
    msq = msq_ref[...].astype(jnp.float32)    # (1, bk)
    bk, bn = w.shape

    ws = w * s.reshape(bk, 1)
    wg = ws.reshape(bk // g, g, bn)
    lo = wg.min(axis=1)
    hi = wg.max(axis=1)
    if spec.symmetric:
        amax = jnp.maximum(jnp.abs(lo), jnp.abs(hi))
        scale = jnp.maximum(amax / spec.qmax, 1e-8)
        zero = jnp.zeros_like(scale)
        qmin, qmax = spec.qmin, spec.qmax
    else:
        lo = jnp.minimum(lo, 0.0)
        hi = jnp.maximum(hi, 0.0)
        scale = jnp.maximum((hi - lo) / (spec.levels - 1), 1e-8)
        zero = jnp.round(-lo / scale)
        qmin, qmax = 0, spec.levels - 1
    s_full = jnp.repeat(scale, g, axis=0)
    z_full = jnp.repeat(zero, g, axis=0)
    codes = jnp.clip(jnp.round(ws / s_full) + z_full, qmin, qmax)
    w_hat = (codes - z_full) * s_full / s.reshape(bk, 1)
    dw = w_hat - w
    out_ref[...] += jnp.sum(msq.reshape(bk, 1) * dw * dw)


@functools.partial(jax.jit, static_argnames=("spec", "bk", "bn", "interpret"))
def quant_error_pallas(w: jax.Array, scales: jax.Array, mean_sq: jax.Array,
                       spec: QuantSpec, *, bk: int = 256, bn: int = 256,
                       interpret: bool = True) -> jax.Array:
    """w: (k, n); scales: (A, k); mean_sq: (k,).  Returns (A,) f32 errors
    normalized by n (matches :func:`repro.kernels.ref.quant_error_ref`)."""
    k, n = w.shape
    a = scales.shape[0]
    from repro.core.quantizer import effective_group_size
    g = effective_group_size(k, spec.group_size)
    bk = min(bk, k)
    bn = min(bn, n)
    if bk % g != 0 or k % bk != 0:
        bk = g  # group size divides k by construction (same invariant
        #         as quant_matmul_pallas), so bk=g always tiles K
    assert k % bk == 0, (k, bk, g)  # repro: noqa[RPR007] bk=g fallback above guarantees this
    # n need not divide the tile: zero-pad the weight columns.  A padded
    # column has w=0 in every group, so lo=hi=0 -> scale clamps to 1e-8,
    # zero=0, codes=0, w_hat=0 — its error contribution is exactly 0 in
    # both the symmetric and asymmetric branches, and the final /n uses
    # the original n.
    pad_n = (-n) % bn
    if pad_n:
        w = jnp.pad(w, ((0, 0), (0, pad_n)))
    np_ = n + pad_n

    grid = (a, k // bk, np_ // bn)
    msq2 = mean_sq.reshape(1, k)
    out = pl.pallas_call(
        functools.partial(_kernel, g=g, spec=spec),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bn), lambda aa, kk, j: (kk, j)),
            pl.BlockSpec((1, bk), lambda aa, kk, j: (aa, kk)),
            pl.BlockSpec((1, bk), lambda aa, kk, j: (0, kk)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda aa, kk, j: (aa, 0)),
        out_shape=jax.ShapeDtypeStruct((a, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary",                                              "arbitrary")),
        interpret=interpret,
        name="quant_error",
    )(w, scales, msq2)
    return out[:, 0] / n
