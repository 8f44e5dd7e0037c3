"""Pallas TPU kernel: W4A16 grouped dequant-matmul.

The serving hot-spot of the FAQ/AWQ deployment format.  Int4 weight codes
are packed two-per-byte in HBM; each grid step stages a ``(bk/2, bn)``
packed block plus its per-group scales/zeros into VMEM, dequantizes
in-register, and feeds the MXU, accumulating in f32 across the K grid
axis.

TPU adaptation notes (vs. AWQ's CUDA dequant-GEMM):
  * HBM->VMEM staging is expressed with BlockSpecs.  Mosaic needs the
    last two dims of every block to be multiples of (8, 128) or the whole
    array dim, so ``bk`` is a multiple of ``8 * g`` (eight scale rows per
    block) and of 256 (the half-K activation blocks below are 128-lane
    aligned), or else the whole K axis.  A scale group never straddles K
    blocks.
  * Packed byte ``i`` holds input channels ``2i`` (low nibble) and
    ``2i + 1`` (high nibble).  Instead of interleaving the two nibble
    planes back into K order in VMEM, the wrapper splits the activation
    into its even and odd input channels, and the kernel computes
    ``x_even @ deq(lo) + x_odd @ deq(hi)``.
  * The per-channel AWQ/FAQ smoothing scale is folded into the activation
    *outside* the kernel (one fused elementwise op), keeping the kernel a
    pure grouped-dequant GEMM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(xe_ref, xo_ref, codes_ref, scale_ref, zero_ref, out_ref):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Mosaic casts uint8 to f32 only through a 32-bit integer
    codes = codes_ref[...].astype(jnp.int32)  # (bk//2, bn) packed bytes
    scale = scale_ref[...]                    # (bk//g, bn)
    zero = zero_ref[...]
    n_groups, bn = scale.shape
    rows = codes.shape[0] // n_groups         # packed rows per group (g/2)

    def dequant(plane):                       # (bk//2, bn) codes of one nibble
        w = plane.astype(jnp.float32).reshape(n_groups, rows, bn)
        return ((w - zero[:, None]) * scale[:, None]).reshape(-1, bn)

    lo = dequant(codes & 0x0F)                # input channels 2i
    hi = dequant(codes >> 4)                  # input channels 2i+1
    xe = xe_ref[...].astype(jnp.float32)      # (bm, bk//2)
    xo = xo_ref[...].astype(jnp.float32)
    out_ref[...] += (jnp.dot(xe, lo, preferred_element_type=jnp.float32)
                     + jnp.dot(xo, hi, preferred_element_type=jnp.float32))


def _k_block(k: int, g: int) -> int:
    """K tile: a multiple of ``8 * g`` and of 256 when one divides ``k``
    (the smallest such), else the whole K axis."""
    step = math.lcm(8 * g, 256)
    return step if k % step == 0 else k


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def quant_matmul_pallas(x: jax.Array, codes: jax.Array, scale: jax.Array,
                        zero: jax.Array, *, bm: int = 128, bn: int = 128,
                        interpret: bool = True) -> jax.Array:
    """x: (m, k) float; codes: (k//2, n) packed uint8;
    scale/zero: (k//g, n) f32.  Returns (m, n) f32."""
    m, k = x.shape
    n = codes.shape[-1]
    n_groups = scale.shape[0]
    g = k // n_groups
    assert g % 2 == 0, (  # repro: noqa[RPR007] packing invariant, not a tile-shape constraint
        f"quant group size must be even to unpack nibble-packed codes "
        f"(g={g})")
    bk = _k_block(k, g)
    bm = min(bm, m)
    bn = min(bn, n)
    # m and n need not divide the MXU tile (hymba's d_model=1600 leaves
    # 1600 % 128 = 64): pad both up to the tile and slice the result.
    # Padded activation rows are zeros; padded weight columns carry
    # scale = zero = 0, so they dequantize to (0 - 0) * 0 = 0 — either
    # way the padded region contributes nothing and is sliced away.
    pad_m = (-m) % bm
    pad_n = (-n) % bn
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    if pad_n:
        codes = jnp.pad(codes, ((0, 0), (0, pad_n)))
        scale = jnp.pad(scale, ((0, 0), (0, pad_n)))
        zero = jnp.pad(zero, ((0, 0), (0, pad_n)))
    mp, np_ = m + pad_m, n + pad_n
    x_even, x_odd = x[:, 0::2], x[:, 1::2]

    grid = (mp // bm, np_ // bn, k // bk)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk // 2), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bm, bk // 2), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // g, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // g, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="dequant_matmul",
    )(x_even, x_odd, codes, scale, zero)
    return out[:m, :n]
