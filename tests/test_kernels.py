"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import QuantSpec, quantize_groupwise
from repro.kernels import ref
from repro.kernels import ops
from repro.kernels.quant_error import quant_error_pallas
from repro.kernels.quant_matmul import quant_matmul_pallas
from repro.kernels.ops import quant_matmul, quant_matmul_experts


@pytest.mark.parametrize("m,k,n", [(64, 256, 128), (128, 512, 256),
                                   (32, 128, 384)])
@pytest.mark.parametrize("xdtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_kernel_vs_oracle(m, k, n, xdtype):
    ks = jax.random.split(jax.random.PRNGKey(m + k + n), 2)
    w = jax.random.normal(ks[0], (k, n))
    x = jax.random.normal(ks[1], (m, k)).astype(xdtype)
    spec = QuantSpec(bits=4, group_size=128)
    qt = quantize_groupwise(w, spec, pack=True)
    out = quant_matmul_pallas(x.astype(jnp.float32), qt.codes, qt.scale,
                              qt.zero, bm=min(64, m))
    expect = ref.quant_matmul_ref(x.astype(jnp.float32), qt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("blocks", [(128, 128), (64, 128), (128, 64)])
def test_quant_matmul_block_shapes(blocks):
    """(bm, bn) tiles; K spans several tiles of lcm(8 * g, 256) = 512."""
    bm, bn = blocks
    w = jax.random.normal(jax.random.PRNGKey(0), (1536, 128))
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 1536))
    spec = QuantSpec(bits=4, group_size=64)
    qt = quantize_groupwise(w, spec, pack=True)
    out = quant_matmul_pallas(x, qt.codes, qt.scale, qt.zero, bm=bm, bn=bn)
    expect = ref.quant_matmul_ref(x, qt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-3)


@pytest.mark.parametrize("a", [1, 5, 21])
@pytest.mark.parametrize("sym", [False, True])
def test_quant_error_kernel_vs_oracle(a, sym):
    k, n = 256, 128
    w = jax.random.normal(jax.random.PRNGKey(a), (k, n))
    scales = jnp.abs(jax.random.normal(jax.random.PRNGKey(a + 1), (a, k))) + 0.5
    msq = jnp.abs(jax.random.normal(jax.random.PRNGKey(a + 2), (k,)))
    spec = QuantSpec(bits=4, group_size=128, symmetric=sym)
    got = quant_error_pallas(w, scales, msq, spec, bk=128, bn=64)
    expect = ref.quant_error_ref(w, scales, msq, spec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=1e-4)


@pytest.mark.parametrize("k,n,g", [(320, 100, 64), (256, 100, 128),
                                   (320, 128, 64)])
def test_quant_error_kernel_non_tile_shapes(k, n, g):
    """Tile-divisibility regression for the error kernel (RPR007 fix):
    n not a multiple of the column tile pads with zero columns (which
    contribute exactly zero error), and k=320 with the default bk=256
    falls back to bk=g instead of tripping an assert."""
    a = 3
    w = jax.random.normal(jax.random.PRNGKey(k + n), (k, n))
    scales = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (a, k))) + 0.5
    msq = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (k,)))
    spec = QuantSpec(bits=4, group_size=g)
    got = quant_error_pallas(w, scales, msq, spec)
    expect = ref.quant_error_ref(w, scales, msq, spec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=1e-4)


@pytest.mark.parametrize("m", [1, 3, 130, 192])
@pytest.mark.parametrize("k,n,g", [(128, 1600, 64), (1600, 128, 100),
                                   (1600, 1600, 100)])
def test_quant_matmul_kernel_non_tile_shapes(m, k, n, g):
    """Tile-divisibility regression (hymba d_model=1600: 1600 % 128 = 64
    used to trip the kernel's assert; non-multiple-of-128 m tripped the
    dispatch's wrong row padding).  m/n pad to the tile inside the
    kernel wrapper; a k that no lcm(8 * g, 256) tile divides runs as
    one K block."""
    w = jax.random.normal(jax.random.PRNGKey(m + n), (k, n))
    x = jax.random.normal(jax.random.PRNGKey(m), (m, k))
    qt = quantize_groupwise(w, QuantSpec(bits=4, group_size=g), pack=True)
    out = quant_matmul_pallas(x, qt.codes, qt.scale, qt.zero)
    assert out.shape == (m, n)
    expect = ref.quant_matmul_ref(x, qt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-3)


@pytest.mark.parametrize("m", [1, 3, 130, 192])
def test_ops_dispatch_kernel_path_non_tile_m(m, monkeypatch):
    """The dispatch must pad to the tile the kernel actually uses —
    forced onto the kernel path (interpret mode) so this is exercised
    off-TPU, where the CPU "ref" default used to hide it."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    k, n = 128, 1600            # hymba-shaped n_out
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n))
    x = jax.random.normal(jax.random.PRNGKey(m), (m, k))
    s = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (k,))) + 0.5
    spec = QuantSpec(bits=4, group_size=64)
    qt = quantize_groupwise(w, spec, act_scale=s, pack=True)
    out = quant_matmul(x, qt)
    assert out.shape == (m, n)
    expect = ref.quant_matmul_ref(x, qt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-3)


def test_ops_dispatch_leading_dims():
    """quant_matmul handles (B, T, k) activations."""
    w = jax.random.normal(jax.random.PRNGKey(0), (128, 64))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 128))
    s = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (128,))) + 0.5
    spec = QuantSpec(bits=4, group_size=64)
    qt = quantize_groupwise(w, spec, act_scale=s, pack=True)
    out = quant_matmul(x, qt)
    expect = ref.quant_matmul_ref(x, qt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-4)
    assert out.shape == (2, 8, 64)


def test_expert_quant_matmul():
    e, c, d, f = 4, 8, 64, 32
    w = jax.random.normal(jax.random.PRNGKey(0), (e, d, f))
    x = jax.random.normal(jax.random.PRNGKey(1), (e, c, d))
    spec = QuantSpec(bits=4, group_size=32)
    qt = jax.vmap(lambda ww: quantize_groupwise(ww, spec, pack=True))(w)
    out = quant_matmul_experts(x, qt)
    for i in range(e):
        sub = jax.tree_util.tree_map(lambda a: a[i], qt)
        np.testing.assert_allclose(np.asarray(out[i]),
                                   np.asarray(ref.quant_matmul_ref(x[i], sub)),
                                   atol=1e-4)


@pytest.mark.parametrize("shape", [(4, 256, 64), (2, 128, 128), (3, 384, 32)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_vs_oracle(shape, causal):
    from repro.kernels.flash_attention import (flash_attention_pallas,
                                               flash_attention_ref)
    bh, t, hd = shape
    ks = jax.random.split(jax.random.PRNGKey(t + hd), 3)
    q = jax.random.normal(ks[0], (bh, t, hd))
    k = jax.random.normal(ks[1], (bh, t, hd))
    v = jax.random.normal(ks[2], (bh, t, hd))
    out = flash_attention_pallas(q, k, v, causal=causal)
    ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("t", [37, 150])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_non_tile_seq_len(t, causal):
    """Sequence lengths that don't divide the (bq, bk) tiles pad to the
    tile grid with masked-out keys (RPR007 fix: the kernel used to
    assert divisibility instead of padding)."""
    from repro.kernels.flash_attention import (flash_attention_pallas,
                                               flash_attention_ref)
    bh, hd = 3, 64
    ks = jax.random.split(jax.random.PRNGKey(t), 3)
    q = jax.random.normal(ks[0], (bh, t, hd))
    k = jax.random.normal(ks[1], (bh, t, hd))
    v = jax.random.normal(ks[2], (bh, t, hd))
    out = flash_attention_pallas(q, k, v, causal=causal)
    expect = flash_attention_ref(q, k, v, causal=causal)
    assert out.shape == (bh, t, hd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5)


def test_flash_attention_gqa_grouped_vs_chunked():
    """Grouped-GQA prefill layout: 4-D q (BKH, G, T, hd) against the
    *unrepeated* k/v must reproduce the model-side chunked attention —
    the wrapper no longer repeats KV to q-heads before the kernel."""
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.models.common import chunked_attention
    b, t, h, kh, hd = 2, 256, 8, 2, 64
    g = h // kh
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, t, h, hd))
    k = jax.random.normal(ks[1], (b, t, kh, hd))
    v = jax.random.normal(ks[2], (b, t, kh, hd))
    expect = chunked_attention(q, k, v, causal=True, chunk=64)
    qr = q.reshape(b, t, kh, g, hd).transpose(0, 2, 3, 1, 4) \
         .reshape(b * kh, g, t, hd)
    out = flash_attention_pallas(
        qr, k.transpose(0, 2, 1, 3).reshape(b * kh, t, hd),
        v.transpose(0, 2, 1, 3).reshape(b * kh, t, hd), causal=True)
    assert out.shape == (b * kh, g, t, hd)
    out = out.reshape(b, kh, g, t, hd).transpose(0, 3, 1, 2, 4) \
             .reshape(b, t, h, hd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# Flash-decode kernel family vs the jnp oracles (forced onto the kernel
# path through the ops dispatch: GQA ratios, per-slot cache_len
# including 1 and full, window masking, non-tile head dims).
# ---------------------------------------------------------------------------

def _decode_inputs(b, h, kh, hd, s, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, 1, h, hd))
    k = jax.random.normal(ks[1], (b, kh, s, hd))     # native (B, KH, S, hd)
    v = jax.random.normal(ks[2], (b, kh, s, hd))
    lens = jnp.array([1, s, 2 * s // 3], jnp.int32)  # 1, full, mid
    return q, k, v, lens


def _q8_caches(k, v):
    """int8-quantize native-layout caches; returns native codes/scales."""
    from repro.models.common import quantize_kv
    kc, ks = quantize_kv(k.transpose(0, 2, 1, 3))
    vc, vs = quantize_kv(v.transpose(0, 2, 1, 3))
    return (kc.transpose(0, 2, 1, 3), ks.transpose(0, 2, 1, 3),
            vc.transpose(0, 2, 1, 3), vs.transpose(0, 2, 1, 3))


def _paged_store(k, v, ps, shuffle_seed=0):
    """Cut native caches into ps-token pages behind a shuffled page
    table with the trash page pinned at physical id 0."""
    b, kh, s, hd = k.shape
    n_per = s // ps
    perm = np.random.RandomState(shuffle_seed).permutation(b * n_per) + 1

    def paged(x):
        pages = x.reshape(b, kh, n_per, ps, x.shape[-1]) \
                 .transpose(0, 2, 1, 3, 4).reshape(b * n_per, kh, ps,
                                                   x.shape[-1])
        store = jnp.zeros((1 + b * n_per,) + pages.shape[1:], pages.dtype)
        return store.at[perm].set(pages)

    table = jnp.asarray(perm.reshape(b, n_per), jnp.int32)
    return paged(k), paged(v), table, paged


@pytest.mark.parametrize("h,kh", [(4, 4), (4, 2), (8, 2)])
@pytest.mark.parametrize("hd", [64, 48])
@pytest.mark.parametrize("window", [None, 32])
def test_flash_decode_dense_vs_ref(h, kh, hd, window, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    q, k, v, lens = _decode_inputs(3, h, kh, hd, 160)
    out = ops.decode_attention(q, k, v, lens, window=window)
    expect = ref.decode_attention_ref(
        q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), lens,
        window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5)


@pytest.mark.parametrize("h,kh", [(4, 4), (8, 2)])
@pytest.mark.parametrize("window", [None, 32])
def test_flash_decode_q8_vs_ref(h, kh, window, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    q, k, v, lens = _decode_inputs(3, h, kh, 64, 160, seed=2)
    kc, ksc, vc, vsc = _q8_caches(k, v)
    out = ops.decode_attention_q8(q, kc, ksc, vc, vsc, lens, window=window)
    expect = ref.decode_attention_q8_ref(
        q, kc.transpose(0, 2, 1, 3), ksc.transpose(0, 2, 1, 3),
        vc.transpose(0, 2, 1, 3), vsc.transpose(0, 2, 1, 3), lens,
        window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-4, rtol=1e-4)


# Paged cases: 24 pages of 16 tokens a slot, so the kernel's 256-token
# blocks (16 pages) split each table in two.  Slot lengths: empty, one
# token, an exact multiple of the page, one ending mid-way through the
# second block, and the full table.  Windows: 24 (inside one page or
# two), 100 (across the block boundary at 256 for the slot of 300; only
# the second block for the full slot).
PAGED_PS, PAGED_NP = 16, 24
PAGED_LENS = (0, 1, 32, 300, PAGED_PS * PAGED_NP)


def _paged_inputs(h, kh, hd, seed):
    """A shuffled page store whose table entries past each slot's live
    length point at the trash page, which holds garbage: the kernel must
    neither visit those pages nor let their contents through."""
    b, s = len(PAGED_LENS), PAGED_PS * PAGED_NP
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, 1, h, hd))
    k = jax.random.normal(ks[1], (b, kh, s, hd))
    v = jax.random.normal(ks[2], (b, kh, s, hd))
    lens = jnp.array(PAGED_LENS, jnp.int32)
    _, _, table, paged = _paged_store(k, v, ps=PAGED_PS, shuffle_seed=seed)
    live = (np.arange(PAGED_NP)[None] * PAGED_PS
            < np.asarray(lens)[:, None])
    table = jnp.where(live, table, 0)

    def store(x, garbage):
        return paged(x).at[0].set(garbage)
    return q, k, v, lens, table, store


def _expect_paged(expect, lens):
    """The oracle spreads an empty slot's softmax over every position;
    the kernel returns zeros there."""
    return np.where(np.asarray(lens)[:, None, None, None] == 0, 0.0,
                    np.asarray(expect))


@pytest.mark.parametrize("h,kh,hd", [(4, 4, 48), (8, 2, 64), (14, 2, 160),
                                     (4, 1, 160)])
@pytest.mark.parametrize("window", [None, 24, 100])
def test_flash_decode_paged_vs_ref(h, kh, hd, window, monkeypatch):
    """Cases (4, 4, 48) and (8, 2, 64) as before, G = 7 at hd 160, and a
    tensor-parallel shard's single kv head."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    q, k, v, lens, table, store = _paged_inputs(h, kh, hd, seed=3)
    k_st, v_st = store(k, 1e3), store(v, -1e3)
    out = ops.paged_decode_attention(q, k_st, v_st, table, lens,
                                     window=window)
    expect = ref.paged_decode_attention_ref(q, k_st, v_st, table, lens,
                                            window=window)
    np.testing.assert_allclose(np.asarray(out), _expect_paged(expect, lens),
                               atol=2e-5)


def _paged_q8_check(h, kh, hd, window, seed):
    q, k, v, lens, table, store = _paged_inputs(h, kh, hd, seed=seed)
    kc, ksc, vc, vsc = _q8_caches(k, v)
    k_st, ks_st = store(kc, 100), store(ksc, 1e3)
    v_st, vs_st = store(vc, -100), store(vsc, 1e3)
    out = ops.paged_decode_attention_q8(q, k_st, ks_st, v_st, vs_st, table,
                                        lens, window=window)
    expect = ref.paged_decode_attention_q8_ref(q, k_st, ks_st, v_st, vs_st,
                                               table, lens, window=window)
    np.testing.assert_allclose(np.asarray(out), _expect_paged(expect, lens),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("window", [None, 24, 100])
def test_flash_decode_paged_q8_vs_ref(window, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    _paged_q8_check(8, 2, 64, window, seed=4)


@pytest.mark.parametrize("h,kh,hd", [(14, 2, 160), (4, 1, 160)])
def test_flash_decode_paged_q8_shapes_vs_ref(h, kh, hd, monkeypatch):
    """The int8 fold at G = 7 and hd 160, and on a single kv head."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    _paged_q8_check(h, kh, hd, 100, seed=6)


def test_flash_decode_ref_mode_dispatch(monkeypatch):
    """mode=ref must bypass the kernel and hit the oracle bit-exactly."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")
    q, k, v, lens = _decode_inputs(3, 8, 2, 64, 96, seed=5)
    out = ops.decode_attention(q, k, v, lens)
    expect = ref.decode_attention_ref(
        q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), lens)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_expert_quant_matmul_kernel_path(monkeypatch):
    """quant_matmul_experts must honor _mode(): forced onto the kernel
    path, every expert goes through quant_matmul_pallas and still
    matches the vmapped ref."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    e, c, d, f = 4, 8, 64, 32
    w = jax.random.normal(jax.random.PRNGKey(0), (e, d, f))
    x = jax.random.normal(jax.random.PRNGKey(1), (e, c, d))
    spec = QuantSpec(bits=4, group_size=32)
    qt = jax.vmap(lambda ww: quantize_groupwise(ww, spec, pack=True))(w)
    out = quant_matmul_experts(x, qt)
    for i in range(e):
        sub = jax.tree_util.tree_map(lambda a: a[i], qt)
        np.testing.assert_allclose(np.asarray(out[i]),
                                   np.asarray(ref.quant_matmul_ref(x[i], sub)),
                                   atol=1e-3)


def test_flash_attention_matches_chunked_model_path():
    """Kernel agrees with the model-side chunked attention (GQA layout)."""
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.models.common import chunked_attention, _repeat_kv
    b, t, h, kh, hd = 2, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, t, h, hd))
    k = jax.random.normal(ks[1], (b, t, kh, hd))
    v = jax.random.normal(ks[2], (b, t, kh, hd))
    ref = chunked_attention(q, k, v, causal=True, chunk=64)
    kr = _repeat_kv(k, h // kh)
    vr = _repeat_kv(v, h // kh)
    out = flash_attention_pallas(
        q.transpose(0, 2, 1, 3).reshape(b * h, t, hd),
        kr.transpose(0, 2, 1, 3).reshape(b * h, t, hd),
        vr.transpose(0, 2, 1, 3).reshape(b * h, t, hd), causal=True)
    out = out.reshape(b, h, t, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
