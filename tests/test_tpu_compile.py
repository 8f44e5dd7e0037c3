"""The main path's Pallas kernels at llama3-8b widths, compiled by the TPU
compiler for a described (not attached) v5e chip.

Interpret mode cannot see what Mosaic refuses: block shapes off the
(8, 128) tiling, casts and transposes it cannot lower.  These compiles
can, at no chip time.  The topology is described inside a fixture (never
at import: only one process may load the TPU library, and every test
worker imports this file), and the tests skip where it cannot be.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flash_decode import (flash_decode_paged_pallas,
                                        flash_decode_paged_q8_pallas,
                                        flash_decode_pallas,
                                        flash_decode_q8_pallas)
from repro.kernels.quant_matmul import quant_matmul_pallas

# llama3-8b: head_dim 128, 32 q / 8 kv heads (G = 4), d_model 4096,
# d_ff 14336; int4 group 64; 16-token KV pages
HD, H, KH, D, FF, GROUP, PAGE = 128, 32, 8, 4096, 14336, 64, 16
B, S_MAX, N_PAGES = 8, 1024, 513


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    described-chip executable is written to the cache but cannot be read
    back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m", [8, 512])
@pytest.mark.parametrize("k,n", [(D, FF), (FF, D)])
def test_quant_matmul_compiles(one_chip, m, k, n):
    """Decode (m=8) and prefill (m=512) rows through the up/gate and down
    projections."""
    txt = _compiled_text(
        lambda x, c, s, z: quant_matmul_pallas(x, c, s, z, interpret=False),
        one_chip, ((m, k), jnp.float32), ((k // 2, n), jnp.uint8),
        ((k // GROUP, n), jnp.float32), ((k // GROUP, n), jnp.float32))
    assert "tpu_custom_call" in txt


def _decode_case(name):
    q = ((B, 1, H, HD), jnp.bfloat16)
    lens = ((B,), jnp.int32)
    table = ((B, S_MAX // PAGE), jnp.int32)
    dense = (B, KH, S_MAX, HD)
    paged = (N_PAGES, KH, PAGE, HD)
    if name == "dense":
        return (lambda *a: flash_decode_pallas(*a, interpret=False),
                [q, (dense, jnp.bfloat16), (dense, jnp.bfloat16), lens])
    if name == "dense_q8":
        sc = (B, KH, S_MAX, 1)
        return (lambda *a: flash_decode_q8_pallas(*a, interpret=False),
                [q, (dense, jnp.int8), (sc, jnp.float32), (dense, jnp.int8),
                 (sc, jnp.float32), lens])
    if name == "paged":
        return (lambda *a: flash_decode_paged_pallas(*a, interpret=False),
                [q, (paged, jnp.bfloat16), (paged, jnp.bfloat16), table,
                 lens])
    sc = (N_PAGES, KH, PAGE, 1)
    return (lambda *a: flash_decode_paged_q8_pallas(*a, interpret=False),
            [q, (paged, jnp.int8), (sc, jnp.float32), (paged, jnp.int8),
             (sc, jnp.float32), table, lens])


@pytest.mark.parametrize("variant", ["dense", "dense_q8", "paged",
                                     "paged_q8"])
def test_flash_decode_compiles(one_chip, variant):
    fn, shapes = _decode_case(variant)
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


@pytest.mark.parametrize("q8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("h,kh,hd", [(32, 8, 160), (56, 8, 128)],
                         ids=["stablelm-12b", "g7"])
def test_paged_decode_compiles_at_cell_size(one_chip, h, kh, hd, q8):
    """The paged decode at the offline-batch cell's size: 32 slots, a
    256-page table over 4000 pages of 16; stablelm-12b's hd 160 (a minor
    dim Mosaic pads to 256 lanes) and a G = 7 group at hd 128."""
    b, pages, width = 32, 4000, 256
    store = (pages, kh, PAGE, hd)
    q = ((b, 1, h, hd), jnp.bfloat16)
    tail = [((b, width), jnp.int32), ((b,), jnp.int32)]
    if q8:
        sc = ((pages, kh, PAGE, 1), jnp.float32)
        fn = lambda *a: flash_decode_paged_q8_pallas(*a, interpret=False)
        shapes = [q, (store, jnp.int8), sc, (store, jnp.int8), sc] + tail
    else:
        fn = lambda *a: flash_decode_paged_pallas(*a, interpret=False)
        shapes = [q, (store, jnp.bfloat16), (store, jnp.bfloat16)] + tail
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


def test_flash_attention_compiles(one_chip):
    """Prefill attention at T=2048 in the grouped GQA layout."""
    t = 2048
    txt = _compiled_text(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True,
                                               interpret=False),
        one_chip, ((KH, H // KH, t, HD), jnp.bfloat16),
        ((KH, t, HD), jnp.bfloat16), ((KH, t, HD), jnp.bfloat16))
    assert "tpu_custom_call" in txt


def test_tensor_parallel_decode_step_compiles(topo, one_chip, monkeypatch):
    """The paged decode step of llama3-8b at published widths (two
    layers) on a described (1, 4) v5e mesh, kernels on: every Mosaic
    kernel must sit inside a shard_map (GSPMD cannot partition one), and
    the step keeps its single logits all-gather."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.configs import ARCHS
    from repro.core import QuantSpec, quantize_model
    from repro.dist.sharding import (SERVE_DECODE_RULES, axis_rules,
                                     shard_hint, tree_shardings)
    from repro.models.registry import build_model

    monkeypatch.setenv("REPRO_KERNEL_MODE", "tpu")
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    model = build_model(ARCHS["llama3-8b"].scaled(n_layers=2))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    stats = jax.eval_shape(
        lambda p: model.forward(p, {"tokens": jnp.zeros((1, 64), jnp.int32)},
                                collect_stats=True)[1]["stats"], params)
    qparams = jax.eval_shape(lambda p, s: quantize_model(
        p, model.quant_site_map(), s, spec=QuantSpec(4, GROUP),
        mode="packed")[0], params, stats)
    store = jax.eval_shape(lambda: model.init_paged_cache(N_PAGES, PAGE))

    def placed(tree, axes):
        shardings = tree_shardings(mesh, tree, axes, rules=SERVE_DECODE_RULES)
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            tree, shardings)

    def step(params, store, token, table, lens):
        logits, store = model.decode_step_paged(params, store, token, table,
                                                lens)
        return shard_hint(logits[:, 0], "batch", None).argmax(-1), store

    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    ints = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)
    with axis_rules(mesh, SERVE_DECODE_RULES):
        txt = jax.jit(step).lower(
            placed(qparams, model.param_axes()),
            placed(store, model.paged_cache_axes()), ints((B, 1)),
            ints((B, S_MAX // PAGE)), ints((B,))).compile().as_text()
    assert "tpu_custom_call" in txt
    assert txt.count(" all-gather(") == 1
