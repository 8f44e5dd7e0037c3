"""Compile a cell's programs for a described TPU v5e chip, without the
chip, and print their memory analysis: the calibration forward, the
batched prefill at each bucket up to the chunk, and the paged decode
step, at the cell's own sizes.

    JAX_PLATFORMS=cpu REPRO_KERNEL_MODE=tpu \
        python3 -m bench.compile_check --workload stablelm-12b.offline-batch

Packed weights enter as shapes (RTN packing, which has the FAQ tree's
shapes less the small per-row ``act_scale``); nothing runs.
"""
from __future__ import annotations

import argparse
import functools
import os
import sys
import time
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)

    from bench import serving

    sys.path.insert(0, str(serving.REPO / "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.configs.base import ModelConfig
    from repro.core import QuantSpec, quantize_model
    from repro.models.registry import build_model
    from repro.serve.stepper import PagedStepper

    jax.config.update("jax_enable_compilation_cache", False)
    cell = serving.load_cell(args.workload)
    e = cell["workload"]["engine"]
    cfg = ModelConfig(**cell["config"]["model"])
    model = build_model(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            tree)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def report(label, fn, *shapes):
        t = time.perf_counter()
        c = jax.jit(fn).lower(*shapes).compile()
        m = c.memory_analysis()
        gb = 1e9
        print(f"{label}: args {m.argument_size_in_bytes / gb:.3f} GB, "
              f"out {m.output_size_in_bytes / gb:.3f} GB, temp "
              f"{m.temp_size_in_bytes / gb:.3f} GB, alias "
              f"{m.alias_size_in_bytes / gb:.3f} GB "
              f"({time.perf_counter() - t:.1f} s to compile)", flush=True)

    key = jax.random.PRNGKey(0)
    fp = jax.eval_shape(model.init, key)
    report("calibration forward (fp weights, 8 x 64 tokens)",
           lambda p, b: model.forward(p, b, collect_stats=True)[1]["stats"],
           on_chip(fp), {"tokens": sds((8, 64), jnp.int32)})
    packed = on_chip(jax.eval_shape(
        lambda k: quantize_model(model.init(k), model.quant_site_map(), None,
                                 method="rtn",
                                 spec=QuantSpec(bits=4, group_size=64),
                                 mode="packed")[0], key))
    n, ps = e["n_slots"], e["page_size"]
    pages_per_slot = -(-e["max_len"] // ps)
    n_pages = e["n_pages"] or 1 + n * pages_per_slot
    eng = types.SimpleNamespace(model=model, n_slots=n, max_len=e["max_len"],
                                _hint_cache=lambda c: c,
                                _gathered=lambda x: x)
    stp = types.SimpleNamespace(engine=eng, page_size=ps,
                                _hint_store=lambda s: s)
    policy = (sds((n,), jnp.float32), None, sds((2,), jnp.uint32), None)
    store = on_chip(jax.eval_shape(
        lambda: model.init_paged_cache(n_pages, ps)))
    chunk = sorted(set(e["buckets"]) | {e["max_len"]})[-2]
    for b in [b for b in e["buckets"] if b <= chunk]:
        fn = functools.partial(PagedStepper._prefill_paged_fn, stp)
        report(f"prefill {n} x {b}",
               lambda p, t, pl, am, tp, kk, sl: fn(p, t, pl, am, tp, None,
                                                   None, kk, sl),
               packed, sds((n, b), jnp.int32), sds((n,), jnp.int32),
               sds((n,), jnp.bool_), policy[0], policy[2],
               sds((n,), jnp.int32))
    fn = functools.partial(PagedStepper._decode_paged_fn, stp)
    report(f"decode {n} slots, {n_pages} pages of {ps}",
           lambda p, s, tab, ln, sl, ac, tp, kk: fn(p, s, tab, ln, sl, ac, tp,
                                                    None, None, kk),
           packed, store, sds((n, pages_per_slot), jnp.int32),
           sds((n,), jnp.int32), sds((n,), jnp.int32), sds((n,), jnp.bool_),
           policy[0], policy[2])

    from bench import reference

    sizes = serving.model_sizes(cfg)
    mix = cell["mix"]
    longest = mix["prompt"]["max"] + mix["output"]["max"]
    t = -(-longest // reference.LEN_STEP) * reference.LEN_STEP
    dims = (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_, float(cfg.rope_theta),
            float(cfg.norm_eps), cfg.vocab_size)
    tables = {n: {"codes": x.codes, "scale": x.scale, "zero": x.zero,
                  "act_scale": sds((cfg.n_layers, x.n_in), jnp.float32)}
              for n, x in packed["blocks"].items() if n in reference.LINEARS}
    ref_params = on_chip(jax.eval_shape(
        lambda: reference.FpModel(sizes).logit_params(tables)))
    with jax.default_matmul_precision("highest"):
        report(f"reference logits ({t} tokens, {reference.ROW_STEP} rows)",
               functools.partial(reference._rows_logits.__wrapped__, dims,
                                 False),
               ref_params, sds((t,), jnp.int32),
               sds((reference.ROW_STEP,), jnp.int32))
    return 0


if __name__ == "__main__":
    sys.exit(main())
