"""Production serving entry point: load a checkpoint (or init), calibrate,
FAQ-quantize to packed int4, and serve synthetic requests.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --tiny \
        --requests 4

Tensor-parallel serving (DESIGN.md §13): ``--mesh DATA,MODEL`` builds a
local device mesh and hands it to the engine — weights, KV caches, and
the flash-decode dispatch all shard along the model axis.  For CPU
smoke tests set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
before launch so enough virtual devices exist.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS
from repro.core import QuantSpec, quantize_model, run_calibration
from repro.data.synthetic import DataConfig, SyntheticLM, calibration_batches
from repro.dist import checkpoint as ckpt
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models.registry import build_model
from repro.obs import Tracer, profile_session
from repro.serve.draft import registry_draft, self_int8_draft
from repro.serve.engine import Request, ServeEngine
from repro.serve.faults import FaultConfig, FaultInjector
from repro.serve.overload import SLOConfig
from repro.serve.spec import SpecConfig


def parse_chunk(arg):
    """'auto' | int tokens | 0/'none' to disable chunked prefill."""
    if arg == "auto":
        return "auto"
    try:
        n = int(arg)
    except ValueError:
        if arg.lower() in ("none", "off"):
            return None
        raise argparse.ArgumentTypeError(
            f"--prefill-chunk expects 'auto', an int, or 0/none, got {arg!r}")
    return n if n > 0 else None


def parse_mesh(arg):
    """'DATA,MODEL' -> (data, model), with clear errors for bad input."""
    if arg is None:
        return None
    try:
        data, model = (int(x) for x in arg.split(","))
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--mesh expects 'DATA,MODEL' (two comma-separated ints), "
            f"got {arg!r}")
    if data < 1 or model < 1:
        raise argparse.ArgumentTypeError(
            f"--mesh sizes must be >= 1, got {arg!r}")
    return data, model


def parse_at(arg):
    """Comma-separated 0-based event indices -> tuple of ints."""
    if not arg:
        return ()
    try:
        return tuple(int(x) for x in arg.split(","))
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected comma-separated ints, got {arg!r}")


@dataclasses.dataclass
class QuantizedModel:
    """What serving needs once the fp weights are gone: the model, its
    packed weights, the calibration statistics (the self-int8 draft is
    built from ``qparams`` and ``stats``) and the synthetic data source
    requests are drawn from."""
    model: Any
    qparams: Any
    stats: dict
    data: SyntheticLM


def quantize_for_serving(cfg, *, method: str = "faq", bits: int = 4,
                         calib_n: int = 16, ckpt_dir=None) -> QuantizedModel:
    """Init (or restore) → calibrate on synthetic data → quantize to the
    packed serving format.  The fp weights are dropped before returning,
    so the device holds only the packed tree afterwards."""
    model = build_model(cfg)
    # one program: eager init would hold every per-layer weight and its
    # stacked copy at once
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    if ckpt_dir:
        step = ckpt.latest_step(ckpt_dir)
        if step is not None:
            params = ckpt.restore(ckpt_dir, step, {"params": params})["params"]
            print(f"loaded checkpoint step {step}")
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size))
    calib = calibration_batches(data, calib_n, 64)
    stats = run_calibration(model.forward, params,
                            [{k: jnp.asarray(v) for k, v in b.items()}
                             for b in calib])
    qparams, _ = quantize_model(params, model.quant_site_map(), stats,
                                method=method,
                                spec=QuantSpec(bits=bits, group_size=64),
                                mode="packed")
    del params
    return QuantizedModel(model=model, qparams=qparams, stats=stats,
                          data=data)


def build_engine(q: QuantizedModel, *, spec_k: int = 0,
                 draft: str = "self-int8", tiny: bool = True,
                 **engine_kw) -> ServeEngine:
    """A :class:`ServeEngine` over ``q``'s packed weights; ``spec_k > 0``
    adds speculative decoding with the named draft.  ``engine_kw`` goes
    to the engine unchanged (slots, cache, mesh, overload, tracing)."""
    spec_cfg = None
    if spec_k > 0:
        # the self-draft re-quantizes the *serving* weights at int8 (the
        # packed codes are all it needs) with the same calibration stats
        if draft == "self-int8":
            d = self_int8_draft(q.model, q.qparams, q.stats)
        else:
            d = registry_draft(draft, tiny=tiny)
        spec_cfg = SpecConfig(k=spec_k, draft=d)
    return ServeEngine(q.model, q.qparams, spec=spec_cfg, **engine_kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b", choices=sorted(ARCHS))
    # BooleanOptionalAction so --no-tiny can actually select the full
    # config (the old store_true/default=True combo was impossible to
    # disable from the command line)
    ap.add_argument("--tiny", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--method", default="faq", choices=["rtn", "awq", "faq"])
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=12)
    ap.add_argument("--calib-n", type=int, default=16)
    ap.add_argument("--n-slots", type=int, default=4,
                    help="decode batch width (continuous-batching slots)")
    ap.add_argument("--max-len", type=int, default=128,
                    help="per-slot KV-cache capacity (prompt + new tokens)")
    ap.add_argument("--paged", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="paged KV cache with shared-prefix reuse "
                         "(DESIGN.md §10); --no-paged keeps the dense "
                         "per-slot cache")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per physical KV page (paged mode)")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="page-pool capacity; default sizes it so every "
                         "slot can hold a full max_len sequence")
    ap.add_argument("--prefill-chunk", type=parse_chunk, default="auto",
                    metavar="auto|N|0",
                    help="chunked prefill: split long admissions into "
                         "bucket-sized chunks so one long prompt can't "
                         "stall other slots' first tokens (DESIGN.md "
                         "§14); 'auto' picks the second-largest bucket, "
                         "an int rounds up to the bucket grid, 0 "
                         "restores monolithic prefill")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding draft depth (tokens "
                         "proposed per cycle; 0 disables — DESIGN.md §12)")
    ap.add_argument("--draft", default="self-int8",
                    help="draft source for --spec-k: 'self-int8' (FAQ "
                         "int8 self-draft sharing the target's KV) or a "
                         "registry config name for an independent draft "
                         "model")
    ap.add_argument("--mesh", type=parse_mesh, default=None,
                    metavar="DATA,MODEL",
                    help="serve tensor-parallel on a (data, model) device "
                         "mesh, e.g. --mesh 1,4 (requires data*model "
                         "devices; DESIGN.md §13)")
    # -- overload response (DESIGN.md §16) --
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request SLO relative to submission; enables "
                         "SLO-aware admission (doomed requests shed early)")
    ap.add_argument("--slo-margin", type=float, default=1.0,
                    help="shed when now + margin*queue_delay_est exceeds "
                         "the deadline")
    ap.add_argument("--quota-tokens", type=int, default=0,
                    help="per-tenant in-flight token quota (0 = off)")
    # -- deterministic fault injection (serve/faults.py) --
    ap.add_argument("--fault-alloc-at", type=parse_at, default=(),
                    metavar="I,J,...",
                    help="veto the i-th page allocations (0-based) to "
                         "exercise backpressure/preemption")
    ap.add_argument("--fault-alloc-every", type=int, default=0,
                    help="veto every Nth page allocation")
    ap.add_argument("--fault-preempt-at", type=parse_at, default=(),
                    metavar="I,J,...",
                    help="force-preempt the latest-deadline slot at the "
                         "i-th serve-loop iterations")
    ap.add_argument("--fault-stall-at", type=parse_at, default=(),
                    metavar="I,J,...",
                    help="inject a slow step at the i-th loop iterations")
    ap.add_argument("--fault-stall-s", type=float, default=0.0)
    ap.add_argument("--fault-seed", type=int, default=0)
    # -- observability (DESIGN.md §17) --
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export the request/step trace as Chrome/"
                         "Perfetto trace_event JSON (open in "
                         "ui.perfetto.dev)")
    ap.add_argument("--trace-capacity", type=int, default=8192,
                    help="trace ring-buffer size (oldest events drop "
                         "beyond it)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="wrap the run in a jax.profiler trace "
                         "(TensorBoard-compatible) and annotate jitted "
                         "dispatches")
    args = ap.parse_args()

    enable_compile_cache()
    mesh = None
    if args.mesh is not None:
        mesh = make_local_mesh(*args.mesh)
        print(f"mesh: data={args.mesh[0]} model={args.mesh[1]} over "
              f"{len(mesh.devices.flat)} {mesh.devices.flat[0].platform} "
              f"devices")

    cfg = ARCHS[args.arch].tiny() if args.tiny else ARCHS[args.arch]
    q = quantize_for_serving(cfg, method=args.method, bits=args.bits,
                             calib_n=args.calib_n, ckpt_dir=args.ckpt_dir)
    slo = None
    if args.deadline_s is not None or args.quota_tokens > 0:
        slo = SLOConfig(margin=args.slo_margin,
                        quota_tokens=args.quota_tokens,
                        seed=args.fault_seed)
    faults = None
    if (args.fault_alloc_at or args.fault_alloc_every
            or args.fault_preempt_at or args.fault_stall_at):
        faults = FaultInjector(FaultConfig(
            seed=args.fault_seed,
            alloc_fail_at=args.fault_alloc_at,
            alloc_fail_every=args.fault_alloc_every,
            preempt_at=args.fault_preempt_at,
            stall_at=args.fault_stall_at, stall_s=args.fault_stall_s))
    tracer = (Tracer(capacity=args.trace_capacity)
              if args.trace_out else None)
    eng = build_engine(q, spec_k=args.spec_k, draft=args.draft,
                       tiny=args.tiny,
                       n_slots=min(args.n_slots, args.requests),
                       max_len=args.max_len, paged=args.paged,
                       page_size=args.page_size, n_pages=args.n_pages,
                       prefill_chunk=args.prefill_chunk, mesh=mesh,
                       slo=slo, faults=faults, tracer=tracer,
                       profile=bool(args.profile_dir))
    if args.paged and not eng.paged:
        print("note: model cache layout does not support paging; "
              "serving from the dense cache")
    if args.spec_k > 0 and eng._spec is None:
        print("note: model lacks the span-write decode path; serving "
              "non-speculatively")
    reqs = [Request(rid=i, prompt=q.data.sequence(40_000_000 + i, 12),
                    max_new_tokens=args.new_tokens)
            for i in range(args.requests)]
    if args.deadline_s is not None:
        t_sub = eng.clock()
        for r in reqs:
            r.arrival = t_sub
            r.deadline = t_sub + args.deadline_s
    t0 = time.time()
    with profile_session(args.profile_dir):
        results = eng.serve(reqs)
    dt = time.time() - t0
    tok = sum(len(v) for v in results.values())
    for rid in sorted(results):
        print(f"req {rid}: {results[rid].tolist()}")
    m = eng.metrics()
    print(f"{tok} tokens in {dt:.1f}s ({tok/dt:.1f} tok/s, "
          f"{args.method} int{args.bits} packed)")
    print(f"prefill: {m['prefill_batches']} batches / "
          f"{m['prefill_traces']} traces (buckets {m['buckets']}, "
          f"chunk {m['prefill_chunk'] or 'off'}, "
          f"{m['chunked_admissions']} chunked), "
          f"decode: {m['decode_steps']} steps, "
          f"retraces: {m['retrace_count']}")
    retraced = {k: v for k, v in m["retrace_by_entry"].items() if v}
    if retraced:
        print(f"retraces by entry: {retraced}")
    if m["paged"]:
        print(f"paged: page_size={m['page_size']}, "
              f"peak {m['pages_peak']}/{m['pages_total']} pages "
              f"({m['peak_cache_bytes']/1e6:.2f} MB), "
              f"prefix hits {m['prefix_hits']} "
              f"({m['prefix_hit_tokens']} tokens skipped), "
              f"cow copies {m['cow_copies']}")
    if slo is not None or faults is not None or m["preempted"]:
        print(f"overload: shed {m['shed']} "
              f"(+{m['shed_retried']} retried), "
              f"expired {m['expired']}, truncated {m['truncated']}, "
              f"preempted {m['preempted']}, resumed {m['resumed']}, "
              f"pressure events {m['pressure_events']}")
    if m["faults"] is not None:
        print(f"faults: {m['faults']}")
    if m["spec"]:
        print(f"spec: k={m['spec_k']} draft={m['draft_kind']}, "
              f"accept_rate {m['accept_rate']:.2f}, "
              f"tokens/step {m['tokens_per_step']:.2f}, "
              f"draft share {m['draft_share']:.2f} "
              f"({m['spec_cycles']} cycles, "
              f"{m['draft_steps']} draft steps)")
    if args.trace_out:
        eng.export_trace(args.trace_out)
        print(f"trace: {m['trace']['events']} events "
              f"({m['trace']['dropped']} dropped) -> {args.trace_out} "
              f"(open in ui.perfetto.dev)")
    if args.profile_dir:
        print(f"profile: jax.profiler trace in {args.profile_dir} "
              f"(tensorboard --logdir)")


if __name__ == "__main__":
    main()
