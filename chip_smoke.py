"""Serve FAQ-int4 llama3-8b at its published widths on a TPU, and check it.

    python chip_smoke.py            # one chip: paged serving, then spec k=4
    python chip_smoke.py --chips 4  # tensor-parallel (1, 4) mesh vs one chip

The model is built through the launcher's own path
(``repro.launch.serve.quantize_for_serving`` → ``build_engine``): seeded
random weights, FAQ calibration on synthetic data, packed int4 weights,
then ``ServeEngine.serve`` on the paged KV cache.  Every served request
is checked against the plain f32 reference (``repro.models.reference``)
on logits.  Everything runs in this one process: a chip belongs to one
process at a time.

The script refuses to run off a TPU, and when ``REPRO_KERNEL_MODE`` would
send the kernels through the interpreter or the jnp reference.  Any
failed phase raises, so the exit code is non-zero and the last line is
not printed.  On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The timings printed on the way are host wall-clock seconds of whole
phases, compiles included; none of them is a device metric.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent

# Depth is the one cut.  At 32 layers the bf16 weights alone are
# 16.06 GB, more than the 15.75 GB a v5e chip gives a program, and
# calibration needs them all at once.  At 16 layers the described-chip
# compiles give: fp weights 9.08 GB, packed int4 tree 4.29 GB, int8 self
# draft 3.93 GB, peak during quantization about 12.2 GB.
N_LAYERS = 16
MAX_LEN = 1024
# The reference runs over each sequence zero-padded to this length (it is
# causal, so padding never reaches the rows that are read): one compile.
REF_LEN = MAX_LEN

# Logit check.  The engine runs in bf16 (the configuration's dtype) and
# the reference in f32 at the highest matmul precision, so the engine may
# pick a token whose reference logit is a little below the top one.
# Near the top the logits are about 4-5, where bf16 steps by 2**-5 =
# 0.031; a bf16 forward of this model family measured on the CPU at
# d_model 1024 (2-8 layers) deviates from the f32 reference by 0.016-0.019
# per logit (std, max 0.10), and a wrong pick costs at most two such
# deviations.  TOL leaves room for the TPU's single-pass bf16 products
# inside f32 matmuls.  Activations or KV held at 8-bit floats (2**-4
# relative, about 0.3 per logit here) exceed it, and also pull the share
# of exact reference argmaxes (about 0.95 in bf16) under MIN_ARGMAX_SHARE.
TOL = 0.25
MIN_ARGMAX_SHARE = 0.5


class SmokeFailure(RuntimeError):
    pass


def log(msg):
    print(msg, flush=True)


def require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def check_device(n_chips):
    """The chip, and the compiled kernels, or nothing."""
    import jax

    from repro.kernels.ops import _mode

    devices = jax.devices()
    dev = devices[0]
    require(dev.platform == "tpu",
            f"no TPU: JAX's first device is {dev.platform!r} ({dev})")
    require(len(devices) >= n_chips,
            f"--chips {n_chips} needs {n_chips} devices, JAX has "
            f"{len(devices)}")
    mode = _mode()
    require(mode == "tpu",
            f"kernel mode resolves to {mode!r} (REPRO_KERNEL_MODE set?); "
            "the smoke runs the compiled Pallas kernels only")
    log(f"device: {dev.device_kind} ({dev.platform}), {len(devices)} "
        f"device(s) visible; kernel mode: {mode}")
    return dev, len(devices)


class CompileCounters:
    """Persistent-cache hits/misses and backend compile seconds, from
    JAX's monitoring events."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def line(self):
        return (f"persistent cache hits {self.hits}, misses {self.misses}; "
                f"backend compile {self.compile_s:.1f} s")


def make_requests(data, lengths, new_tokens, base):
    from repro.serve import Request

    prompts = {i: data.sequence(base + i, n) for i, n in enumerate(lengths)}
    reqs = [Request(rid=i, prompt=p, max_new_tokens=new_tokens)
            for i, p in prompts.items()]
    return prompts, reqs


def serve(eng, reqs, label, new_tokens):
    t0 = time.perf_counter()
    out = eng.serve(reqs)
    dt = time.perf_counter() - t0
    n = sum(len(v) for v in out.values())
    require(sorted(out) == sorted(r.rid for r in reqs),
            f"{label}: served {sorted(out)}, asked for "
            f"{sorted(r.rid for r in reqs)}")
    for rid, toks in out.items():
        require(len(toks) == new_tokens,
                f"{label}: request {rid} got {len(toks)} tokens, "
                f"expected {new_tokens}")
    m = eng.metrics()
    log(f"{label}: {len(out)} requests, {n} tokens in {dt:.1f} s host wall "
        f"clock (compiles included); prefill {m['prefill_batches']} "
        f"batches over {m['prefill_traces']} traces, "
        f"{m['chunked_admissions']} chunked admissions, "
        f"{m['decode_steps']} decode steps")
    return out, m


def check_against_reference(q, prompts, out, label):
    """Every emitted token's reference logit within TOL of the reference
    maximum at its position, and most emitted tokens exact argmaxes."""
    from repro.models.reference import greedy_gaps, reference_logits

    cfg = q.model.cfg
    gaps = []
    for rid, prompt in prompts.items():
        seq = np.concatenate([prompt, out[rid]]).astype(np.int32)
        require(len(seq) <= REF_LEN, f"{label}: request {rid} too long")
        padded = np.zeros(REF_LEN, np.int32)
        padded[:len(seq)] = seq
        ref = reference_logits(cfg, q.qparams, padded)
        g = greedy_gaps(ref, len(prompt), out[rid])
        require(np.isfinite(g).all(),
                f"{label}: request {rid} non-finite reference logits")
        gaps.append(g)
    gaps = np.concatenate(gaps)
    share = float(np.mean(gaps == 0.0))
    log(f"{label} vs f32 reference: {len(gaps)} tokens, largest gap to the "
        f"reference max {gaps.max():.4f} (tolerance {TOL}), exact argmax "
        f"share {share:.3f} (floor {MIN_ARGMAX_SHARE})")
    require(gaps.max() <= TOL,
            f"{label}: a token's reference logit is {gaps.max():.4f} below "
            f"the reference max (tolerance {TOL})")
    require(share >= MIN_ARGMAX_SHARE,
            f"{label}: only {share:.3f} of tokens are reference argmaxes")


def one_chip_phases(q):
    from repro.launch.serve import build_engine

    # 6 requests on 4 slots: prompts span the 128..1024 prefill buckets,
    # and the two longer than the 512 chunk go through chunked prefill
    prompts, reqs = make_requests(q.data, (600, 431, 260, 130, 97, 517),
                                  24, 50_000_000)
    eng = build_engine(q, paged=True, n_slots=4, max_len=MAX_LEN)
    require(eng.paged, "engine fell back to the dense cache")
    out, m = serve(eng, reqs, "paged", 24)
    require(m["chunked_admissions"] >= 1, "no chunked prefill happened")
    require(m["prefill_traces"] >= 2, "only one prefill bucket was used")
    del eng
    check_against_reference(q, prompts, out, "paged")

    prompts, reqs = make_requests(q.data, (300, 180, 45), 20, 60_000_000)
    t0 = time.perf_counter()
    eng = build_engine(q, spec_k=4, draft="self-int8", paged=True,
                       n_slots=4, max_len=MAX_LEN)
    log(f"spec: self-int8 draft built in {time.perf_counter() - t0:.1f} s")
    require(eng._spec is not None, "engine declined speculative decoding")
    out, m = serve(eng, reqs, "spec k=4", 20)
    require(m["spec_cycles"] >= 1, "no speculative cycle ran")
    log(f"spec k=4: accept rate {m['accept_rate']:.3f}, "
        f"{m['tokens_per_step']:.2f} tokens per step")
    del eng
    check_against_reference(q, prompts, out, "spec k=4")


def tensor_parallel_phase(q):
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import build_engine

    prompts, reqs = make_requests(q.data, (600, 260, 130, 45), 24,
                                  70_000_000)
    mesh = make_local_mesh(1, 4)
    eng = build_engine(q, paged=True, n_slots=4, max_len=MAX_LEN, mesh=mesh)
    out_tp, _ = serve(eng, reqs, "tensor-parallel (1, 4)", 24)
    del eng
    _, reqs = make_requests(q.data, (600, 260, 130, 45), 24, 70_000_000)
    eng = build_engine(q, paged=True, n_slots=4, max_len=MAX_LEN)
    out_one, _ = serve(eng, reqs, "single device", 24)
    del eng
    check_against_reference(q, prompts, out_tp, "tensor-parallel (1, 4)")
    check_against_reference(q, prompts, out_one, "single device")
    differ = [rid for rid in prompts
              if not np.array_equal(out_tp[rid], out_one[rid])]
    first = {rid: int(np.argmax(out_tp[rid] != out_one[rid]))
             for rid in differ}
    log(f"tensor-parallel vs single-device tokens: "
        f"{'identical' if not differ else 'differ'} "
        f"({len(differ)} of {len(prompts)} requests differ; first "
        f"differing position by request: {first})")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tensor-parallel path on a "
                         "(1, 4) mesh and the single-device run it is "
                         "compared with")
    args = ap.parse_args()

    sys.path.insert(0, str(REPO / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise SmokeFailure(f"run from a checkout of the repository ({e})")
    import jax

    dev, count = check_device(args.chips)

    from repro.configs import ARCHS
    from repro.core.methods import PRESEARCHED_GAMMA, PRESEARCHED_WINDOW
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import quantize_for_serving

    counters = CompileCounters()
    cache_dir = enable_compile_cache()
    log(f"compile cache: {cache_dir}")
    base = ARCHS["llama3-8b"]
    cfg = base.scaled(n_layers=N_LAYERS)
    log(f"model: {cfg.name} at published widths (d_model {cfg.d_model}, "
        f"{cfg.n_heads} q / {cfg.n_kv_heads} kv heads, head_dim "
        f"{cfg.head_dim_}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{cfg.dtype}); FAQ gamma {PRESEARCHED_GAMMA} window "
        f"{PRESEARCHED_WINDOW}, int4 group 64; depth {cfg.n_layers} of "
        f"{base.n_layers} layers (cut: 32 layers of bf16 weights, "
        f"16.06 GB, do not fit the chip's 15.75 GB during calibration)")

    t0 = time.perf_counter()
    q = quantize_for_serving(cfg, method="faq", bits=4, calib_n=16)
    jax.block_until_ready(q.qparams)
    log(f"set-up: init + calibration + FAQ quantization "
        f"{time.perf_counter() - t0:.1f} s host wall clock; "
        f"{counters.line()}")

    if args.chips == 1:
        one_chip_phases(q)
    else:
        tensor_parallel_phase(q)

    log(f"total: {counters.line()}")
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"peak memory in use on {dev}: "
            f"{stats['peak_bytes_in_use'] / 1e9:.2f} GB (memory_stats)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)


if __name__ == "__main__":
    try:
        main()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
