"""Run one benchmark cell on the chip and print its result line.

    python3 -m bench.run --workload stablelm-12b.offline-batch --seed 7 \
        --seconds 30 --trace 0

Everything a cell is made of is found by its name in ``BENCHMARK.json``:
``bench/workloads/<cell>.json`` (engine settings, rate, check limits),
``bench/configs/<config>.json`` (sizes) and ``bench/traffic/<mix>.json``
(the mix's parameters); each metric is read by
``bench/metrics/<metric>.py``.  One process per run: set-up (seeded
weights, calibration, FAQ int4 packing, engine build, warm-up of the
cell's own shapes), the measured window, then the comparison with the
plain reference that decides ``correct``.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` ``breakdown``), and last of all ``checks``: each number
compared with its limit.  Off a TPU, with fewer chips than the cell
asks for, or with ``REPRO_KERNEL_MODE`` set, the run exits non-zero and
prints no result.  ``--control 1`` puts the reference in lower precision
in the program's place (int3 tables, float8 tokens), so that the same
check reads the control (used to set the limits; the benchmark's own
runs leave it off).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse                                              # noqa: E402
import gc                                                    # noqa: E402
import importlib.util                                        # noqa: E402
import json                                                  # noqa: E402
import os                                                    # noqa: E402
import shutil                                                # noqa: E402
import sys                                                   # noqa: E402
import traceback                                             # noqa: E402

from bench import serving, traffic                           # noqa: E402

REPO = serving.REPO
CACHE_DIR = REPO / ".jax_cache"
RUNS_DIR = REPO / ".bench_runs"
CLOCK = serving.CLOCK


class NoDevice(RuntimeError):
    pass


def log(msg):
    print(msg, flush=True)


def check_device(chips: int):
    """The TPU with the cell's chips and the compiled kernels, or
    :class:`NoDevice`."""
    if os.environ.get("REPRO_KERNEL_MODE"):
        raise NoDevice("REPRO_KERNEL_MODE is set; the benchmark runs the "
                       "compiled Pallas kernels only")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX's first device is {devices[0]}")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX has "
                       f"{len(devices)}")
    return devices[0], len(devices)


class TraceWindow:
    """Profiles ``seconds`` from the middle of the measured window,
    started and stopped from the feed's poll, with the host span that
    marks the traced window for :mod:`bench.trace`."""

    def __init__(self, out_dir, window_s: float, seconds: float):
        self.out_dir = str(out_dir)
        self.offset = max(0.0, (window_s - seconds) / 2)
        self.seconds = min(seconds, window_s)
        self.start = self.lo = self.hi = None
        self._span = None

    def __call__(self, now):
        import jax

        from bench.trace import WINDOW_SPAN

        if self.start is None:
            self.start = now + self.offset
        if self._span is None and self.lo is None and now >= self.start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._span.__enter__()
            self.lo = CLOCK()
        elif self._span is not None and now >= self.lo + self.seconds:
            self.stop()

    def stop(self):
        import jax

        if self._span is None:
            return
        self.hi = CLOCK()
        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()


class RunData:
    """What the metric readers read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def read_metric(name: str, run: RunData):
    path = serving.BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def execute(cell: dict, seed: int, seconds: float, trace: bool,
            control: bool, device=None) -> dict:
    """Set up, drive the window, check, and return the result object.
    ``device`` is the chip (None where the caller drives it without one,
    as the self-tests do on the CPU)."""
    import jax

    from repro.configs.base import ModelConfig

    work, name = cell["workload"], cell["name"]
    counter = serving.CompileCounter()
    cfg = ModelConfig(**cell["config"]["model"])
    sizes = serving.model_sizes(cfg)
    rng_warm, rng_sample = traffic.seed_streams(seed, 4)[2:]

    t = CLOCK()
    q = serving.quantize(cfg)
    quantize_s = CLOCK() - t
    t = CLOCK()
    eng = serving.build(q, work, annotate=trace)
    steplog = serving.StepLog(eng._stepper, annotate=trace)
    built_s = CLOCK() - t
    first = []
    if cell["mix"]["arrivals"] == "backlog":
        items = traffic.plan(cell["mix"], rate=None, seconds=seconds,
                             seed=seed, vocab=cfg.vocab_size,
                             n_slots=eng.n_slots)
        first = [len(it.prompt) for it in items[:eng.n_slots]]
    t = CLOCK()
    warm = serving.warm_up(eng, cfg.vocab_size, rng_warm, first,
                           work.get("warm_groups"))
    warm_s = CLOCK() - t
    steplog.prefills.clear()
    steplog.decodes.clear()
    log(f"set-up: quantize {quantize_s:.3f} s, engine {built_s:.3f} s, "
        f"warm-up {warm_s:.3f} s (buckets {warm['buckets']}, chunk "
        f"{eng.prefill_chunk}, first admission {first}, scatter groups "
        f"{warm['groups']}); compiles so far {counter.snapshot()}")

    tw = None
    if trace:
        tdir = RUNS_DIR / "trace" / name
        shutil.rmtree(tdir, ignore_errors=True)
        tw = TraceWindow(tdir, seconds, work["trace_seconds"])
    window = serving.Window(eng, cell, seed, seconds, tracer=tw)
    reg0 = eng.registry.snapshot()
    c0 = counter.snapshot()
    setup_s = CLOCK() - T_PROCESS
    window.run()
    if tw is not None:
        tw.stop()
    c1 = counter.snapshot()
    reg = eng.registry.delta(reg0)
    in_window = {k: c1[k] - c0[k] for k in c1}
    log(f"window: {seconds} s measured, {window.t_end - window.t0:.3f} s "
        f"to the last request's end; compiles inside: {in_window}")

    dev_info = {"platform": jax.devices()[0].platform,
                "kind": jax.devices()[0].device_kind,
                "count": len(jax.devices())}
    stats = (device.memory_stats() or {}) if device is not None else {}
    dev_info["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    reduced = None
    if trace:
        from bench import trace as trace_mod

        planes = trace_mod.load(str(tdir))
        if planes is not None:
            for line in trace_mod.outline(planes)[:40]:
                log(f"trace: {line}")
            reduced = trace_mod.reduce(
                planes, work["programs"],
                ("bench.admit", "bench.decode_step", "decode_paged",
                 "prefill_paged"))
        shutil.rmtree(tdir, ignore_errors=True)
        if reduced is not None:
            log(f"trace: host window {reduced['window']}, device ops "
                f"{reduced['device_extent']}, programs "
                f"{reduced['programs']}")
            dev_info["busy_s"] = reduced["busy_s"]
            dev_info["window_s"] = reduced["window_s"]

    # the program's state goes before the reference runs
    chunk = eng.prefill_chunk
    tables = serving.served_tables(q.qparams)
    window.eng = None
    eng = q = None
    gc.collect()

    recs = window.window_recs()
    failed = [r for r in recs
              if _failed(r, window.backlog, window.t0 + seconds)]
    chk = work["check"]
    sample = serving.pick_sample(recs, chunk, rng_sample,
                                 chk["sample_tokens"], chk["sample_requests"])
    t = CLOCK()
    cmp = serving.compare(sizes, tables, sample, control)
    tables = None
    log(f"reference: {cmp['requests']} requests, {cmp['tokens']} served "
        f"tokens compared in {CLOCK() - t:.3f} s; prompts "
        f"{[r.plen for r in sample]}; worst table {cmp['worst_table']}")
    late = window.lateness()
    log(f"generator lateness: {len(late)} releases, p50 "
        f"{traffic.percentile(late, 50)} s, max "
        f"{max(late) if late else None} s")

    run = RunData(cell=cell, window=window, recs=recs, steplog=steplog,
                  reg=reg, trace=reduced, sizes=sizes, seconds=seconds,
                  setup={"setup_s": setup_s, "quantize_s": quantize_s},
                  traced=(tw.lo, tw.hi) if tw is not None else None,
                  device=dev_info)
    metrics = {}
    for m in serving.metrics_for(cell["spec"], name, trace):
        v = read_metric(m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    checks = {"max_logit_gap": {"value": cmp["max_gap"],
                                "limit": chk["max_logit_gap"]},
              "table_err_vs_rtn": {"value": cmp["table_err"],
                                   "limit": chk["max_table_err_vs_rtn"]},
              "failed_requests": {"value": len(failed), "limit": 0},
              "compared_tokens": {"value": cmp["tokens"],
                                  "limit": chk["min_compared_tokens"]}}
    correct = (cmp["max_gap"] <= chk["max_logit_gap"]
               and cmp["table_err"] is not None
               and cmp["table_err"] <= chk["max_table_err_vs_rtn"]
               and not failed
               and cmp["tokens"] >= chk["min_compared_tokens"])
    result = {"correct": bool(correct), "attempted": len(recs),
              "failed": len(failed), "metrics": metrics, "device": dev_info}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result


def _failed(rec, backlog: bool, window_end: float) -> bool:
    """A window request fails where it ends with fewer tokens than it
    asked for: in the open loop, where every request runs to its end,
    at all; in a backlog, whose requests have the window's end as their
    deadline, before that end.  A backlog request still queued, or still
    filling its prompt, when the window closes is cut there: late, not
    wrong."""
    short = len(rec.tokens) != rec.max_new
    if backlog:
        return short and (rec.done is None or rec.done <= window_end)
    return short


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(REPO / "src"))
    cell = serving.load_cell(args.workload)
    try:
        device, _ = check_device(cell["entry"]["chips"])
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    import jax

    from repro.kernels.ops import _mode
    from repro.launch.compile_cache import enable_compile_cache

    if _mode() != "tpu":
        print(f"bench: kernel mode {_mode()!r}, not the compiled kernels",
              file=sys.stderr, flush=True)
        return 3
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"device: {device.device_kind}; compile cache "
        f"{enable_compile_cache()}")
    result = execute(cell, args.seed, args.seconds, bool(args.trace),
                     bool(args.control), device)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:
        traceback.print_exc()
        sys.exit(1)
