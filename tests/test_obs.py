"""Observability layer (DESIGN.md §17): metrics registry semantics, the
frozen engine-metrics surface, span tracing determinism, and the
bounded trace ring under overload."""
import json

import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models.registry import build_model
from repro.obs import (DEFAULT_MS_EDGES, Histogram, MetricsRegistry,
                       Tracer, check_span_nesting, dist_ms,
                       never_nan_percentile, validate_trace)
from repro.serve import (FaultConfig, FaultInjector, Request, Scheduler,
                         ServeEngine, TrafficConfig, make_trace)


@pytest.fixture(scope="module")
def fp_setup():
    cfg = ARCHS["llama3-8b"].tiny()
    m = build_model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _ticker(dt=0.001):
    tick = {"t": 0.0}

    def clock():
        tick["t"] += dt
        return tick["t"]
    return clock


# -- registry primitives ------------------------------------------------------

def test_counter_gauge_histogram_snapshot_delta():
    r = MetricsRegistry()
    c = r.counter("serve.tokens")
    c.inc(5)
    r.gauge("pool.in_use").set(7)
    h = r.histogram("serve.step_ms")
    for x in (0.5, 3.0, 30.0, 3000.0):
        h.observe(x)
    snap = r.snapshot()
    assert snap["serve.tokens"] == 5
    assert snap["pool.in_use"] == 7
    assert snap["serve.step_ms"]["count"] == 4
    c.inc(2)
    h.observe(1.0)
    r.gauge("pool.in_use").set(3)
    d = r.delta(snap)
    # counters and histograms subtract; gauges report current
    assert d["serve.tokens"] == 2
    assert d["pool.in_use"] == 3
    assert d["serve.step_ms"]["count"] == 1
    assert sum(d["serve.step_ms"]["counts"]) == 1


def test_labels_qualify_names_and_kinds_clash():
    r = MetricsRegistry()
    r.counter("serve.shed_by_tenant", tenant="a").inc()
    r.counter("serve.shed_by_tenant", tenant="b").inc(2)
    snap = r.snapshot()
    assert snap["serve.shed_by_tenant{tenant=a}"] == 1
    assert snap["serve.shed_by_tenant{tenant=b}"] == 2
    with pytest.raises(TypeError):
        r.gauge("serve.shed_by_tenant", tenant="a")


def test_metric_group_mapping_protocol_and_rebind():
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    g = r1.group("faults").init(stalls=0, preempts=0)
    g["stalls"] += 3
    assert dict(g) == {"stalls": 3, "preempts": 0}
    assert "stalls" in g and len(g) == 2
    assert sorted(g.keys()) == ["preempts", "stalls"]
    g.rebind(r2)
    g["preempts"] += 1
    assert r2.snapshot()["faults.preempts"] == 1
    assert r2.snapshot()["faults.stalls"] == 3  # value survives the move


def test_counter_preserves_value_type():
    r = MetricsRegistry()
    g = r.group("serve").init(steps=0, serve_time_s=0.0)
    g["steps"] += 1
    g["serve_time_s"] += 0.25
    assert isinstance(g["steps"], int)
    assert isinstance(g["serve_time_s"], float)


# -- shared percentile math ---------------------------------------------------

def test_never_nan_percentile_hardening():
    assert never_nan_percentile([], 99) == 0.0
    assert never_nan_percentile([float("nan"), float("inf")], 50) == 0.0
    xs = list(range(1, 101))
    assert never_nan_percentile(xs, 50) == float(np.percentile(xs, 50))


def test_dist_ms_frozen_shape():
    # the exact shape loadgen.summarize always reported
    assert dist_ms([]) == dict(p50=0.0, p95=0.0, p99=0.0, mean=0.0, n=0)
    d = dist_ms([0.1, 0.2, 0.3])
    assert set(d) == {"p50", "p95", "p99", "mean", "n"} and d["n"] == 3
    assert d["p50"] == pytest.approx(200.0)


def test_histogram_buckets_and_percentile():
    h = Histogram.from_samples([0.5, 2.0, 8.0, 40.0, 999.0, 50_000.0])
    s = h.snapshot()
    assert s["count"] == 6 and s["counts"][-1] == 1     # overflow bucket
    assert len(s["counts"]) == len(DEFAULT_MS_EDGES) + 1
    assert 0.0 < h.percentile(50) <= 1000.0
    assert h.percentile(0) >= 0.0
    with pytest.raises(ValueError):
        Histogram(edges=(5.0, 1.0))


# -- frozen metrics surface ---------------------------------------------------

FROZEN_SUMMARY_KEYS = {
    "requests", "completed", "expired", "truncated", "shed", "preempted",
    "resumed", "tokens_generated", "tokens_per_s", "tokens_per_step",
    "tokens_per_step_by_request", "spec",
}

FROZEN_METRIC_KEYS = {
    "tokens_generated", "decode_steps", "prefill_batches", "completed",
    "expired", "truncated", "shed", "shed_retried", "preempted", "resumed",
    "admitted", "pressure_events", "serve_time_s", "prefill_calls",
    "prefill_traces", "decode_traces", "retrace_count", "paged", "buckets",
    "spec", "faults", "prefill_chunk", "chunked_admissions",
    "tokens_per_step", "tokens_per_s",
}


def test_engine_metrics_keys_and_summary_frozen(fp_setup):
    cfg, m, params = fp_setup
    eng = ServeEngine(m, params, n_slots=2, max_len=64)
    sched = Scheduler(eng)
    for i in range(3):
        sched.submit(Request(rid=i, prompt=np.arange(1, 7, dtype=np.int32),
                             max_new_tokens=4))
    res = sched.run()
    mm = eng.metrics()
    missing = FROZEN_METRIC_KEYS - set(mm)
    assert not missing, f"frozen metrics keys went missing: {missing}"
    assert FROZEN_SUMMARY_KEYS == set(res.summary)
    assert res.summary["completed"] == 3
    assert res.summary["tokens_generated"] == 12
    # the registry delta rides along, qualified-name keyed
    assert res.registry_delta["serve.completed"] == 3
    assert res.registry_delta["serve.tokens_generated"] == 12
    # per-entry retrace breakdown sums to the old opaque counter
    assert sum(mm["retrace_by_entry"].values()) == mm["retrace_count"]


def test_summary_is_delta_not_lifetime(fp_setup):
    cfg, m, params = fp_setup
    eng = ServeEngine(m, params, n_slots=2, max_len=64)
    sched = Scheduler(eng)
    for run in range(2):
        sched.submit(Request(rid=run, prompt=np.arange(1, 5, dtype=np.int32),
                             max_new_tokens=3))
        s = sched.run().summary
        assert s["completed"] == 1 and s["tokens_generated"] == 3


# -- span tracing -------------------------------------------------------------

def _traced_run(cfg, m, params, *, capacity=8192):
    tracer = Tracer(capacity=capacity)
    eng = ServeEngine(m, params, n_slots=2, max_len=64,
                      clock=_ticker(), tracer=tracer)
    tcfg = TrafficConfig(n_requests=8, rate=100.0, max_new_tokens=4,
                         prompt_len_median=6, prompt_len_max=20,
                         vocab_size=cfg.vocab_size, seed=7)
    Scheduler(eng).run_traffic(make_trace(tcfg))
    return eng, tracer


def test_trace_export_deterministic_bytes(tmp_path, fp_setup):
    cfg, m, params = fp_setup
    paths = []
    for i in range(2):
        eng, _ = _traced_run(cfg, m, params)
        p = tmp_path / f"trace{i}.json"
        eng.export_trace(p)
        paths.append(p)
    b0, b1 = paths[0].read_bytes(), paths[1].read_bytes()
    assert b0 == b1, "fake-clock trace export must be byte-identical"
    obj = json.loads(b0)
    assert validate_trace(obj) == []
    names = {e["name"] for e in obj["traceEvents"]}
    assert {"queue", "prefill", "decode", "arrival", "retire"} <= names


def test_spans_nest_across_preempt_resume(fp_setup):
    """A forced preemption closes the decode span and the resume opens a
    fresh queue/prefill/decode triple; all spans stay balanced."""
    cfg, m, params = fp_setup
    tracer = Tracer()
    faults = FaultInjector(FaultConfig(preempt_at=(3,)))
    eng = ServeEngine(m, params, n_slots=2, max_len=64, clock=_ticker(),
                      tracer=tracer, faults=faults)
    reqs = [Request(rid=i, prompt=np.arange(1, 8, dtype=np.int32),
                    max_new_tokens=6) for i in range(3)]
    eng.serve(reqs)
    assert eng.metrics()["preempted"] >= 1
    events = tracer.events()
    assert check_span_nesting(events) == []
    names = [e["name"] for e in events]
    assert "preempt" in names
    # the preempted request's row shows two queue spans (original +
    # resume) and its decode span carries the preempt outcome
    pre = [e for e in events if e["name"] == "preempt"][0]
    rid = pre["tid"]
    row = [e for e in events if e.get("tid") == rid]
    assert sum(1 for e in row if e["name"] == "queue") == 2
    outcomes = [e.get("args", {}).get("outcome")
                for e in row if e["name"] == "decode"]
    assert "preempt" in outcomes


def test_spans_cover_chunked_prefill(fp_setup):
    cfg, m, params = fp_setup
    tracer = Tracer()
    eng = ServeEngine(m, params, n_slots=2, max_len=64, clock=_ticker(),
                      tracer=tracer, prefill_chunk=8)
    long_prompt = (np.arange(40) % cfg.vocab_size + 1).astype(np.int32)
    eng.serve([Request(rid=0, prompt=long_prompt, max_new_tokens=4)])
    assert eng.metrics()["chunked_admissions"] == 1
    events = tracer.events()
    assert check_span_nesting(events) == []
    names = [e["name"] for e in events]
    assert "chunked_admit" in names and "fill_done" in names
    # the prefill span covers the teacher-forced fill: it ends at the
    # first emitted token, after fill_done
    fill_done = [e for e in events if e["name"] == "fill_done"][0]
    prefill = [e for e in events if e["name"] == "prefill"][0]
    assert prefill["ts"] + prefill["dur"] >= fill_done["ts"]


def test_trace_ring_bounded_under_storm(fp_setup):
    cfg, m, params = fp_setup
    eng, tracer = _traced_run(cfg, m, params, capacity=64)
    assert len(tracer.events()) <= 64
    assert tracer.dropped > 0
    obj = tracer.to_json()
    assert validate_trace(obj) == []
    assert obj["otherData"]["dropped"] == tracer.dropped
    # ring eviction drops whole complete events, never halves: nesting
    # of what remains is still balanced
    assert check_span_nesting(tracer.events()) == []


def test_step_spans_and_histogram(fp_setup):
    cfg, m, params = fp_setup
    tracer = Tracer()
    eng = ServeEngine(m, params, n_slots=2, max_len=64, clock=_ticker(),
                      tracer=tracer)
    eng.serve([Request(rid=0, prompt=np.arange(1, 6, dtype=np.int32),
                       max_new_tokens=4)])
    phases = {e["name"] for e in tracer.events() if e.get("cat") == "step"}
    assert {"admit", "decode_step", "sampler_sync"} <= phases
    snap = eng.registry.snapshot()
    assert snap["serve.step_ms{phase=decode_step}"]["count"] \
        == eng.metrics()["decode_steps"]


def test_untraced_engine_has_no_trace_key(fp_setup):
    cfg, m, params = fp_setup
    eng = ServeEngine(m, params, n_slots=1, max_len=64)
    eng.serve([Request(rid=0, prompt=np.arange(1, 5, dtype=np.int32),
                       max_new_tokens=2)])
    assert "trace" not in eng.metrics()
    with pytest.raises(ValueError):
        eng.export_trace("/tmp/never-written.json")


def test_compile_events_and_retrace_by_entry(fp_setup):
    cfg, m, params = fp_setup
    tracer = Tracer()
    eng = ServeEngine(m, params, n_slots=2, max_len=64, clock=_ticker(),
                      tracer=tracer)
    eng.serve([Request(rid=i, prompt=np.arange(1, 6 + i, dtype=np.int32),
                       max_new_tokens=3) for i in range(2)])
    jit_events = [e for e in tracer.events() if e.get("cat") == "jit"]
    assert any(e["name"] == "compile" for e in jit_events)
    entries = {e["args"]["entry"] for e in jit_events}
    assert "decode" in entries


# -- step phases: one helper, three sinks -------------------------------------

PLAIN_STEP_PHASES = ("prepare", "dispatch", "sampler_sync", "emit")


def _phase_run(m, params, paged, **kw):
    eng = ServeEngine(m, params, n_slots=2, max_len=64, paged=paged, **kw)
    eng.serve([Request(rid=i, prompt=np.arange(1, 6 + i, dtype=np.int32),
                       max_new_tokens=4) for i in range(3)])
    return eng


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_plain_step_phases_once_per_step(fp_setup, paged):
    """prepare, dispatch, sampler_sync and emit each run once per plain
    decode step, inside decode_step where they belong, on both
    steppers."""
    cfg, m, params = fp_setup
    tracer = Tracer()
    eng = _phase_run(m, params, paged, clock=_ticker(), tracer=tracer)
    assert eng.paged == paged
    steps = eng.metrics()["decode_steps"]
    assert steps > 0
    snap = eng.registry.snapshot()
    for phase in PLAIN_STEP_PHASES + ("decode_step",):
        assert snap[f"serve.step_ms{{phase={phase}}}"]["count"] == steps
    events = tracer.events()
    spans = [e for e in events if e.get("cat") == "step"]
    for phase in PLAIN_STEP_PHASES:
        assert sum(e["name"] == phase for e in spans) == steps
    assert check_span_nesting(events) == []
    # prepare and dispatch nest inside decode_step; emit follows it
    outer = [e for e in spans if e["name"] == "decode_step"]
    for name in ("prepare", "dispatch"):
        for e in (e for e in spans if e["name"] == name):
            assert any(o["ts"] <= e["ts"]
                       and e["ts"] + e["dur"] <= o["ts"] + o["dur"]
                       for o in outer)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_unobserved_engine_records_no_step_phases(fp_setup, paged):
    cfg, m, params = fp_setup
    eng = _phase_run(m, params, paged)
    assert eng.metrics()["decode_steps"] > 0
    assert not any(k.startswith("serve.step_ms")
                   for k in eng.registry.snapshot())


def _host_names(path):
    from jax.profiler import ProfileData

    files = sorted(path.rglob("*.xplane.pb"))
    assert files
    pd = ProfileData.from_file(str(files[-1]))
    return {ev.name for plane in pd.planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events}


@pytest.mark.parametrize("profile", [True, False], ids=["profile", "plain"])
def test_profile_puts_step_phases_in_the_host_plane(tmp_path, fp_setup,
                                                    profile):
    """With profile=True the step phases and the jitted dispatch land in
    the profiler's host plane as named annotations (and feed the
    phase histogram without a tracer); with profile=False none do."""
    cfg, m, params = fp_setup
    eng = ServeEngine(m, params, n_slots=2, max_len=64, paged=True,
                      profile=profile)
    reqs = [Request(rid=0, prompt=np.arange(1, 6, dtype=np.int32),
                    max_new_tokens=3)]
    eng.serve(reqs)                       # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.serve([Request(rid=1, prompt=np.arange(1, 6, dtype=np.int32),
                           max_new_tokens=3)])
    finally:
        jax.profiler.stop_trace()
    names = _host_names(tmp_path)
    serve_names = {n for n in names if n.startswith("serve.")}
    if profile:
        assert {f"serve.{p}" for p in PLAIN_STEP_PHASES
                + ("admit", "decode_step")} <= serve_names
        assert "decode_paged" in names
        assert any(k.startswith("serve.step_ms")
                   for k in eng.registry.snapshot())
    else:
        assert not serve_names
        assert "decode_paged" not in names
