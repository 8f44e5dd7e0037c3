"""Serve-side observability glue (DESIGN.md §17).

The engine's serve loop stays thin (RPR005, module line budget);
everything it does to *observe itself* lives here as free functions
over the engine + request state, same pattern as :mod:`.overload`:

* **request lifecycle** — :func:`enqueued` / :func:`bound` /
  :func:`first_token` / :func:`retired` (+ :func:`preempted` /
  :func:`shed` / :func:`settled`) stamp the request's phase-boundary
  times and emit its swimlane spans: ``queue`` (enqueue → slot bind),
  ``prefill`` (bind → first emitted token, covering chunked
  teacher-forcing), ``decode`` (first token → retire/preempt).  A
  preemption closes the decode span and restarts the clock, so a
  twice-preempted request renders as three queue/prefill/decode
  triples on one row.
* **engine step loop** — :func:`step_span` wraps one phase of the
  loop (admit pass, decode step and its ``prepare`` / ``dispatch`` /
  ``sampler_sync`` parts, spec cycle, ``emit``): it feeds the
  phase-labeled ``serve.step_ms`` histogram, emits an engine-track span
  to the tracer, and with ``profile=True`` runs the phase under a
  ``serve.<phase>`` profiler annotation, on the device trace's clock.
  :func:`annotate` is that annotation alone (the jitted entry points'
  ``TraceCounter`` uses it).
* **pages** — :func:`page_event` marks alloc / copy-on-write / trim /
  pressure instants with a pages-in-use counter track.
* **metrics digest** — :func:`collect_metrics` is the body of
  ``ServeEngine.metrics()``: the frozen key surface existing consumers
  (benches, tests, launch scripts) read, now assembled from the
  registry-backed groups, plus the per-entry-point retrace breakdown
  (``retrace_by_entry``) that de-opaques ``retrace_count``.

Every timestamp is read through ``eng.clock`` — the injectable seam
(RPR006) — and nothing here touches device values: tracing adds zero
host transfers to the serve path (RPR002 + the HLO audit stay clean).
With ``eng.tracer is None`` the lifecycle and page hooks return early;
an engine with neither a tracer nor ``profile=True`` is unobserved, and
the step-loop hooks then cost one attribute check.
"""
from __future__ import annotations

from contextlib import contextmanager, nullcontext

from repro.obs import PID_REQUESTS, annotation


# ---------------------------------------------------------------------------
# Request lifecycle
# ---------------------------------------------------------------------------

def enqueued(eng, req):
    """Request entered the engine's queue (directly or via the arrival
    feed): open its swimlane and stamp the queue-span start."""
    tr = eng.tracer
    if tr is None:
        return
    req.t_enqueue = req.arrival if req.arrival is not None else eng.clock()
    tr.thread_name(PID_REQUESTS, req.rid, f"req {req.rid}")
    tr.instant("arrival", pid=PID_REQUESTS, tid=req.rid, cat="lifecycle",
               args=dict(tenant=req.tenant, resume=bool(req.resume)))


def bound(eng, req, s: int):
    """Slot granted: close the queue span, start the prefill phase."""
    tr = eng.tracer
    if tr is None:
        return
    now = eng.clock()
    if req.t_enqueue is not None:
        tr.complete("queue", req.t_enqueue, now, pid=PID_REQUESTS,
                    tid=req.rid, cat="lifecycle",
                    args=dict(slot=s, resume=bool(req.resume)))
    req.t_bind, req.t_first = now, None


def first_token(eng, req):
    """First emitted token: close the prefill span (for chunked or
    prefix-hit admissions this includes the teacher-forced fill steps —
    the whole time the request occupied a slot without emitting)."""
    req.t_first = eng.clock()
    tr = eng.tracer
    if tr is not None and req.t_bind is not None:
        tr.complete("prefill", req.t_bind, req.t_first, pid=PID_REQUESTS,
                    tid=req.rid, cat="lifecycle")


def fill_done(eng, req):
    """A chunked / prefix-hit admission finished teacher-forcing its
    prompt tail (the next sampled token is real output)."""
    tr = eng.tracer
    if tr is not None:
        tr.instant("fill_done", pid=PID_REQUESTS, tid=req.rid,
                   cat="lifecycle")


def retired(eng, req, outcome: str):
    """Terminal outcome from a slot: close the decode span."""
    tr = eng.tracer
    if tr is None:
        return
    now = eng.clock()
    start = req.t_first if req.t_first is not None else req.t_bind
    if start is not None:
        tr.complete("decode", start, now, pid=PID_REQUESTS, tid=req.rid,
                    cat="lifecycle",
                    args=dict(outcome=outcome,
                              tokens=len(req.out_tokens or [])))
    tr.instant("retire", pid=PID_REQUESTS, tid=req.rid, cat="lifecycle",
               args=dict(outcome=outcome))


def preempted(eng, req, s: int):
    """Evicted mid-flight: close the decode span as a preemption and
    restart the request's queue clock — the resume renders as a fresh
    queue/prefill/decode triple on the same row."""
    tr = eng.tracer
    if tr is None:
        return
    now = eng.clock()
    start = req.t_first if req.t_first is not None else req.t_bind
    if start is not None:
        tr.complete("decode", start, now, pid=PID_REQUESTS, tid=req.rid,
                    cat="lifecycle",
                    args=dict(outcome="preempt",
                              tokens=len(req.out_tokens or [])))
    tr.instant("preempt", pid=PID_REQUESTS, tid=req.rid, cat="lifecycle",
               args=dict(slot=s))
    req.t_enqueue, req.t_bind, req.t_first = now, None, None


def shed(eng, req, retried: bool):
    """Admission-time shed (terminal or retried), tenant-labeled."""
    eng.registry.counter("serve.shed_by_tenant", tenant=req.tenant).inc()
    tr = eng.tracer
    if tr is not None:
        tr.instant("shed_retry" if retried else "shed", pid=PID_REQUESTS,
                   tid=req.rid, cat="lifecycle",
                   args=dict(tenant=req.tenant, retries=req.retries))


def settled(eng, req, outcome: str):
    """Terminal outcome without ever taking a slot (expiry at
    admission, zero-budget completion)."""
    tr = eng.tracer
    if tr is not None:
        tr.instant("settle", pid=PID_REQUESTS, tid=req.rid,
                   cat="lifecycle", args=dict(outcome=outcome))


# ---------------------------------------------------------------------------
# Engine step loop
# ---------------------------------------------------------------------------

@contextmanager
def step_span(eng, phase: str, **args):
    """One step-loop phase: its duration on ``eng.clock`` lands in the
    phase-labeled ``serve.step_ms`` histogram, on the tracer's engine
    track (with a tracer) and, under ``profile=True``, in the device
    trace as the ``serve.<phase>`` annotation.  An unobserved engine
    pays one attribute check."""
    if not eng._observed:
        yield args
        return
    t0 = eng.clock()
    try:
        with annotate(eng, f"serve.{phase}"):
            yield args
    finally:
        t1 = eng.clock()
        if eng.tracer is not None:
            eng.tracer.complete(phase, t0, t1, cat="step",
                                args=args or None)
        eng.registry.histogram("serve.step_ms",
                               phase=phase).observe((t1 - t0) * 1e3)


def annotate(eng, name: str):
    """Named ``jax.profiler`` region when the engine profiles, else a
    no-op context."""
    if eng is not None and eng._profile:
        return annotation(name)
    return nullcontext()


def page_event(eng, kind: str, **args):
    """Page-machinery instant (alloc / cow / trim / pressure) plus a
    pages-in-use counter sample for the Perfetto counter track."""
    tr = eng.tracer
    if tr is None:
        return
    tr.instant(kind, cat="pages", args=args or None)
    if eng.paged:
        tr.counter("pages_in_use",
                   {"pages": eng.pool.pages_in_use()})


def export_trace(eng, path) -> str:
    """Write the engine's trace as Chrome/Perfetto trace_event JSON."""
    if eng.tracer is None:
        raise ValueError("engine was built without a tracer — pass "
                         "tracer=repro.obs.Tracer() to ServeEngine")
    return eng.tracer.export(path)


# ---------------------------------------------------------------------------
# Metrics digest (the body of ServeEngine.metrics())
# ---------------------------------------------------------------------------

def collect_metrics(eng) -> dict:
    """Assemble the engine's frozen metrics surface from the
    registry-backed groups.  Key set is a strict superset of the
    pre-registry dict (``tests/test_obs.py`` guards the frozen part);
    ``retrace_by_entry`` names which jitted body retraced instead of
    one summed integer."""
    m = dict(eng._m)
    entries = [("prefill_admit", eng._prefill_admit),
               ("admit_one", eng._admit_one),
               ("prefill1", eng._prefill1),
               ("decode", eng._decode)]
    m["prefill_calls"] = (eng._prefill_admit.calls
                          + eng._admit_one.calls + eng._prefill1.calls)
    m["prefill_traces"] = eng._prefill_admit.traces
    m["prefill_traces_single"] = (eng._admit_one.traces
                                  + eng._prefill1.traces)
    m["decode_traces"] = eng._decode.traces
    m["paged"] = eng.paged
    m["mesh"] = dict(eng.mesh.shape) if eng.mesh is not None else None
    m["prefill_chunk"] = eng.prefill_chunk or 0
    if eng.paged:
        entries += [("prefill_paged", eng._prefill_paged),
                    ("decode_paged", eng._decode_paged)]
        m["prefill_calls"] += eng._prefill_paged.calls
        m["prefill_traces"] += eng._prefill_paged.traces
        m["decode_traces"] += eng._decode_paged.traces
        m["page_size"] = eng.page_size
        m["pages_total"] = eng.n_pages - 1       # minus the trash page
        m["pages_in_use"] = eng.pool.pages_in_use()
        m["pages_peak"] = eng.pool.in_use_peak
        m["page_bytes"] = eng.page_bytes()
        # peak_cache_bytes counts *pinned* pages — the provisioning
        # signal a deployment would size n_pages from.  The engine's
        # actual device allocation is alloc_cache_bytes (the full
        # pool; with the deadlock-free default sizing that exceeds
        # the dense cache — pass n_pages to provision to peak+slack)
        m["peak_cache_bytes"] = eng.pool.in_use_peak * eng.page_bytes()
        m["alloc_cache_bytes"] = sum(leaf.nbytes
                                     for leaf in eng._store.values())
        m["page_allocs"] = eng.pool.alloc_count
        m["cow_copies"] = eng.pool.cow_copies
        m["page_evictions"] = eng.pool.evictions
        m["prefix_index_blocks"] = len(eng.pool.index)
        m["prefix_lookups"] = eng.pool.prefix_lookups
        m["prefix_block_hits"] = eng.pool.prefix_block_hits
    m["retrace_count"] = sum(max(0, c.traces - 1) for _, c in entries)
    by_entry = {name: max(0, c.traces - 1) for name, c in entries}
    m["buckets"] = list(eng.buckets)
    m["faults"] = (eng.faults.metrics()
                   if eng.faults is not None else None)
    m["spec"] = eng._spec is not None
    if eng._spec is not None:
        m.update(eng._spec.metrics())
        m["accept_rate"] = (m["accepted_tokens"]
                            / max(m["proposed_tokens"], 1))
        # share of emitted tokens that the draft proposed (the rest
        # are prefill first-tokens and verify corrections/bonuses);
        # uses the emitted count, not acceptances — a burst cut by a
        # budget or deadline accepts more than it emits
        m["draft_share"] = (m["emitted_draft_tokens"]
                            / max(m["tokens_generated"], 1))
        by_entry.update({name: max(0, c.traces - 1)
                         for name, c in eng._spec.trace_entries()})
    m["retrace_by_entry"] = by_entry
    m["tokens_per_step"] = (m["tokens_generated"]
                            / max(m["decode_steps"], 1))
    dt = m["serve_time_s"]
    m["tokens_per_s"] = (m["tokens_generated"] / dt) if dt > 0 else 0.0
    if eng.tracer is not None:
        m["trace"] = dict(events=eng.tracer.recorded,
                          dropped=eng.tracer.dropped,
                          capacity=eng.tracer.capacity)
    return m
