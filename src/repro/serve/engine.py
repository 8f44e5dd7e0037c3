"""Bucketed continuous-batching engine over FAQ-quantized weights.

Slot-based continuous batching: bucketed batched prefill (admission
compiles at most once per length bucket), a jitted on-device batched
sampler fused with the decode step (one int32 transferred per slot per
step), and inactive-slot masking inside the jitted decode wrapper so a
draining batch can never advance a dead slot's cache length past
``max_len``.

The engine itself is a thin orchestrator over three composable parts
(DESIGN.md §14): the :class:`.slots.SlotTable` (host-side slot state),
an :class:`.admission.AdmissionPipeline` (bucketed / paged prefix-hit /
single-request admission strategies), and a :mod:`.stepper` (the jitted
prefill/decode/spec cores per cache kind).  Dense and paged serving run
the *same* ``serve()`` loop — the cache kind only changes which stepper
is plugged in.

**Chunked prefill** (``prefill_chunk``, default ``"auto"``): a prompt
longer than the chunk is admitted as its first chunk through one
bucket-sized batched prefill; the remainder teacher-forces through the
batched decode step, one token per step, interleaved with every other
slot's decoding — a long admission can never stall the decode batch for
more than one chunk.  ``"auto"`` picks the second-largest bucket;
``0``/``None`` restores monolithic prefill.  Greedy outputs are
token-for-token identical either way (teacher-forced decode writes the
same KV as prefill at the same positions).

The weights are the *packed* QuantizedTensor representation — every
matmul runs through the dequant-matmul kernel path, i.e. the paper's
deployment format is the first-class serving path, not a simulation.
Models whose ``prefill`` does not accept ``prompt_len`` (hymba's ring
buffer, recurrent xlstm) fall back to per-request exact-length prefill
through :func:`.cache_ops.write_slot` — only the compile-per-length
cost remains.  ``paged=True`` swaps in the page-pool stepper with
shared-prefix reuse (:mod:`.pages`, DESIGN.md §10); ``spec=SpecConfig``
turns decode steps into speculative draft+verify cycles (:mod:`.spec`,
DESIGN.md §12) with greedy output unchanged.

``clock=`` injects the deadline clock (default ``time.time``) — one
seam for EDF-expiry tests and the open-loop traffic harness
(:mod:`.loadgen`) instead of per-test monkeypatching.  ``serve()`` also
accepts a ``feed`` (an :class:`.loadgen.ArrivalFeed` or anything with
``poll``/``pending``/``next_time``): requests are then admitted as
their arrival times pass instead of all up front.
"""
from __future__ import annotations

import inspect
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import (SERVE_DECODE_RULES, SERVE_PREFILL_RULES,
                                 axis_rules, shard_hint, tree_hint,
                                 tree_shardings)
from repro.obs import MetricsRegistry
from . import instrument
from .admission import AdmissionPipeline, ServeRun
from .buckets import bucket_for, default_buckets
from .cache_ops import truncate_slot
from .overload import (SLOAdmission, never_admissible, pick_victim,
                       preempt_slot, relieve_pressure, shed_request)
from .pages import PagePressure
from .sampler import policy_in_use, sample_tokens
from .slots import Request, SlotTable, TraceCounter, empty_tokens
from .stepper import DenseStepper, PagedStepper

__all__ = ["Request", "ServeEngine", "TraceCounter"]


def _empty() -> np.ndarray:
    return empty_tokens()


class ServeEngine:
    def __init__(self, model, params, *, n_slots: int = 4,
                 max_len: int = 512, buckets=None, rng_seed: int = 0,
                 paged: bool = False, page_size: int = 16,
                 n_pages: Optional[int] = None, spec=None, mesh=None,
                 prefill_chunk="auto", clock=None, slo=None, faults=None,
                 tracer=None, registry=None, profile: bool = False):
        self.model = model
        self.mesh = mesh
        self.clock = clock if clock is not None else time.time  # repro: noqa[RPR006] the seam's own wall-clock default
        # observability (DESIGN.md §17): one registry for every
        # component's counters; an optional span tracer whose clock is
        # re-pointed at the engine's seam (fake-clock determinism).
        # Must exist before the stepper/spec/overload components so
        # their groups land in it.
        self.registry = registry if registry is not None else MetricsRegistry()
        self.tracer = tracer
        if tracer is not None:
            tracer.clock = self.clock
        self._profile = bool(profile)
        # the step-loop hooks' one check (instrument.step_span)
        self._observed = tracer is not None or self._profile
        # overload seams (DESIGN.md §16): slo is an SLOConfig or
        # SLOAdmission (shed gate + tenant quotas), faults a
        # FaultInjector consulted by the pool and the serve loop.  Both
        # must bind before the stepper so the page pool sees them.
        self.faults = faults
        self.slo = (slo if slo is None or isinstance(slo, SLOAdmission)
                    else SLOAdmission(slo))
        if self.faults is not None:
            self.faults.counts.rebind(self.registry)
        if self.slo is not None:
            self.slo.bind_registry(self.registry)
        # serve-time sharding (DESIGN.md §13): with a mesh, weights are
        # laid out tensor-parallel once at admission-to-engine time —
        # QuantizedTensor codes *and* scales split on the same logical
        # axes — and every jitted entry point traces under its regime's
        # rule table (prefill vs decode).  mesh=None is the single-device
        # fast path: every placement/hint helper below degrades to
        # identity and the engine behaves exactly as before.
        self._cache_axes = (model.cache_axes()
                            if hasattr(model, "cache_axes") else None)
        self.params = self._place(params, model.param_axes()
                                  if hasattr(model, "param_axes") else None)
        self.n_slots = n_slots
        self.max_len = max_len
        self.cfg = model.cfg
        if buckets is None:
            self.buckets = default_buckets(max_len)
        else:
            # the largest bucket is always exactly max_len so every
            # admissible prompt has a bucket (same invariant as
            # default_buckets)
            self.buckets = tuple(sorted({min(int(b), max_len)
                                         for b in buckets} | {max_len}))
        self._supports_plen = (
            "prompt_len" in inspect.signature(model.prefill).parameters)
        probe = getattr(model, "supports_paged", None)
        self.paged = bool(paged and self._supports_plen
                          and probe is not None and probe())
        self._key = jax.random.PRNGKey(rng_seed)
        self._rng_step = 0

        # chunked prefill: "auto" = second-largest bucket (disabled when
        # the grid has one bucket — nothing to chunk to); 0/None =
        # monolithic; an explicit chunk rounds *up* to the bucket grid so
        # chunking never adds a compile beyond the existing buckets.
        # Requires prompt_len prefill (the fallback path admits exact
        # lengths and cannot teacher-force through the batched step).
        if not self._supports_plen or not prefill_chunk:
            self.prefill_chunk = None
        elif prefill_chunk == "auto":
            self.prefill_chunk = (self.buckets[-2]
                                  if len(self.buckets) > 1 else None)
        else:
            self.prefill_chunk = bucket_for(self.buckets,
                                            int(prefill_chunk))

        # the stepper owns the jitted entry points and device cache
        # state; TraceCounter-wrapped so metrics() reports "*_traces"
        self._stepper = (PagedStepper(self, page_size, n_pages)
                         if self.paged else DenseStepper(self))
        self._sample = self._jit(sample_tokens, SERVE_DECODE_RULES)

        # speculative decoding (DESIGN.md §12): spec is a SpecConfig with
        # a draft source; models without the span-write decode path fall
        # back to plain decode
        self._spec = None
        probe_spec = getattr(model, "supports_spec", None)
        if spec is not None and probe_spec is not None and probe_spec():
            from .spec import SpecRunner
            self._spec = SpecRunner(self, spec)
            self._truncate = self._jit(truncate_slot, SERVE_DECODE_RULES)

        self._admission = AdmissionPipeline(self)
        self._m = self.registry.group("serve").init(
            tokens_generated=0, decode_steps=0, prefill_batches=0,
            admitted=0, completed=0, expired=0, truncated=0,
            prefix_hits=0, prefix_hit_tokens=0, fill_steps=0,
            chunked_admissions=0, serve_time_s=0.0,
            shed=0, shed_retried=0, preempted=0, resumed=0,
            pressure_events=0)
        self._stall_spins = 0
        self._hold_fill = False      # one-iteration admission hold after
                                     # a pressure-relieving preemption
        self._req_stats: dict = {}   # rid -> dict(tokens=..., steps=...)

    # -- stepper state (back-compat attribute surface) -----------------------
    @property
    def _prefill1(self):
        return self._stepper._prefill1

    @property
    def _prefill_admit(self):
        return self._stepper._prefill_admit

    @property
    def _admit_one(self):
        return self._stepper._admit_one

    @property
    def _decode(self):
        return self._stepper._decode

    def _paged_stepper(self) -> PagedStepper:
        if not self.paged:
            raise AttributeError("dense engine has no paged state")
        return self._stepper

    @property
    def pool(self):
        return self._paged_stepper().pool

    @property
    def _store(self):
        return self._paged_stepper().store

    @property
    def page_size(self):
        return self._paged_stepper().page_size

    @property
    def pages_per_slot(self):
        return self._paged_stepper().pages_per_slot

    @property
    def n_pages(self):
        return self._paged_stepper().n_pages

    @property
    def _prefill_paged(self):
        return self._paged_stepper()._prefill_paged

    @property
    def _decode_paged(self):
        return self._paged_stepper()._decode_paged

    # -- mesh plumbing -------------------------------------------------------
    def _jit(self, fn, rules):
        """jit ``fn``; with a mesh, every call (so also the trace) runs
        under ``axis_rules(mesh, rules)``.  The raw jitted callable stays
        reachable as ``.jitted`` (lowering/compile introspection)."""
        jf = jax.jit(fn)  # repro: noqa[RPR001] this IS the seam every other serve jit routes through
        if self.mesh is None:
            return jf

        def wrapped(*args):
            with axis_rules(self.mesh, rules):
                return jf(*args)

        wrapped.jitted = jf
        return wrapped

    def _place(self, tree, axes_tree):
        """Place a param/cache tree onto the mesh per its logical-axis
        annotations (identity without a mesh or annotations)."""
        if self.mesh is None or axes_tree is None or tree is None:
            return tree
        return jax.device_put(
            tree, tree_shardings(self.mesh, tree, axes_tree,
                                 rules=SERVE_DECODE_RULES))

    def _hint_cache(self, cache):
        """Pin a dense cache tree to its canonical layout inside a jitted
        body — keeps the steady-state decode layout stable step to step."""
        if self.mesh is None or self._cache_axes is None:
            return cache
        return tree_hint(cache, self._cache_axes)

    @staticmethod
    def _gathered(step_logits):
        """Replicate one step's (B, V) logits before sampling.  The
        projection leaves them vocab-sharded (logits_from_hidden's hint);
        this second constraint is the decode step's single all-gather —
        argmax/sampling then runs replicated with no further collectives.
        Identity without an active mesh."""
        return shard_hint(step_logits, "batch", None)

    # -- helpers -------------------------------------------------------------
    def _next_key(self):
        self._rng_step += 1
        return jax.random.fold_in(self._key, self._rng_step)

    @staticmethod
    def _policy_args(temps, top_k, top_p):
        """Device policy args for the jitted bodies, with top-k/top-p
        dropped to ``None`` when no slot in the batch uses them — the
        full-vocab sort/argsort behind those masks would otherwise run
        every decode step (None vs array is a different jit signature,
        so each variant compiles once).  The in-use predicates are
        shared with the speculative cycle (:func:`.sampler.policy_in_use`)."""
        use_tk, use_tp = policy_in_use(top_k, top_p)
        tk = jnp.asarray(top_k, jnp.int32) if use_tk else None
        tp = jnp.asarray(top_p, jnp.float32) if use_tp else None
        return jnp.asarray(temps, jnp.float32), tk, tp

    def _check_prompt(self, req: Request) -> int:
        n = int(np.asarray(req.prompt).shape[0])
        if n < 1:
            raise ValueError(f"req {req.rid}: empty prompt")
        limit = self.buckets[-1] if self._supports_plen else self.max_len
        if n > limit:
            raise ValueError(
                f"req {req.rid}: prompt length {n} exceeds {limit}")
        return n

    # -- single-request path -------------------------------------------------
    def generate(self, request: Request) -> np.ndarray:
        """Single-request generate (tests / quickstart): exact-length
        batch-1 prefill + batch-1 decode through the same jitted sampler
        ops as the batched path."""
        self._check_prompt(request)
        if request.max_new_tokens <= 0:
            return _empty()
        t0 = self.clock()
        cache = self._place(self.model.init_cache(1, self.max_len),
                            self._cache_axes)
        tok = jnp.asarray(np.asarray(request.prompt, np.int32))[None]
        logits, cache = self._prefill1(self.params, tok, cache)
        temps, top_k, top_p = self._policy_args(
            [request.temperature], [request.top_k], [request.top_p])
        active = jnp.ones((1,), bool)
        nxt = self._sample(logits[:, 0], temps, top_k, self._next_key(),
                           top_p)
        out = [int(nxt[0])]
        n_steps = min(request.max_new_tokens - 1,
                      self.max_len - len(request.prompt))
        for _ in range(n_steps):
            nxt, cache = self._decode(self.params, cache, nxt, active,
                                      temps, top_k, top_p,
                                      self._next_key())
            self._m["decode_steps"] += 1
            out.append(int(nxt[0]))
        self._m["tokens_generated"] += len(out)
        self._m["serve_time_s"] += self.clock() - t0
        return np.asarray(out, np.int32)

    # -- per-request accounting ----------------------------------------------
    def _settle(self, req: Request, results: dict, out, counter: str):
        """Record a request's terminal outcome without a slot."""
        req.outcome = counter
        results[req.rid] = out
        self._m[counter] += 1
        instrument.settled(self, req, counter)
        if req.on_finish:
            req.on_finish(req.rid, out)

    def _handle_immediate(self, req: Request, results: dict) -> bool:
        """True if the request completes without ever taking a slot.
        A deadline exactly at the admission instant still admits (the
        cutoff is strict ``>``).  A resumed preempted request that
        expires while re-queued keeps the tokens it already produced
        (truncated, not expired).  The SLO shed gate runs last: fresh
        requests whose deadline the queue-delay estimate says cannot be
        met are rejected before they waste a slot."""
        if req.deadline is not None and self.clock() > req.deadline:
            out = (np.asarray(req.out_tokens, np.int32)
                   if req.resume and req.out_tokens else _empty())
            self._settle(req, results,
                         out, "truncated" if len(out) else "expired")
            return True
        if req.max_new_tokens <= 0:
            self._settle(req, results, _empty(), "completed")
            return True
        if self.slo is not None and not req.resume \
                and self.slo.should_shed(req, self.clock()):
            shed_request(self, req, results)
            return True
        return False

    def _eligible(self, req: Request) -> bool:
        """Admissible right now (tenant under its in-flight quota)."""
        return self.slo is None or self.slo.quota_ok(req)

    def _emit(self, req: Request, tok: int):
        if req.t_first is None:
            instrument.first_token(self, req)
        req.out_tokens.append(tok)
        self._m["tokens_generated"] += 1
        self._req_stats.setdefault(
            req.rid, dict(tokens=0, steps=0))["tokens"] += 1
        if req.on_token:
            req.on_token(req.rid, tok)

    def _count_step(self, rid: int):
        """One engine step (prefill, decode step, or spec cycle) in
        which request ``rid`` occupied a live slot — the denominator of
        its ``tokens_per_step``."""
        self._req_stats.setdefault(
            rid, dict(tokens=0, steps=0))["steps"] += 1

    def request_summary(self) -> dict:
        """Per-request ``tokens_per_step`` (tokens emitted per engine
        step while resident; > 1 only with speculative bursts)."""
        return {rid: s["tokens"] / max(s["steps"], 1)
                for rid, s in self._req_stats.items()}

    def _admit_bind(self, run: ServeRun, req: Request, s: int, eff=None):
        """Bind + engine-level admission accounting (shared by every
        admission strategy).  ``eff`` is the effective prompt — prompt
        plus already-emitted tokens for a resumed preemptee.  Admission
        is where the SLO layer observes queue delay (arrival to bind,
        the same quantity the traffic percentiles report) and charges
        the tenant's in-flight quota."""
        if self.slo is not None:
            self.slo.acquire(req)
            if req.arrival is not None:
                self.slo.observe(self.clock() - req.arrival)
        if req.resume:
            self._m["resumed"] += 1
        run.st.bind(req, s)
        instrument.bound(self, req, s)
        req.resume = False
        self._m["admitted"] += 1
        self._req_stats.setdefault(req.rid, dict(tokens=0, steps=0))
        if self._spec is not None:
            self._spec.admit_slot(s, req.prompt if eff is None else eff)
        if req.on_admit:
            req.on_admit(req.rid)

    def _post_admit(self, run: ServeRun, req: Request, s: int, tok: int):
        """First-token emission for a fully-prefilled admission (chunked
        admissions emit nothing until their fill drains)."""
        self._count_step(req.rid)
        self._emit(req, tok)
        self._finish_checks(run, req, s, None)

    def _finish(self, run: ServeRun, s: int, counter: str = "completed"):
        st = run.st
        req = st.req[s]
        out = np.asarray(req.out_tokens, np.int32)
        run.results[req.rid] = out
        req.outcome = counter
        self._m[counter] += 1
        instrument.retired(self, req, counter)
        if self.slo is not None:
            self.slo.release(req)
        st.clear(s)
        self._stepper.retire(st, s)
        if req.on_finish:
            req.on_finish(req.rid, out)

    def _finish_checks(self, run: ServeRun, req: Request, s: int, now):
        if len(req.out_tokens) >= req.max_new_tokens:
            self._finish(run, s)
        elif now is not None and req.deadline is not None \
                and now > req.deadline:
            self._finish(run, s, counter="truncated")
        elif run.st.slot_len[s] >= self.max_len:
            self._finish(run, s, counter="truncated")

    # -- unified continuous-batching loop ------------------------------------
    def serve(self, requests: List[Request] = (), *, feed=None) -> dict:
        """Run requests to completion with slot-based batching.

        Returns {rid: np.ndarray of generated tokens}.  Requests with
        ``max_new_tokens=0`` complete immediately with an empty sequence;
        requests whose ``deadline`` already passed at admission expire
        with an empty sequence; a running request whose deadline passes
        mid-decode is truncated at the tokens produced so far.

        One loop serves both cache kinds: the dense block and the paged
        pool differ only in the stepper plugged into the engine.  With
        ``feed`` (open-loop traffic), arrivals whose time has passed are
        polled into the queue every iteration and the loop idles —
        without busy-spinning the decode step — until the feed drains.

        Page exhaustion never escapes this loop: a step (or an
        injected-fault admission reservation) raising
        :class:`.pages.PagePressure` is relieved by preempting the
        latest-deadline slot and retrying — throughput degrades, the
        loop does not die (DESIGN.md §16).
        """
        self._req_stats = {}         # per-serve scope (no unbounded growth)
        t0 = self.clock()
        for r in requests:
            self._check_prompt(r)
            instrument.enqueued(self, r)
        run = ServeRun(self, requests)
        st = run.st
        self._stepper.begin()

        while True:
            if self.faults is not None:
                self._fault_tick(run)
            if feed is not None:
                for r in feed.poll(self.clock()):
                    self._check_prompt(r)
                    instrument.enqueued(self, r)
                    run.queue.append(r)
            try:
                # a pressure-relieving preemption holds admission for one
                # iteration: the retried step gets first claim on the
                # freed pages (otherwise the loop would re-admit the
                # victim right back into the same shortage — a livelock,
                # not backpressure)
                hold_fill, self._hold_fill = self._hold_fill, False
                if run.queue and st.free() and not hold_fill:
                    with instrument.step_span(self, "admit"):
                        self._admission.fill_slots(run)
                if not st.any_active():
                    waiting = feed is not None and feed.pending()
                    if run.queue and self._stall_shed(run, waiting):
                        continue
                    if waiting:
                        self._idle_wait(feed)
                        continue
                    if run.queue:
                        continue    # immediates drained; re-admit
                    break
                k_eff = self._spec_k(st.slot_len, st.active, st.req,
                                     filling=st.filling())
                if k_eff >= 1:
                    self._spec_step(run, k_eff)
                else:
                    self._plain_step(run)
            except PagePressure as pp:
                instrument.page_event(self, "page_pressure", slot=pp.slot)
                self._hold_fill = relieve_pressure(self, run, pp)
        self._m["serve_time_s"] += self.clock() - t0
        return run.results

    def _fault_tick(self, run: ServeRun):
        """Consume this iteration's injected faults: scheduled stalls
        burn through the injector's ``advance``; a scheduled forced
        preemption evicts the normal victim (exercising preempt/resume
        even without page pressure, dense included)."""
        self.faults.on_loop()
        if self.faults.take_preempt():
            victim = pick_victim(run.st)
            if victim is not None:
                self.faults.count_preempt()
                preempt_slot(self, run, victim)

    def _stall_shed(self, run: ServeRun, waiting: bool) -> bool:
        """No slot active but the queue is non-empty: with every quota
        free and the pool at its emptiest, a head that still cannot
        bind never will — shed it terminally.  A bounded spin backstop
        catches anything else (pathological fault schedules) unless
        arrivals are still pending (``waiting`` — idling is then the
        correct behavior, not a stall)."""
        head = run.queue[0]
        stuck = never_admissible(self, head)
        self._stall_spins = 0 if stuck or waiting else self._stall_spins + 1
        if stuck is None and self._stall_spins < 4096:
            return False
        self._stall_spins = 0
        shed_request(self, run.queue.pop(0), run.results, terminal=True)
        return True

    def _idle_wait(self, feed):
        """No active slots but arrivals still pending: sleep (real time,
        capped small so fake clocks can't wedge the loop) until the next
        scheduled arrival."""
        nxt = feed.next_time()
        if nxt is None:
            time.sleep(2e-4)
            return
        time.sleep(min(max(nxt - self.clock(), 0.0), 5e-3))

    def _plain_step(self, run: ServeRun):
        """One masked decode step + shared post-step bookkeeping
        (teacher-forced fill consumption, emission, finish checks)."""
        st = run.st
        with instrument.step_span(self, "decode_step"):
            self._stepper.plain_step(st)
            with instrument.step_span(self, "sampler_sync"):
                toks = np.asarray(st.slot_last)  # repro: noqa[RPR002] the designed per-step budget: one int32 per slot for emission
        self._m["decode_steps"] += 1
        with instrument.step_span(self, "emit"):
            self._emit_step(run, toks)

    def _emit_step(self, run: ServeRun, toks):
        """Per-slot emission and finish checks after a plain step."""
        st = run.st
        now = self.clock()
        for s in range(self.n_slots):
            req = st.req[s]
            if req is None or not st.active[s]:
                continue
            self._count_step(req.rid)
            st.slot_len[s] += 1
            assert st.slot_len[s] <= self.max_len, \
                f"slot {s}: cache len {st.slot_len[s]} > max_len"
            if st.fill[s] is not None:
                self._m["fill_steps"] += 1
                st.fill[s] = st.fill[s][1:]
                if len(st.fill[s]):
                    if req.deadline is not None and now > req.deadline:
                        self._finish(run, s, counter="truncated")
                    continue        # still prefilling this slot
                # fill done: this step consumed the last prompt token,
                # so the sampled token is the first output
                st.fill[s] = None
                self._stepper.fill_done(st, s)
                instrument.fill_done(self, req)
            self._emit(req, int(toks[s]))
            self._finish_checks(run, req, s, now)

    def _spec_step(self, run: ServeRun, k_eff: int):
        """One speculative draft+verify burst + shared emission loop;
        rejected suffixes roll back through the stepper hooks."""
        st = run.st
        with instrument.step_span(self, "spec_cycle", k=k_eff) as sa:
            out, n_acc = self._stepper.spec_cycle(st, k_eff)
            sa["accepted"] = int(n_acc.sum())
            with instrument.step_span(self, "sampler_sync"):
                last_np = np.asarray(st.slot_last).copy()  # repro: noqa[RPR002] burst emission rewrites slot_last on host; k+1 int32 per slot
        self._m["decode_steps"] += 1
        with instrument.step_span(self, "emit"):
            self._emit_burst(run, out, n_acc, last_np)

    def _emit_burst(self, run: ServeRun, out, n_acc, last_np):
        """Per-slot emission of a speculative burst, finish checks and
        the rejected-suffix rollback."""
        st = run.st
        now = self.clock()
        for s in range(self.n_slots):
            req = st.req[s]
            if req is None or not st.active[s]:
                continue
            self._count_step(req.rid)
            consumed = 0
            for i in range(int(n_acc[s]) + 1):
                consumed = i + 1
                st.slot_len[s] += 1
                assert st.slot_len[s] <= self.max_len, \
                    f"slot {s}: cache len {st.slot_len[s]} > max_len"
                last_np[s] = int(out[s, i])
                self._emit(req, int(out[s, i]))
                if len(req.out_tokens) >= req.max_new_tokens:
                    self._finish(run, s)
                    break
                elif req.deadline is not None and now > req.deadline:
                    self._finish(run, s, counter="truncated")
                    break
                elif st.slot_len[s] >= self.max_len:
                    self._finish(run, s, counter="truncated")
                    break
            # draft proposals that reached the output (position n_acc is
            # the correction/bonus, not a proposal)
            self._spec.m["emitted_draft_tokens"] += \
                min(consumed, int(n_acc[s]))
            if st.active[s]:
                self._stepper.post_spec_slot(st, s)
        st.slot_last = jnp.asarray(last_np)
        self._stepper.spec_rollback(st)

    def _spec_k(self, slot_len, active, slot_req, filling=()) -> int:
        """Draft depth for this iteration: the configured k shrunk to
        (a) the tightest active slot's remaining cache room (a cycle
        writes k+1 fresh positions per slot) and (b) the *largest*
        remaining token budget across active slots — when every slot is
        near its ``max_new_tokens`` a full-depth burst would be paid
        for and thrown away, so the depth tracks what can still be
        emitted (slots below the max just drop their surplus, which is
        cheap).  0 means "run a plain decode step" — near-capacity
        slots and prompt-filling slots (chunked or prefix-hit) keep the
        exact truncation semantics of non-speculative serving."""
        if self._spec is None or any(filling):
            return 0
        room = min(self.max_len - int(slot_len[s])
                   for s in range(self.n_slots) if active[s])
        budget = max(slot_req[s].max_new_tokens - len(slot_req[s].out_tokens)
                     for s in range(self.n_slots) if active[s])
        return max(0, min(self._spec.cfg.k, room - 1, budget - 1))

    # -- observability -------------------------------------------------------
    def metrics(self) -> dict:
        """Counter snapshot: throughput, prefill/decode call and trace
        counts, the retrace count (compiles beyond the first per jitted
        entry point — bounded by len(buckets)-1 for the bucketed
        prefill) plus its per-entry breakdown (``retrace_by_entry``).
        Assembled by :func:`.instrument.collect_metrics` from the
        registry-backed groups; the key surface is frozen
        (tests/test_obs.py)."""
        return instrument.collect_metrics(self)

    def export_trace(self, path) -> str:
        """Write this engine's span trace as Chrome/Perfetto
        trace_event JSON (requires ``tracer=`` at construction)."""
        return instrument.export_trace(self, path)

    def page_bytes(self) -> int:
        """Device bytes of one physical KV page (every leaf, all
        layers)."""
        if not self.paged:
            return 0
        return sum(leaf.nbytes // leaf.shape[1]
                   for leaf in self._store.values())
