"""Set-up, warm-up and the measured window of one serving cell.

The system under test is the program's normal serving path: seeded
weights, FAQ calibration and int4 packing (the steps of
``repro.launch.serve.quantize_for_serving``, with the weights drawn
from the run's seed), then ``build_engine`` → ``ServeEngine.serve`` over
the paged KV cache with chunked prefill and greedy sampling.  The
benchmark drives it through a feed and the requests' own callbacks, and
wraps the stepper's two device entry points (``admit_group`` and
``plain_step``) to log the work of each call: its own spans around the
calls into the layer, on the host clock.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import traffic

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
CLOCK = time.perf_counter


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The workload entry of ``BENCHMARK.json`` with its workload,
    configuration and traffic files."""
    spec = load_json(REPO / "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    work = load_json(BENCH / "workloads" / f"{name}.json")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == entry["config"])
    return {
        "name": name,
        "entry": entry,
        "spec": spec,
        "workload": work,
        "config": load_json(REPO / cfg_entry["file"]),
        "mix": load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
    }


def metrics_for(spec: dict, cell: str, traced: bool) -> List[dict]:
    """The metrics a cell reports: end-to-end ones untraced, per-layer
    ones traced, each where its ``workloads`` list names the cell."""
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


class CompileCounter:
    """Jaxpr traces and backend compiles, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.traces = self.compiles = 0
        self.cache_hits = self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return dict(traces=self.traces, compiles=self.compiles,
                    cache_hits=self.cache_hits,
                    cache_misses=self.cache_misses)


def model_sizes(cfg) -> dict:
    """The sizes ``bench.work`` and ``bench.reference`` read."""
    return dict(n_layers=cfg.n_layers, d_model=cfg.d_model,
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim_, d_ff=cfg.d_ff,
                vocab_size=cfg.vocab_size, rope_theta=cfg.rope_theta,
                norm_eps=cfg.norm_eps, dtype=cfg.dtype)


def quantize(cfg):
    """The program's build path as its launcher runs it: init from
    ``PRNGKey(0)``, calibration on the synthetic set, FAQ int4 (group 64)
    packing, waited for here so that its time is all inside the span."""
    import jax

    from repro.launch.serve import quantize_for_serving

    q = quantize_for_serving(cfg, method="faq", bits=4)
    jax.block_until_ready(q.qparams)
    return q


class StepLog:
    """The benchmark's spans around the stepper's device entry points:
    for each batched prefill its dispatch time and the admitted prompt
    lengths, for each decode step its dispatch time and the cache
    length of every active slot before the step.  With ``annotate`` each
    call also runs under a named profiler span."""

    def __init__(self, stepper, annotate: bool):
        import jax

        self.prefills: List[tuple] = []     # (t, [admitted lengths], bucket)
        self.decodes: List[tuple] = []      # (t, [contexts])
        admit, step = stepper.admit_group, stepper.plain_step
        span = (jax.profiler.TraceAnnotation if annotate
                else (lambda _: contextlib.nullcontext()))

        def admit_group(st, tokens, plen, admit_mask, group, reserved=None):
            self.prefills.append((CLOCK(), [int(plen[s]) for _, s in group],
                                  int(tokens.shape[1])))
            with span("bench.admit"):
                return admit(st, tokens, plen, admit_mask, group, reserved)

        def plain_step(st):
            self.decodes.append((CLOCK(),
                                 [int(x) for x in st.slot_len[st.active]]))
            with span("bench.decode_step"):
                return step(st)

        stepper.admit_group = admit_group
        stepper.plain_step = plain_step

    def between(self, lo: float, hi: float):
        return ([p for p in self.prefills if lo <= p[0] <= hi],
                [d for d in self.decodes if lo <= d[0] <= hi])


def build(q, work: dict, annotate: bool):
    from repro.launch.serve import build_engine

    e = work["engine"]
    eng = build_engine(q, paged=True, n_slots=e["n_slots"],
                       max_len=e["max_len"], buckets=tuple(e["buckets"]),
                       page_size=e["page_size"], n_pages=e["n_pages"],
                       prefill_chunk=e["prefill_chunk"], clock=CLOCK,
                       profile=annotate)
    if not eng.paged:
        raise RuntimeError("engine fell back to the dense cache")
    return eng


def warm_up(eng, vocab: int, rng, first: List[int] = (),
            groups: Optional[List[int]] = None) -> dict:
    """Compile every program the cell's traffic can reach: one batched
    prefill per prompt bucket up to the chunk, a chunked admission and
    its teacher-forced fill, decode, the admission that opens the window
    (``first``: the prompt lengths queued at its start, replayed in their
    order, each cut to two tokens past the chunk so that its fill stays
    short), and the page scatter of each admission group size in
    ``groups`` (all sizes where None) at every bucket."""
    import jax
    import jax.numpy as jnp

    from repro.serve import Request
    from repro.serve.pages import PagePool

    chunk = eng.prefill_chunk
    buckets = [b for b in eng.buckets if chunk is None or b <= chunk]
    lengths = list(buckets)
    longest = eng.max_len - 2
    if chunk is not None and chunk + 2 <= longest:
        lengths.append(chunk + 2)
        longest = chunk + 2

    def serve(lens):
        reqs = [Request(rid=-1 - i, max_new_tokens=2,
                        prompt=rng.integers(1, vocab, n, dtype=np.int32))
                for i, n in enumerate(lens)]
        out = eng.serve(reqs)
        if any(len(out[r.rid]) != 2 for r in reqs):
            raise RuntimeError("warm-up requests did not complete")

    serve(lengths)
    if len(first):
        serve([min(int(n), longest) for n in first])
    stp = eng._stepper
    cfg = eng.model.cfg
    groups = range(1, eng.n_slots + 1) if groups is None else groups
    for b in buckets:
        npg = -(-b // stp.page_size)
        shape = (cfg.n_layers, eng.n_slots, cfg.n_kv_heads,
                 npg * stp.page_size, cfg.head_dim_)
        scratch = {"k": jnp.zeros(shape, stp.store["k"].dtype),
                   "v": jnp.zeros(shape, stp.store["v"].dtype),
                   "len": jnp.zeros((eng.n_slots,), jnp.int32)}
        for g in groups:
            stp.store = stp._scatter_pages(
                stp.store, scratch, jnp.arange(g, dtype=jnp.int32),
                jnp.full((g, npg), PagePool.TRASH, jnp.int32))
        del scratch
    jax.block_until_ready(stp.store)
    return {"buckets": buckets, "lengths": lengths, "groups": list(groups)}


@dataclasses.dataclass
class Rec:
    """One request as the benchmark saw it, on the host clock."""
    rid: int
    plen: int
    max_new: int
    in_window: bool
    due: Optional[float] = None
    admit: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: Optional[float] = None
    prompt: Optional[np.ndarray] = None


class Window:
    """Drives one measured window through ``ServeEngine.serve(feed=...)``.

    Open-loop mixes: the window's requests arrive at their due times
    over ``seconds``; arrivals go on at the same rate after it until the
    window's last request has finished, and then every request still in
    flight is cut by setting its deadline to now.  A backlog mix queues
    its requests at the start with the window's end as their deadline,
    so the engine stops at the window's end.  ``tracer`` (if given) is
    called on every poll of the feed."""

    MAX_DRAIN_S = 150.0

    def __init__(self, eng, cell: dict, seed: int, seconds: float,
                 tracer=None):
        self.eng = eng
        self.seconds = seconds
        mix, work = cell["mix"], cell["workload"]
        vocab = eng.model.cfg.vocab_size
        self.backlog = mix["arrivals"] == "backlog"
        rate = work.get("rate_per_s")
        items = traffic.plan(mix, rate=rate, seconds=seconds, seed=seed,
                             vocab=vocab, n_slots=eng.n_slots)
        more = None if self.backlog else traffic.background(
            mix, rate=rate, seconds=seconds, seed=seed, vocab=vocab,
            rid0=len(items))
        self.recs: Dict[int, Rec] = {}
        self.live: Dict[int, object] = {}      # rid -> Request, unfinished
        self.feed = traffic.Feed(items, self._request, more)
        self.feed.on_poll = self._on_poll
        self.tracer = tracer
        self.window_left = len(items)
        self.t0 = self.t_end = None
        self.cut = False

    def _request(self, it):
        from repro.serve import Request

        rec = Rec(rid=it.rid, plen=len(it.prompt), max_new=it.max_new,
                  in_window=it.in_window, prompt=it.prompt)
        rec.due = self.feed.t0 + it.due
        self.recs[it.rid] = rec
        req = Request(rid=it.rid, prompt=it.prompt,
                      max_new_tokens=it.max_new,
                      on_admit=self._on_admit, on_token=self._on_token,
                      on_finish=self._on_finish)
        if self.backlog:
            req.deadline = self.t0 + self.seconds
        self.live[it.rid] = req
        return req

    def _on_admit(self, rid):
        self.recs[rid].admit = CLOCK()

    def _on_token(self, rid, tok):
        rec = self.recs[rid]
        rec.times.append(CLOCK())
        rec.tokens.append(int(tok))

    def _on_finish(self, rid, out):
        rec = self.recs[rid]
        rec.done = CLOCK()
        self.live.pop(rid, None)
        if rec.in_window:
            self.window_left -= 1
            if self.window_left == 0 and not self.backlog:
                self._cut(rec.done)

    def _cut(self, now):
        """Stop arrivals and end every request still in flight."""
        self.feed.close()
        self.cut = True
        for req in self.live.values():
            req.deadline = now

    def _on_poll(self, now):
        if self.tracer is not None:
            self.tracer(now)
        if not self.cut and now > self.t0 + self.seconds + self.MAX_DRAIN_S:
            self._cut(now)

    def run(self):
        self.t0 = CLOCK()
        self.feed.start(self.t0)
        self.eng.serve((), feed=self.feed)
        self.t_end = CLOCK()

    # -- what the readers use ---------------------------------------------
    def window_recs(self) -> List[Rec]:
        return [r for r in self.recs.values() if r.in_window]

    def lateness(self) -> List[float]:
        return [rel - due for due, rel in self.feed.released.values()]


def served_tables(qparams) -> dict:
    """The served block linears as plain arrays, each stacked over layers:
    a packed linear becomes a dict of its codes, scale, zero and
    act_scale.  Nothing else of the served tree is kept."""
    from bench.reference import LINEARS
    from repro.core.quantizer import QuantizedTensor

    out = {}
    for n in LINEARS:
        x = qparams["blocks"][n]
        out[n] = ({"codes": x.codes, "scale": x.scale, "zero": x.zero,
                   "act_scale": x.act_scale}
                  if isinstance(x, QuantizedTensor) else x)
    return out


def pick_sample(recs: List[Rec], chunk: Optional[int], rng,
                tokens: int, max_requests: int) -> List[Rec]:
    """A sample drawn from the seed of the requests that produced tokens:
    the longest of them, one whose prompt was admitted in chunks and one
    that was not (where there are such), then others at random until
    ``tokens`` served tokens or ``max_requests`` requests."""
    pool = [r for r in recs if r.tokens]
    if not pool:
        return []
    pool.sort(key=lambda r: r.rid)
    chosen = [max(pool, key=lambda r: (r.plen + len(r.tokens), r.rid))]
    if chunk is not None:
        for want in (True, False):
            kind = [r for r in pool if (r.plen > chunk) == want
                    and r not in chosen]
            if kind:
                chosen.append(kind[int(rng.integers(len(kind)))])
    rest = [r for r in pool if r not in chosen]
    for i in rng.permutation(len(rest)):
        if len(chosen) >= max_requests \
                or sum(len(r.tokens) for r in chosen) >= tokens:
            break
        chosen.append(rest[int(i)])
    return chosen


ACT_ROWS = 512      # tokens whose fp activations weigh the table check


def compare(sizes: dict, tables: dict, sample: List[Rec],
            control: bool) -> dict:
    """The two comparisons with the reference (``bench/reference.py``):

    * ``table_err``: the worst served linear's squared output error on
      the fp model's activations (over the first ``ACT_ROWS`` tokens of
      the sample's first request) as a share of plain int4 RTN's;
    * ``max_gap``: the widest gap by which a served token's reference
      logit lies below the reference's best at its position.

    With ``control`` the reference in lower precision takes the
    program's place: int3 RTN tables for the first, and for the second
    the token the float8 reference puts first at each position."""
    from bench import reference

    fp = reference.FpModel(sizes)
    out = {"tokens": 0, "requests": len(sample), "max_gap": 0.0,
           "table_err": None, "worst_table": None}
    if not sample:
        return out
    r0 = sample[0]
    acts = np.concatenate([r0.prompt, r0.tokens])[:ACT_ROWS]
    shares = reference.table_error(fp, tables, acts,
                                   low_bits=3 if control else 0)
    out["worst_table"] = max(shares, key=shares.get)
    out["table_err"] = shares[out["worst_table"]]
    params = fp.logit_params(tables)
    for r in sample:
        ref = reference.served_rows_logits(sizes, params, r.prompt, r.tokens)
        picked = r.tokens
        if control:
            picked = reference.served_rows_logits(
                sizes, params, r.prompt, r.tokens, lowp=True).argmax(axis=-1)
        g = reference.logit_gaps(ref, picked)
        out["tokens"] += len(r.tokens)
        out["max_gap"] = max(out["max_gap"], float(g.max())
                             if np.isfinite(g).all() else float("inf"))
    return out
