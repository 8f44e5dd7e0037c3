"""Slot-table state shared by every admission strategy and cache kind.

The serving engine is slot-based continuous batching: ``n_slots`` fixed
batch rows, each either free or bound to one in-flight
:class:`Request`.  :class:`SlotTable` owns the *host-side* mirror of
that binding — per-slot request pointers, sampling policy rows, the
host-tracked cache lengths, the pending prompt tails of chunked
admissions, and the per-slot prompt block hashes the paged prefix index
keys on.  Device state (the dense cache block or the page store) lives
in the stepper (:mod:`.stepper`); the engine's serve loop and the
admission strategies (:mod:`.admission`) only ever talk to slots
through this table, which is what lets dense and paged share one loop.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import instrument


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (T,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0     # 0 => greedy
    top_k: int = 0               # 0 => disabled
    top_p: float = 0.0           # 0 or >= 1 => disabled (nucleus)
    deadline: Optional[float] = None   # absolute engine-clock cutoff
    on_token: Optional[Callable[[int, int], None]] = None
    on_finish: Optional[Callable[[int, np.ndarray], None]] = None
    on_admit: Optional[Callable[[int], None]] = None
    out_tokens: Optional[list] = None
    # overload machinery (DESIGN.md §16)
    tenant: str = "default"      # quota/fairness bucket
    rel_deadline: Optional[float] = None  # deadline relative to arrival
    arrival: Optional[float] = None       # stamped by the arrival feed
    on_shed: Optional[Callable] = None    # (req, retry_after_s) on shed
    retries: int = 0             # shed-retry re-arrivals so far
    preempts: int = 0            # times evicted from a slot
    resume: bool = False         # re-queued mid-flight; keep out_tokens
    outcome: Optional[str] = None    # completed|expired|truncated|shed
    # lifecycle stamps (serve/instrument.py): engine-clock times of the
    # current queue/prefill/decode phase boundaries; a preemption
    # resets them so the resume traces as a fresh triple
    t_enqueue: Optional[float] = None
    t_bind: Optional[float] = None
    t_first: Optional[float] = None


def effective_prompt(req: Request) -> np.ndarray:
    """The token sequence admission must (re)build KV for: the prompt,
    plus — for a resumed preempted request — everything it already
    emitted.  Treating prompt+out as the prompt makes resume ordinary
    admission: prefill (or a prefix-index hit) recomputes exactly the
    KV that was released, and the first sampled token continues the
    output stream bit-identically under greedy decoding."""
    p = np.asarray(req.prompt, np.int32)
    if req.resume and req.out_tokens:
        return np.concatenate([p, np.asarray(req.out_tokens, np.int32)])
    return p


class TraceCounter:
    """Wraps a jitted callable; counts calls and distinct input
    shape/dtype signatures (== XLA traces for a jit with no static
    args).  The serving tests assert prefill traces <= bucket count.

    With a ``name`` and an ``engine`` that has a tracer, every *new*
    signature also lands on the trace as a ``compile`` (first trace) or
    ``retrace`` instant — so a recompile mid-traffic shows up as a named
    event instead of a mystery latency spike.  When the engine was built
    with ``profile=True`` each dispatch runs under a ``jax.profiler``
    annotation of that name (:func:`.instrument.annotate`)."""

    def __init__(self, fn, name: Optional[str] = None, engine=None):
        self.fn = fn
        self.name = name or getattr(fn, "__name__", "jit")
        self.engine = engine
        self.calls = 0
        self._sigs = set()

    def _on_new_sig(self):
        eng = self.engine
        if eng is not None and eng.tracer is not None:
            eng.tracer.instant(
                "compile" if len(self._sigs) == 1 else "retrace",
                cat="jit", args=dict(entry=self.name,
                                     trace=len(self._sigs),
                                     call=self.calls))

    def __call__(self, *args):
        self.calls += 1
        sig = tuple(
            (leaf.shape, str(leaf.dtype))
            for leaf in jax.tree_util.tree_leaves(args)
            if hasattr(leaf, "shape"))
        if sig not in self._sigs:
            self._sigs.add(sig)
            self._on_new_sig()
        with instrument.annotate(self.engine, self.name):
            return self.fn(*args)

    @property
    def traces(self) -> int:
        return len(self._sigs)


def empty_tokens() -> np.ndarray:
    return np.zeros((0,), np.int32)


class SlotTable:
    """Host-side slot <-> request state.

    ``slot_len`` is the host mirror of each slot's valid cache length
    (dense ``cache["len"]`` / paged page-table occupancy).  ``fill[s]``
    is the not-yet-prefilled prompt tail of a chunked or prefix-hit
    admission — while non-None the slot is teacher-forcing its prompt
    through the decode step and emits nothing.  ``hashes[s]`` keeps the
    prompt's block hashes for paged prefix-index registration.
    """

    def __init__(self, n: int):
        self.n = n
        self.req: List[Optional[Request]] = [None] * n
        self.active = np.zeros(n, bool)
        self.temps = np.zeros(n, np.float32)
        self.top_k = np.zeros(n, np.int32)
        self.top_p = np.zeros(n, np.float32)
        self.slot_len = np.zeros(n, np.int64)
        self.fill: List[Optional[np.ndarray]] = [None] * n
        self.hashes: List[Optional[list]] = [None] * n
        self.slot_last = jnp.zeros((n,), jnp.int32)

    def free(self) -> List[int]:
        return [s for s in range(self.n) if self.req[s] is None]

    def any_active(self) -> bool:
        return bool(self.active.any())

    def bind(self, req: Request, s: int):
        """Bind a request to slot ``s`` (policy rows + request pointer;
        engine-level accounting stays in the engine).  A resumed
        preempted request keeps its emitted tokens — the finish checks
        and token budget continue from where the eviction cut it."""
        if not req.resume:
            req.out_tokens = []
        self.req[s] = req
        self.active[s] = True
        self.temps[s] = req.temperature
        self.top_k[s] = req.top_k
        self.top_p[s] = req.top_p

    def clear(self, s: int):
        self.req[s] = None
        self.active[s] = False
        self.fill[s] = None
        self.hashes[s] = None

    def filling(self) -> List[bool]:
        """Per-active-slot "still teacher-forcing its prompt" flags —
        feeds the spec-depth decision (no speculative bursts while any
        slot is mid-prompt)."""
        return [self.fill[s] is not None
                for s in range(self.n) if self.active[s]]

    def input_tokens(self):
        """Next decode-step input per slot: the last sampled token,
        with filling slots teacher-forced from their prompt tail.

        Steady state (nothing filling) passes ``slot_last`` through as
        the device array — the steppers feed it straight back into the
        jitted step, so the common decode path never round-trips the
        sampled tokens device→host→device.  Only a slot mid-prompt
        (chunked or prefix-hit admission) forces the transfer, because
        its next input lives in a host-side prompt tail."""
        filling = [s for s in range(self.n)
                   if self.active[s] and self.fill[s] is not None]
        if not filling:
            return self.slot_last
        sl = np.asarray(self.slot_last).copy()  # repro: noqa[RPR002] fill tokens live on host; only chunked-admission steps pay this
        for s in filling:
            sl[s] = self.fill[s][0]
        return sl
