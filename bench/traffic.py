"""One general generator for every traffic mix, and the open-loop feed.

A mix is a data file, ``bench/traffic/<mix>.json``::

    {"arrivals": "poisson" | "backlog",
     "prompt": {"median": 1024, "sigma": 0.8, "min": 32, "max": 4096},
     "output": {"median": 160, "sigma": 0.7, "min": 8, "max": 512},
     "backlog_per_slot": 6,          # backlog only
     "order": "random" | "longest_first"}   # optional, random by default

Lengths are log-normal and clipped, as in the program's own load
generator (``serve/loadgen.py`` ``make_trace``), and arrivals are
Poisson at the cell's fixed rate.  To keep runs of different seeds
doing the same work, the lengths and the gaps between arrivals are not
drawn at random: they are the distribution's quantiles at evenly spaced
probabilities, so every seed gets the same multiset.  The seed picks
their order (which prompt length goes with which output length, and
which gap comes when) and the token ids.

``Feed`` has the interface ``ServeEngine.serve(feed=...)`` polls
(``poll``/``pending``/``next_time``/``push``, copied from the program's
``ArrivalFeed``): requests are released once their due time has passed.
"""
from __future__ import annotations

import bisect
import dataclasses
from statistics import NormalDist
from typing import Callable, List, Optional

import numpy as np


@dataclasses.dataclass
class Item:
    """One planned request: due offset from the window's start, sizes,
    and whether it arrives inside the measured window."""
    rid: int
    due: float
    prompt: np.ndarray
    max_new: int
    in_window: bool


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` clipped log-normal lengths at evenly spaced quantiles."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf(p) for p in _quantiles(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def poisson_offsets(n: int, seconds: float, rng) -> np.ndarray:
    """``n`` arrival offsets in ``[0, seconds)``: exponential gaps at
    evenly spaced quantiles, scaled so that the mean rate is exactly
    ``n / seconds``.  The median gap runs from the last arrival to the
    window's end; the seed orders the others."""
    gaps = -np.log1p(-_quantiles(n))
    inner = np.delete(gaps, n // 2)[rng.permutation(n - 1)]
    starts = np.concatenate([[0.0], np.cumsum(inner)])
    return starts * (seconds / gaps.sum())


def seed_streams(seed: int, n: int) -> List[np.random.Generator]:
    """Independent generators from one seed of any size."""
    ss = np.random.SeedSequence(int(seed))
    return [np.random.default_rng(s) for s in ss.spawn(n)]


def block(mix: dict, n: int, rng, vocab: int, rid0: int, t0: float,
          seconds: float, in_window: bool) -> List[Item]:
    """``n`` requests over ``[t0, t0 + seconds)`` (all at ``t0`` for a
    backlog), lengths from the mix, order and token ids from ``rng``.
    A mix with ``"order": "longest_first"`` queues its prompts from the
    longest down, as a batch job sorted by length does; the seed then
    pairs output lengths with them and draws the token ids."""
    plen = lognormal_lengths(mix["prompt"], n)
    order = mix.get("order", "random")
    if order == "longest_first":
        plen = plen[::-1]
    elif order == "random":
        plen = plen[rng.permutation(n)]
    else:
        raise ValueError(f"unknown order {order!r}")
    olen = lognormal_lengths(mix["output"], n)[rng.permutation(n)]
    if mix["arrivals"] == "backlog":
        due = np.zeros(n)
    elif mix["arrivals"] == "poisson":
        due = poisson_offsets(n, seconds, rng)
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    return [Item(rid=rid0 + i, due=t0 + float(due[i]),
                 prompt=rng.integers(1, vocab, int(plen[i]), dtype=np.int32),
                 max_new=int(olen[i]), in_window=in_window)
            for i in range(n)]


def window_count(mix: dict, rate: Optional[float], seconds: float,
                 n_slots: int) -> int:
    """Requests that arrive inside the window."""
    if mix["arrivals"] == "backlog":
        return int(mix["backlog_per_slot"]) * n_slots
    return max(1, int(round(rate * seconds)))


def plan(mix: dict, *, rate: Optional[float], seconds: float, seed: int,
         vocab: int, n_slots: int) -> List[Item]:
    """The window's requests.  Open-loop mixes keep arriving after the
    window, at the same rate, until its requests are done: see
    :func:`background`."""
    rng = seed_streams(seed, 1)[0]
    n = window_count(mix, rate, seconds, n_slots)
    return block(mix, n, rng, vocab, 0, 0.0, seconds, True)


class Feed:
    """Open-loop valve for ``ServeEngine.serve(feed=...)``.

    ``start(t0)`` anchors offset 0 at absolute time ``t0``.  ``poll(now)``
    releases every request whose due time has passed, in due order, and
    stamps its release time; ``on_poll`` (if set) is called first with
    ``now`` on every poll, which is once per engine loop iteration.
    After ``close()`` nothing more is released and ``pending()`` is
    False.  ``more`` (if set) is called with the last due offset when the
    planned requests run out, and returns further items or ``[]``."""

    def __init__(self, items: List[Item], make_request: Callable,
                 more: Optional[Callable] = None):
        self._items = sorted(items, key=lambda it: it.due)
        self._i = 0
        self.t0: Optional[float] = None
        self.make_request = make_request
        self.more = more
        self.on_poll: Optional[Callable[[float], None]] = None
        self.released: dict = {}     # rid -> (due_abs, release_abs)
        self.closed = False

    def start(self, t0: float):
        self.t0 = t0

    def close(self):
        self.closed = True

    def _refill(self):
        if self.more is not None and self._i >= len(self._items):
            last = self._items[-1].due if self._items else 0.0
            self._items.extend(self.more(last))

    def poll(self, now: float):
        if self.on_poll is not None:
            self.on_poll(now)
        out = []
        if self.closed:
            return out
        self._refill()
        while self._i < len(self._items) \
                and self.t0 + self._items[self._i].due <= now:
            it = self._items[self._i]
            self._i += 1
            due = self.t0 + it.due
            req = self.make_request(it)
            req.arrival = due
            self.released[it.rid] = (due, now)
            out.append(req)
            self._refill()
        return out

    def push(self, t_abs: float, req):
        """Re-release a request at ``t_abs`` (shed retry); the mixes here
        set no deadlines, so the engine never sheds."""
        it = Item(rid=req.rid, due=t_abs - self.t0,
                  prompt=np.asarray(req.prompt), max_new=req.max_new_tokens,
                  in_window=False)
        keys = [x.due for x in self._items[self._i:]]
        self._items.insert(self._i + bisect.bisect_right(keys, it.due), it)

    def pending(self) -> bool:
        if self.closed:
            return False
        self._refill()
        return self._i < len(self._items)

    def next_time(self) -> Optional[float]:
        if self.t0 is None or not self.pending():
            return None
        return self.t0 + self._items[self._i].due


def background(mix: dict, *, rate: float, seconds: float, seed: int,
               vocab: int, rid0: int) -> Callable:
    """``Feed.more`` for an open-loop mix: after the window, further
    blocks of the same size and rate, so the window's last requests see
    the same load to their end."""
    rng = seed_streams(seed, 2)[1]
    n = max(1, int(round(rate * seconds)))
    state = {"rid": rid0}

    def more(last_due: float) -> List[Item]:
        t0 = max(last_due, seconds) + 1.0 / rate
        items = block(mix, n, rng, vocab, state["rid"], t0, seconds, False)
        state["rid"] += n
        return items

    return more


def percentile(xs, q: float) -> Optional[float]:
    """Linear-interpolation percentile (numpy's default), None when empty."""
    if len(xs) == 0:
        return None
    return float(np.percentile(np.asarray(xs, np.float64), q))
