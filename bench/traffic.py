"""The one traffic generator, driven by ``bench/traffic/<mix>.json``, and
the two load loops that play what it generates against a callable.

A request is a list of triple patterns answered by one call. A mix file
names the loop and its parameters:

* ``"loop": "open"`` — independent users. ``rate_per_s`` × ``seconds``
  requests arrive at the times of a Poisson process conditioned on that
  count (sorted uniform times over the window); ``mix`` items are dealt
  out in fixed proportions. The arrival times and the order of shapes
  are drawn once for the cell, not from the seed, so every seed offers
  the same load in the same rhythm; the seed draws the data and the
  bound terms. ``workers`` threads take requests as they fall due;
  latency runs from each request's due time.
* ``"loop": "closed"`` — ``clients`` threads, each sending its next
  request when the last returns. A client works in rounds; ``round``
  items make one round, shuffled per client and round.

Items:

* ``{"kind": "patterns", "shapes": [...], "patterns": n}``: one request
  of `n` patterns of one shape, each binding the terms of a stored triple
  drawn uniformly. In an open mix, the items share the requests evenly and
  each item's share is split evenly over its shapes; in a round, an item
  makes one request per shape.
* ``{"kind": "predicate_scan"}`` (rounds only): one ``?p?`` request per
  predicate.

Seeds: stream 1 draws the window's traffic, stream 2 the warm-up's, and
stream 3 the sample of a closed loop's requests whose answers are kept
whole for the check (an open loop keeps every answer whole); the data
itself comes from the bare seed. Stream 4 of seed 0 is the open loop's
schedule: arrival times and order of shapes.
"""
from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field

import numpy as np

WINDOW, WARMUP, SAMPLE, SCHEDULE = 1, 2, 3, 4


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1), stream])


@dataclass
class Request:
    kind: str          # the pattern shape, e.g. "s??" or "?p?"
    patterns: list     # [(s, p, o)] with None for an unbound term


def _pattern(shape: str, row) -> tuple:
    return tuple(None if ch == "?" else int(v) for ch, v in zip(shape, row))


def _pattern_request(item: dict, shape: str, triples: np.ndarray, rng) -> Request:
    """Bound terms copied from uniformly drawn stored triples."""
    rows = triples[rng.integers(0, len(triples), int(item["patterns"]))]
    return Request(shape, [_pattern(shape, r) for r in rows])


def _deal(weights: list, n: int) -> list:
    """Largest-remainder split of `n` over `weights`: the same counts for
    every seed."""
    w = np.asarray(weights, dtype=np.float64)
    share = w / w.sum() * n
    counts = np.floor(share).astype(int)
    rest = n - counts.sum()
    counts[np.argsort(-(share - counts), kind="stable")[:rest]] += 1
    return counts.tolist()


def open_requests(mix: list, triples, n: int, rng, order_rng) -> list:
    """`n` requests of an open mix: shapes in the order `order_rng` deals
    them, terms drawn by `rng`."""
    slots = []  # (item, shape, weight)
    for item in mix:
        if item["kind"] != "patterns":
            raise ValueError(f"open loops take pattern items, not {item['kind']!r}")
        shapes = item["shapes"]
        for shape in shapes:
            slots.append((item, shape, 1.0 / len(shapes)))
    counts = _deal([w for _, _, w in slots], n)
    order = order_rng.permutation(np.repeat(np.arange(len(slots)), counts))
    return [_pattern_request(slots[j][0], slots[j][1], triples, rng) for j in order]


def arrivals(n: int, seconds: float, rng) -> np.ndarray:
    """Due times of `n` Poisson arrivals in [0, seconds)."""
    return np.sort(rng.uniform(0.0, seconds, n))


class RoundStream:
    """One closed-loop client's requests, round after round."""

    def __init__(self, items: list, triples, n_preds: int, rng):
        self.items, self.triples, self.n_preds, self.rng = items, triples, n_preds, rng
        self._queue: list = []

    def round(self) -> list:
        """One round's requests, in this client's seeded order."""
        out = []
        for item in self.items:
            if item["kind"] == "predicate_scan":
                out += [Request("?p?", [(None, p, None)]) for p in range(self.n_preds)]
            elif item["kind"] == "patterns":
                out += [_pattern_request(item, shape, self.triples, self.rng)
                        for shape in item["shapes"]]
            else:
                raise ValueError(f"unknown round item {item['kind']!r}")
        return [out[i] for i in self.rng.permutation(len(out))]

    def next(self) -> Request:
        if not self._queue:
            self._queue = self.round()[::-1]
        return self._queue.pop()


# -- the load loops ---------------------------------------------------------

@dataclass
class Record:
    """One request of the window; times in seconds from the window start."""

    index: int
    kind: str
    patterns: list
    due: float
    start: float = -1.0
    end: float = -1.0
    counts: list | None = None   # results per pattern
    answer: list | None = None   # kept whole only for sampled requests
    error: str | None = None

    @property
    def done(self) -> bool:
        return self.end >= 0

    @property
    def n_triples(self) -> int:
        return sum(self.counts) if self.counts else 0


@dataclass
class Window:
    seconds: float
    records: list = field(default_factory=list)
    lag_s: list = field(default_factory=list)   # how late each send was
    closed_s: float = 0.0     # when the last request ended (or the deadline)


def _serve(call, rec: Record, t0: float, keep: bool, span) -> None:
    rec.start = time.perf_counter() - t0
    try:
        with span(rec.kind):
            ans = call(rec.patterns)
        rec.counts = [len(a) for a in ans]
        if keep:
            rec.answer = ans
    except Exception as e:  # a failed request is counted and reported
        rec.error = f"{type(e).__name__}: {e}"
    rec.end = time.perf_counter() - t0


def run_open(call, requests: list, due: np.ndarray, seconds: float, workers: int,
             grace: float, keep, span) -> Window:
    """Send each request at its due time; wait for all, at most `grace`
    seconds past the later of the window's close and the last due time."""
    win = Window(seconds)
    win.records = [Record(i, r.kind, r.patterns, float(d))
                   for i, (r, d) in enumerate(zip(requests, due))]
    todo: queue.Queue = queue.Queue()
    left = threading.Semaphore(0)
    t0 = time.perf_counter()

    def worker():
        while True:
            rec = todo.get()
            if rec is None:
                return
            _serve(call, rec, t0, keep(rec.index), span)
            left.release()

    threads = [threading.Thread(target=worker, daemon=True, name=f"bench-client-{i}")
               for i in range(workers)]
    for t in threads:
        t.start()
    for rec in win.records:
        wait = rec.due - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        win.lag_s.append(time.perf_counter() - t0 - rec.due)
        todo.put(rec)
    deadline = t0 + max(seconds, float(due[-1]) if len(due) else 0.0) + grace
    for _ in win.records:
        if not left.acquire(timeout=max(deadline - time.perf_counter(), 0.0)):
            break
    win.closed_s = time.perf_counter() - t0
    for _ in threads:
        todo.put(None)
    return win


def run_closed(call, streams: list, seconds: float, grace: float, keep, span) -> Window:
    """Each client sends its next request when its last returns, until the
    window closes; requests in flight then are waited for (at most
    `grace` seconds) and checked, but their results fall outside it."""
    win = Window(seconds)
    lock = threading.Lock()
    t0 = time.perf_counter()

    def client(c: int, stream: RoundStream):
        n = 0
        while time.perf_counter() - t0 < seconds:
            req = stream.next()
            rec = Record(-1, req.kind, req.patterns, time.perf_counter() - t0)
            with lock:
                rec.index = len(win.records)
                win.records.append(rec)
            _serve(call, rec, t0, keep(c, n), span)
            n += 1

    threads = [threading.Thread(target=client, args=(c, s), daemon=True,
                                name=f"bench-client-{c}")
               for c, s in enumerate(streams)]
    for t in threads:
        t.start()
    deadline = t0 + seconds + grace
    for t in threads:
        t.join(timeout=max(deadline - time.perf_counter(), 0.0))
    win.closed_s = time.perf_counter() - t0
    return win
