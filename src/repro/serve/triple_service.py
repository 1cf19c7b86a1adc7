"""Batched triple-query serving on the compressed grammar.

Production traffic arrives as independent (S,P,O) lookups; answering them
one at a time wastes the engine's batch path. `TripleQueryService`
accumulates submitted patterns into a pending micro-batch and executes the
whole batch in ONE level-synchronous frontier (`TripleQueryEngine
.query_batch_view`), so per-request Python overhead is paid once per
flush instead of once per query. Results flow through
:class:`~repro.core.query.QueryResultView` internally — duplicate tickets
share one per-pattern entry instead of replicated copies (`flush_view`
exposes the view; `flush` materializes shared tuple lists per ticket).
`query_many` is the synchronous convenience wrapper (submit-all + flush).

The engine's cross-request result cache makes dedup streaming: a pattern
seen in any earlier flush (or earlier in this one) is answered from the
cache instead of re-executing the frontier. Flush-time stats therefore
separate *submitted* queries from *executed* unique patterns and *cache
hits*.

The service is numpy-only — it runs wherever the engine runs. With
tracing on (:mod:`repro.tracing`), each ``query``/``query_many`` is an
``itr.request`` span and its tuple building an ``itr.tuples`` span.

Thread-safety: the pending queue is internally locked, and every
request-plane entry (``query``/``query_many``/``flush``) takes its
tickets atomically — two threads calling ``query()`` concurrently can
never read each other's results. What happens *after* the tickets are
taken depends on the subclass: :class:`ShardedTripleService
<repro.serve.sharded.ShardedTripleService>` executes under a reader lock
and is safe from any number of threads, while
:class:`TripleQueryService` fronts one engine (one arena, one frontier)
and must not be flushed from two threads at once — the full contract is
in ``docs/CONCURRENCY.md``.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

import numpy as np

from repro.core.query import QueryResultView, TripleQueryEngine
from repro.tracing import span


@dataclass
class ServiceStats:
    """Rolling serving counters.

    `queries` counts submitted patterns; `executed` counts unique patterns
    that actually ran on the engine (frontier or scalar worklist); and
    `cache_hits` counts unique patterns answered from the cross-request
    result cache. In-batch duplicates are neither executed nor cache hits —
    they ride on batch dedup — so `executed + cache_hits <= queries` per
    flush, with equality only when every pattern in the flush is distinct.
    """

    queries: int = 0
    batches: int = 0
    results: int = 0
    executed: int = 0
    cache_hits: int = 0
    inserted: int = 0   # triples actually added via the mutation API
    deleted: int = 0    # triples actually removed
    rebuilds: int = 0   # grammar recompressions (auto + explicit)
    total_s: float = 0.0

    @property
    def cache_hit_rate(self) -> float:
        n = self.executed + self.cache_hits
        return self.cache_hits / n if n else 0.0


@dataclass
class _Pending:
    s: list = field(default_factory=list)
    p: list = field(default_factory=list)
    o: list = field(default_factory=list)


class MicroBatchService:
    """Shared request plane for micro-batching query services.

    Provides the pending queue (`submit` -> ticket, None = unbound slot,
    encoded as -1), the view-backed `flush` (shared tuple lists per
    unique pattern — treat results as read-only), and the synchronous
    entries `query` / `query_many`. Subclasses implement
    :meth:`_flush_columns`, which executes aligned int64 pattern columns
    and returns the :class:`QueryResultView`.

    The pending queue is guarded by an internal lock, and each
    synchronous entry takes its tickets *atomically*: `query` grabs its
    own ticket together with everything already pending (flushing
    bystanders alongside, as ever), `query_many` takes the whole queue
    but returns only its own patterns' results, and two threads doing
    either can never observe each other's tickets. The raw
    `submit`/`flush` split remains single-caller by nature — a ticket is
    an index into whichever flush happens next, so handing submit and
    flush to different threads needs external coordination (see
    ``docs/CONCURRENCY.md``).
    """

    def __init__(self):
        self._pending = _Pending()
        self._pending_lock = threading.Lock()

    def _submit_locked(self, s: int | None, p: int | None, o: int | None) -> int:
        ticket = len(self._pending.s)
        self._pending.s.append(-1 if s is None else int(s))
        self._pending.p.append(-1 if p is None else int(p))
        self._pending.o.append(-1 if o is None else int(o))
        return ticket

    def submit(self, s: int | None, p: int | None, o: int | None) -> int:
        """Queue one (S,P,O) pattern; returns its ticket in the next flush."""
        with self._pending_lock:
            return self._submit_locked(s, p, o)

    @property
    def pending(self) -> int:
        return len(self._pending.s)

    def _take_pending_locked(self):
        batch, self._pending = self._pending, _Pending()
        if not batch.s:
            return None
        return (np.asarray(batch.s, dtype=np.int64),
                np.asarray(batch.p, dtype=np.int64),
                np.asarray(batch.o, dtype=np.int64))

    def _take_pending(self):
        with self._pending_lock:
            return self._take_pending_locked()

    def _flush_columns(self, s, p, o) -> QueryResultView:
        """Execute one taken batch (aligned int64 columns, -1 = unbound).

        Subclass hook: owns timing/stats and the actual execution. Must
        be safe to call without the pending lock held — the sharded
        service runs it under its reader lock from many threads at once.
        """
        raise NotImplementedError

    def flush_view(self) -> QueryResultView:
        """Execute all pending queries; results as a shared-entry view
        indexed by ticket (:class:`QueryResultView`) — duplicate tickets
        share one entry, nothing is replicated. An empty flush is a
        no-op: no batch is counted, no time accrued.
        """
        cols = self._take_pending()
        if cols is None:
            return QueryResultView.empty()
        return self._flush_columns(*cols)

    def flush(self) -> list[tuple]:
        """Execute all pending queries; returns results indexed by ticket.

        View-backed: each result sequence is built once per unique
        pattern and shared — as an immutable tuple — across duplicate
        tickets.
        """
        return self.flush_view().tuple_lists()

    def query(self, s: int | None, p: int | None, o: int | None) -> tuple:
        """One synchronous query: submit + flush, returning THIS pattern's
        results (anything already pending is flushed alongside, its
        tickets still owned by whoever submitted them). The ticket take
        is atomic, so concurrent `query` callers get disjoint batches."""
        with span("itr.request"):
            with self._pending_lock:
                ticket = self._submit_locked(s, p, o)
                cols = self._take_pending_locked()
            view = self._flush_columns(*cols)
            with span("itr.tuples"):
                return view.tuple_lists()[ticket]

    def query_many(self, patterns) -> list[tuple]:
        """patterns: iterable of (s, p, o) with None = unbound. Returns
        one result tuple per pattern, in order — results for tickets
        other callers already had pending are flushed alongside but not
        returned here (they belong to those callers' flush)."""
        with span("itr.request"):
            with self._pending_lock:
                base = len(self._pending.s)
                for s, p, o in patterns:
                    self._submit_locked(s, p, o)
                cols = self._take_pending_locked()
            if cols is None:
                return []
            view = self._flush_columns(*cols)
            with span("itr.tuples"):
                return view.tuple_lists()[base:]


class TripleQueryService(MicroBatchService):
    """Micro-batching front end over a :class:`TripleQueryEngine`.

    `submit` returns a ticket (index into the next flush); `flush` runs the
    pending batch and returns one result list per ticket. `max_batch`
    bounds a single frontier's width: larger pending sets are executed in
    chunks so memory stays flat under unselective patterns.
    """

    def __init__(self, engine: TripleQueryEngine, max_batch: int = 1024):
        super().__init__()
        self.engine = engine
        self.max_batch = int(max_batch)
        self.stats = ServiceStats()

    def _flush_columns(self, s, p, o) -> QueryResultView:
        """Execute one taken batch on the engine, chunked by `max_batch`.

        NOT safe from multiple threads at once: the engine reuses one
        frontier arena per instance. Use the sharded service (which
        wraps execution in per-engine locks) for concurrent callers.
        """
        n = len(s)
        cache = self.engine.cache
        before = cache.stats.snapshot() if cache is not None else None
        views: list[QueryResultView] = []
        t0 = time.perf_counter()
        executed_uncached = 0
        for lo in range(0, n, self.max_batch):
            hi = min(lo + self.max_batch, n)
            chunk = self.engine.query_batch_view(s[lo:hi], p[lo:hi], o[lo:hi])
            views.append(chunk)
            self.stats.batches += 1
            if before is None:  # no cache: in-batch dedup still collapses
                executed_uncached += len(chunk.entries)
        view = views[0] if len(views) == 1 else QueryResultView.concat(views)
        dt = time.perf_counter() - t0
        self.stats.queries += n
        self.stats.results += view.total_results()
        self.stats.total_s += dt
        if before is not None:
            # engine cache counters moved once per *unique* pattern: the
            # hit delta is served-from-cache, the miss delta is executed
            self.stats.cache_hits += cache.stats.hits - before.hits
            self.stats.executed += cache.stats.misses - before.misses
        else:
            self.stats.executed += executed_uncached
        return view

    # -- mutation ---------------------------------------------------------
    def insert_triples(self, triples) -> int:
        """Insert (s, p, o) rows into the engine's delta overlay; returns
        how many were actually new. Subsequent flushes see them — the
        engine bumps its cache generation and auto-rebuilds past
        ``ITR_DELTA_BUDGET`` (see :meth:`TripleQueryEngine.insert_triples`)."""
        before = self.engine.rebuild_count
        n = self.engine.insert_triples(triples)
        self.stats.inserted += n
        self.stats.rebuilds += self.engine.rebuild_count - before
        return n

    def delete_triples(self, triples) -> int:
        """Delete (s, p, o) rows; returns how many were actually present."""
        before = self.engine.rebuild_count
        n = self.engine.delete_triples(triples)
        self.stats.deleted += n
        self.stats.rebuilds += self.engine.rebuild_count - before
        return n

    def rebuild(self, config=None) -> bool:
        """Recompress base+delta now (regardless of budget); True if the
        overlay was non-empty and a rebuild ran."""
        rebuilt = self.engine.rebuild(config)
        self.stats.rebuilds += rebuilt
        return rebuilt
