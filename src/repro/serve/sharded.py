"""Sharded multi-engine triple serving: partitioned engines behind a
scatter-gather router with a shared result-cache tier.

One engine per graph partition (``repro.distributed.partition``), all
sharing a single :class:`~repro.core.result_cache.QueryResultCache` keyed
by ``(shard, S, P, O)`` through per-shard views — one budget, one stats
block, no collisions. The router sends each pattern to the single shard
that owns it when the partition axis is bound (P under ``predicate_hash``,
S under ``node_range``) and scatter-gathers the unselective ones (``?P?``
and ``??O`` under ``node_range``, ``S??``/``??O`` under
``predicate_hash``, ``???`` always) across every shard in ONE micro-batch
flush each — a flush never issues more than one engine call per shard per
``max_batch`` chunk, regardless of how many patterns scatter.

Merging is view-based end to end: each shard answers with shared
per-pattern entry arrays (:class:`~repro.core.query.QueryResultView`), a
scattered pattern's answer is the concatenation of its per-shard entries
(partitions are disjoint, so no dedup), and duplicate tickets share one
merged entry. Merged results are themselves cached in a reserved
namespace of the shared tier, so a *warm* scattered pattern is one
lookup — no fan-out, no re-concatenation. ``flush()`` materializes tuple
lists per *unique* pattern — ``flush_view()`` is the zero-replication
escape hatch.

Partitions are mutable. ``insert_triples``/``delete_triples`` route
mutation rows to their owning shard (``PartitionPlan.route_triples`` —
the same placement rule the build used, so a shard's overlay only ever
holds triples that shard would answer for) and apply them to that
engine's :class:`~repro.core.delta.DeltaOverlay`; ``invalidate(shard)``
then bumps ONLY the mutated shards' cache generations (plus the merged
namespace, whose entries depend on every shard) so the other shards stay
warm. When a shard's overlay outgrows the engines' ``ITR_DELTA_BUDGET``
it alone is recompressed through the RePair pipeline and atomically
swapped — :meth:`ShardedTripleService.rebuild` is the explicit handle —
which is what makes rebuild cost O(dirty shards), not O(graph).

Partitions also *re-cut themselves*. Mutation skews shard loads and the
build-time `PartitionPlan` never follows, so the tier watches its live
per-shard edge counts (base + overlay) and — when their ``max/mean``
skew crosses ``ITR_REBALANCE_SKEW``, or on an explicit
:meth:`ShardedTripleService.rebalance` — computes a successor plan
(`repro.distributed.rebalance`: re-quantiled ``node_range`` boundaries
or LPT-re-packed predicate groups) and migrates the rows whose owner
changed, in bounded batches: each batch arrives through the destination
overlay before leaving the source via tombstones inside one call, so
partitions stay disjoint at every public boundary, and only the two
shards it touched lose their warm cache entries. While moves are
pending the router stops trusting single-shard ownership for any
pattern the outgoing and incoming plans route differently (it scatters
instead — exact on disjoint partitions wherever each row currently
sits), and mutations of in-motion rows delete on both candidate shards
/ insert on the incoming owner after probing the outgoing one, so
serving and writes stay exact mid-migration.

The tier is **safe under concurrent callers** (contract:
``docs/CONCURRENCY.md``). Queries run as *readers* under the service's
:class:`~repro.serve.concurrency.RWLock` — any number of threads flush at
once, each seeing one consistent (plan, migration, engines) state —
while mutations, rebuilds, rebalancing, and failure handling are fully
exclusive *writers*, so every single-threaded routing invariant above
survives arbitrary interleaving. Within one flush, scatter-gather work
additionally fans out across shard engines on a thread pool sized by
``ITR_SERVE_THREADS`` (engines are independent and the post-build read
path is numpy, which releases the GIL); per-engine locks serialize the
engines' internal scratch state, and the merge is deterministic in shard
order, so threaded and sequential flushes are byte-identical.
"""
from __future__ import annotations

import itertools
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

from repro.core import (
    Hypergraph,
    LabelTable,
    QueryResultCache,
    TripleQueryEngine,
    compress,
)
from repro.core.flatten import concat_ragged
from repro.core.delta import as_triple_rows
from repro.core.query import (
    _DEFAULT_BUDGET,
    QueryResultView,
    _env_flag,
    _freeze_entry,
)
from repro.distributed.partition import (
    PartitionPlan,
    make_plan,
    partition_triples,
)
from repro.distributed.rebalance import (
    live_shard_edges,
    measure_skew,
    plan_rebalance,
    resolve_rebalance_skew,
)
from repro.persist.crash import crash_point
from repro.serve.concurrency import RWLock, resolve_serve_threads
from repro.serve.triple_service import MicroBatchService
from repro.tracing import span

# sentinel: "create a default shared QueryResultCache unless disabled by env"
_DEFAULT_CACHE = object()

# sentinel: "resolve the rebalance trigger from ITR_REBALANCE_SKEW"
_DEFAULT_SKEW = object()

# migration rows an AUTO-triggered rebalance applies per mutation call:
# the trigger starts the migration and each subsequent applied mutation
# drains another bounded chunk, so one insert never blocks on moving the
# whole diff (explicit rebalance() drains to completion on demand)
_AUTO_MOVES_PER_CALL = 4096

# reserved shard id for cross-shard MERGED scattered results in the shared
# tier (real shards are >= 0; -1 is the single-engine default namespace).
# A warm scattered pattern is then one lookup instead of a full fan-out +
# re-concatenation; invalidate() bumps this namespace alongside any shard,
# since a merged entry depends on every shard's data.
_MERGED_SHARD = -2


@dataclass
class ShardedServiceStats:
    """Rolling counters for the scatter-gather router.

    `owned` / `scattered` count *unique* patterns per flush (the unit of
    routing work); `shard_batches` counts per-shard engine micro-batch
    executions — each flush issues up to ``ceil(sub_batch / max_batch)``
    chunks per shard, where a shard's sub-batch is its owned patterns
    plus every scattered one.
    """

    queries: int = 0
    flushes: int = 0
    results: int = 0
    unique_patterns: int = 0
    owned: int = 0
    scattered: int = 0
    merged_hits: int = 0  # scattered patterns answered from the merged tier
    shard_batches: int = 0
    inserted: int = 0     # triples actually added (mutation no-ops excluded)
    deleted: int = 0      # triples actually removed
    rebuilds: int = 0     # per-shard grammar recompressions (auto + explicit)
    rebalances: int = 0   # migrations started (auto-trigger + explicit)
    migrated_rows: int = 0  # rows moved between shards by rebalancing
    degraded_patterns: int = 0  # patterns answered with a failed shard's hole
    replica_flushes: int = 0  # flushes served by a read replica group
    bgp_queries: int = 0      # whole-BGP joins answered (hits + executions)
    bgp_cache_hits: int = 0   # BGPs served straight from the merged cache
    string_queries: int = 0   # query_strings / query_bgp_strings calls
    unknown_term_empties: int = 0  # string queries short-circuited to empty
    total_s: float = 0.0


class ShardedTripleService(MicroBatchService):
    """Scatter-gather front end over P partitioned :class:`TripleQueryEngine`s.

    Construct directly from pre-built engines + plan (engines must cover
    plan.n_shards, in shard order), or via :meth:`build` from raw triples.
    The request plane (`submit`/`flush`/`query_many`) is the shared
    :class:`~repro.serve.triple_service.MicroBatchService` surface.
    """

    def __init__(self, engines: list[TripleQueryEngine], plan: PartitionPlan,
                 cache: QueryResultCache | None = None, max_batch: int = 1024,
                 config=None, rebalance_skew=_DEFAULT_SKEW,
                 serve_threads: int | None = None):
        super().__init__()
        assert len(engines) == plan.n_shards, \
            f"{len(engines)} engines for {plan.n_shards} shards"
        self.engines = engines
        self.plan = plan
        self.cache = cache  # the shared tier (engines hold shard views of it)
        self.max_batch = int(max_batch)
        self.config = config  # RepairConfig reused by per-shard rebuilds
        self.stats = ShardedServiceStats()
        # concurrency discipline (docs/CONCURRENCY.md): queries read-lock,
        # every mutating surface write-locks, so routing invariants pinned
        # single-threaded hold under any interleaving
        self._rw = RWLock()
        # engines keep per-instance scratch (frontier arena, memo tables),
        # so two threads of ONE flush must not enter the same engine at once
        self._engine_locks = [threading.Lock() for _ in engines]
        self._stats_lock = threading.Lock()  # stats blocks are not atomic
        # flush ids: the `flush` stat of a flush's spans on pool threads
        self._flush_ids = itertools.count(1)
        #: scatter fan-out width (threads per flush); 1 = sequential
        self.serve_threads = resolve_serve_threads(serve_threads)
        self._pool: ThreadPoolExecutor | None = None  # lazy, sized on first use
        self._pool_lock = threading.Lock()
        # auto-rebalance trigger (max/mean live-edge skew); None = explicit only
        if rebalance_skew is _DEFAULT_SKEW:
            self.rebalance_skew = resolve_rebalance_skew()
        else:
            self.rebalance_skew = None if rebalance_skew is None \
                else resolve_rebalance_skew(rebalance_skew)
        self._migration = None        # in-flight RebalancePlan, or None
        self._futile_total: int | None = None  # auto-trigger backoff anchor
        #: shards whose recovery failed — served as empty holes, writes refused
        self.failed_shards: set[int] = set()
        # durability hook (repro.persist.service installs it): called as
        # _journal(kind, payload) BEFORE a rebalance state change applies
        self._journal = None
        # cache-namespace indirection: shard k's entries live under
        # namespace _cache_ns[k] of the shared tier, merged scatter results
        # under _merged_ns. The primary uses the identity mapping; replica
        # group services (repro.serve.replication) get disjoint negative
        # namespaces, so a lagging replica serves from its own generation's
        # entries and never mixes them with the primary's fresher ones.
        self._cache_ns: list[int] = list(range(plan.n_shards))
        self._merged_ns: int = _MERGED_SHARD
        # read-replica dispatch (a ReplicationManager once the durable
        # service enables replication; flushes then prefer a replica group)
        self._replicas = None
        # optional TermDict for the string-term surfaces (attach_term_dict)
        self.term_dict = None

    # -- construction ----------------------------------------------------
    @classmethod
    def build(cls, triples: np.ndarray, n_nodes: int, n_preds: int,
              n_shards: int = 4, strategy: str = "predicate_hash",
              config=None, cache=_DEFAULT_CACHE, crossover: int | None = None,
              max_batch: int = 1024, delta_budget=_DEFAULT_BUDGET,
              rebalance_skew=_DEFAULT_SKEW,
              serve_threads: int | None = None) -> "ShardedTripleService":
        """Partition -> compress each subgraph -> one engine per shard.

        `cache` is the shared result-cache tier (default: one
        :class:`QueryResultCache` shared by all shards, disabled by
        ``ITR_RESULT_CACHE=0``; pass ``None`` to disable explicitly).
        `delta_budget` is each engine's mutation-overlay rebuild threshold
        (default: read ``ITR_DELTA_BUDGET``; ``None`` = auto-rebuild off).
        `rebalance_skew` is the live max/mean shard-load ratio at/above
        which the mutation path starts an online rebalance (default: read
        ``ITR_REBALANCE_SKEW``; ``None`` = only explicit ``rebalance()``).
        `serve_threads` is the scatter fan-out width (default: read
        ``ITR_SERVE_THREADS``, falling back to the core count).
        """
        plan = make_plan(strategy, n_shards, n_nodes, n_preds, triples=triples)
        if cache is _DEFAULT_CACHE:
            cache = QueryResultCache() if _env_flag("ITR_RESULT_CACHE", True) else None
        engine_kwargs = {} if delta_budget is _DEFAULT_BUDGET \
            else {"delta_budget": delta_budget}
        engines = []
        for k, sub in enumerate(partition_triples(triples, plan)):
            table = LabelTable.terminals([2] * n_preds)
            graph = Hypergraph.from_triples(sub, n_nodes)
            grammar, _ = compress(graph, table, config)
            engine = TripleQueryEngine(
                grammar,
                cache=cache.shard_view(k) if cache is not None else None,
                crossover=crossover, config=config, **engine_kwargs)
            engine._base_edges = len(sub)  # skew checks skip the decompress
            engines.append(engine)
        return cls(engines, plan, cache, max_batch, config=config,
                   rebalance_skew=rebalance_skew, serve_threads=serve_threads)

    @property
    def n_shards(self) -> int:
        return self.plan.n_shards

    # -- request plane ---------------------------------------------------
    def _flush_columns(self, s, p, o) -> QueryResultView:
        """Execute one taken batch under the reader lock.

        Safe from any number of threads at once: the read lock pins one
        consistent (plan, migration, engines) state for the whole flush,
        and everything `_run` touches concurrently (shared cache,
        per-engine scratch, stats) is locked at its own level.
        """
        n = len(s)
        flush = next(self._flush_ids)
        t0 = time.perf_counter()
        with span("itr.flush", flush=flush):
            with span("itr.wait.rwlock"):
                self._rw.acquire_read()
            try:
                group = None
                if self._replicas is not None and not self._rw.write_held:
                    # write_held while we hold read means WE are the writer (a
                    # write-locked probe, e.g. contains_triples mid-mutation):
                    # it must observe the primary's half-applied state, not a
                    # replica's. Plain readers can never see write_held here.
                    group = self._replicas.acquire()
                if group is not None:
                    try:
                        # the whole flush runs on ONE consistent replica group,
                        # so merged scatter results never mix freshness levels;
                        # the group's own read lock excludes its WAL-tail applies
                        with group.service._rw.read():
                            view = group.service._run(s, p, o, flush)
                    finally:
                        self._replicas.release(group)
                else:
                    view = self._run(s, p, o, flush)
            finally:
                self._rw.release_read()
        dt = time.perf_counter() - t0
        with self._stats_lock:
            st = self.stats
            st.queries += n
            st.flushes += 1
            st.results += view.total_results()
            st.total_s += dt
            if group is not None:
                st.replica_flushes += 1
        return view

    def query_bgp(self, patterns):
        """Evaluate a basic graph pattern over the sharded tier.

        Sub-pattern batches go through :meth:`_flush_columns`, so each
        join step inherits the full serving stack: micro-batch dedup, the
        shared result cache, owned-vs-scatter shard routing, the threaded
        fan-out pool, and replica dispatch. Whole-BGP results are cached
        in the merged-scatter namespace (disable with ``ITR_BGP_CACHE=0``)
        keyed by the canonicalized pattern list; the namespace generation
        is bumped by `invalidate()` on ANY shard change, so it acts as a
        tier-wide generation vector and stale joins can never be served.

        Concurrency: each join step takes the read lock on its own (same
        discipline as `query`), so a BGP is atomic *per step*, not across
        steps — a mutation landing between steps can surface a mixed
        view, exactly like two independent `query` calls would see. The
        cache insert is guarded by the generation observed before the
        first step, so such a mixed result is never cached.
        """
        from repro.core.bgp import (
            SelectivityStats,
            bgp_cache_key,
            bgp_variables,
            decode_result_entry,
            encode_result_entry,
            execute_bgp,
            parse_bgp,
        )
        patterns = parse_bgp(patterns)
        out_vars = bgp_variables(patterns)
        cache = self.cache if _env_flag("ITR_BGP_CACHE", True) else None
        key = gen0 = None
        if cache is not None:
            key = bgp_cache_key(patterns)
            gen0 = cache.generation(self._merged_ns)
            hit = cache.lookup(*key, shard=self._merged_ns)
            if hit is not None:
                with self._stats_lock:
                    self.stats.bgp_queries += 1
                    self.stats.bgp_cache_hits += 1
                return decode_result_entry(hit, out_vars)
        with self._rw.read():  # pin engines for the stats pass only
            stats = SelectivityStats.merge(
                eng.selectivity() for eng in self.engines)
        result = execute_bgp(patterns, self._flush_columns, stats)
        if cache is not None and cache.generation(self._merged_ns) == gen0:
            cache.insert(*key, encode_result_entry(result),
                         shard=self._merged_ns)
        with self._stats_lock:
            self.stats.bgp_queries += 1
        return result

    # -- string-term surfaces (require an attached TermDict) ---------------
    def attach_term_dict(self, term_dict) -> None:
        """Attach a :class:`~repro.core.term_dict.TermDict` mapping term
        strings to the dense ids this tier serves. One dictionary covers
        the whole tier (ids are global, shards are an id-space partition);
        string queries resolve terms once here at the boundary, then run
        on ids through the normal scatter-gather path."""
        self.term_dict = term_dict

    def _require_term_dict(self):
        if self.term_dict is None:
            raise ValueError(
                "no term dictionary attached — call attach_term_dict() "
                "(or ingest through repro.data.ingest, which attaches one)")
        return self.term_dict

    def query_strings(self, s: str | None, p: str | None, o: str | None):
        """One (S, P, O) pattern with *term strings* (``None`` = unbound).
        A bound term the dictionary has never seen short-circuits to
        ``[]`` without touching any shard. Returns term triples."""
        td = self._require_term_dict()
        from repro.core.term_dict import resolve_string_triple
        s_id, p_id, o_id, known = resolve_string_triple(td, s, p, o)
        with self._stats_lock:
            self.stats.string_queries += 1
            if not known:
                self.stats.unknown_term_empties += 1
        if not known:
            return []
        out = []
        for label, nodes in self.query(s_id, p_id, o_id):
            if len(nodes) != 2:
                raise ValueError(
                    f"string queries need rank-2 edges, got rank {len(nodes)}")
            out.append((td.node_term(nodes[0]), td.pred_term(label),
                        td.node_term(nodes[1])))
        return out

    def query_bgp_strings(self, patterns) -> list[dict]:
        """`query_bgp` with string terms: patterns are (s, p, o) tuples of
        ``?var`` names / constant term strings; unknown constants
        short-circuit to ``[]`` without executing any join step. Returns
        ``[{var: term}, ...]`` binding rows (deterministic order)."""
        td = self._require_term_dict()
        from repro.core.term_dict import bgp_result_to_terms, resolve_string_bgp
        id_patterns, pred_vars, known = resolve_string_bgp(td, patterns)
        with self._stats_lock:
            self.stats.string_queries += 1
            if not known:
                self.stats.unknown_term_empties += 1
        if not known:
            return []
        return bgp_result_to_terms(td, self.query_bgp(id_patterns), pred_vars)

    def add_node_terms(self, terms) -> np.ndarray:
        """Mint node ids for *terms* (known terms keep theirs); int64 ids
        in input order. Node ids may extend past the build-time universe —
        the plan routes them (clipped node ranges / hashed predicates)."""
        with self._rw.write():
            return self._require_term_dict().add_node_terms(terms)

    def add_pred_terms(self, terms) -> np.ndarray:
        """Mint predicate ids for *terms*. Predicate capacity is fixed at
        build time (`n_preds` terminal labels per shard engine), so terms
        that would mint past it raise instead of corrupting the id space —
        pre-size `n_preds` when building a tier for streaming ingestion."""
        with self._rw.write():
            td = self._require_term_dict()
            fresh = [t for t in dict.fromkeys(terms) if td.pred_id(t) is None]
            if td.n_preds + len(fresh) > self.plan.n_preds:
                raise ValueError(
                    f"predicate capacity exhausted: tier was built with "
                    f"n_preds={self.plan.n_preds}, dictionary holds "
                    f"{td.n_preds}, cannot mint {len(fresh)} more — rebuild "
                    "the tier with a larger predicate capacity")
            return td.add_pred_terms(terms)

    # -- fan-out pool ------------------------------------------------------
    def set_serve_threads(self, n: int | None) -> int:
        """Change the scatter fan-out width; returns the resolved value.
        ``None`` re-reads ``ITR_SERVE_THREADS``. The old pool (if any) is
        drained and replaced lazily on the next threaded flush."""
        self.serve_threads = resolve_serve_threads(n)
        with self._pool_lock:
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=True)
        return self.serve_threads

    def close(self) -> None:
        """Drain the fan-out pool and shut down any attached replica tier
        (idempotent across the whole hierarchy — every close here and in
        the replica groups' own services is a no-op the second time; the
        primary service itself stays usable, a later threaded flush just
        re-creates its pool)."""
        replicas, self._replicas = self._replicas, None
        if replicas is not None:
            replicas.close()  # closes each group service's pool too
        with self._pool_lock:
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=True)

    def _ensure_pool(self) -> ThreadPoolExecutor:
        with self._pool_lock:
            if self._pool is None:
                width = min(self.serve_threads, max(1, self.n_shards))
                self._pool = ThreadPoolExecutor(
                    max_workers=width, thread_name_prefix="itr-serve")
            return self._pool

    # -- scatter-gather core ---------------------------------------------
    def _run(self, s: np.ndarray, p: np.ndarray, o: np.ndarray,
             flush: int) -> QueryResultView:
        """One flush's unique patterns, routed, executed on the shards and
        merged; `flush` is the id its spans on pool threads carry."""
        with span("itr.route"):
            # service-level dedup: route and merge each unique pattern once
            key = np.stack([s, p, o], axis=1)
            uniq, inv = np.unique(key, axis=0, return_inverse=True)
            inv = inv.reshape(-1)
            nu = len(uniq)
            u_s, u_p, u_o = uniq[:, 0], uniq[:, 1], uniq[:, 2]
            routes = self._route_patterns(u_s, u_p, u_o)
            cache = self.cache

            entries: list = [None] * nu
            # scattered patterns: the merged cross-shard result is itself cached
            # (reserved namespace), so a warm repeat is one lookup, not a fan-out
            scatter: list[int] = []
            merged_hits = 0
            for u in np.flatnonzero(routes < 0):
                u = int(u)
                hit = cache.lookup(u_s[u], u_p[u], u_o[u], shard=self._merged_ns) \
                    if cache is not None else None
                if hit is None:
                    scatter.append(u)
                else:
                    entries[u] = hit
                    merged_hits += 1
            scatter = np.asarray(scatter, dtype=np.int64)
            degraded = 0
            if self.failed_shards:
                # every pattern owned by (or scattered across) a failed shard is
                # answered with that shard's rows missing — count the holes
                failed = sorted(self.failed_shards)
                degraded = int(np.isin(routes, failed).sum()) + len(scatter)
            with self._stats_lock:
                self.stats.unique_patterns += nu
                self.stats.merged_hits += merged_hits
                self.stats.owned += int((routes >= 0).sum())
                self.stats.scattered += int((routes < 0).sum())
                self.stats.degraded_patterns += degraded

        # merge-missing scattered patterns accumulate one chunk per shard;
        # work items are collected first so they can fan out across the pool
        parts: dict[int, list] = {int(u): [] for u in scatter}
        work: list[tuple[int, np.ndarray, np.ndarray]] = []
        for k in range(len(self.engines)):
            if k in self.failed_shards:
                continue  # hole: owned patterns fall through to empty entries
            own = np.flatnonzero(routes == k)
            idx = own if len(scatter) == 0 else np.concatenate([own, scatter])
            if len(idx) == 0:
                continue
            work.append((k, own, idx))
        if len(work) > 1 and self.serve_threads > 1:
            # pool workers call _shard_entries only — they never touch the
            # RWLock (a worker acquiring read while a writer waits on the
            # submitting reader would deadlock by writer preference)
            pool = self._ensure_pool()
            futs = [pool.submit(self._shard_entries, k,
                                u_s[idx], u_p[idx], u_o[idx], flush)
                    for k, _, idx in work]
            with span("itr.wait.shards"):
                results = [f.result() for f in futs]
        else:
            results = [self._shard_entries(k, u_s[idx], u_p[idx], u_o[idx], flush)
                       for k, _, idx in work]
        # merge in shard order (work is k-ascending): threaded and
        # sequential flushes produce byte-identical views
        with span("itr.merge"):
            n_batches = 0
            for (k, own, idx), (pos_entries, nb) in zip(work, results):
                n_batches += nb
                for j, u in enumerate(own):
                    entries[int(u)] = pos_entries[j]
                for j, u in enumerate(scatter):
                    parts[int(u)].append(pos_entries[len(own) + j])
            with self._stats_lock:
                self.stats.shard_batches += n_batches
            for u, chunks in parts.items():
                # merged chunks are shared across duplicate tickets: read-only.
                # A scattered result is deliberately held twice in the shared
                # tier (per-shard chunks + this merged copy): the merged entry
                # makes warm repeats O(1), while the per-shard chunks mean a
                # single-shard invalidate() re-executes ONE shard, not all P.
                entry = _freeze_entry(concat_ragged(chunks))
                entries[u] = entry
                if cache is not None:
                    cache.insert(u_s[u], u_p[u], u_o[u], entry,
                                 shard=self._merged_ns)
            for u in range(nu):  # shards==0 or routing gaps: empty result
                if entries[u] is None:
                    entries[u] = _freeze_entry(concat_ragged([]))
        return QueryResultView(entries, inv)

    def _route_patterns(self, s: np.ndarray, p: np.ndarray, o: np.ndarray
                        ) -> np.ndarray:
        """Owning shard per unique pattern (-1 = scatter-gather).

        The migration-safe routing rule: while a rebalance migration is
        in flight, a pattern is sent to a single shard only when the
        outgoing AND incoming plans agree on it — rows whose ownership is
        changing may physically sit on either side mid-migration, and
        agreement means none of the pattern's rows are among them.
        Everything else scatters, which is exact on disjoint partitions
        regardless of migration progress.
        """
        routes = self.plan.route_batch(s, p, o)
        if self._migration is not None:
            incoming = self._migration.new_plan.route_batch(s, p, o)
            routes = np.where(routes == incoming, routes, -1)
        return routes

    def _shard_entries(self, k: int, s, p, o, flush: int) -> tuple[list, int]:
        """One shard's entries for its sub-batch, in submission order —
        one engine micro-batch per `max_batch` chunk. Returns
        ``(entries, n_batches)``; runs under the shard's engine lock, so
        threaded fan-out never interleaves inside one engine (each keeps
        per-instance scratch: the frontier arena, memo tables)."""
        engine = self.engines[k]
        out: list = []
        n_batches = 0
        lock = self._engine_locks[k]
        with span("itr.wait.engine", flush=flush, shard=k):
            lock.acquire()
        try:
            with span("itr.shard.exec", flush=flush, shard=k):
                for lo in range(0, len(s), self.max_batch):
                    hi = min(lo + self.max_batch, len(s))
                    view = engine.query_batch_view(s[lo:hi], p[lo:hi], o[lo:hi])
                    out.extend(view.entry(i) for i in range(view.n_queries))
                    n_batches += 1
        finally:
            lock.release()
        return out, n_batches

    # -- mutation ---------------------------------------------------------
    def insert_triples(self, triples) -> int:
        """Insert (s, p, o) rows; returns how many were actually new.

        Each row is routed to its owning shard (`PartitionPlan
        .route_triples`) and applied to that engine's delta overlay; only
        the mutated shards' cache generations are bumped (plus the merged
        scatter-gather namespace). A shard whose overlay exceeds the
        engines' ``ITR_DELTA_BUDGET`` recompresses itself on the spot —
        the incremental-rebuild path.
        """
        return self._mutate(triples, insert=True)

    def delete_triples(self, triples) -> int:
        """Delete (s, p, o) rows; returns how many were actually present.
        Routing, invalidation, and the rebuild budget behave exactly as in
        :meth:`insert_triples`."""
        return self._mutate(triples, insert=False)

    def _mutate(self, triples, insert: bool) -> int:
        rows = as_triple_rows(triples)
        if len(rows) == 0:
            return 0
        if int(rows[:, 1].max()) >= self.plan.n_preds:
            raise ValueError(
                f"predicate ids must be < {self.plan.n_preds}; "
                f"got {int(rows[:, 1].max())}")
        with self._rw.write():  # exclusive: no flush observes a half-applied
            # mutation, routing state never changes under a reader
            if self._migration is None:
                applied = self._apply_rows(rows, insert,
                                           self.plan.route_triples(rows))
            else:
                applied = self._mutate_in_flight(rows, insert)
            if insert:
                self.stats.inserted += applied
            else:
                self.stats.deleted += applied
            if applied:
                self._maybe_auto_rebalance()
        return applied

    def _apply_rows(self, rows: np.ndarray, insert: bool,
                    shards: np.ndarray) -> int:
        """Apply mutation rows to the given per-row shards; bump only the
        shards that actually changed."""
        if self.failed_shards and \
                np.isin(shards, sorted(self.failed_shards)).any():
            raise RuntimeError(
                f"cannot mutate failed shards {sorted(self.failed_shards)}; "
                "restore them with reingest_shard() first")
        applied = 0
        for k in np.unique(shards):
            k = int(k)
            engine = self.engines[k]
            sub = rows[shards == k]
            before = engine.rebuild_count
            n = engine.insert_triples(sub) if insert \
                else engine.delete_triples(sub)
            self.stats.rebuilds += engine.rebuild_count - before
            if n:  # only mutated shards lose their warm cache entries
                applied += n
                self.invalidate(k)
        return applied

    def _mutate_in_flight(self, rows: np.ndarray, insert: bool) -> int:
        """Mutations while a rebalance migration is in flight.

        Rows whose owner is the same under the outgoing and incoming
        plans apply normally — none of them are in motion. A row whose
        ownership is changing may physically sit on either side, so:

        * deletes are first discarded from the pending moves (a later
          migration batch must never resurrect them) and then applied to
          BOTH candidate shards — each engine's set semantics no-ops the
          side that doesn't hold the row;
        * inserts probe the outgoing owner and, only if the row is not
          visible there (an unmigrated copy would otherwise end up
          duplicated across shards), land on the INCOMING owner — where
          the completed migration will expect them.
        """
        mig = self._migration
        old_s = self.plan.route_triples(rows)
        new_s = mig.new_plan.route_triples(rows)
        stable = old_s == new_s
        applied = self._apply_rows(rows[stable], insert, old_s[stable]) \
            if stable.any() else 0
        if stable.all():
            return applied
        moving = ~stable
        mrows, ma, mb = rows[moving], old_s[moving], new_s[moving]
        if insert:
            present = np.zeros(len(mrows), dtype=bool)
            for k in np.unique(ma):
                sel = ma == k
                present[sel] = self.engines[int(k)].contains_triples(mrows[sel])
            if not present.all():
                applied += self._apply_rows(mrows[~present], True,
                                            mb[~present])
        else:
            mig.discard(mrows)
            applied += self._apply_rows(mrows, False, ma)
            applied += self._apply_rows(mrows, False, mb)
        return applied

    def contains_triples(self, triples) -> np.ndarray:
        """bool[n]: is each (s, p, o) row currently visible in the tier?
        Routed like fully-bound queries, so it is exact mid-migration and
        while degraded (rows on a failed shard read as absent)."""
        rows = np.asarray(triples, dtype=np.int64).reshape(-1, 3)
        out = np.zeros(len(rows), dtype=bool)
        for i, (s, p, o) in enumerate(rows):
            out[i] = len(self.query(int(s), int(p), int(o))) > 0
        return out

    def rebuild(self, shard: int | None = None, force: bool = False) -> list[int]:
        """Incrementally recompress dirty shards; returns rebuilt shard ids.

        With `shard` given, that shard rebuilds if its overlay is
        non-empty. With `shard=None`, every shard whose overlay exceeds
        its engine's budget rebuilds — or every shard with any overlay at
        all under `force=True` (the "flush all deltas now" maintenance
        knob). Clean shards are never touched, which is the point: rebuild
        cost scales with the mutated fraction of the graph, not its size.
        """
        shards = range(self.n_shards) if shard is None else [int(shard)]
        rebuilt: list[int] = []
        with self._rw.write():  # engine.rebuild swaps engine internals —
            # it must never overlap a flush reading the same engine
            for k in shards:
                engine = self.engines[k]
                if engine.delta.is_empty:
                    continue
                over = engine.delta_budget is not None \
                    and engine.delta.size > engine.delta_budget
                if shard is not None or force or over:
                    engine.rebuild(self.config)
                    self.stats.rebuilds += 1
                    self.invalidate(k)
                    rebuilt.append(k)
        return rebuilt

    def delta_sizes(self) -> list[int]:
        """Per-shard overlay size (rows diverging from the compressed
        base) — the quantity :meth:`rebuild` budgets against."""
        return [e.delta.size for e in self.engines]

    # -- online rebalancing ------------------------------------------------
    def rebalance(self, force: bool = False,
                  max_moves: int | None = None) -> dict:
        """Re-cut the partition online; migrate rows between shards.

        With a migration already in flight this continues it — up to
        `max_moves` rows (``None`` = run to completion). Otherwise the
        live ``max/mean`` shard skew is measured and, when it is at/above
        the service's trigger (or under ``force=True``), a successor plan
        is computed (`plan_rebalance`: re-quantiled ``node_range``
        boundaries or LPT-re-packed predicate groups) and migration
        starts. Each migrated batch arrives through the destination
        shard's delta overlay and leaves the source via tombstones inside
        this call, so partitions stay disjoint at every public boundary
        and queries between calls are exact (the router scatters any
        pattern the two plans route differently while moves are pending).
        Only the shards a batch touched have their cache generations
        bumped. A re-cut that cannot move anything (structurally stuck
        skew, e.g. fewer predicates than shards) is adopted as-is and
        arms the auto-trigger backoff.

        Returns a summary: ``skew`` (at entry), ``moved`` (rows migrated
        by THIS call), ``pending`` (rows still to move), ``active``
        (migration still in flight).
        """
        with self._rw.write():  # plan/migration state swaps exclusively:
            # a reader sees either the old routing state or the new one
            if self.failed_shards:
                raise RuntimeError(
                    f"cannot rebalance with failed shards "
                    f"{sorted(self.failed_shards)}; restore them with "
                    "reingest_shard() first")
            skew = self.skew()
            if self._migration is None:
                threshold = self.rebalance_skew
                if not force and (threshold is None or skew < threshold):
                    return {"skew": skew, "moved": 0, "pending": 0,
                            "active": False}
                mig = plan_rebalance(self.plan, self.engines)
                if mig.total_rows == 0:
                    # same assignment for every live row: adopt the re-cut
                    # (future routing may still improve) and back off
                    self._journal_event("plan_swap", mig.new_plan)
                    self.plan = mig.new_plan
                    self._futile_total = int(
                        live_shard_edges(self.engines).sum())
                    return {"skew": skew, "moved": 0, "pending": 0,
                            "active": False}
                self._journal_event("rebalance_begin", mig.new_plan)
                self._migration = mig
                self.stats.rebalances += 1
                self._futile_total = None
            moved = self._apply_migration(max_moves)
            return {"skew": skew, "moved": moved,
                    "pending": self._migration.pending_rows
                    if self._migration is not None else 0,
                    "active": self._migration is not None}

    def _apply_migration(self, max_moves: int | None = None) -> int:
        """Migrate up to `max_moves` pending rows; finalize when drained.

        Each batch inserts into the destination overlay BEFORE tombstoning
        the source — both sides change inside this method, so no query can
        observe the transient double-ownership — and bumps only the two
        touched shards' generations. Once every move has been applied the
        successor plan becomes the routing plan: at that point it is the
        exact description of where every row lives.
        """
        mig = self._migration
        moved = 0
        for src, dst, batch in mig.take(max_moves):
            self._journal_event("migrate", (src, dst, batch))
            moved += self._apply_migration_batch(src, dst, batch)
        self.stats.migrated_rows += moved
        if mig.done:
            self._journal_event("plan_swap", mig.new_plan)
            self.plan = mig.new_plan
            self._migration = None
        return moved

    def _apply_migration_batch(self, src: int, dst: int,
                               batch: np.ndarray) -> int:
        """Move one logged batch from `src` to `dst` — idempotently.

        Only the rows still *visible at the source* are inserted at the
        destination. Live migration never notices (the `RebalancePlan`
        contract puts every pending row on its src shard), but WAL replay
        does: re-applying an already-applied batch after a crash must not
        duplicate rows onto dst, and a batch replayed after the row was
        deleted (discard happened post-append) must not resurrect it.
        The src-side delete is set-semantic, so it is idempotent as-is.
        """
        e_src, e_dst = self.engines[src], self.engines[dst]
        at_src = e_src.contains_triples(batch)
        batch = batch[at_src]
        if len(batch) == 0:
            return 0
        before = e_src.rebuild_count + e_dst.rebuild_count
        crash_point("migrate.pre_apply")
        e_dst.insert_triples(batch)
        crash_point("migrate.mid_apply")
        e_src.delete_triples(batch)
        self.stats.rebuilds += \
            e_src.rebuild_count + e_dst.rebuild_count - before
        self.invalidate(src)
        self.invalidate(dst)
        return len(batch)

    def _journal_event(self, kind: str, payload) -> None:
        """Hand a rebalance state change to the installed durability hook
        BEFORE it applies (write-ahead ordering); no-op when undurable."""
        if self._journal is not None:
            self._journal(kind, payload)

    def _maybe_auto_rebalance(self) -> None:
        """Mutation-path trigger: start a rebalance once live skew reaches
        the threshold, migrating at most ``_AUTO_MOVES_PER_CALL`` rows per
        mutation call — the trigger pays the plan computation, then every
        subsequent applied mutation drains another bounded chunk, so no
        single write blocks on moving the whole diff. Backoff: when a
        triggered re-cut could not move anything, auto checks stay off
        until the tier's live size drifts >25% from that futile snapshot —
        an unfixable structural skew must not cost an O(graph) plan
        computation per mutation."""
        if self.rebalance_skew is None or self.n_shards < 2 \
                or self.failed_shards:
            return
        if self._migration is not None:  # drain the in-flight migration
            self._apply_migration(_AUTO_MOVES_PER_CALL)
            return
        counts = live_shard_edges(self.engines)
        total = int(counts.sum())
        if self._futile_total is not None and \
                abs(total - self._futile_total) * 4 <= self._futile_total:
            return
        if measure_skew(counts) >= self.rebalance_skew:
            self.rebalance(force=True, max_moves=_AUTO_MOVES_PER_CALL)

    @property
    def migration_active(self) -> bool:
        """True while rebalance moves are pending (routing is in its
        conservative dual-plan mode)."""
        return self._migration is not None

    def live_edges(self) -> list[int]:
        """Per-shard live triple counts (compressed base + overlay), the
        load signal rebalancing watches — unlike :meth:`shard_sizes`,
        which reports compressed start-graph edges."""
        return [int(v) for v in live_shard_edges(self.engines)]

    def skew(self) -> float:
        """Live ``max/mean`` shard-load ratio (1.0 = balanced; compare
        against `rebalance_skew`)."""
        return measure_skew(live_shard_edges(self.engines))

    # -- degraded serving --------------------------------------------------
    def mark_shard_failed(self, shard: int) -> None:
        """Degrade one shard: serve around it instead of dying with it.

        The recovery path calls this when a shard's snapshot won't load
        (corruption, missing files). The shard's engine is replaced by an
        empty placeholder, queries keep flowing — owned patterns answer
        empty, scattered patterns merge the surviving shards — with every
        affected pattern counted in ``stats.degraded_patterns``. Writes to
        the failed shard and rebalancing are refused until
        :meth:`reingest_shard` restores it.
        """
        k = int(shard)
        if not 0 <= k < self.n_shards:
            raise ValueError(f"shard {k} out of range [0, {self.n_shards})")
        with self._rw.write():  # the engine swap must not race a flush
            self.failed_shards.add(k)
            self.engines[k] = self._build_shard_engine(
                k, np.zeros((0, 3), dtype=np.int64))
            self.invalidate(k)

    def reingest_shard(self, shard: int, triples) -> int:
        """Restore a failed shard from re-ingested rows (e.g. re-extracted
        from the upstream source); returns how many rows it now holds.
        Compresses the rows into a fresh engine, clears the failure flag,
        and invalidates the shard's (and merged) cache namespaces."""
        k = int(shard)
        if k not in self.failed_shards:
            raise ValueError(f"shard {k} is not marked failed")
        rows = as_triple_rows(triples)
        with self._rw.write():
            mine = rows[self.plan.route_triples(rows) == k] \
                if len(rows) else rows
            self.engines[k] = self._build_shard_engine(k, mine)
            self.failed_shards.discard(k)
            self.invalidate(k)
        return len(mine)

    def _build_shard_engine(self, k: int, rows: np.ndarray) -> TripleQueryEngine:
        """Compress `rows` into a fresh engine wired to shard `k`'s cache
        view (the build-time recipe, reused by degrade/reingest)."""
        table = LabelTable.terminals([2] * self.plan.n_preds)
        graph = Hypergraph.from_triples(rows, self.plan.n_nodes)
        grammar, _ = compress(graph, table, self.config)
        engine = TripleQueryEngine(
            grammar,
            cache=self.cache.shard_view(self._cache_ns[k])
            if self.cache is not None else None,
            config=self.config)
        engine._base_edges = len(rows)
        return engine

    # -- maintenance / introspection -------------------------------------
    def invalidate(self, shard: int | None = None) -> None:
        """Invalidate cached results (generation bump on the shared tier):
        one shard's entries, or every shard's when `shard` is None. The
        hook for mutable partitions — other shards stay warm. Merged
        cross-shard entries depend on every shard, so their namespace is
        bumped on any invalidation."""
        if self.cache is None:
            return
        shards = range(self.n_shards) if shard is None else [shard]
        for k in shards:
            self.cache.bump_generation(self._cache_ns[k])
        self.cache.bump_generation(self._merged_ns)

    def cache_stats(self):
        """Shared-tier cache counters (None when caching is disabled)."""
        return self.cache.stats if self.cache is not None else None

    def shard_sizes(self) -> list[int]:
        """Start-graph edges per shard (partition balance diagnostics)."""
        return [int(e.grammar.start.n_edges) for e in self.engines]
