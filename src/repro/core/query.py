"""Triple queries on the compressed grammar (paper §Answering triple queries).

Patterns: any subset of (S, P, O) bound. Case analysis per the paper:

* S or O bound  -> decompress one row of the start graph's incidence-matrix
  k²-tree (no full decompression) to seed the frontier with incident edges.
* only P bound  -> seed with start-graph edges labeled P (binary search on
  the Elias–Fano label list) plus edges of every nonterminal A whose NT
  matrix row says A can generate P.
* nothing bound -> all start edges (equivalent to decompression).

Execution is *batched and level-synchronous*: `query_batch` runs many
(S,P,O) patterns in one frontier by carrying a query-id column. Each
iteration expands ALL nonterminal edges at once through the flattened
grammar's CSR gathers (`repro.core.flatten`), applies the S/O-containment
and NT[label,P] prunes as boolean masks, and partitions terminals into a
preallocated result arena (`FrontierArena`) that is reused across calls.
Seeding uses the k²-tree's batched multi-row expansion, so one traversal
serves every S/O-bound query in the batch — pruned expansion plus batching
is what makes queries fast on the grammar.

The serving path is cache- and width-aware:

* a cross-request :class:`QueryResultCache` (LRU over (S,P,O) patterns,
  with a dedicated ``?P?`` segment) turns repeats *across* micro-batches
  into gathers — streaming dedup, not just in-batch dedup;
* cache-missing work narrower than the engine's measured crossover width
  routes to the per-query `query_scalar` worklist when every pattern is
  selective (S or O bound) — tiny frontiers pay more in numpy per-level
  overhead than the worklist pays in Python. The width is calibrated at
  engine build and overridable via ``ITR_QUERY_CROSSOVER``.

`query` is a batch of one; `query_scalar` keeps the seed per-query Python
worklist as the parity/benchmark reference.

The engine is also the *write* surface: `insert_triples`/`delete_triples`
record mutations in an uncompressed :class:`~repro.core.delta.DeltaOverlay`
(insert buffer + tombstone set) that every executed batch merges in, so
queries stay exact while the grammar itself is untouched. Once the overlay
outgrows the engine's budget (``ITR_DELTA_BUDGET``), `rebuild` recompresses
base+delta through the RePair pipeline and atomically swaps the engine's
internals; the cross-request cache is generation-bumped on every mutation
so stale entries can never be served.
"""
from __future__ import annotations

import os
import time

import numpy as np

from repro.core.delta import DeltaOverlay, as_triple_rows, resolve_delta_budget
from repro.core.encode import EncodedGrammar, encode
from repro.core.flatten import FlatGrammar, FrontierArena, _ragged_arange, concat_ragged
from repro.core.grammar import Grammar
from repro.core.hypergraph import _ragged_take
from repro.core.result_cache import QueryResultCache
from repro.core.succinct import K2Tree
from repro.persist.crash import crash_point
from repro.tracing import span, traced

_EMPTY = np.zeros(0, dtype=np.int64)

# sentinel: "create a default QueryResultCache unless disabled by env"
_DEFAULT_CACHE = object()

# sentinel: "resolve the delta budget from ITR_DELTA_BUDGET"
_DEFAULT_BUDGET = object()

# calibration cap: scalar routing never extends past this batch width
_MAX_CROSSOVER = 8


class QueryResultView:
    """Batch results as qid -> *shared* per-pattern entry arrays.

    The materialized batch layout (`query_batch_arrays`) replicates each
    duplicated pattern's full result per query id — for warm repeated
    ``?P?`` traffic that replication IS the cost floor. A view instead
    holds one ``(labels, nodes_flat, offsets)`` entry per *unique* pattern
    plus the qid -> entry mapping; duplicates share the same backing
    arrays with zero copies. All arrays are read-only (they may alias live
    cache entries). `materialize()` is the escape hatch back to the flat
    ``(qids, labels, nodes_flat, offsets)`` layout.
    """

    __slots__ = ("entries", "qid_entry")

    def __init__(self, entries: list, qid_entry: np.ndarray):
        self.entries = entries                       # one per unique pattern
        self.qid_entry = np.asarray(qid_entry, dtype=np.int64)

    @property
    def n_queries(self) -> int:
        return len(self.qid_entry)

    def entry(self, qid: int):
        """(labels, nodes_flat, offsets) of query `qid` — shared, read-only."""
        return self.entries[int(self.qid_entry[qid])]

    def result_counts(self) -> np.ndarray:
        """Matching-edge count per query id (duplicates counted per qid)."""
        per_entry = np.array([len(e[0]) for e in self.entries], dtype=np.int64)
        return per_entry[self.qid_entry] if len(per_entry) else \
            np.zeros(self.n_queries, dtype=np.int64)

    def total_results(self) -> int:
        return int(self.result_counts().sum())

    def entry_tuples(self, index: int) -> list[tuple]:
        """Entry `index` as (label, (v0..vk)) tuples (built per entry, so
        duplicate qids can share ONE list instead of converting each)."""
        labels, nodes, offsets = self.entries[index]
        return [(int(labels[j]), tuple(int(v) for v in nodes[offsets[j]:offsets[j + 1]]))
                for j in range(len(labels))]

    def tuples(self, qid: int) -> list[tuple]:
        return self.entry_tuples(int(self.qid_entry[qid]))

    def tuple_lists(self) -> list[tuple]:
        """Per-qid (label, nodes) result sequences, built ONCE per unique
        pattern — duplicate qids share one *immutable tuple* (mutating a
        shared list would silently corrupt the sibling ticket's answer;
        a tuple fails loudly). This is the service flush path."""
        shared: list = [None] * len(self.entries)
        out: list[tuple] = []
        for ei in self.qid_entry:
            ei = int(ei)
            if shared[ei] is None:
                shared[ei] = tuple(self.entry_tuples(ei))
            out.append(shared[ei])
        return out

    def materialize(self):
        """Escape hatch back to the flat batch layout.

        Returns ``(qids, labels, nodes_flat, offsets)`` with every
        duplicate pattern's results replicated per query id — identical
        layout and content to `query_batch_arrays`. This re-pays exactly
        the replication cost the view exists to avoid, so call it only at
        boundaries that require the flat form (legacy consumers, array
        serialization); duplicate-heavy warm traffic should stay on the
        view's shared entries.
        """
        counts = np.array([len(e[0]) for e in self.entries], dtype=np.int64)
        u_l, u_n, u_o = concat_ragged(self.entries)
        return _replicate_sorted(u_l, u_n, np.diff(u_o), u_o, counts, self.qid_entry)

    @staticmethod
    def empty() -> "QueryResultView":
        """Zero-query view (the empty-flush no-op result)."""
        return QueryResultView([], np.zeros(0, dtype=np.int64))

    @staticmethod
    def concat(views: list["QueryResultView"]) -> "QueryResultView":
        """Stack views over consecutive qid ranges (micro-batch chunks)."""
        entries: list = []
        qid_chunks = []
        for v in views:
            qid_chunks.append(v.qid_entry + len(entries))
            entries.extend(v.entries)
        qid_entry = np.concatenate(qid_chunks) if qid_chunks else _EMPTY
        return QueryResultView(entries, qid_entry)


def _freeze_entry(entry):
    """Mark an entry's arrays read-only: view entries are shared across
    duplicate qids (and may back cache entries), so in-place mutation must
    raise instead of silently corrupting a sibling's answer."""
    for a in entry:
        a.flags.writeable = False
    return entry


def _env_flag(name: str, default: bool) -> bool:
    v = os.environ.get(name, "").strip().lower()
    if not v:
        return default
    return v not in ("0", "off", "false", "no")


class TripleQueryEngine:
    """Query engine over a grammar + its succinct encoding.

    `cache` is the cross-request result cache (pass ``None`` to disable,
    or your own :class:`QueryResultCache` — or a
    :class:`~repro.core.result_cache.ShardCacheView` of a shared tier, as
    the sharded service does — to share/size it; the default is
    engine-private and can be switched off with ``ITR_RESULT_CACHE=0``).
    `crossover` is the batch width at/below which cache-missing selective
    patterns run on the scalar worklist instead of the frontier (``None``
    = read ``ITR_QUERY_CROSSOVER`` or calibrate at build; ``0`` = always
    use the frontier).
    `delta_budget` bounds the mutation overlay before :meth:`rebuild`
    recompresses automatically (default: read ``ITR_DELTA_BUDGET``;
    ``None`` = never auto-rebuild, ``0`` = recompress after every
    mutation batch — see :func:`repro.core.delta.resolve_delta_budget`).
    `config` is the :class:`~repro.core.repair.RepairConfig` rebuilds
    recompress with — pass the one the grammar was built with, or
    budget-triggered auto-rebuilds would silently fall back to default
    compression parameters.
    """

    def __init__(self, grammar: Grammar, encoded: EncodedGrammar | None = None,
                 cache=_DEFAULT_CACHE, crossover: int | None = None,
                 delta_budget=_DEFAULT_BUDGET, config=None):
        self.grammar = grammar
        self.encoded = encoded if encoded is not None else encode(grammar)
        self.T = grammar.table.n_terminals
        self.ranks = grammar.table.ranks
        # NT reachability matrix, k²-compressed (paper: matrix NT)
        gen = grammar.nt_generates()
        if gen.size:
            r, c = np.nonzero(gen)
            self.nt_k2 = K2Tree(r, c, gen.shape[0], gen.shape[1])
        else:
            self.nt_k2 = None
        self._nt_rows: dict[int, set] = {}
        # flattened grammar: CSR rule bodies + NT bitsets for batch expansion
        self.flat = FlatGrammar.from_grammar(grammar)
        # start graph in label-sorted order, struct-of-arrays (frontier seeds
        # and expansions are pure gathers over these)
        self._start_sorted = grammar.start.gather_edges(
            np.argsort(grammar.start.labels, kind="stable"))
        g = self._start_sorted
        self._sorted_labels = g.labels
        self._sorted_ranks = g.ranks()
        self._sorted_offsets = g.offsets
        self._sorted_nodes = g.nodes_flat
        # decoded rule bodies for the scalar reference path
        self._rules = {
            lbl: [(int(r.rhs.labels[j]), r.rhs.edge_nodes(j)) for j in range(r.rhs.n_edges)]
            for lbl, r in grammar.rules.items()
        }
        self._edge_cache = [
            (int(g.labels[j]), g.nodes_flat[g.offsets[j]:g.offsets[j + 1]])
            for j in range(g.n_edges)
        ]
        # result arena: shared across frontier levels, reused across calls
        self._arena = FrontierArena()
        if cache is _DEFAULT_CACHE:
            cache = QueryResultCache() if _env_flag("ITR_RESULT_CACHE", True) else None
        self.cache: QueryResultCache | None = cache
        self.crossover = self._calibrate_crossover() if crossover is None else int(crossover)
        # mutation overlay: uncompressed (inserts, tombstones) delta merged
        # into every executed batch; bounded by the rebuild budget
        self.delta = DeltaOverlay()
        self._base_edges: int | None = None  # lazy |base triples| cache
        self.config = config  # RepairConfig reused by rebuilds
        if delta_budget is _DEFAULT_BUDGET:
            self.delta_budget = resolve_delta_budget()
        else:  # explicit None = auto-rebuild off; ints resolve (neg = off)
            self.delta_budget = None if delta_budget is None \
                else resolve_delta_budget(delta_budget)
        self.rebuild_count = 0
        self._select_stats = None  # lazy SelectivityStats (see selectivity())
        self.term_dict = None  # optional TermDict (attach_term_dict)

    @classmethod
    def from_state(cls, grammar: Grammar, encoded: EncodedGrammar,
                   flat: FlatGrammar, *, crossover: int, cache=_DEFAULT_CACHE,
                   delta_budget: int | None = None, config=None,
                   base_edges: int | None = None,
                   rebuild_count: int = 0) -> "TripleQueryEngine":
        """Reconstruct an engine from prebuilt parts — the snapshot load
        path. No RePair, no `encode`, no `FlatGrammar.from_grammar`, no
        crossover calibration: everything expensive arrives precomputed.

        `grammar.start` must be in label-sorted edge order (the order
        `encoded.incidence` indexes and snapshots persist); `crossover`
        and `delta_budget` are the already-resolved stored values. The
        overlay starts empty — callers restore it via
        :meth:`~repro.core.delta.DeltaOverlay.load_rows`. Attribute
        assignments mirror ``__init__`` one-for-one; keep the two in sync.
        """
        self = cls.__new__(cls)
        self.grammar = grammar
        self.encoded = encoded
        self.T = grammar.table.n_terminals
        self.ranks = grammar.table.ranks
        # NT k²-tree from the flat bitsets instead of grammar.nt_generates()
        # (identical content: flat rows are label-T slots, and encode
        # guarantees rule labels are contiguous)
        if flat.nt_gen.size:
            r, c = np.nonzero(flat.nt_gen)
            self.nt_k2 = K2Tree(r, c, flat.nt_gen.shape[0], flat.nt_gen.shape[1])
        else:
            self.nt_k2 = None
        self._nt_rows = {}
        self.flat = flat
        g = grammar.start
        if g.n_edges and bool(np.any(np.diff(g.labels) < 0)):
            raise ValueError("from_state needs a label-sorted start graph")
        self._start_sorted = g
        self._sorted_labels = g.labels
        self._sorted_ranks = g.ranks()
        self._sorted_offsets = g.offsets
        self._sorted_nodes = g.nodes_flat
        self._rules = {
            lbl: [(int(r.rhs.labels[j]), r.rhs.edge_nodes(j))
                  for j in range(r.rhs.n_edges)]
            for lbl, r in grammar.rules.items()
        }
        self._edge_cache = [
            (int(g.labels[j]), g.nodes_flat[g.offsets[j]:g.offsets[j + 1]])
            for j in range(g.n_edges)
        ]
        self._arena = FrontierArena()
        if cache is _DEFAULT_CACHE:
            cache = QueryResultCache() if _env_flag("ITR_RESULT_CACHE", True) else None
        self.cache = cache
        self.crossover = int(crossover)
        self.delta = DeltaOverlay()
        self._base_edges = None if base_edges is None else int(base_edges)
        self.config = config
        self.delta_budget = None if delta_budget is None \
            else resolve_delta_budget(delta_budget)
        self.rebuild_count = int(rebuild_count)
        self._select_stats = None
        self.term_dict = None
        return self

    # -- crossover calibration -------------------------------------------
    def _calibrate_crossover(self) -> int:
        """Measured batch width at/below which the scalar worklist beats a
        frontier of the same width on a selective probe. A frontier of one
        pays numpy per-level overhead on arrays of length ~1; the worklist
        pays per-edge Python — which side wins depends on the grammar, so
        measure it on this one instead of hardcoding."""
        env = os.environ.get("ITR_QUERY_CROSSOVER", "").strip()
        if env:
            try:
                return max(0, int(env))
            except ValueError:
                pass
        g = self._start_sorted
        if g.n_edges == 0 or len(g.nodes_flat) == 0:
            return 1
        probe = int(g.nodes_flat[0])
        s1 = np.array([probe], dtype=np.int64)
        u1 = np.full(1, -1, dtype=np.int64)
        t_scalar = t_batch = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            self.query_scalar(probe, None, None)
            t_scalar = min(t_scalar, time.perf_counter() - t0)
            t0 = time.perf_counter()
            self._run_batch_unique(s1, u1, u1)
            t_batch = min(t_batch, time.perf_counter() - t0)
        if t_scalar <= 0:
            return 1
        return int(np.clip(t_batch / t_scalar, 0, _MAX_CROSSOVER))

    # -- helpers --------------------------------------------------------
    def _nt_generates(self, label: int, p: int) -> bool:
        if self.nt_k2 is None:
            return False
        row = self._nt_rows.get(label)
        if row is None:
            row = set(self.nt_k2.row(label - self.T).tolist())
            self._nt_rows[label] = row
        return p in row

    def _edge(self, j: int) -> tuple[int, np.ndarray]:
        """Sorted-start edge j (pre-reconstructed at load)."""
        return self._edge_cache[j]

    def _edges_with_label(self, label: int) -> np.ndarray:
        lo = np.searchsorted(self._sorted_labels, label, side="left")
        hi = np.searchsorted(self._sorted_labels, label, side="right")
        return np.arange(lo, hi, dtype=np.int64)

    def _row_edges(self, node: int) -> np.ndarray:
        """Edges incident to `node` via one k²-tree row decompression."""
        if node < 0 or node >= self.encoded.incidence.n_rows:
            return np.zeros(0, dtype=np.int64)
        return self.encoded.incidence.row(node)

    # -- batched seeding -------------------------------------------------
    @traced("itr.engine.seed")
    def _seed_batch(self, s: np.ndarray, p: np.ndarray, o: np.ndarray):
        """Start-graph edge ids seeding each query; returns (qids, edge_ids)."""
        nq = len(s)
        all_qids, all_eids = [], []

        so = (s >= 0) | (o >= 0)
        so_q = np.flatnonzero(so)
        if so_q.size:
            nodes = np.where(s[so_q] >= 0, s[so_q], o[so_q])
            idx, eids = self.encoded.incidence.rows_many(nodes)
            all_qids.append(so_q[idx])
            all_eids.append(eids)

        p_only = ~so & (p >= 0)
        p_q = np.flatnonzero(p_only)
        if p_q.size:
            pq = p[p_q]
            # seed labels: the terminal P itself + every NT generating P
            seed_labels = [pq]
            owners = [p_q]
            valid = (pq >= 0) & (pq < self.T)
            if self.flat.n_rules and valid.any():
                ntmask = self.flat.nt_gen[:, np.clip(pq, 0, self.T - 1)].T  # (nq, R)
                ntmask &= valid[:, None]
                qi, ri = np.nonzero(ntmask)
                seed_labels.append(self.flat.rule_labels[ri])
                owners.append(p_q[qi])
            lbls = np.concatenate(seed_labels)
            own = np.concatenate(owners)
            lo = np.searchsorted(self._sorted_labels, lbls, side="left")
            hi = np.searchsorted(self._sorted_labels, lbls, side="right")
            counts = hi - lo
            all_eids.append(np.repeat(lo, counts) + _ragged_arange(counts))
            all_qids.append(np.repeat(own, counts))

        open_q = np.flatnonzero(~so & (p < 0))
        if open_q.size:
            E = len(self._sorted_labels)
            all_eids.append(np.tile(np.arange(E, dtype=np.int64), len(open_q)))
            all_qids.append(np.repeat(open_q, E))

        if not all_qids:
            return _EMPTY, _EMPTY
        return np.concatenate(all_qids), np.concatenate(all_eids)

    # -- batched frontier ------------------------------------------------
    def _run_batch(self, s: np.ndarray, p: np.ndarray, o: np.ndarray):
        """Cache-aware batch execution.

        Duplicate (S,P,O) patterns in the batch — common under real traffic
        and dominant for the unselective ?P?/??? patterns — are executed
        once and their results replicated per query id at the end. With a
        result cache attached the dedup is *streaming*: unique patterns are
        first looked up in the cross-request cache, only the misses run
        (through the frontier, or the scalar worklist below the crossover
        width), and their results are inserted for future batches.

        Returns result arrays (qids, labels, nodes_flat, offsets) of the
        matching terminal edges, ragged, unordered across queries. The
        arrays may share memory with cache entries — treat as read-only.
        """
        cache = self.cache
        n = len(s)
        if cache is None:
            # cache-less path stays entry-free: splitting per unique query
            # just to re-concatenate would copy every result once for
            # nothing when the batch has no duplicates
            if n > 1:  # dedup never helps a batch of one
                key = np.stack([s, p, o], axis=1)
                uniq, inv = np.unique(key, axis=0, return_inverse=True)
                if len(uniq) < n:
                    u_res = self._execute_unique(uniq[:, 0], uniq[:, 1], uniq[:, 2])
                    return _replicate_results(u_res, inv.reshape(-1))
            return self._execute_unique(s, p, o)

        # cached execution IS the view path; materialize replicates per qid
        view = self._run_batch_view(s, p, o)
        if view.n_queries == 1:  # hot serving path: alias the entry, no gather
            labels, nodes, offsets = view.entries[0]
            return np.zeros(len(labels), dtype=np.int64), labels, nodes, offsets
        return view.materialize()

    def _execute_unique(self, s: np.ndarray, p: np.ndarray, o: np.ndarray):
        """Crossover dispatch: tiny all-selective batches take the scalar
        worklist; everything else takes the level-synchronous frontier.
        Every execution path funnels through here, so this is also where
        the mutation overlay is merged in (tombstoned base edges dropped,
        matching inserted triples appended) — views, caches, and the
        sharded tier all see post-overlay results."""
        w = len(s)
        if 0 < w <= self.crossover and bool(np.all((s >= 0) | (o >= 0))):
            res = self._run_scalar_batch(s, p, o)
        else:
            res = self._run_batch_unique(s, p, o)
        if not self.delta.is_empty:
            with span("itr.engine.overlay"):
                res = self.delta.merge_batch(res, s, p, o)
        return res

    @traced("itr.engine.scalar")
    def _run_scalar_batch(self, s: np.ndarray, p: np.ndarray, o: np.ndarray):
        """Per-query worklist over a tiny batch, frontier-shaped results."""
        qids: list[int] = []
        labels: list[int] = []
        ranks: list[int] = []
        nodes: list[int] = []
        for i in range(len(s)):
            res = self.query_scalar(int(s[i]) if s[i] >= 0 else None,
                                    int(p[i]) if p[i] >= 0 else None,
                                    int(o[i]) if o[i] >= 0 else None)
            for lbl, nd in res:
                qids.append(i)
                labels.append(lbl)
                ranks.append(len(nd))
                nodes.extend(nd)
        if not labels:
            return _EMPTY, _EMPTY, _EMPTY, np.zeros(1, dtype=np.int64)
        offsets = np.concatenate([[0], np.cumsum(ranks)]).astype(np.int64)
        return (np.asarray(qids, dtype=np.int64), np.asarray(labels, dtype=np.int64),
                np.asarray(nodes, dtype=np.int64), offsets)

    def _run_batch_unique(self, s: np.ndarray, p: np.ndarray, o: np.ndarray):
        qids, eids = self._seed_batch(s, p, o)
        return self._frontier(s, p, o, qids, eids)

    @traced("itr.engine.frontier")
    def _frontier(self, s, p, o, qids: np.ndarray, eids: np.ndarray):
        """The level loop: expand the seeded edges rule by rule, keeping
        the terminals each pattern matches."""
        labels = self._sorted_labels[eids]
        ranks = self._sorted_ranks[eids]
        take = _ragged_take(self._sorted_offsets, eids, ranks)
        nodes = self._sorted_nodes[take]
        offsets = np.concatenate([[0], np.cumsum(ranks)]).astype(np.int64)

        arena = self._arena  # engine-owned result arena, reused across calls
        arena.reset()
        guard = 0
        while len(labels):
            guard += 1
            assert guard <= self.flat.n_rules + 2, "frontier expansion did not terminate"
            is_nt = labels >= self.T

            # terminals: match filter -> arena (one slice-assign per level)
            t_sel = ~is_nt
            if t_sel.any():
                tl, tn, to, (tq,) = _ragged_select(labels, nodes, offsets, t_sel, qids)
                tr = np.diff(to)
                first = _slot(tn, to, tr, 0)
                second = _slot(tn, to, tr, 1)
                sq, pq, oq = s[tq], p[tq], o[tq]
                match = (pq < 0) | (tl == pq)
                match &= (sq < 0) | ((tr >= 1) & (first == sq))
                match &= (oq < 0) | ((tr >= 2) & (second == oq))
                if match.any():
                    midx = np.flatnonzero(match)
                    mranks = tr[midx]
                    take = _ragged_take(to, midx, mranks)
                    arena.push(tq[midx], tl[midx], mranks, tn[take])

            if not is_nt.any():
                break
            # nonterminals: S/O-containment and NT[label,P] prunes as masks
            nl, nn, no, (nq,) = _ragged_select(labels, nodes, offsets, is_nt, qids)
            nr = np.diff(no)
            sq, pq, oq = s[nq], p[nq], o[nq]
            keep = np.ones(len(nl), dtype=bool)
            if (sq >= 0).any():
                keep &= (sq < 0) | _contains(nn, no, nr, sq)
            if (oq >= 0).any():
                keep &= (oq < 0) | _contains(nn, no, nr, oq)
            if (pq >= 0).any():
                valid_p = (pq >= 0) & (pq < self.T)
                gen = self.flat.generates(nl, np.clip(pq, 0, max(self.T - 1, 0)))
                keep &= (pq < 0) | (valid_p & gen)
            if not keep.any():
                break
            el, en, eo, (eq,) = _ragged_select(nl, nn, no, keep, nq)
            labels, nodes, offsets, (qids,) = self.flat.expand(el, en, eo, eq)

        return arena.finish()

    # -- main entries ----------------------------------------------------
    def query_batch_arrays(self, s_arr, p_arr, o_arr):
        """Array-native batch query. -1 (or None) marks an unbound slot.

        Returns (qids, labels, nodes_flat, offsets): matching terminal edge
        i belongs to query qids[i], has label labels[i] and node tuple
        nodes_flat[offsets[i]:offsets[i+1]]. Treat the arrays as
        READ-ONLY: with a result cache attached, single-query results
        alias live cache entries (they are marked non-writeable, so an
        in-place mutation raises instead of corrupting future answers).
        """
        s, p, o = _normalize_batch(s_arr, p_arr, o_arr)
        return self._run_batch(s, p, o)

    def query_batch_view(self, s_arr, p_arr, o_arr) -> QueryResultView:
        """Batch query returning a :class:`QueryResultView`: one shared
        entry per unique pattern, qid -> entry mapping, no per-duplicate
        materialization. This is the serving path for duplicate-heavy
        traffic (warm repeated ``?P?`` batches stop paying the replication
        cost floor); `.materialize()` recovers the flat array layout."""
        s, p, o = _normalize_batch(s_arr, p_arr, o_arr)
        return self._run_batch_view(s, p, o)

    def _run_batch_view(self, s: np.ndarray, p: np.ndarray, o: np.ndarray) -> QueryResultView:
        """Cache-aware execution producing per-unique-pattern entries.

        Same streaming-dedup discipline as `_run_batch` — look unique
        patterns up in the cross-request cache, execute only the misses,
        insert their split results — but duplicates share entries instead
        of being replicated into a flat batch.
        """
        cache = self.cache
        n = len(s)
        if n == 1:  # hot serving path: no stack/unique/split machinery
            hit = cache.lookup(s[0], p[0], o[0]) if cache is not None else None
            if hit is None:
                _, r_l, r_n, r_o = self._execute_unique(s, p, o)
                hit = _freeze_entry((r_l, r_n, r_o))
                if cache is not None:
                    cache.insert(s[0], p[0], o[0], hit)
            return QueryResultView([hit], np.zeros(1, dtype=np.int64))
        key = np.stack([s, p, o], axis=1)
        uniq, inv = np.unique(key, axis=0, return_inverse=True)
        inv = inv.reshape(-1)
        nu = len(uniq)
        entries: list = [None] * nu
        miss: list[int] = []
        with span("itr.engine.cache"):
            for i in range(nu):
                hit = cache.lookup(uniq[i, 0], uniq[i, 1], uniq[i, 2]) \
                    if cache is not None else None
                if hit is None:
                    miss.append(i)
                else:
                    entries[i] = hit
        if miss:
            mi = np.asarray(miss, dtype=np.int64)
            fresh = self._execute_unique(uniq[mi, 0], uniq[mi, 1], uniq[mi, 2])
            for j, entry in enumerate(_split_per_query(fresh, len(mi))):
                i = int(mi[j])
                entries[i] = _freeze_entry(entry)  # shared across duplicate
                if cache is not None:              # qids even when uncached
                    cache.insert(uniq[i, 0], uniq[i, 1], uniq[i, 2], entry)
        return QueryResultView(entries, inv)

    def query_batch(self, s_arr, p_arr, o_arr) -> list[list[tuple]]:
        """Batch query returning, per query, (label, (v0..vk)) tuples —
        identical contents to `query_scalar`/`query_oracle` per query."""
        s, p, o = _normalize_batch(s_arr, p_arr, o_arr)
        r_q, r_l, r_n, r_o = self._run_batch(s, p, o)
        results: list[list[tuple]] = [[] for _ in range(len(s))]
        order = np.argsort(r_q, kind="stable")
        for i in order:
            q = int(r_q[i])
            results[q].append(
                (int(r_l[i]), tuple(int(v) for v in r_n[r_o[i]:r_o[i + 1]])))
        return results

    def query(self, s: int | None, p: int | None, o: int | None) -> list[tuple]:
        """Return matching terminal edges as (label, (v0..vk)) tuples."""
        # cache-less selective single query below the crossover: the scalar
        # worklist already produces tuples — skip the array round-trip
        # (only while the overlay is empty: query_scalar is base-only)
        if self.cache is None and self.crossover >= 1 and self.delta.is_empty \
                and (s is not None or o is not None):
            return self.query_scalar(s, p, o)
        return self.query_batch([s], [p], [o])[0]

    def query_scalar(self, s: int | None, p: int | None, o: int | None) -> list[tuple]:
        """Seed-era per-query Python worklist over the COMPRESSED BASE only.

        Not the query path — `query`/`query_batch*` are (they batch,
        cache, and merge the mutation overlay). This survives as (a) the
        parity oracle tests compare the batched frontier against, (b) the
        pre-batching baseline benchmarks report speedups over, and (c) the
        executor the crossover dispatch routes tiny selective batches to.
        It deliberately ignores `delta`: overlay merging happens once per
        executed batch in `_execute_unique`, above this level.
        """
        if s is not None or o is not None:
            r = s if s is not None else o
            seeds = [self._edge(int(j)) for j in self._row_edges(int(r))]
        elif p is not None:
            seeds = [self._edge(int(j)) for j in self._edges_with_label(int(p))]
            for lbl in self._rules:
                if self._nt_generates(lbl, int(p)):
                    seeds.extend(self._edge(int(j)) for j in self._edges_with_label(lbl))
        else:
            g = self._start_sorted
            seeds = [(int(g.labels[j]), g.edge_nodes(j)) for j in range(g.n_edges)]

        out: list[tuple] = []
        z = list(seeds)
        while z:
            label, nodes = z.pop()
            if label >= self.T:  # nonterminal
                if s is not None and s not in nodes:
                    continue
                if o is not None and o not in nodes:
                    continue
                if p is not None and not self._nt_generates(label, p):
                    continue
                for child_label, params in self._rules[label]:
                    z.append((child_label, nodes[params]))
            else:
                if self._matches(label, nodes, s, p, o):
                    out.append((label, tuple(int(v) for v in nodes)))
        return out

    @staticmethod
    def _matches(label, nodes, s, p, o) -> bool:
        if p is not None and label != p:
            return False
        if s is not None and (len(nodes) < 1 or nodes[0] != s):
            return False
        if o is not None and (len(nodes) < 2 or nodes[1] != o):
            return False
        return True

    # -- mutation --------------------------------------------------------
    def insert_triples(self, triples) -> int:
        """Insert (s, p, o) rows; returns how many were actually new.

        Rows already visible (in the base and not tombstoned, or already
        buffered) are no-ops; rows matching a tombstone are resurrected.
        Predicates must be terminal labels of this grammar; node ids may
        extend past the base graph (the node universe grows at the next
        rebuild). Any applied mutation bumps the result cache's generation
        and, once the overlay exceeds `delta_budget`, triggers an
        automatic :meth:`rebuild`.
        """
        rows = as_triple_rows(triples)
        if len(rows):
            if int(rows[:, 1].max()) >= self.T:
                raise ValueError(
                    f"predicate ids must be < {self.T} (terminal labels); "
                    f"got {int(rows[:, 1].max())}")
            if bool(np.any(self.ranks[rows[:, 1]] != 2)):
                raise ValueError(
                    "predicates must be rank-2 terminal labels (ITR+ "
                    "node-label terminals are not triple predicates)")
            rows = rows[~self._exists_rows(rows)]
        applied = self.delta.insert_rows(rows)
        self._after_mutation(applied)
        return applied

    def delete_triples(self, triples) -> int:
        """Delete (s, p, o) rows; returns how many were actually present.

        Deleting an overlay insert un-buffers it; deleting a base triple
        tombstones it; deleting an absent triple is a no-op. Cache
        generation and the rebuild budget are handled as in
        :meth:`insert_triples`.
        """
        rows = as_triple_rows(triples)
        if len(rows):
            rows = rows[self._exists_rows(rows)]
        applied = self.delta.delete_rows(rows)
        self._after_mutation(applied)
        return applied

    def contains_triples(self, triples) -> np.ndarray:
        """bool per (s, p, o) row: is it currently visible on THIS engine
        (base minus tombstones plus inserts)? Row-aligned with the input
        (no dedup/sort) and cache-detached — the probe the sharded tier
        uses to keep partitions disjoint while a migration is in flight.
        """
        rows = np.asarray(triples, dtype=np.int64)
        if rows.size == 0:
            return np.zeros(0, dtype=bool)
        if rows.ndim != 2 or rows.shape[1] != 3:
            raise ValueError(
                f"expected (n, 3) triple rows, got shape {rows.shape}")
        return self._exists_rows(rows)

    @property
    def base_edges(self) -> int:
        """Triple count of the compressed base — the live-load signal
        rebalancing reads (`live = base_edges + inserts - tombstones`).
        Lazily decompressed once per grammar and cached: mutations only
        touch the overlay, and a rebuild swaps in a fresh (uncounted)
        engine state. Requires a pure triple grammar, like
        :meth:`base_triples`."""
        if self._base_edges is None:
            self._base_edges = len(self.base_triples())
        return self._base_edges

    def _exists_rows(self, rows: np.ndarray) -> np.ndarray:
        """bool per row: is this triple currently visible (base minus
        tombstones plus inserts)? Runs one cache-detached batch query —
        membership probes must not pollute the cross-request cache with
        entries the mutation is about to invalidate."""
        cache, self.cache = self.cache, None
        try:
            view = self._run_batch_view(rows[:, 0], rows[:, 1], rows[:, 2])
        finally:
            self.cache = cache
        return view.result_counts() > 0

    def _after_mutation(self, applied: int) -> None:
        if not applied:
            return
        if self.cache is not None:
            self.cache.bump_generation()
        if self.delta_budget is not None and self.delta.size > self.delta_budget:
            self.rebuild()

    def base_triples(self) -> np.ndarray:
        """The compressed base as (n, 3) rows — requires a pure triple
        grammar (every decompressed edge rank-2; ITR+ node-label
        hyperedges cannot be expressed as triples)."""
        g = self.grammar.decompress()
        if len(g.labels) and not bool(np.all(g.ranks() == 2)):
            raise ValueError("base graph has non-triple (rank != 2) edges; "
                             "triple mutation/rebuild needs a pure triple set")
        starts = g.offsets[:-1]
        return np.stack(
            [g.nodes_flat[starts], g.labels, g.nodes_flat[starts + 1]], axis=1) \
            if len(g.labels) else np.zeros((0, 3), dtype=np.int64)

    def current_triples(self) -> np.ndarray:
        """The logical triple set: decompressed base with the overlay
        applied (tombstones removed, inserts appended)."""
        return self.delta.apply(self.base_triples())

    # -- BGP joins -------------------------------------------------------
    def selectivity(self):
        """Join-ordering stats (per-predicate cardinalities, distinct
        subject/object counts) computed once per build from the flattened
        CSR arrays — no decompression. Lazily cached; `rebuild()` swaps
        the whole engine state, so the next call recomputes for the new
        grammar. The mutation overlay is ignored: it is bounded by the
        rebuild budget and stats only order joins, never gate answers."""
        if self._select_stats is None:
            from repro.core.bgp import SelectivityStats
            self._select_stats = SelectivityStats.from_csr(
                self._sorted_labels, self._sorted_ranks, self._sorted_nodes,
                self._sorted_offsets, self.flat, self.T)
        return self._select_stats

    def query_bgp(self, patterns):
        """Evaluate a basic graph pattern — a conjunction of triple
        patterns with shared `?var` terms, e.g. ``"?x 0 ?y . ?y 1 17"`` —
        and return a :class:`~repro.core.bgp.BGPResult`. Joins are planned
        by `selectivity()` and each step runs through `query_batch_view`,
        so sub-patterns get the batched frontier + result cache for free."""
        from repro.core.bgp import execute_bgp
        return execute_bgp(patterns, self.query_batch_view, self.selectivity())

    # -- string-term surfaces (require an attached TermDict) --------------
    def attach_term_dict(self, term_dict) -> None:
        """Attach a :class:`~repro.core.term_dict.TermDict` so this engine
        can answer string-term queries (`query_strings`,
        `query_bgp_strings`). The dictionary survives `rebuild`."""
        self.term_dict = term_dict

    def _require_term_dict(self):
        if self.term_dict is None:
            raise ValueError(
                "no term dictionary attached — call attach_term_dict() "
                "(or ingest through repro.data.ingest, which attaches one)")
        return self.term_dict

    def query_strings(self, s: str | None, p: str | None, o: str | None):
        """Answer one (S, P, O) pattern with *term strings*: each slot is a
        term or ``None`` (unbound). Terms resolve to ids once, here at the
        boundary; a bound term the dictionary has never seen short-circuits
        to ``[]`` without executing. Returns ``(s, p, o)`` term triples."""
        td = self._require_term_dict()
        from repro.core.term_dict import resolve_string_triple
        s_id, p_id, o_id, known = resolve_string_triple(td, s, p, o)
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
        ``?var`` names / constant term strings. Unknown constants
        short-circuit to ``[]``. Returns ``[{var: term}, ...]`` binding
        rows (deterministic `BGPResult` order)."""
        td = self._require_term_dict()
        from repro.core.term_dict import bgp_result_to_terms, resolve_string_bgp
        id_patterns, pred_vars, known = resolve_string_bgp(td, patterns)
        if not known:
            return []
        return bgp_result_to_terms(td, self.query_bgp(id_patterns), pred_vars)

    def rebuild(self, config=None) -> bool:
        """Recompress base+delta into a fresh grammar and swap it in.

        The full RePair pipeline runs on the overlay-applied triple set
        with `config` (default: the config this engine was built with);
        every derived structure (succinct encoding, flattened CSR, NT
        k²-tree, arena, crossover) is rebuilt, then the engine's
        attributes are replaced in one ``__dict__`` update — the engine
        is never observable in a partially-rebuilt state *between* method
        calls. The engine is NOT thread-safe, though: a query executing
        concurrently with the swap can read attributes from both sides of
        it; serialize rebuilds against queries externally. The attached
        cache view survives the swap and gets a generation bump. Returns
        True if a rebuild ran (False when the overlay is empty).
        """
        if self.delta.is_empty:
            return False
        from repro.core.hypergraph import Hypergraph, LabelTable
        from repro.core.repair import compress

        config = config if config is not None else self.config
        triples = self.current_triples()
        n_nodes = self.grammar.start.n_nodes
        if len(triples):
            n_nodes = max(n_nodes, int(triples[:, [0, 2]].max()) + 1)
        table = LabelTable.terminals(self.grammar.table.ranks[:self.T].copy(),
                                     names=self.grammar.table.names)
        grammar, _ = compress(Hypergraph.from_triples(triples, n_nodes), table,
                              config)
        fresh = TripleQueryEngine(grammar, cache=self.cache,
                                  crossover=self.crossover,
                                  delta_budget=self.delta_budget,
                                  config=config)
        fresh._base_edges = len(triples)  # the new base IS these rows
        rebuilds = self.rebuild_count + 1
        term_dict = self.term_dict  # survives the swap, like the cache view
        # a kill here loses only memory: the swap below never touches disk,
        # so recovery replays snapshot + WAL and re-reaches this state
        crash_point("engine.rebuild")
        self.__dict__.update(fresh.__dict__)
        self.rebuild_count = rebuilds
        self.term_dict = term_dict
        if self.cache is not None:
            self.cache.bump_generation()
        return True

    # -- convenience -----------------------------------------------------
    def neighbors_out_batch(self, vs) -> list[np.ndarray]:
        """Per v: distinct objects (outgoing neighborhood), one batch.

        View-backed: duplicate vs share one distinct-node computation and
        one (read-only) result array instead of per-duplicate copies."""
        vs = self._sanitize_nodes(vs)
        view = self._run_batch_view(
            vs, np.full(len(vs), -1, np.int64), np.full(len(vs), -1, np.int64))
        per_entry = [_entry_distinct_slot(e, 1) for e in view.entries]
        return [per_entry[i] for i in view.qid_entry]

    def neighbors_in_batch(self, vs) -> list[np.ndarray]:
        """Per v: distinct subjects (incoming neighborhood), one batch."""
        vs = self._sanitize_nodes(vs)
        view = self._run_batch_view(
            np.full(len(vs), -1, np.int64), np.full(len(vs), -1, np.int64), vs)
        per_entry = [_entry_distinct_slot(e, 0) for e in view.entries]
        return [per_entry[i] for i in view.qid_entry]

    def _sanitize_nodes(self, vs) -> np.ndarray:
        """Negative node ids would read as 'unbound' — remap them to an
        out-of-range row so they yield empty results instead."""
        vs = np.asarray(vs, dtype=np.int64)
        return np.where(vs < 0, self.encoded.incidence.n_rows, vs)

    def neighbors_out(self, v: int) -> np.ndarray:
        """v ? ? -> distinct objects (outgoing neighborhood)."""
        return self.neighbors_out_batch([v])[0]

    def neighbors_in(self, v: int) -> np.ndarray:
        """? ? v -> distinct subjects (incoming neighborhood)."""
        return self.neighbors_in_batch([v])[0]


# ----------------------------------------------------------------------
def _normalize_batch(s_arr, p_arr, o_arr) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """None/-1-sentinel columns -> aligned int64 arrays with -1 = unbound."""
    if s_arr is None and p_arr is None and o_arr is None:
        raise ValueError(
            "at least one of s/p/o must be an array — with all three None the "
            "batch size is unknown (for all-unbound queries pass [None] * n)")
    cols = []
    n = max(len(c) for c in (s_arr, p_arr, o_arr) if c is not None)
    for c in (s_arr, p_arr, o_arr):
        if c is None:
            cols.append(np.full(n, -1, dtype=np.int64))
        else:
            cols.append(np.array([-1 if v is None else int(v) for v in c], dtype=np.int64)
                        if isinstance(c, (list, tuple)) else np.asarray(c, dtype=np.int64))
    s, p, o = cols
    assert len(s) == len(p) == len(o), "query columns must be aligned"
    return s, p, o


def _ragged_select(labels, nodes, offsets, mask, *payload):
    """Select edges where mask holds from a ragged (labels, nodes, offsets)
    batch; payload columns are filtered alongside."""
    idx = np.flatnonzero(mask)
    ranks = np.diff(offsets)[idx]
    take = _ragged_take(offsets, idx, ranks)
    new_offsets = np.concatenate([[0], np.cumsum(ranks)]).astype(np.int64)
    return labels[idx], nodes[take], new_offsets, tuple(c[idx] for c in payload)


def _slot(nodes, offsets, ranks, m: int) -> np.ndarray:
    """nodes[offsets[e] + m] per edge, -1 where rank <= m (no branch)."""
    pos = offsets[:-1] + m
    safe = np.minimum(pos, max(len(nodes) - 1, 0))
    vals = nodes[safe] if len(nodes) else np.full(len(ranks), -1, np.int64)
    return np.where(ranks > m, vals, -1)


def _contains(nodes, offsets, ranks, targets) -> np.ndarray:
    """Per edge e: does target[e] occur among its nodes? (segment any)"""
    n_edges = len(ranks)
    seg = np.repeat(np.arange(n_edges, dtype=np.int64), ranks)
    hits = nodes == np.repeat(targets, ranks)
    return np.bincount(seg[hits], minlength=n_edges).astype(bool)


def _split_per_query(res, nq: int) -> list[tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Split batch result arrays into per-query (labels, nodes, offsets)
    cache entries: one stable sort by query id, then slicing. Entries are
    COPIES — a view would pin the whole batch's backing buffer for the
    lifetime of the cache entry, defeating the cache's edge budget."""
    r_q, r_l, r_n, r_o = res
    order = np.argsort(r_q, kind="stable")
    labels = r_l[order]
    ranks = np.diff(r_o)[order]
    nodes = r_n[_ragged_take(r_o, order, ranks)]
    offsets = np.concatenate([[0], np.cumsum(ranks)]).astype(np.int64)
    bounds = np.concatenate([[0], np.cumsum(np.bincount(r_q, minlength=nq))]).astype(np.int64)
    out = []
    for i in range(nq):
        e0, e1 = bounds[i], bounds[i + 1]
        n0 = offsets[e0]
        out.append((labels[e0:e1].copy(), nodes[n0:offsets[e1]].copy(),
                    offsets[e0:e1 + 1] - n0))
    return out


def _replicate_results(u_res, inv: np.ndarray):
    """Map result arrays of deduped queries back to the full batch: original
    query q receives a copy of unique-query inv[q]'s results (all gathers)."""
    u_q, u_l, u_n, u_o = u_res
    n_uniq = int(inv.max()) + 1 if len(inv) else 0
    order = np.argsort(u_q, kind="stable")
    u_q, u_l = u_q[order], u_l[order]
    u_ranks = np.diff(u_o)[order]
    take = _ragged_take(u_o, order, u_ranks)
    u_n = u_n[take]
    u_o = np.concatenate([[0], np.cumsum(u_ranks)]).astype(np.int64)
    counts = np.bincount(u_q, minlength=n_uniq)
    return _replicate_sorted(u_l, u_n, u_ranks, u_o, counts, inv)


def _replicate_sorted(u_l, u_n, u_ranks, u_o, counts, inv: np.ndarray):
    """Replication core for unique results already grouped in unique-query
    order (the cache-assembly path lands here directly — no argsort, no
    pre-gather): `counts[u]` edges per unique query, `inv[q]` = the unique
    query whose results original query q receives."""
    starts = np.cumsum(counts) - counts
    out_counts = counts[inv]
    eidx = np.repeat(starts[inv], out_counts) + _ragged_arange(out_counts)
    r_q = np.repeat(np.arange(len(inv), dtype=np.int64), out_counts)
    r_l = u_l[eidx]
    ranks = u_ranks[eidx]
    r_n = u_n[_ragged_take(u_o, eidx, ranks)]
    r_o = np.concatenate([[0], np.cumsum(ranks)]).astype(np.int64)
    return r_q, r_l, r_n, r_o


def _entry_distinct_slot(entry, slot: int) -> np.ndarray:
    """Distinct node at tuple position `slot` within one result entry.
    Read-only: duplicate queries share this array, so an in-place mutation
    must fail loudly instead of corrupting the sibling's result."""
    _, nodes, offsets = entry
    ranks = np.diff(offsets)
    vals = _slot(nodes, offsets, ranks, slot)
    out = np.unique(vals[ranks > slot])
    out.flags.writeable = False
    return out


def query_oracle(graph, s, p, o) -> list[tuple]:
    """Reference: scan the uncompressed hypergraph (tests/benchmarks)."""
    out = []
    for e in range(graph.n_edges):
        label = int(graph.labels[e])
        nodes = graph.edge_nodes(e)
        if TripleQueryEngine._matches(label, nodes, s, p, o):
            out.append((label, tuple(int(v) for v in nodes)))
    return out
