"""Succinct encoding of an SL-HR grammar (paper §Succinct Encoding).

Start graph: edges sorted by label id; the monotone label sequence is
Elias–Fano coded; the node×edge incidence matrix (dedup'd) is a k²-tree;
per-edge *index-functions* — π_e mapping connection-type m to the position
of e[m] in the duplicate-free sorted node list ζ_e — are deduplicated,
δ-coded once each, and referenced by δ-coded per-edge ids. Loops are thereby
absorbed without extra rules (paper §Handling loops).

Rules: right-hand sides only, in nonterminal order (topological after
prune), each as δ(#edges) then per edge δ(label+1) δ(node+1)*rank(label).
Rule ranks are recovered as max(node)+1 (every external occurs in the RHS).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.core.grammar import Grammar, Rule
from repro.core.hypergraph import Hypergraph, LabelTable
from repro.core.succinct import EliasFano, K2Tree, delta_decode, delta_encode


@dataclass
class EncodedGrammar:
    n_nodes: int
    n_edges: int
    n_terminals: int
    terminal_ranks: np.ndarray
    label_ef: EliasFano          # sorted per-edge label ids
    incidence: K2Tree            # rows = nodes, cols = edges (sorted order)
    fn_stream: tuple[np.ndarray, int]   # δ stream of unique index-functions
    fn_lengths: np.ndarray       # rank of each unique index-function
    n_fns: int
    edge_fn_stream: tuple[np.ndarray, int]  # δ stream of per-edge fn ids (+1)
    rule_stream: tuple[np.ndarray, int]     # δ stream of all rule bodies
    rule_symbol_count: int       # total δ symbols in rule_stream
    n_rules: int
    names: list[str] | None = None

    # ------------------------------------------------------------------
    def size_in_bytes(self, include_dictionary: bool = False) -> int:
        total = 8 * 4  # header counts
        total += (len(self.terminal_ranks) * 2 + 7) // 8 or 1
        total += self.label_ef.size_in_bytes()
        total += self.incidence.size_in_bytes()
        total += (self.fn_stream[1] + 7) // 8
        total += (self.edge_fn_stream[1] + 7) // 8
        total += (self.rule_stream[1] + 7) // 8
        if include_dictionary and self.names is not None:
            total += sum(len(s) + 1 for s in self.names)
        return total

    def decode(self) -> Grammar:
        labels = self.label_ef.to_numpy()
        # unique index-functions
        fn_vals = delta_decode(*self.fn_stream, int(self.fn_lengths.sum()) + self.n_fns)
        fns, pos = [], 0
        for _ in range(self.n_fns):
            rank = int(fn_vals[pos]) - 1 + 1  # δ(rank) stored as rank (>=1)
            pi = fn_vals[pos + 1 : pos + 1 + rank].astype(np.int64) - 1
            fns.append(pi)
            pos += 1 + rank
        fn_ids = delta_decode(*self.edge_fn_stream, self.n_edges).astype(np.int64) - 1
        # reconstruct edges: zeta from ONE batched incidence-column traversal
        # (all edges at once), nodes = zeta[pi] as a flat ragged gather. Past
        # DESCENT_MAX_BATCH edges that traversal goes level by level (each
        # level one wide rank), not fused: a whole-matrix frontier is bounded
        # by the tree, not by a batch; the answer is the same either way
        eidx, zeta_flat = self.incidence.cols_many(np.arange(self.n_edges, dtype=np.int64))
        zeta_counts = np.bincount(eidx, minlength=self.n_edges).astype(np.int64)
        zeta_starts = np.cumsum(zeta_counts) - zeta_counts
        fn_flat = np.concatenate(fns) if fns else np.zeros(0, np.int64)
        fn_lens = np.asarray(self.fn_lengths, dtype=np.int64)
        fn_starts = np.cumsum(fn_lens) - fn_lens
        ranks = fn_lens[fn_ids] if self.n_edges else np.zeros(0, np.int64)
        ends = np.cumsum(ranks)
        slot = np.arange(int(ranks.sum()), dtype=np.int64) - np.repeat(ends - ranks, ranks)
        pi_vals = fn_flat[np.repeat(fn_starts[fn_ids], ranks) + slot]
        flat = zeta_flat[np.repeat(zeta_starts, ranks) + pi_vals]
        offsets = np.concatenate([[0], ends]).astype(np.int64)
        start = Hypergraph(self.n_nodes, labels.astype(np.int64), flat, offsets)

        # rules
        vals = delta_decode(*self.rule_stream, self.rule_symbol_count).astype(np.int64)
        ranks = list(self.terminal_ranks)
        rules: dict[int, Rule] = {}
        pos = 0
        for i in range(self.n_rules):
            lbl = self.n_terminals + i
            n_e = int(vals[pos]); pos += 1
            r_labels, r_nodes = [], []
            for _ in range(n_e):
                el = int(vals[pos]) - 1; pos += 1
                r = int(ranks[el])
                nds = vals[pos : pos + r] - 1; pos += r
                r_labels.append(el)
                r_nodes.append(np.asarray(nds, dtype=np.int64))
            rank = int(max(n.max() for n in r_nodes)) + 1
            ranks.append(rank)
            rhs = Hypergraph.from_edges(rank, list(zip(r_labels, [n.tolist() for n in r_nodes])))
            rules[lbl] = Rule(lbl, rank, rhs)
        table = LabelTable(np.asarray(ranks, dtype=np.int64), self.n_terminals, self.names)
        return Grammar(table, start, rules)


def encode(grammar: Grammar) -> EncodedGrammar:
    g = grammar
    start, table = g.start, g.table
    order = np.argsort(start.labels, kind="stable")
    start = start.gather_edges(order)
    labels_sorted = start.labels

    # incidence matrix points (deduplicated by the k2 builder)
    ranks = start.ranks()
    edge_ids = np.repeat(np.arange(start.n_edges, dtype=np.int64), ranks)
    incidence = K2Tree(start.nodes_flat, edge_ids, max(start.n_nodes, 1), max(start.n_edges, 1))

    # index-functions
    fn_dict: dict[tuple, int] = {}
    fn_list: list[np.ndarray] = []
    per_edge = np.zeros(start.n_edges, dtype=np.int64)
    for e in range(start.n_edges):
        nodes = start.edge_nodes(e)
        zeta = np.unique(nodes)
        pi = np.searchsorted(zeta, nodes)
        key = tuple(pi.tolist())
        if key not in fn_dict:
            fn_dict[key] = len(fn_list)
            fn_list.append(pi)
        per_edge[e] = fn_dict[key]
    fn_symbols = []
    fn_lengths = np.array([len(pi) for pi in fn_list], dtype=np.int64)
    for pi in fn_list:
        fn_symbols.append(len(pi))           # δ(rank)
        fn_symbols.extend((pi + 1).tolist())  # δ(π(m)+1)
    fn_stream = delta_encode(np.asarray(fn_symbols if fn_symbols else [], dtype=np.uint64))
    edge_fn_stream = delta_encode((per_edge + 1).astype(np.uint64))

    # rules in label order (renumbered grammars are topological)
    rule_labels = sorted(g.rules.keys())
    assert rule_labels == list(range(table.n_terminals, table.n_terminals + len(rule_labels)))
    symbols = []
    for lbl in rule_labels:
        rhs = g.rules[lbl].rhs
        symbols.append(rhs.n_edges)
        for j in range(rhs.n_edges):
            symbols.append(int(rhs.labels[j]) + 1)
            symbols.extend((rhs.edge_nodes(j) + 1).tolist())
    rule_stream = delta_encode(np.asarray(symbols if symbols else [], dtype=np.uint64))

    return EncodedGrammar(
        n_nodes=start.n_nodes,
        n_edges=start.n_edges,
        n_terminals=table.n_terminals,
        terminal_ranks=table.ranks[: table.n_terminals].copy(),
        label_ef=EliasFano(labels_sorted, universe=int(table.n_labels)),
        incidence=incidence,
        fn_stream=fn_stream,
        fn_lengths=fn_lengths,
        n_fns=len(fn_list),
        edge_fn_stream=edge_fn_stream,
        rule_stream=rule_stream,
        rule_symbol_count=len(symbols),
        n_rules=len(rule_labels),
        names=table.names,
    )
