"""The plain reference: triple patterns answered by scanning sorted arrays.

It imports nothing of the store and takes nothing the store made: only
the generated triples. A pattern is ``(s, p, o)`` with ``None`` for an
unbound term; its answer is every stored triple that matches, each once,
as rows ``(s, p, o)`` in lexicographic order.
"""
from __future__ import annotations

import numpy as np

_AXES = (0, 2, 1)  # narrow by S, then O, then P: the most selective first


class TripleReference:
    def __init__(self, triples: np.ndarray):
        self.triples = np.asarray(triples, dtype=np.int64).reshape(-1, 3)
        self._order = {}
        self._keys = {}
        for c in _AXES:
            order = np.argsort(self.triples[:, c], kind="stable")
            self._order[c] = order
            self._keys[c] = self.triples[order, c]

    def answer(self, pattern) -> np.ndarray:
        bound = [c for c in _AXES if pattern[c] is not None]
        if bound:
            c = bound[0]
            v = int(pattern[c])
            lo, hi = np.searchsorted(self._keys[c], [v, v + 1])
            rows = self.triples[self._order[c][lo:hi]]
        else:
            rows = self.triples
        for c in bound[1:]:
            rows = rows[rows[:, c] == int(pattern[c])]
        return sort_rows(rows)


def sort_rows(rows: np.ndarray) -> np.ndarray:
    rows = np.asarray(rows, dtype=np.int64).reshape(-1, 3)
    if len(rows) < 2:
        return rows
    return rows[np.lexsort((rows[:, 2], rows[:, 1], rows[:, 0]))]
