"""k²-tree (Brisaboa et al. [7]) over a sparse 0/1 matrix, built from COO.

ITR uses k²-trees twice: for the node×edge *incidence matrix* of the start
graph, and for the NT (nonterminal × terminal-label) reachability matrix of
the triple-query engine.

Layout note: the classic structure concatenates all internal levels into one
bitmap T plus a leaf bitmap L and navigates with a single rank. We keep one
BitVector per level (identical total bit count, plus one pointer per level);
child block of the j-th set bit of level t is block j of level t+1. This
keeps construction fully vectorized (digit-radix sort per level) and row/
column expansion a simple per-level frontier sweep.

On a TPU backend the levels are also laid out on the device once, when the
tree is built or loaded, and the descent's batched rank runs there
(`repro.core.succinct.device_rank`); `rank_calls` counts rank calls per
call site and side.
"""
from __future__ import annotations

from collections import Counter

import numpy as np

from repro.core.succinct import device_rank
from repro.core.succinct.bitvector import BitVector


class K2Tree:
    def __init__(self, rows: np.ndarray, cols: np.ndarray, n_rows: int, n_cols: int, k: int = 2):
        rows = np.asarray(rows, dtype=np.int64)
        cols = np.asarray(cols, dtype=np.int64)
        if rows.size:
            if rows.min() < 0 or rows.max() >= n_rows or cols.min() < 0 or cols.max() >= n_cols:
                raise ValueError("point out of bounds")
        self.n_rows, self.n_cols, self.k = int(n_rows), int(n_cols), int(k)
        side = max(n_rows, n_cols, 1)
        h = 1
        while k**h < side:
            h += 1
        self.h = h
        self.side = k**h
        self.n_points = 0
        self.levels: list[BitVector] = []
        self._build(rows, cols)
        self._place()

    @classmethod
    def from_levels(cls, n_rows: int, n_cols: int, k: int, h: int,
                    n_points: int, level_words: list, level_bits: list) -> "K2Tree":
        """Reconstruct from persisted per-level bitvector words (the
        snapshot load path): no COO radix build, only rank-index
        recomputation inside each :meth:`BitVector.from_words`."""
        self = cls.__new__(cls)
        self.n_rows, self.n_cols, self.k = int(n_rows), int(n_cols), int(k)
        self.h = int(h)
        self.side = self.k ** self.h
        self.n_points = int(n_points)
        if len(level_words) != self.h and not (len(level_words) == 1
                                               and n_points == 0):
            raise ValueError(
                f"{len(level_words)} levels for a height-{self.h} k2-tree")
        self.levels = [BitVector.from_words(w, int(nb))
                       for w, nb in zip(level_words, level_bits)]
        self._place()
        return self

    def _place(self):
        """Upload the levels once, where the platform runs rank on the device."""
        self.device = device_rank.DeviceLevels(self.levels) \
            if device_rank.enabled() else None
        #: rank calls by (call site, "device" | "host")
        self.rank_calls: Counter = Counter()

    def _rank(self, site: str, t: int, pos: np.ndarray) -> np.ndarray:
        if self.device is not None and len(pos) >= device_rank.DEVICE_MIN_BATCH:
            self.rank_calls[site, "device"] += 1
            return self.device.rank1(t, pos)
        self.rank_calls[site, "host"] += 1
        return self.levels[t].rank1(pos)

    def _build(self, rows: np.ndarray, cols: np.ndarray):
        k, k2, h = self.k, self.k * self.k, self.h
        if rows.size == 0:
            self.levels = [BitVector(np.zeros(k2, dtype=np.uint8))]
            return
        # dedup points
        flat = rows * self.n_cols + cols
        flat = np.unique(flat)
        rows = flat // self.n_cols
        cols = flat % self.n_cols
        self.n_points = len(flat)

        # child digit of each point at each level
        childs = np.empty((h, len(rows)), dtype=np.int64)
        for t in range(h):
            scale = k ** (h - 1 - t)
            childs[t] = (rows // scale % k) * k + (cols // scale % k)

        levels = []
        keys = np.zeros(len(rows), dtype=np.int64)  # node key at current level (root=0)
        for t in range(h):
            pair = keys * k2 + childs[t]
            uniq_keys, key_idx = np.unique(keys, return_inverse=True)
            uniq_pair = np.unique(pair)
            bits = np.zeros(len(uniq_keys) * k2, dtype=np.uint8)
            # position of each set child bit: parent's index in level order * k2 + child
            parent_of_pair = np.searchsorted(uniq_keys, uniq_pair // k2)
            bits[parent_of_pair * k2 + uniq_pair % k2] = 1
            levels.append(BitVector(bits))
            # next level node key = rank of (key,child) among set bits == index in uniq_pair
            keys = np.searchsorted(uniq_pair, pair)
        self.levels = levels

    # ---------------- queries ----------------
    # The row/col expansion is *batched*: many fixed coordinates traverse the
    # tree together, level-synchronously, carrying a query-id column; each
    # level issues ONE vectorized rank1 over the concatenated child bit
    # positions (the k²-tree hot op — on the device on a TPU backend).

    def access(self, r: int, c: int) -> int:
        k, k2 = self.k, self.k * self.k
        block = 0
        for t in range(self.h):
            scale = k ** (self.h - 1 - t)
            child = (r // scale % k) * k + (c // scale % k)
            bitpos = block * k2 + child
            if bitpos >= self.levels[t].n or not int(self.levels[t].access(bitpos)):
                return 0
            block = int(self._rank("access", t, np.array([bitpos]))[0])
        return 1

    def row(self, r: int) -> np.ndarray:
        """All columns c with M[r, c] = 1, without decompressing the matrix."""
        return self._lines(np.array([r], dtype=np.int64), axis=0)[1]

    def col(self, c: int) -> np.ndarray:
        """All rows r with M[r, c] = 1."""
        return self._lines(np.array([c], dtype=np.int64), axis=1)[1]

    def rows_many(self, rs: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Batched row expansion: one traversal for many rows.

        Returns (idx, cols): query rs[idx[i]] has a 1 at column cols[i];
        pairs are sorted by (idx, col). Out-of-range rows yield no pairs.
        """
        return self._lines(rs, axis=0)

    def cols_many(self, cs: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Batched column expansion; see :meth:`rows_many`."""
        return self._lines(cs, axis=1)

    def _lines(self, fixed: np.ndarray, axis: int) -> tuple[np.ndarray, np.ndarray]:
        k, k2 = self.k, self.k * self.k
        fixed = np.asarray(fixed, dtype=np.int64)
        limit_fixed = self.n_rows if axis == 0 else self.n_cols
        limit_free = self.n_cols if axis == 0 else self.n_rows
        ok = (fixed >= 0) & (fixed < limit_fixed)
        qids = np.flatnonzero(ok).astype(np.int64)
        fvals = fixed[qids]
        blocks = np.zeros(len(qids), dtype=np.int64)
        prefixes = np.zeros(len(qids), dtype=np.int64)  # free-axis coordinate prefix
        free = np.arange(k, dtype=np.int64)
        empty = (np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.int64))
        for t in range(self.h):
            if len(blocks) == 0:
                return empty
            scale = k ** (self.h - 1 - t)
            fixed_digit = fvals // scale % k
            # candidate children: fixed axis digit fixed, free axis digit 0..k-1
            if axis == 0:  # row query: row digit fixed, col digit free
                child = fixed_digit[:, None] * k + free[None, :]
            else:  # col query: col digit fixed, row digit free
                child = free[None, :] * k + fixed_digit[:, None]
            bitpos = (blocks[:, None] * k2 + child).reshape(-1)
            new_prefix = (prefixes[:, None] * k + free[None, :]).reshape(-1)
            new_qids = np.repeat(qids, k)
            new_fvals = np.repeat(fvals, k)
            lv = self.levels[t]
            valid = bitpos < lv.n
            setbit = np.zeros(len(bitpos), dtype=bool)
            if valid.any():
                setbit[valid] = lv.access(bitpos[valid]).astype(bool)
            bitpos = bitpos[setbit]
            qids, fvals, prefixes = new_qids[setbit], new_fvals[setbit], new_prefix[setbit]
            if t < self.h - 1:
                blocks = self._rank("descent", t, bitpos)  # one batched rank per level
            else:
                keep = prefixes < limit_free
                qids, coords = qids[keep], prefixes[keep]
                order = np.lexsort((coords, qids))
                return qids[order], coords[order]
        return empty

    def to_dense(self) -> np.ndarray:
        out = np.zeros((self.n_rows, self.n_cols), dtype=np.uint8)
        r_idx, cols = self.rows_many(np.arange(self.n_rows, dtype=np.int64))
        out[r_idx, cols] = 1
        return out

    def size_in_bytes(self) -> int:
        return sum(lv.size_in_bytes() for lv in self.levels) + 8 * len(self.levels)
