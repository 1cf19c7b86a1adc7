"""Rank/select bitvector over packed uint32 words.

Bit `i` lives at word `i // 32`, bit position `i % 32` (LSB-first). Rank is
O(1) via per-word exclusive prefix popcounts (a 1/32 space overhead,
accounted separately so size reports can include or exclude the index);
select is O(log W) via searchsorted over the prefix array.

Construction is fully vectorized numpy; queries have both scalar and batched
(numpy array) entry points, all on the host. `rank1` here is the parity
oracle for the device rank of the k²-tree levels
(`repro.core.succinct.device_rank`, `tests/test_succinct.py`).
"""
from __future__ import annotations

import numpy as np

_M1 = np.uint32(0x55555555)
_M2 = np.uint32(0x33333333)
_M4 = np.uint32(0x0F0F0F0F)


def popcount32(words: np.ndarray) -> np.ndarray:
    """Vectorized popcount of uint32 words (SWAR)."""
    w = words.astype(np.uint32, copy=True)
    w = w - ((w >> np.uint32(1)) & _M1)
    w = (w & _M2) + ((w >> np.uint32(2)) & _M2)
    w = (w + (w >> np.uint32(4))) & _M4
    with np.errstate(over="ignore"):  # SWAR multiply wraps by design
        return ((w * np.uint32(0x01010101)) >> np.uint32(24)).astype(np.int64)


def pack_bits(bits: np.ndarray) -> np.ndarray:
    """Pack a bool/0-1 array into uint32 words (LSB-first)."""
    bits = np.asarray(bits, dtype=np.uint8)
    n = len(bits)
    n_words = (n + 31) // 32
    padded = np.zeros(n_words * 32, dtype=np.uint8)
    padded[:n] = bits
    lanes = padded.reshape(n_words, 32).astype(np.uint32)
    shifts = np.arange(32, dtype=np.uint32)
    return (lanes << shifts).sum(axis=1, dtype=np.uint32)


def unpack_bits(words: np.ndarray, n_bits: int) -> np.ndarray:
    """Inverse of pack_bits."""
    shifts = np.arange(32, dtype=np.uint32)
    lanes = (words[:, None] >> shifts) & np.uint32(1)
    return lanes.reshape(-1)[:n_bits].astype(np.uint8)


class BitVector:
    """Immutable bitvector with O(1) rank1 and O(log) select1."""

    def __init__(self, bits: np.ndarray):
        bits = np.asarray(bits, dtype=np.uint8)
        self.n = int(len(bits))
        self.words = pack_bits(bits)
        pc = popcount32(self.words)
        # word_ranks[w] = number of 1s strictly before word w
        self.word_ranks = np.concatenate([[0], np.cumsum(pc)]).astype(np.int64)
        self.n_ones = int(self.word_ranks[-1])

    @classmethod
    def from_positions(cls, positions: np.ndarray, n: int) -> "BitVector":
        bits = np.zeros(n, dtype=np.uint8)
        if len(positions):
            bits[np.asarray(positions, dtype=np.int64)] = 1
        return cls(bits)

    @classmethod
    def from_words(cls, words: np.ndarray, n: int) -> "BitVector":
        """Reconstruct from already-packed words (the snapshot load path):
        only the rank index is recomputed — no unpack/repack round-trip.
        `words` may be a read-only mmap view; it is never written to."""
        self = cls.__new__(cls)
        self.n = int(n)
        self.words = np.asarray(words, dtype=np.uint32)
        if len(self.words) != (self.n + 31) // 32:
            raise ValueError(
                f"{len(self.words)} words cannot back {self.n} bits")
        pc = popcount32(self.words)
        self.word_ranks = np.concatenate([[0], np.cumsum(pc)]).astype(np.int64)
        self.n_ones = int(self.word_ranks[-1])
        return self

    def __len__(self) -> int:
        return self.n

    def access(self, i) -> np.ndarray:
        i = np.asarray(i, dtype=np.int64)
        return ((self.words[i >> 5] >> (i & 31).astype(np.uint32)) & np.uint32(1)).astype(np.uint8)

    def rank1(self, i) -> np.ndarray:
        """Number of set bits in [0, i). Accepts scalars or arrays; i in [0, n]."""
        i = np.asarray(i, dtype=np.int64)
        w = i >> 5
        rem = (i & 31).astype(np.uint32)
        mask = np.where(rem == 0, np.uint32(0), (np.uint32(1) << rem) - np.uint32(1))
        # i == n with n % 32 == 0 indexes one-past-last word; guard it.
        wordvals = self.words[np.minimum(w, len(self.words) - 1)] if len(self.words) else np.zeros_like(w, dtype=np.uint32)
        partial = popcount32(np.where(w < len(self.words), wordvals & mask, np.uint32(0)))
        return self.word_ranks[np.minimum(w, len(self.word_ranks) - 1)] + partial

    def rank0(self, i) -> np.ndarray:
        i = np.asarray(i, dtype=np.int64)
        return i - self.rank1(i)

    def select1(self, j) -> np.ndarray:
        """Position of the j-th (0-based) set bit. Accepts scalars or arrays."""
        j = np.asarray(j, dtype=np.int64)
        if np.any(j >= self.n_ones) or np.any(j < 0):
            raise IndexError("select1 argument out of range")
        # word containing the (j+1)-th one:
        w = np.searchsorted(self.word_ranks, j, side="right") - 1
        within = (j - self.word_ranks[w]).astype(np.int64)
        # scan bits of word w for the `within`-th set bit (vectorized over 32 lanes)
        words = self.words[w]
        shifts = np.arange(32, dtype=np.uint32)
        lanes = ((np.atleast_1d(words)[:, None] >> shifts) & np.uint32(1)).astype(np.int64)
        cum = np.cumsum(lanes, axis=1) - lanes  # ones strictly before each lane
        hit = (lanes == 1) & (cum == np.atleast_1d(within)[:, None])
        pos_in_word = hit.argmax(axis=1)
        out = (np.atleast_1d(w) << 5) + pos_in_word
        return out[0] if j.ndim == 0 else out

    def size_in_bytes(self, include_rank_index: bool = True) -> int:
        base = self.words.nbytes
        if include_rank_index:
            # production layout: one 32-bit cumulative count per 8 words (256 bits)
            base += 4 * ((len(self.words) + 7) // 8)
        return base

    def to_numpy(self) -> np.ndarray:
        return unpack_bits(self.words, self.n)
