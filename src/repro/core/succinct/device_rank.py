"""The k²-tree's levels on the device, and the programs that read them.

Where JAX's default backend is a TPU, every :class:`~repro.core.succinct.
K2Tree` lays its levels out as one device word array and one device rank
array when it is built or loaded — never lazily from concurrent readers.
Two programs read them, one algorithm scheduled by batch size:

* a batch of ``FUSED_MIN_BATCH`` to ``DESCENT_MAX_BATCH`` fixed
  coordinates descends the whole tree in one launch
  (:func:`repro.kernels.k2_descent.k2_descent`, through
  :meth:`DeviceLevels.descend`);
* any other descent (one coordinate, or a whole-matrix expansion) runs
  level by level on the host, and ranks each level's positions on the
  device where there are at least ``DEVICE_MIN_BATCH`` of them
  (:func:`repro.kernels.bitvec_rank.bitvec_rank`, through
  :meth:`DeviceLevels.rank1`).

Elsewhere the levels stay on the host and numpy answers (``BitVector.
rank1`` and the per-level descent, which are also the parity oracles).
Nothing falls back: a device error raises.

Compiles are bounded. The concatenated word array is padded to a power of
two (its width bucket). A rank batch is padded to a power of two of at
least ``MIN_POSITIONS`` (its position bucket); one rank program serves each
(width bucket, position bucket) pair, whatever tree or level asks. A fused
descent pads its coordinates to ``DESCENT_MAX_BATCH``; its program is keyed
by the width bucket, the tree's arity and height, the axis, and two
capacities (frontier entries per level, pairs returned) that each tree keeps
per axis and only grows.
"""
from __future__ import annotations

import functools

import numpy as np

from repro.tracing import traced

#: rank batches smaller than this stay on the host, where one device
#: dispatch and two transfers are expected to cost more than numpy (the
#: threshold is not measured on a chip); every call is counted per site
#: and side in ``K2Tree.rank_calls``
DEVICE_MIN_BATCH = 32
#: the fewest fixed coordinates a fused descent takes. On a v5e even a
#: batch of a few coordinates walks ~14 levels of a jamendo shard tree
#: wide enough to rank on the device, one round trip each, where one
#: fused launch costs about as much as two; a single coordinate (``row``,
#: ``col``) keeps the per-level walk
FUSED_MIN_BATCH = 2
#: the most fixed coordinates one fused descent takes (the services'
#: ``max_batch``); a larger batch, such as a whole-matrix expansion,
#: descends level by level, where each level is already one wide launch
DESCENT_MAX_BATCH = 1024
MIN_POSITIONS = 256
MIN_WIDTH = 1024
_INT32_MAX = 2**31 - 1


def enabled() -> bool:
    """True where k²-tree levels live on the device: a TPU backend."""
    import jax

    return jax.default_backend() == "tpu"


def _pow2_at_least(n: int, floor: int) -> int:
    return max(floor, 1 << max(int(n) - 1, 0).bit_length())


def position_bucket(q: int) -> int:
    """Padded length of a batch of `q` positions."""
    return _pow2_at_least(q, MIN_POSITIONS)


def width_bucket(w: int) -> int:
    """Padded length of a tree's concatenated word array of `w` words."""
    return _pow2_at_least(w, MIN_WIDTH)


def rank_args(width: int, bucket: int, sharding=None) -> tuple:
    """Argument shapes of the rank program: words, ranks, positions, offset."""
    import jax
    import jax.numpy as jnp

    return (jax.ShapeDtypeStruct((width,), jnp.uint32, sharding=sharding),
            jax.ShapeDtypeStruct((width,), jnp.int32, sharding=sharding),
            jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=sharding),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding))


@functools.lru_cache(maxsize=None)
@traced("itr.k2.compile")
def _program(width: int, bucket: int):
    """The compiled rank for one (width bucket, position bucket) pair."""
    from repro.kernels.bitvec_rank import bitvec_rank

    return bitvec_rank.lower(*rank_args(width, bucket)).compile()


def descent_args(width: int, h: int, sharding=None) -> tuple:
    """Argument shapes of the fused descent: words, ranks, level offsets,
    level bit counts, fixed coordinates and their count."""
    import jax
    import jax.numpy as jnp

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (arg((width,), jnp.uint32), arg((width,), jnp.int32),
            arg((h,), jnp.int32), arg((h,), jnp.int32),
            arg((DESCENT_MAX_BATCH,), jnp.int32), arg((), jnp.int32))


@functools.lru_cache(maxsize=None)
@traced("itr.k2.compile")
def _descent_program(width: int, k: int, h: int, axis: int, cap: int, out_cap: int):
    """The compiled fused descent for one width bucket and capacity pair."""
    from repro.kernels.k2_descent import k2_descent

    return k2_descent.lower(*descent_args(width, h), k=k, axis=axis,
                            cap=cap, out_cap=out_cap).compile()


def descent_positions(q: int, sizes: np.ndarray, cap: int) -> tuple[int, int]:
    """Frontier entries a fused descent of `q` coordinates processed
    (asked) and the chunk-padded entries its loops ran over (launched)."""
    from repro.kernels.k2_descent import CHUNK

    entries = np.minimum(np.concatenate([[q], sizes[:-1]]), cap)
    return int(entries.sum()), int((-(-entries // CHUNK) * CHUNK).sum())


def compiled_programs() -> int:
    """Number of rank and descent programs this process has compiled."""
    return _program.cache_info().currsize + _descent_program.cache_info().currsize


class DeviceLevels:
    """One tree's levels as one device word array plus one rank array.

    Level t occupies entries ``[offsets[t], offsets[t] + W_t + 1)``: its
    ``W_t`` packed words and one zero word, so ``rank1(n)`` (one past the
    last bit) reads in bounds, next to its ``W_t + 1`` exclusive prefix
    ranks. Every index the device sees is int32, a descent's coordinates
    too; a level, a tree or a `side` (the tree's ``k**h``) that would not
    fit raises ``OverflowError`` here instead of wrapping.
    """

    def __init__(self, levels, side: int = 0):
        import jax

        if side > _INT32_MAX + 1:
            raise OverflowError(
                f"k2-tree side {side} exceeds int32 device coordinates")
        sizes = [len(lv.words) + 1 for lv in levels]
        for t, lv in enumerate(levels):
            if lv.n > _INT32_MAX or lv.n_ones > _INT32_MAX:
                raise OverflowError(
                    f"k2-tree level {t} ({lv.n} bits, {lv.n_ones} ones) "
                    "exceeds int32 device indexing")
        width = width_bucket(sum(sizes))
        if width > _INT32_MAX:
            raise OverflowError(
                f"k2-tree of {sum(sizes)} words exceeds int32 device indexing")
        words = np.zeros(width, dtype=np.uint32)
        ranks = np.zeros(width, dtype=np.int32)
        self.offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int64)
        for lv, off in zip(levels, self.offsets):
            words[off:off + len(lv.words)] = lv.words
            ranks[off:off + len(lv.word_ranks)] = lv.word_ranks
        self.n_bits = [lv.n for lv in levels]
        self.nbytes = words.nbytes + ranks.nbytes
        self.words = jax.device_put(words)
        self.ranks = jax.device_put(ranks)
        self.level_offsets = jax.device_put(self.offsets.astype(np.int32))
        self.level_bits = jax.device_put(np.asarray(self.n_bits, dtype=np.int32))

    def rank1(self, t: int, i: np.ndarray) -> np.ndarray:
        """Batched rank1 on level `t` at int64 positions `i` in [0, n]."""
        i = np.asarray(i, dtype=np.int64)
        q = len(i)
        if q and (int(i.min()) < 0 or int(i.max()) > self.n_bits[t]):
            raise IndexError(f"rank position outside level {t} "
                             f"[0, {self.n_bits[t]}]")
        bucket = position_bucket(q)
        pos = np.zeros(bucket, dtype=np.int32)
        pos[:q] = i
        out = _program(len(self.words), bucket)(
            self.words, self.ranks, pos, np.int32(self.offsets[t]))
        return np.asarray(out)[:q].astype(np.int64)

    def descend(self, fixed: np.ndarray, k: int, axis: int, cap: int, out_cap: int):
        """One fused descent of in-range coordinates `fixed` (at most
        ``DESCENT_MAX_BATCH``): a level keeps at most `cap` entries, the
        last at most `out_cap` pairs. Returns each level's true survivor
        count and the (index into `fixed`, free coordinate) pairs kept,
        sorted; a count above its capacity means they were cut short."""
        q = len(fixed)
        if not q <= min(cap, DESCENT_MAX_BATCH):
            raise ValueError(f"{q} coordinates for a descent of capacity {cap}")
        pad = np.zeros(DESCENT_MAX_BATCH, dtype=np.int32)
        pad[:q] = fixed
        h = len(self.n_bits)
        out = np.asarray(_descent_program(len(self.words), k, h, axis, cap, out_cap)(
            self.words, self.ranks, self.level_offsets, self.level_bits,
            pad, np.int32(q))).astype(np.int64)
        sizes = out[:h]
        n = min(int(sizes[-1]), out_cap)
        return sizes, out[h:h + n], out[h + out_cap:h + out_cap + n]
