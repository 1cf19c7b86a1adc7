"""The k²-tree's levels on the device, and the batched rank that reads them.

Where JAX's default backend is a TPU, every :class:`~repro.core.succinct.
K2Tree` lays its levels out as one device word array and one device rank
array when it is built or loaded — never lazily from concurrent readers —
and the batched ``rank1`` of its descent runs there
(:func:`repro.kernels.bitvec_rank.bitvec_rank`). Elsewhere the levels
stay on the host and numpy answers (``BitVector.rank1``, which is
also the parity oracle). Nothing falls back: a device error raises.

Compiles are bounded. The concatenated word array is padded to a power of
two (its width bucket) and each batch of positions to a power of two of at
least ``MIN_POSITIONS`` (its position bucket); one compiled program serves
each (width bucket, position bucket) pair, whatever tree or level asks.
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


def compiled_programs() -> int:
    """Number of rank programs this process has compiled."""
    return _program.cache_info().currsize


class DeviceLevels:
    """One tree's levels as one device word array plus one rank array.

    Level t occupies entries ``[offsets[t], offsets[t] + W_t + 1)``: its
    ``W_t`` packed words and one zero word, so ``rank1(n)`` (one past the
    last bit) reads in bounds, next to its ``W_t + 1`` exclusive prefix
    ranks. Every index the device sees is int32; a level or a tree that
    would not fit raises ``OverflowError`` here instead of wrapping.
    """

    def __init__(self, levels):
        import jax

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
