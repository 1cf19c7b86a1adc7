"""Batched bitvector rank1 on the device: the k²-tree per-level descent's rank.

rank1(pos) = word_ranks[off + pos/32] + popcount(words[off + pos/32] & mask(pos%32))

Plain XLA: two gathers and ``lax.population_count``, fused by the compiler.
There is no Pallas kernel on this path: a kernel body cannot index a VMEM
ref with a vector of word ids on TPU, and holding the whole word array in
one block does not fit a kernel's scoped memory at real level sizes. XLA
gathers straight from HBM at any width (``tests/test_tpu_compile.py``
compiles it for a v5e at 2²⁴ words).

`offset` is a traced scalar, so one compiled program serves every level of
a tree whose levels share one concatenated word array
(:class:`repro.core.succinct.device_rank.DeviceLevels`). The fused descent
(:mod:`repro.kernels.k2_descent`) reads the same expression,
:func:`rank_in_word`, from the words it gathers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def rank_in_word(word, word_rank, rem):
    """rank1 at bit `rem` (uint32, 0..31) of a gathered `word` whose
    exclusive prefix popcount is `word_rank`: the ones strictly before it."""
    mask = (jnp.uint32(1) << rem) - jnp.uint32(1)  # rem == 0 -> 0
    return word_rank + lax.population_count(word & mask).astype(jnp.int32)


@jax.jit
def bitvec_rank(words, word_ranks, positions, offset=0):
    """words: (W,) uint32; word_ranks: (W,) int32 exclusive prefix popcounts;
    positions: (Q,) int32 bit positions with ``offset + pos // 32 < W``.
    Returns (Q,) int32 rank1 at each position. Callers validate positions:
    an out-of-range gather index is clamped, not reported."""
    w = offset + (positions >> 5)
    return rank_in_word(words[w], word_ranks[w], (positions & 31).astype(jnp.uint32))
