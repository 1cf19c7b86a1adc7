"""The whole k²-tree row/column descent as one device program.

``K2Tree._lines`` walks a batch of fixed coordinates down the tree level by
level: at each level every live (query, block, prefix) entry tests its `k`
candidate child bits, and the set ones become the next level's entries, the
child block being the bit's rank1. Here every level runs inside one jitted
program over the tree's device levels
(:class:`repro.core.succinct.device_rank.DeviceLevels`), so a batch costs
one launch and one device→host copy instead of a rank round trip per level.

The frontier lives in one device array of two halves, each of static
capacity `cap`; a level reads one half and writes the other. A level walks
its live entries in chunks of ``CHUNK`` (a loop whose trip count follows
the live count, so the work follows the frontier and not the capacity).
Per chunk:

1. each entry forms its `k` candidate bit positions ``block * k² + child``;
2. it gathers the word and the word's exclusive rank under them, and takes
   both the access bit and :func:`~repro.kernels.bitvec_rank.rank_in_word`
   from that gather. Where ``k²`` divides 32 (k = 2, 4) a block's ``k²`` bits
   lie in one word, so an entry gathers once for all its candidates;
   otherwise (k = 3) each candidate gathers its own word;
3. candidates out of the level or unset are masked;
4. the survivors are compacted in order by a stable sort on "unset" and
   written after the ones the level has already kept. On a v5e the sort
   takes 40% less time than a ``jnp.nonzero`` compaction and its gather,
   whose per-element scatter and gather dominated the step.

Entries stay sorted by (query, free coordinate) at every step, so the output
is sorted too. Each level's true survivor count comes back with the pairs:
a count above `cap` (or, at the last level, above `out_cap`) means the answer
was cut short, and the caller grows the capacity and runs the batch again.

Plain XLA, as :mod:`repro.kernels.bitvec_rank` explains: the level and chunk
loops compile once whatever the tree's height, and the jitted function's
name puts ``k2_descent`` in its XLA module's name, which the benchmark's
trace reduction matches. Every index is int32: the caller keeps trees whose
side or levels would not fit off the device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.bitvec_rank import rank_in_word

#: frontier entries a level processes per step of its loop; every level
#: takes at least one step, and a step's cost grows with its width, so a
#: narrower chunk favours the small batches of one shard's flush
CHUNK = 1024


@functools.partial(jax.jit, static_argnames=("k", "axis", "cap", "out_cap"))
def k2_descent(words, ranks, offsets, n_bits, fixed, n_fixed, *, k: int, axis: int,
               cap: int, out_cap: int):
    """words, ranks: (W,) uint32 / int32, a tree's concatenated levels;
    offsets, n_bits: (h,) int32, each level's first word and its bits;
    fixed: (Q,) int32 in-range fixed coordinates, the first `n_fixed` live;
    k: the tree's arity; axis: 0 expands rows, 1 columns; cap: the most
    entries a level keeps; out_cap (<= cap): the most pairs returned.

    Returns one (h + 2 * out_cap,) int32 array: each level's survivor count,
    then the last level's query ids and free coordinates (the first
    ``min(count, out_cap)`` of each are the answer, sorted by (query,
    coord))."""
    h, k2, width = offsets.shape[0], k * k, CHUNK * k
    size = cap + width  # a chunk read or written from any base <= cap fits
    scales = jnp.asarray([k ** (h - 1 - t) for t in range(h)], jnp.int32)
    free = jnp.arange(k, dtype=jnp.int32)[None, :]
    q = fixed.shape[0]
    # frontier[half] holds (query id, block, free-coordinate prefix) rows; a
    # level reads half t % 2 and writes the other, in place
    frontier = jnp.zeros((2, 3, size), jnp.int32).at[0, 0, :q].set(
        jnp.arange(q, dtype=jnp.int32))

    def level(t, carry):
        frontier, n, sizes = carry
        n_in = jnp.minimum(n, cap)

        def chunk(c, state):
            frontier, count = state
            base = c * CHUNK
            qid, block, prefix = lax.dynamic_slice(frontier, (t % 2, 0, base), (1, 3, CHUNK))[0]
            live = base + jnp.arange(CHUNK, dtype=jnp.int32) < n_in
            digit = lax.rem(lax.div(fixed[qid], scales[t]), jnp.int32(k))
            child = digit[:, None] * k + free if axis == 0 else free * k + digit[:, None]
            pos = block[:, None] * k2 + child                    # (CHUNK, k)
            if 32 % k2 == 0:
                w = offsets[t] + ((block * k2) >> 5)
                word, wrank = words[w][:, None], ranks[w][:, None]
            else:
                w = offsets[t] + (pos >> 5)
                word, wrank = words[w], ranks[w]
            rem = (pos & 31).astype(jnp.uint32)
            keep = (live[:, None] & (pos < n_bits[t]) & (((word >> rem) & 1) == 1)).reshape(-1)
            rank = jnp.broadcast_to(rank_in_word(word, wrank, rem), pos.shape).reshape(-1)
            _, *rows = lax.sort((1 - keep.astype(jnp.int32), jnp.repeat(qid, k), rank,
                                 (prefix[:, None] * k + free).reshape(-1)),
                                num_keys=1, is_stable=True)
            frontier = lax.dynamic_update_slice(
                frontier, jnp.stack(rows)[None], (1 - t % 2, 0, jnp.minimum(count, cap)))
            return frontier, count + jnp.sum(keep, dtype=jnp.int32)

        frontier, count = lax.fori_loop(0, (n_in + CHUNK - 1) // CHUNK, chunk,
                                        (frontier, jnp.int32(0)))
        return frontier, count, sizes.at[t].set(count)

    frontier, _, sizes = lax.fori_loop(0, h, level, (frontier, n_fixed, jnp.zeros(h, jnp.int32)))
    last = frontier[h % 2]
    return jnp.concatenate([sizes, last[0, :out_cap], last[2, :out_cap]])
