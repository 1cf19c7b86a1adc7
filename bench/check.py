"""Is what the timed path returned correct? The answers of the window's
requests against the plain reference (``bench/reference.py``).

Three numbers, each with the limit it must not pass:

* ``lost_requests`` — requests that raised or never came back;
* ``wrong_counts`` — patterns, over every request that came back, whose
  number of results differs from the reference's;
* ``wrong_answers`` — patterns, over the requests whose answers were kept
  whole (all of them, or a sample drawn from the seed), whose set of
  triples differs from the reference's.

The comparison is exact, so every limit is 0.
"""
from __future__ import annotations

import itertools

import numpy as np

from bench.reference import TripleReference, sort_rows

LIMITS = {"lost_requests": 0, "wrong_counts": 0, "wrong_answers": 0}


def answer_rows(answer) -> np.ndarray:
    """One pattern's answer, ``(p, (s, o))`` tuples, as sorted (s, p, o) rows."""
    flat = np.fromiter(itertools.chain.from_iterable(
        (nodes[0], p, nodes[1]) for p, nodes in answer), dtype=np.int64,
        count=3 * len(answer))
    return sort_rows(flat.reshape(-1, 3))


def compare(records: list, triples: np.ndarray) -> dict:
    """name -> {"value": n, "limit": limit}, plus the counts checked."""
    ref = TripleReference(triples)
    counts: dict = {}

    def ref_count(pattern) -> int:
        if pattern not in counts:
            counts[pattern] = len(ref.answer(pattern))
        return counts[pattern]

    lost = wrong_counts = wrong_answers = 0
    n_counted = n_whole = 0
    for rec in records:
        if not rec.done or rec.error is not None or rec.counts is None:
            lost += 1
            continue
        for pattern, n in zip(rec.patterns, rec.counts):
            n_counted += 1
            wrong_counts += n != ref_count(pattern)
        if len(rec.counts) != len(rec.patterns):
            wrong_counts += abs(len(rec.patterns) - len(rec.counts))
        if rec.answer is not None:
            for pattern, got in zip(rec.patterns, rec.answer):
                n_whole += 1
                want = ref.answer(pattern)
                rows = answer_rows(got)
                wrong_answers += not (rows.shape == want.shape
                                      and np.array_equal(rows, want))
    values = {"lost_requests": lost, "wrong_counts": wrong_counts,
              "wrong_answers": wrong_answers}
    checks = {k: {"value": int(v), "limit": LIMITS[k]} for k, v in values.items()}
    return {"checks": checks, "patterns_counted": n_counted,
            "patterns_compared_whole": n_whole}
