#!/usr/bin/env python
"""Chip smoke: the triple store's query path on one TPU, end to end.

Builds the paper's jamendo dataset at its published scale (the seeded
stand-in ``PAPER_DATASETS["jamendo"](scale=1.0)``: 396,531 nodes,
1,047,951 triples, 25 predicates) into a durable 4-shard tier, answers
queries of all eight (S,P,O) shapes through ``query_many``, snapshots,
reopens the tier from disk and answers the same queries again. It checks

* a sample of every shape against ``query_oracle`` over the generated
  triples,
* every answer after the reopen against the answer before it,
* that every S/O-bound shape ran k²-tree ranks on the device, in both
  passes.

Run from the repository root, on a machine with a TPU::

    python chip_smoke.py [--seed N]

It exits non-zero, and prints no result line, where JAX finds no TPU,
where any answer differs, or where an S/O-bound shape made no device rank
call. Otherwise the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Every time it prints is a smoke timing of one cold run, not a metric.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SCALE = 1.0
N_SHARDS = 4
SHAPES = ("spo", "sp?", "s?o", "s??", "?po", "?p?", "??o", "???")
PER_SHAPE = 96      # queries per shape; S/O-bound batches this wide reach the device
ORACLE_SAMPLE = 24  # queries per shape checked against query_oracle


def require_tpu():
    """The first device, which must be a TPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    return dev


def make_queries(triples, rng) -> dict[str, list[tuple]]:
    """PER_SHAPE patterns per shape, each bound from a stored triple."""
    out = {}
    for shape in SHAPES:
        rows = triples[rng.integers(0, len(triples), PER_SHAPE)]
        out[shape] = [tuple(int(v) if c != "?" else None for c, v in zip(shape, row))
                      for row in rows]
    return out


def check_oracle(triples, n_nodes, queries, answers) -> list[str]:
    """Compare the first ORACLE_SAMPLE answers of each shape with
    query_oracle. The oracle scans only the triples that share the
    pattern's first bound term (a plain numpy pre-filter; the oracle
    applies the whole match), and all of them for ``???``."""
    import numpy as np

    from repro.core import Hypergraph
    from repro.core.query import query_oracle

    order = {c: np.argsort(triples[:, c], kind="stable") for c in range(3)}
    keys = {c: triples[order[c], c] for c in range(3)}

    def candidates(pattern):
        bound = [c for c in (0, 2, 1) if pattern[c] is not None]
        if not bound:
            return triples
        c = bound[0]
        lo, hi = np.searchsorted(keys[c], [pattern[c], pattern[c] + 1])
        return triples[np.sort(order[c][lo:hi])]

    t0 = time.perf_counter()
    sample = {q: got for shape in SHAPES
              for q, got in list(zip(queries[shape], answers[shape]))[:ORACLE_SAMPLE]}
    failures = []
    for q, got in sample.items():  # duplicate patterns share one answer
        graph = Hypergraph.from_triples(candidates(q), n_nodes)
        want = sorted(query_oracle(graph, *q))
        if sorted(got) != want:
            failures.append(f"{q}: {len(got)} results, oracle {len(want)}")
    print(f"oracle: {ORACLE_SAMPLE * len(SHAPES)} queries checked "
          f"({len(sample)} distinct), smoke timing {time.perf_counter() - t0:.1f} s")
    return failures


def trees(svc) -> list:
    out = []
    for eng in svc.engines:
        out.append(eng.encoded.incidence)
        if eng.nt_k2 is not None:
            out.append(eng.nt_k2)
    return out


def rank_counts(svc) -> Counter:
    total: Counter = Counter()
    for t in trees(svc):
        for (_site, side), n in t.rank_calls.items():
            total[side] += n
    return total


def answer_all(svc, queries, label: str) -> tuple[dict, dict]:
    """Answers per shape, and device/host rank calls per shape."""
    answers, calls = {}, {}
    for shape in SHAPES:
        before = rank_counts(svc)
        t0 = time.perf_counter()
        answers[shape] = svc.query_many(queries[shape])
        dt = time.perf_counter() - t0
        calls[shape] = rank_counts(svc) - before
        n_res = sum(len(a) for a in answers[shape])
        print(f"{label} {shape}: {len(queries[shape])} queries, {n_res} results, "
              f"rank calls device={calls[shape]['device']} "
              f"host={calls[shape]['host']}, smoke timing {dt:.3f} s")
    return answers, calls


def so_bound_without_device(calls: dict) -> list[str]:
    return [s for s in SHAPES if (s[0] != "?" or s[2] != "?")
            and calls[s]["device"] == 0]


def run(seed: int = 0, scale: float = SCALE) -> int:
    """The whole smoke; returns the process exit code."""
    dev = require_tpu()
    import jax
    import numpy as np

    from repro.compile_cache import enable_compile_cache
    from repro.core.succinct import device_rank
    from repro.data.synthetic import PAPER_DATASETS
    from repro.persist.service import DurableShardedService

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {json.dumps(device)}")
    print(f"compile cache: {enable_compile_cache()}")

    t0 = time.perf_counter()
    ds = PAPER_DATASETS["jamendo"](scale=scale, seed=seed)
    triples = ds.triples
    print(f"dataset: jamendo stand-in scale={scale} seed={seed}: "
          f"{ds.n_nodes} nodes, {ds.n_triples} triples, {ds.n_preds} predicates "
          f"(generated in {time.perf_counter() - t0:.1f} s)")

    store = Path(tempfile.mkdtemp(prefix="store_", dir=_store_parent()))
    failures: list[str] = []
    try:
        t0 = time.perf_counter()
        svc = DurableShardedService.build(triples, ds.n_nodes, ds.n_preds,
                                          root=store, n_shards=N_SHARDS)
        print(f"build: {N_SHARDS} shards, smoke timing {time.perf_counter() - t0:.1f} s")
        try:
            placed = [t for t in trees(svc) if t.device is not None]
            print(f"k2-tree levels on device: {len(placed)} trees, "
                  f"{sum(t.device.nbytes for t in placed)} bytes")
            stats = dev.memory_stats()
            if stats:
                print(f"device memory: bytes_in_use={stats.get('bytes_in_use')} "
                      f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
            queries = make_queries(triples, np.random.default_rng(seed))
            first, calls = answer_all(svc, queries, "pass 1")
            failures += [f"pass 1 {s}: no device rank call"
                         for s in so_bound_without_device(calls)]
            failures += check_oracle(triples, ds.n_nodes, queries, first)
            t0 = time.perf_counter()
            svc.snapshot()
        finally:
            svc.close()
        svc = DurableShardedService.open(store)
        print(f"snapshot + reopen: smoke timing {time.perf_counter() - t0:.1f} s")
        try:
            second, calls = answer_all(svc, queries, "pass 2")
        finally:
            svc.close()
        failures += [f"pass 2 {s}: no device rank call"
                     for s in so_bound_without_device(calls)]
        before = {q: a for s in SHAPES for q, a in zip(queries[s], first[s])}
        after = {q: b for s in SHAPES for q, b in zip(queries[s], second[s])}
        failures += [f"{q}: differs after reopen" for q in before
                     if sorted(before[q]) != sorted(after[q])]
        print(f"reopen: {len(before)} distinct answers compared")
        print(f"compiled rank programs: {device_rank.compiled_programs()}")
    finally:
        shutil.rmtree(store, ignore_errors=True)

    if failures:
        for f in failures[:50]:
            print(f"FAIL {f}", file=sys.stderr)
        print(f"chip_smoke: {len(failures)} failures", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


def _store_parent() -> Path:
    parent = ROOT / ".smoke_store"
    parent.mkdir(exist_ok=True)
    return parent


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the generated dataset and queries")
    args = parser.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    return run(args.seed)


if __name__ == "__main__":
    sys.exit(main())
