"""Paper Figure 4: average runtime of 500 queries per triple pattern on the
geo-coordinates-en stand-in, per engine (ITR vs k²-triples vs HDT-BT).

The paper's claim under test: ITR answers every pattern except ?P? faster
than (or comparable to) the baselines, in milliseconds.

Beyond the paper, `BENCH_query_latency.json` tracks the serving-perf
trajectory from PR 1 onward:

* per-pattern µs for the batched engine (`query_batch_arrays`) vs the seed
  per-query worklist (`query_scalar`), plus `batch_throughput_qps`;
* a `warm_cache` section — cold (cache-miss + insert) vs warm (all-hit)
  batch runs against the uncached baseline, exercising the cross-request
  result cache incl. its ?P? segment;
* a `crossover_dispatch` section — single-query latency of the dispatched
  `engine.query` vs the scalar worklist vs a forced frontier-of-one, per
  selective pattern, at the engine's calibrated crossover width;
* a `sharded` section (PR 3) — per-shard-count mixed-workload throughput
  for both partition strategies, scatter-gather latency vs the single
  engine on the unselective patterns, and the warm repeated-``?P?``
  micro-batch workload through the view path (`query_batch_view`): shared
  entries instead of per-duplicate replication, which is the PR 2
  `warm_cache` cost floor the view is built to beat;
* a `mutation` section (PR 4) — overlay query overhead vs delta size
  (the same mixed workload on one engine at increasing insert+tombstone
  counts, relative to the clean engine) and incremental per-shard
  rebuild vs a full recompress of the mutated triple set (the
  amortization the delta budget buys);
* a `rebalance` section (PR 5) — a skewed mutation burst concentrates
  rows on one `node_range` shard, then `rebalance()` re-cuts the
  boundaries online: mixed-workload latency before/after, live skew
  before/after, and the cost of the incremental tombstone/insert
  migration vs a full re-partition (fresh `ShardedTripleService.build`)
  of the same logical triples;
* a `recovery` section (PR 6) — durable-tier cold start: reopening the
  service from its mmap-able snapshot (`DurableShardedService.open`) vs
  recompressing the same triples through RePair from scratch, gated as
  ``cold_start_speedup``; plus the WAL replay rate (records/s through
  recovery) and the first-query-after-restore latency (the page-fault
  cost mmap defers out of the open path).
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

from benchmarks.common import (
    BATCH_QUERIES_PER_PATTERN,
    PATTERNS,
    QUERIES_PER_PATTERN,
    bind_pattern,
    build_all,
    engine_cache_disabled,
    sample_rows,
    time_queries,
    time_query_batch,
)
from repro.data.synthetic import PAPER_DATASETS

# selective patterns: S or O bound — the ones eligible for scalar dispatch
DISPATCH_PATTERNS = ["s??", "sp?", "s?o", "??o", "spo"]
WARM_CACHE_PATTERNS = ["s??", "?p?", "sp?", "??o"]
# sharded-tier sweep: shard counts per strategy + the mixed routing workload
SHARD_COUNTS = (1, 2, 4)
SHARDED_MIXED_CYCLE = ["s??", "sp?", "?p?", "??o"]


def run(dataset="geo-coordinates-en", n_queries=500, quiet=False,
        json_path="BENCH_query_latency.json", scale=None):
    ds = PAPER_DATASETS[dataset]() if scale is None else PAPER_DATASETS[dataset](scale=scale)
    built = build_all(ds)
    built.pop("raw_bytes")
    itr = built["ITR"]["engine"]
    rows = []
    bench = {"dataset": dataset, "n_queries": n_queries, "patterns": {}}
    for pattern in PATTERNS:
        row = {"pattern": pattern}
        checks = {}
        for method, b in built.items():
            us, n_res = time_queries(b["engine"], ds, pattern, n_queries)
            row[method] = us
            checks[method] = n_res
        # seed per-query reference path (pre-batching worklist)
        scalar_us, scalar_n = time_queries(
            itr, ds, pattern, n_queries, query_fn=itr.query_scalar)
        checks["ITR-scalar"] = scalar_n
        # batched throughput on the full workload
        bat_us, bat_n, qps = time_query_batch(itr, ds, pattern, n_queries)
        # batched parity on the same capped sample as the per-query engines
        # (the timing run above already IS that sample unless caps differ)
        n_par = min(n_queries, QUERIES_PER_PATTERN.get(pattern, n_queries))
        n_bat = min(n_queries, BATCH_QUERIES_PER_PATTERN.get(pattern, n_queries))
        if n_par == n_bat:
            checks["ITR-batched"] = bat_n
        else:
            _, par_n, _ = time_query_batch(itr, ds, pattern, n_par)
            checks["ITR-batched"] = par_n
        # engines must agree on result counts (correctness guard)
        assert len(set(checks.values())) == 1, f"{pattern}: result mismatch {checks}"
        row["ITR-batched"] = bat_us
        speedup = scalar_us / bat_us if bat_us > 0 else float("inf")
        bench["patterns"][pattern] = {
            "scalar_us": scalar_us,
            "batched_us": bat_us,
            "speedup_vs_scalar": speedup,
            "batch_qps": qps,
            "n_results_batched": bat_n,
            "baseline_us": {m: row[m] for m in built},
        }
        rows.append(row)
        if not quiet:
            times = " ".join(f"{m}={row[m]:9.1f}us" for m in built)
            print(f"fig4 {pattern} {times} batched={bat_us:9.1f}us "
                  f"({speedup:5.1f}x vs scalar)  (n={checks['ITR']})")
    _bench_warm_cache(itr, ds, bench, n_queries, quiet)
    _bench_crossover(itr, ds, bench, n_queries, quiet)
    _bench_sharded(itr, ds, bench, n_queries, quiet)
    _bench_mutation(itr, ds, bench, n_queries, quiet)
    _bench_rebalance(itr, ds, bench, n_queries, quiet)
    _bench_bgp(itr, ds, bench, n_queries, quiet)
    _bench_recovery(ds, bench, quiet)
    _bench_ingestion(ds, bench, quiet)
    _finalize_throughput(bench, n_queries)
    if json_path:
        try:  # a full rewrite must not erase the committed CI gate baseline
            prior = json.loads(Path(json_path).read_text())
            if "smoke_baseline" in prior:
                bench["smoke_baseline"] = prior["smoke_baseline"]
        except (OSError, ValueError):
            pass
        Path(json_path).write_text(json.dumps(bench, indent=2))
    if not quiet:
        print(f"batch_throughput_qps={bench['batch_throughput_qps']:.0f}"
              + (f" -> {json_path}" if json_path else " (not written)"))
    return rows


def _bench_warm_cache(itr, ds, bench: dict, n_queries: int, quiet: bool) -> None:
    """Streaming repeated-pattern serving: a hot set of patterns queried in
    micro-batches. In-batch dedup collapses repeats *within* one flush; only
    the cross-request cache collapses them *across* flushes — so the
    uncached baseline re-executes every micro-batch's unique patterns while
    the warm pass answers them all from the LRU. The acceptance bar is warm
    throughput >= 5x the uncached batch path on this workload.
    """
    if itr.cache is None:
        return
    hot, micro = 32, 32
    n_flushes = max(2, min(16, n_queries // micro))
    rng = np.random.default_rng(1)
    out = {}
    for pattern in WARM_CACHE_PATTERNS:
        pool = np.unique(sample_rows(ds, 4 * hot), axis=0)[:hot]
        batches = []
        for _ in range(n_flushes):
            picks = pool[rng.integers(0, len(pool), micro)]
            batches.append(bind_pattern(pattern, picks))
        total_q = n_flushes * micro

        def run_workload():
            t0 = time.perf_counter()
            for s_arr, p_arr, o_arr in batches:
                itr.query_batch_arrays(s_arr, p_arr, o_arr)
            return (time.perf_counter() - t0) / total_q * 1e6

        # min over reps: the CI gate compares warm/uncached ratios, and a
        # load spike hitting one side of a single-shot measurement skews
        # the ratio by several x (same rationale as the dispatch section)
        with engine_cache_disabled(itr):
            uncached_us = min(run_workload() for _ in range(2))
        itr.cache.clear()
        cold_us = run_workload()  # first flush misses, later flushes hit
        warm_us = min(run_workload() for _ in range(2))  # all-hit steady state
        out[pattern] = {
            "uncached_us": uncached_us,
            "cold_us": cold_us,
            "warm_us": warm_us,
            "warm_speedup_vs_uncached": uncached_us / warm_us if warm_us > 0 else float("inf"),
            "warm_qps": 1e6 / warm_us if warm_us > 0 else float("inf"),
        }
        if not quiet:
            print(f"cache {pattern} uncached={uncached_us:9.1f}us cold={cold_us:9.1f}us "
                  f"warm={warm_us:9.1f}us ({out[pattern]['warm_speedup_vs_uncached']:5.1f}x"
                  f" vs uncached batch)")
    # single-query point lookups: the purest repeated-pattern serving case
    s0, p0, o0 = (int(v) for v in sample_rows(ds, 1)[0])
    reps = 50
    with engine_cache_disabled(itr):
        t0 = time.perf_counter()
        for _ in range(reps):
            itr.query(s0, None, None)
        point_uncached_us = (time.perf_counter() - t0) / reps * 1e6
    itr.cache.clear()
    itr.query(s0, None, None)  # populate
    t0 = time.perf_counter()
    for _ in range(reps):
        itr.query(s0, None, None)
    point_warm_us = (time.perf_counter() - t0) / reps * 1e6
    agg_uncached = sum(p["uncached_us"] for p in out.values())
    agg_warm = sum(p["warm_us"] for p in out.values())
    st = itr.cache.stats
    bench["warm_cache"] = {
        "hot_patterns": hot,
        "micro_batch": micro,
        "n_flushes": n_flushes,
        "patterns": out,
        "aggregate_warm_speedup_vs_uncached":
            agg_uncached / agg_warm if agg_warm > 0 else float("inf"),
        "point_lookup": {
            "uncached_us": point_uncached_us,
            "warm_us": point_warm_us,
            "warm_speedup": point_uncached_us / point_warm_us if point_warm_us > 0 else float("inf"),
        },
        "cache_stats": {"hits": st.hits, "misses": st.misses,
                        "evictions": st.evictions, "inserts": st.inserts,
                        "predicate_hits": st.predicate_hits,
                        "hit_rate": st.hit_rate},
    }
    if not quiet:
        print(f"cache point-lookup uncached={point_uncached_us:9.1f}us "
              f"warm={point_warm_us:9.1f}us "
              f"({bench['warm_cache']['point_lookup']['warm_speedup']:5.1f}x)")


def _bench_crossover(itr, ds, bench: dict, n_queries: int, quiet: bool) -> None:
    """Single-query latency per selective pattern: the dispatched engine
    entry (`query`) — timed on the real serving path, cache attached and
    cold (unique patterns, so every call is a miss + insert) — must be no
    worse than the seed scalar worklist; the forced frontier-of-one
    documents the gap the dispatch closes."""

    def _cold_dispatched_us(pattern: str, nq: int) -> float:
        if itr.cache is None:  # cache-less engine: query() IS the worklist
            return time_queries(itr, ds, pattern, nq)[0]
        rows = np.unique(sample_rows(ds, 2 * nq), axis=0)[:nq]  # no repeats:
        itr.cache.clear()                                       # all misses
        t0 = time.perf_counter()
        for s, p, o in rows:
            itr.query(int(s) if pattern[0] == "s" else None,
                      int(p) if pattern[1] == "p" else None,
                      int(o) if pattern[2] == "o" else None)
        return (time.perf_counter() - t0) / len(rows) * 1e6

    out = {}
    for pattern in DISPATCH_PATTERNS:
        nq = min(n_queries, QUERIES_PER_PATTERN.get(pattern, n_queries), 100)
        # min over reps: single-run wall timings jitter more than the
        # dispatch overhead being measured
        dispatched_us = min(_cold_dispatched_us(pattern, nq) for _ in range(2))
        scalar_us = min(time_queries(itr, ds, pattern, nq,
                                     query_fn=itr.query_scalar)[0] for _ in range(2))
        crossover = itr.crossover
        itr.crossover = 0  # force the frontier path (time_queries detaches the cache)
        try:
            frontier_us, _ = time_queries(itr, ds, pattern, nq)
        finally:
            itr.crossover = crossover
        out[pattern] = {
            "dispatched_us": dispatched_us,
            "scalar_us": scalar_us,
            "frontier_single_us": frontier_us,
            "dispatched_vs_scalar": dispatched_us / scalar_us if scalar_us > 0 else float("inf"),
        }
        if not quiet:
            print(f"dispatch {pattern} dispatched={dispatched_us:9.1f}us "
                  f"scalar={scalar_us:9.1f}us frontier1={frontier_us:9.1f}us")
    bench["crossover_dispatch"] = {"crossover_width": itr.crossover, "patterns": out}


def _bench_sharded(itr, ds, bench: dict, n_queries: int, quiet: bool) -> None:
    """Sharded serving tier: partitioned engines + scatter-gather router +
    shared cache, plus the view-based warm path.

    Three measurements land in ``bench["sharded"]``:

    * per-shard-count cold/warm throughput of a mixed selective/unselective
      workload through `ShardedTripleService`, both partition strategies;
    * scatter-gather overhead: the unselective patterns on a 4-shard
      service (caches detached) vs the single engine's uncached batch;
    * the warm repeated-``?P?`` micro-batch workload through
      `query_batch_view` vs the materializing `query_batch_arrays` — the
      view must beat the PR 2 `warm_cache` warm number because it skips
      the per-duplicate replication entirely.
    """
    from repro.serve.sharded import ShardedTripleService

    section: dict = {"shard_counts": list(SHARD_COUNTS), "strategies": {}}

    # mixed workload: rows bound through a rotating pattern cycle
    nq = min(n_queries, 200)
    rows = sample_rows(ds, nq, seed=3)
    mixed = [bind_pattern(SHARDED_MIXED_CYCLE[i % len(SHARDED_MIXED_CYCLE)],
                          rows[i:i + 1]) for i in range(nq)]
    mixed = [(s[0], p[0], o[0]) for s, p, o in mixed]

    def run_mixed(svc) -> float:
        t0 = time.perf_counter()
        svc.query_many(mixed)
        return (time.perf_counter() - t0) / nq * 1e6

    widest: dict = {}  # strategy -> max-shard-count service, reused below
    for strategy in ("predicate_hash", "node_range"):
        per = {}
        for n_shards in SHARD_COUNTS:
            svc = ShardedTripleService.build(
                ds.triples, ds.n_nodes, ds.n_preds,
                n_shards=n_shards, strategy=strategy)
            cold_us = run_mixed(svc)   # cache misses + inserts
            st = svc.stats
            routing = (st.owned, st.scattered, st.shard_batches)
            warm_us = run_mixed(svc)   # shared-tier hits
            per[str(n_shards)] = {
                "cold_us_per_query": cold_us,
                "warm_us_per_query": warm_us,
                "warm_qps": 1e6 / warm_us if warm_us > 0 else float("inf"),
                # routing counts from the cold pass only (one workload's worth)
                "owned_unique": routing[0],
                "scattered_unique": routing[1],
                "shard_batches": routing[2],
                "shard_edges": svc.shard_sizes(),
            }
            if n_shards == max(SHARD_COUNTS):
                widest[strategy] = svc
            if not quiet:
                print(f"sharded {strategy} P={n_shards} cold={cold_us:9.1f}us "
                      f"warm={warm_us:9.1f}us owned={routing[0]} "
                      f"scattered={routing[1]}")
        section["strategies"][strategy] = per

    # scatter-gather vs single engine, caches detached on both sides.
    # Each pattern runs on a strategy where it genuinely scatters: ?P? is
    # OWNED under predicate_hash (that axis exists to own it), so its
    # scatter cost shows only under node_range; ??O scatters under both.
    sg = {}
    for pattern, strategy in (("?p?", "node_range"), ("??o", "predicate_hash")):
        svc = widest[strategy]
        nqp = min(n_queries, QUERIES_PER_PATTERN.get(pattern, n_queries))
        # min over reps on both sides: these ratios feed the CI gate
        single_us = min(time_query_batch(itr, ds, pattern, nqp)[0]
                        for _ in range(2))
        s_arr, p_arr, o_arr = bind_pattern(pattern, sample_rows(ds, nqp, seed=0))
        # detach engine caches AND the shared tier (merged-entry namespace)
        # so every rep measures the execution fan-out, not a cache hit
        caches = [e.cache for e in svc.engines]
        svc_cache, svc.cache = svc.cache, None
        for e in svc.engines:
            e.cache = None
        try:
            def run_scatter() -> float:
                t0 = time.perf_counter()
                for s, p, o in zip(s_arr, p_arr, o_arr):
                    svc.submit(s, p, o)
                svc.flush_view()
                return (time.perf_counter() - t0) / nqp * 1e6

            sharded_us = min(run_scatter() for _ in range(2))
        finally:
            svc.cache = svc_cache
            for e, c in zip(svc.engines, caches):
                e.cache = c
        sg[pattern] = {
            "strategy": strategy,
            "single_engine_us": single_us,
            "sharded_us": sharded_us,
            "sharded_vs_single": sharded_us / single_us if single_us > 0 else float("inf"),
        }
        if not quiet:
            print(f"sharded scatter {pattern} [{strategy}] single={single_us:9.1f}us "
                  f"sharded(P={max(SHARD_COUNTS)})={sharded_us:9.1f}us")
    section["scatter_gather"] = sg

    # warm ?P? through the view path: the PR 2 warm_cache workload shape
    # (hot pattern pool, micro-batches), materialized vs view-based
    if itr.cache is not None:
        hot, micro = 32, 32
        n_flushes = max(2, min(16, n_queries // micro))
        rng = np.random.default_rng(1)
        pool = np.unique(sample_rows(ds, 4 * hot), axis=0)[:hot]
        batches = []
        for _ in range(n_flushes):
            picks = pool[rng.integers(0, len(pool), micro)]
            batches.append(bind_pattern("?p?", picks))
        total_q = n_flushes * micro

        def run_flushes(fn) -> float:
            t0 = time.perf_counter()
            for s_arr, p_arr, o_arr in batches:
                fn(s_arr, p_arr, o_arr)
            return (time.perf_counter() - t0) / total_q * 1e6

        itr.cache.clear()
        run_flushes(itr.query_batch_arrays)            # populate
        # min over reps: speedup_vs_materialized feeds the CI gate
        warm_mat_us = min(run_flushes(itr.query_batch_arrays) for _ in range(2))
        view_warm_us = min(run_flushes(itr.query_batch_view) for _ in range(2))

        # the same workload through the warm scatter-gather tier, on the
        # strategy where ?P? actually fans out (node_range)
        svc_nr = widest["node_range"]

        def sharded_flush(s_arr, p_arr, o_arr):
            for s, p, o in zip(s_arr, p_arr, o_arr):
                svc_nr.submit(s, p, o)
            svc_nr.flush_view()

        run_flushes(sharded_flush)                     # populate shared tier
        sharded_view_warm_us = min(run_flushes(sharded_flush) for _ in range(2))
        section["warm_view"] = {
            "materialized_warm_us": warm_mat_us,
            "view_warm_us": view_warm_us,
            "speedup_vs_materialized":
                warm_mat_us / view_warm_us if view_warm_us > 0 else float("inf"),
            "sharded_view_warm_us": sharded_view_warm_us,
            "view_warm_qps": 1e6 / view_warm_us if view_warm_us > 0 else float("inf"),
        }
        if not quiet:
            print(f"sharded warm-view ?p? materialized={warm_mat_us:9.1f}us "
                  f"view={view_warm_us:9.1f}us "
                  f"({section['warm_view']['speedup_vs_materialized']:5.1f}x) "
                  f"sharded-view={sharded_view_warm_us:9.1f}us")
    bench["sharded"] = section


def _bench_mutation(itr, ds, bench: dict, n_queries: int, quiet: bool) -> None:
    """Mutation subsystem: what writes cost the read path, and what the
    delta budget buys at rebuild time.

    * *Overlay overhead*: a cache-less engine runs the mixed batch
      workload after its delta overlay is grown to a small and a large
      tier (half inserts, half tombstones — both merge steps exercised),
      timed against a from-scratch engine compressed from the SAME
      logical triple set. Same logical set -> same result volume, so the
      gated ratio ``us(overlay) / us(recompressed)`` isolates pure
      overlay cost instead of confounding it with tombstones shrinking
      (or inserts growing) the results being materialized.
    * *Incremental rebuild*: mutations targeting ONE predicate land on
      one shard of a 4-shard predicate-hash service; `rebuild(force=True)`
      recompresses just that shard, timed against a from-scratch
      `ShardedTripleService.build` on the mutated triple set. The gated
      ratio is ``full_s / incremental_s`` (the amortization factor).
    """
    from repro.core import (
        Hypergraph,
        LabelTable,
        TripleQueryEngine,
        compress,
    )
    from repro.serve.sharded import ShardedTripleService

    rng = np.random.default_rng(7)
    nq = min(n_queries, 100)
    rows = sample_rows(ds, nq, seed=5)
    batches = [bind_pattern(pat, rows) for pat in SHARDED_MIXED_CYCLE]

    engine = TripleQueryEngine(itr.grammar, itr.encoded, cache=None,
                               crossover=0, delta_budget=None)

    def run_workload(e) -> float:
        t0 = time.perf_counter()
        for s_arr, p_arr, o_arr in batches:
            e.query_batch_arrays(s_arr, p_arr, o_arr)
        return (time.perf_counter() - t0) / (nq * len(batches)) * 1e6

    def recompressed() -> TripleQueryEngine:
        """From-scratch engine on the overlay engine's logical triples —
        the tier's fair baseline (identical results, no overlay)."""
        logical = engine.current_triples()
        n_nodes = ds.n_nodes
        if len(logical):
            n_nodes = max(n_nodes, int(logical[:, [0, 2]].max()) + 1)
        grammar, _ = compress(
            Hypergraph.from_triples(logical, n_nodes),
            LabelTable.terminals([2] * ds.n_preds))
        return TripleQueryEngine(grammar, cache=None, crossover=0,
                                 delta_budget=None)

    del_pool = np.unique(np.asarray(ds.triples, dtype=np.int64), axis=0)
    rng.shuffle(del_pool)
    del_cursor = [0]

    def grow_delta(target: int) -> None:
        """Half inserts / half tombstones, re-drawing until the overlay
        reaches `target` (random inserts colliding with base rows are
        filtered out by set semantics, so one draw may fall short)."""
        for _ in range(8):
            need = target - engine.delta.size
            if need <= 0:
                return
            n_ins = (need + 1) // 2
            fresh = np.stack([rng.integers(0, ds.n_nodes, n_ins),
                              rng.integers(0, ds.n_preds, n_ins),
                              rng.integers(0, ds.n_nodes, n_ins)], axis=1)
            engine.insert_triples(fresh)
            n_del = min(target - engine.delta.size,
                        len(del_pool) - del_cursor[0])
            if n_del > 0:
                engine.delete_triples(
                    del_pool[del_cursor[0]:del_cursor[0] + n_del])
                del_cursor[0] += n_del

    # min over reps: overhead_vs_clean feeds the CI gate
    pristine_us = min(run_workload(engine) for _ in range(2))
    tiers = {}
    for tier, target in (("small", 64), ("large", 512)):
        grow_delta(target)
        tier_us = min(run_workload(engine) for _ in range(2))
        clean_us = min(run_workload(recompressed()) for _ in range(2))
        tiers[tier] = {
            "delta_rows": engine.delta.size,
            "us_per_query": tier_us,
            "recompressed_us_per_query": clean_us,
            "overhead_vs_clean": tier_us / clean_us if clean_us > 0 else float("inf"),
        }
        if not quiet:
            print(f"mutation overlay {tier} delta={engine.delta.size} "
                  f"recompressed={clean_us:9.1f}us overlaid={tier_us:9.1f}us "
                  f"({tiers[tier]['overhead_vs_clean']:5.2f}x)")

    # incremental per-shard rebuild vs full recompress of the mutated set
    n_shards = 4
    svc = ShardedTripleService.build(ds.triples, ds.n_nodes, ds.n_preds,
                                     n_shards=n_shards, cache=None,
                                     strategy="predicate_hash", crossover=0,
                                     delta_budget=None, rebalance_skew=None)
    p0 = int(ds.triples[0, 1])  # one predicate -> one owning shard
    n_mut = max(16, len(ds.triples) // 50)
    fresh = np.stack([rng.integers(0, ds.n_nodes, n_mut),
                      np.full(n_mut, p0, dtype=np.int64),
                      rng.integers(0, ds.n_nodes, n_mut)], axis=1)
    svc.insert_triples(fresh)
    dirty = [k for k, d in enumerate(svc.delta_sizes()) if d]
    delta_rows = int(sum(svc.delta_sizes()))
    mutated = np.concatenate([t.current_triples() for t in svc.engines])
    t0 = time.perf_counter()
    rebuilt = svc.rebuild(force=True)
    incr_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ShardedTripleService.build(mutated, ds.n_nodes, ds.n_preds,
                               n_shards=n_shards, cache=None,
                               strategy="predicate_hash", crossover=0,
                               delta_budget=None)
    full_s = time.perf_counter() - t0
    bench["mutation"] = {
        "overlay": {"pristine_us_per_query": pristine_us, "tiers": tiers},
        "rebuild": {
            "n_shards": n_shards,
            "dirty_shards": len(dirty),
            "rebuilt_shards": rebuilt,
            "delta_rows": delta_rows,
            "incremental_s": incr_s,
            "full_s": full_s,
            "full_vs_incremental": full_s / incr_s if incr_s > 0 else float("inf"),
        },
    }
    if not quiet:
        print(f"mutation rebuild dirty={dirty} incremental={incr_s * 1e3:9.1f}ms "
              f"full={full_s * 1e3:9.1f}ms "
              f"({bench['mutation']['rebuild']['full_vs_incremental']:5.1f}x)")


def _bench_rebalance(itr, ds, bench: dict, n_queries: int, quiet: bool) -> None:
    """Online rebalancing under a skewed write burst.

    A 4-shard `node_range` tier takes a burst of inserts whose subjects
    all fall inside shard 0's range — the hot-shard shape mutation
    produces in practice — then `rebalance(force=True)` re-quantiles the
    boundaries and migrates the diff. Recorded (caches detached so shard
    balance is the only variable):

    * mixed-workload latency on the skewed tier, right after the
      migration (moved rows still in destination overlays), and at
      steady state once the dirty shards rebuild;
    * live `max/mean` skew before/after (deterministic, gated);
    * migration cost (plan + tombstone/insert moves) vs a full
      re-partition (`ShardedTripleService.build` on the same logical
      triples) — the amortization online re-cutting buys, gated as
      ``full_vs_migration``.
    """
    from repro.serve.sharded import ShardedTripleService

    n_shards = 4
    svc = ShardedTripleService.build(ds.triples, ds.n_nodes, ds.n_preds,
                                     n_shards=n_shards, cache=None,
                                     strategy="node_range", crossover=0,
                                     delta_budget=None, rebalance_skew=None)
    # hot burst: subjects packed into shard 0's range, distinct enough
    # that a quantile re-cut CAN split them across shards
    rng = np.random.default_rng(11)
    lo = int(svc.plan.boundaries[0])
    hi = max(int(svc.plan.boundaries[1]), lo + 1)
    n_burst = max(64, len(ds.triples) // 4)
    burst = np.stack([rng.integers(lo, hi, n_burst),
                      rng.integers(0, ds.n_preds, n_burst),
                      rng.integers(0, ds.n_nodes, n_burst)], axis=1)
    inserted = svc.insert_triples(burst)
    skew_before = svc.skew()

    nq = min(n_queries, 100)
    rows = sample_rows(ds, nq, seed=9)
    hot = burst[rng.integers(0, len(burst), nq)]
    rows[::2] = hot[::2]  # half the probes target the hot range
    mixed = [bind_pattern(SHARDED_MIXED_CYCLE[i % len(SHARDED_MIXED_CYCLE)],
                          rows[i:i + 1]) for i in range(nq)]
    mixed = [(s[0], p[0], o[0]) for s, p, o in mixed]

    def run_mixed() -> float:
        t0 = time.perf_counter()
        svc.query_many(mixed)
        return (time.perf_counter() - t0) / nq * 1e6

    before_us = min(run_mixed() for _ in range(2))
    logical = np.concatenate([e.current_triples() for e in svc.engines])

    t0 = time.perf_counter()
    res = svc.rebalance(force=True)
    migration_s = time.perf_counter() - t0
    skew_after = svc.skew()
    after_us = min(run_mixed() for _ in range(2))
    # steady state: fold the migration overlays into fresh grammars
    svc.rebuild(force=True)
    after_rebuild_us = min(run_mixed() for _ in range(2))

    n_nodes = max(ds.n_nodes, int(logical[:, [0, 2]].max()) + 1) \
        if len(logical) else ds.n_nodes
    t0 = time.perf_counter()
    ShardedTripleService.build(logical, n_nodes, ds.n_preds,
                               n_shards=n_shards, cache=None,
                               strategy="node_range", crossover=0,
                               delta_budget=None, rebalance_skew=None)
    full_s = time.perf_counter() - t0

    bench["rebalance"] = {
        "n_shards": n_shards,
        "burst_rows": int(inserted),
        "migrated_rows": svc.stats.migrated_rows,
        "skew_before": skew_before,
        "skew_after": skew_after,
        "skew_after_vs_before": skew_after / skew_before
        if skew_before > 0 else float("inf"),
        "mixed_before_us": before_us,
        "mixed_after_us": after_us,
        "mixed_after_rebuild_us": after_rebuild_us,
        "migration_s": migration_s,
        "full_repartition_s": full_s,
        "full_vs_migration": full_s / migration_s
        if migration_s > 0 else float("inf"),
    }
    if not quiet:
        print(f"rebalance skew {skew_before:5.2f}->{skew_after:5.2f} "
              f"moved={svc.stats.migrated_rows} "
              f"mixed {before_us:9.1f}us->{after_us:9.1f}us"
              f"->{after_rebuild_us:9.1f}us(rebuilt) "
              f"migration={migration_s * 1e3:9.1f}ms "
              f"full={full_s * 1e3:9.1f}ms "
              f"({bench['rebalance']['full_vs_migration']:5.1f}x), "
              f"pending={res['pending']}")


def _naive_bgp_join(query_fn, patterns) -> list[tuple]:
    """The baseline `query_bgp` must beat: fetch each pattern's full
    result through the ordinary per-pattern query surface, then join the
    Python way — a dict index on the shared variables, patterns in the
    order given (no planning, no id-array joins). Returns sorted binding
    tuples, the `BGPResult.tuples()` comparison shape."""
    from repro.core.bgp import bgp_variables, parse_bgp

    patterns = parse_bgp(patterns)
    out_vars = bgp_variables(patterns)
    bindings: list[dict] = [{}]
    for pat in patterns:
        terms = pat.terms
        res = query_fn(*(None if isinstance(t, str) else t for t in terms))
        solved = set(bindings[0]) if bindings else set()
        shared = [v for v in pat.variables() if v in solved]
        index: dict = {}
        for label, (s, o) in res:
            vals: dict = {}
            ok = True
            for slot, val in enumerate((s, label, o)):
                term = terms[slot]
                if isinstance(term, str):
                    if term in vals and vals[term] != val:
                        ok = False
                        break
                    vals[term] = val
            if ok:
                index.setdefault(
                    tuple(vals[v] for v in shared), []).append(vals)
        nxt = []
        for b in bindings:
            for vals in index.get(tuple(b[v] for v in shared), []):
                nb = dict(b)
                nb.update(vals)
                nxt.append(nb)
        bindings = nxt
        if not bindings:
            break
    return sorted(tuple(b[v] for v in out_vars) for b in bindings)


def _chain_predicates(triples, k: int, n_preds: int) -> list[int]:
    """Predicates (p1, .., pk) such that `?a p1 ?b . ?b p2 ?c ...` is
    satisfiable, found by walking actual rows subject-to-object; falls
    back to the most frequent predicates when no k-hop walk exists (a
    0-binding chain still measures the join machinery, just less of it)."""
    by_subj: dict = {}
    for s, p, o in triples.tolist():
        by_subj.setdefault(s, []).append((p, o))
    for s, p, o in triples.tolist():
        chain, node = [p], o
        while len(chain) < k and by_subj.get(node):
            p2, node = by_subj[node][0]
            chain.append(p2)
        if len(chain) == k:
            return chain
    freq = np.argsort(-np.bincount(triples[:, 1], minlength=n_preds))
    return [int(freq[i % len(freq)]) for i in range(k)]


def _bench_bgp(itr, ds, bench: dict, n_queries: int, quiet: bool) -> None:
    """BGP joins over the sharded tier (PR 9).

    Three shapes derived from the dataset's most frequent predicates — a
    2-pattern chain, a 3-pattern chain, and a 2-pattern star — each
    measured three ways on a 2-shard `predicate_hash` tier:

    * ``cold_us``: `query_bgp` with every cache namespace invalidated
      first (planner + bind/hash joins + sub-pattern fetches, all cold);
    * ``warm_us``: the identical BGP again — a whole-BGP hit in the
      merged cache namespace;
    * ``naive_us``: the per-pattern-then-Python-join baseline
      (`_naive_bgp_join`), also from a cold cache, same fetch surface.

    Gated: ``chain3.planned_vs_naive`` (naive/cold, higher is better) —
    the planned id-array join path must keep beating materialize-and-loop
    Python joins; ``chain3.warm_speedup`` (cold/warm) — the whole-BGP
    cache must keep short-circuiting repeat analytical queries.
    """
    from repro.serve.sharded import ShardedTripleService

    svc = ShardedTripleService.build(ds.triples, ds.n_nodes, ds.n_preds,
                                     n_shards=2, crossover=0,
                                     delta_budget=None, rebalance_skew=None)
    p1, p2, p3 = _chain_predicates(ds.triples, 3, ds.n_preds)
    shapes = {
        "chain2": f"?a {p1} ?b . ?b {p2} ?c",
        "chain3": f"?a {p1} ?b . ?b {p2} ?c . ?c {p3} ?d",
        "star2": f"?h {p1} ?a . ?h {p2} ?b",
    }
    section: dict = {"n_shards": 2, "predicates": [p1, p2, p3]}
    reps = 3
    for name, bgp in shapes.items():
        cold_s = warm_s = naive_s = float("inf")
        res = None
        for _ in range(reps):
            svc.invalidate()  # sub-pattern AND whole-BGP namespaces
            t0 = time.perf_counter()
            res = svc.query_bgp(bgp)
            cold_s = min(cold_s, time.perf_counter() - t0)
            t0 = time.perf_counter()
            svc.query_bgp(bgp)
            warm_s = min(warm_s, time.perf_counter() - t0)
        for _ in range(reps):
            svc.invalidate()  # same cold start the planned path gets
            t0 = time.perf_counter()
            naive = _naive_bgp_join(svc.query, bgp)
            naive_s = min(naive_s, time.perf_counter() - t0)
        assert naive == res.tuples(), f"bgp {name}: naive/planned mismatch"
        section[name] = {
            "bgp": bgp,
            "n_bindings": len(res),
            "cold_us": cold_s * 1e6,
            "warm_us": warm_s * 1e6,
            "naive_us": naive_s * 1e6,
            "planned_vs_naive": naive_s / cold_s if cold_s > 0 else float("inf"),
            "warm_speedup": cold_s / warm_s if warm_s > 0 else float("inf"),
        }
        if not quiet:
            r = section[name]
            print(f"bgp {name} n={len(res)} cold={r['cold_us']:9.1f}us "
                  f"warm={r['warm_us']:9.1f}us naive={r['naive_us']:9.1f}us "
                  f"({r['planned_vs_naive']:5.1f}x vs naive, "
                  f"{r['warm_speedup']:5.1f}x warm)")
    bench["bgp"] = section


def _bench_recovery(ds, bench: dict, quiet: bool) -> None:
    """Durable-tier cold start and WAL replay (PR 6).

    Three measurements land in ``bench["recovery"]``:

    * ``cold_start_speedup`` (gated): reopening the service from its
      snapshot (`DurableShardedService.open`, mmap-backed arrays, no
      RePair) vs compressing the same triples from scratch — the whole
      point of persisting engine state;
    * ``first_query_after_open_us``: the first query on the reopened
      tier, i.e. the page-fault cost mmap defers out of the open path;
    * ``wal_replay_records_per_s``: recovery throughput with a log of
      mutation records to replay over the snapshot (recorded, not gated
      — an absolute rate, machine-dependent).
    """
    import shutil
    import tempfile

    from repro.persist.service import DurableShardedService
    from repro.serve.sharded import ShardedTripleService

    n_shards = 2
    kwargs = dict(n_shards=n_shards, cache=None, crossover=0,
                  delta_budget=None, rebalance_skew=None)
    root = tempfile.mkdtemp(prefix="itr-bench-recovery-")
    try:
        svc = DurableShardedService.build(
            ds.triples, ds.n_nodes, ds.n_preds, root=root, **kwargs)
        svc.close()
        # min over reps: cold_start_speedup feeds the CI gate
        def timed_open():
            t0 = time.perf_counter()
            opened = DurableShardedService.open(
                root, cache=None, rebalance_skew=None)
            return time.perf_counter() - t0, opened

        cold_start_s, svc = timed_open()
        for _ in range(1):
            svc.close()
            again_s, svc = timed_open()
            cold_start_s = min(cold_start_s, again_s)
        s0 = int(ds.triples[0, 0])
        t0 = time.perf_counter()
        svc.query(s0, None, None)
        first_query_us = (time.perf_counter() - t0) * 1e6

        repair_s = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            ShardedTripleService.build(
                ds.triples, ds.n_nodes, ds.n_preds, **kwargs)
            repair_s = min(repair_s, time.perf_counter() - t0)

        # a log's worth of mutation records to replay over the snapshot
        rng = np.random.default_rng(13)
        n_records, per_record = 32, 8
        for _ in range(n_records):
            svc.insert_triples(np.stack(
                [rng.integers(0, ds.n_nodes, per_record),
                 rng.integers(0, ds.n_preds, per_record),
                 rng.integers(0, ds.n_nodes, per_record)], axis=1))
        svc.close()
        t0 = time.perf_counter()
        svc = DurableShardedService.open(
            root, cache=None, rebalance_skew=None)
        replay_open_s = time.perf_counter() - t0
        replayed = svc.last_recovery.replayed_records
        svc.close()

        bench["recovery"] = {
            "n_shards": n_shards,
            "cold_start_s": cold_start_s,
            "repair_rebuild_s": repair_s,
            "cold_start_speedup": repair_s / cold_start_s
            if cold_start_s > 0 else float("inf"),
            "first_query_after_open_us": first_query_us,
            "wal_records_replayed": int(replayed),
            "replay_open_s": replay_open_s,
            "wal_replay_records_per_s": replayed / replay_open_s
            if replay_open_s > 0 else float("inf"),
        }
        if not quiet:
            r = bench["recovery"]
            print(f"recovery cold-start={cold_start_s * 1e3:9.1f}ms "
                  f"repair-rebuild={repair_s * 1e3:9.1f}ms "
                  f"({r['cold_start_speedup']:5.1f}x) "
                  f"first-query={first_query_us:9.1f}us "
                  f"replay={replayed}rec/{replay_open_s * 1e3:.1f}ms "
                  f"({r['wal_replay_records_per_s']:.0f}rec/s)")
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _bench_ingestion(ds, bench: dict, quiet: bool) -> None:
    """Streaming RDF ingestion + term-dictionary footprint (PR 10).

    The dataset is serialized to N-Triples, then streamed back through
    :func:`repro.data.ingest.ingest_file` into an empty sharded tier.
    ``bench["ingestion"]`` records:

    * ``dict_vs_plain_bytes`` (gated, lower = better): the front-coded
      term dictionary's bytes vs a plain-Python forward+reverse mapping
      (raw term bytes stored twice + 8-byte id and pointer slots) —
      deterministic for a given dataset, so it gates tightly;
    * ``terms_per_s`` / ``rows_per_s``: mint and ingest throughput
      (recorded, not gated — absolute rates are machine-dependent);
    * ``dict_bytes_per_term`` vs ``hdt_model_bytes_per_term``: footprint
      against the IRI-length model the N-Triples size baseline assumes
      (:func:`repro.baselines.ntriples.ntriples_size_bytes`).
    """
    import shutil
    import tempfile

    from repro.data.ingest import ingest_file
    from repro.data.rdf import write_ntriples
    from repro.serve.sharded import ShardedTripleService

    tmp = tempfile.mkdtemp(prefix="itr-bench-ingest-")
    try:
        path = f"{tmp}/graph.nt"
        write_ntriples(path, ds.triples)
        svc = ShardedTripleService.build(
            np.zeros((0, 3), dtype=np.int64), n_nodes=1, n_preds=ds.n_preds,
            n_shards=2, cache=None, crossover=0, delta_budget=None,
            rebalance_skew=None)
        stats = ingest_file(svc, path)
        td = svc.term_dict
        n_terms = td.n_nodes + td.n_preds
        raw = sum(len(t.encode()) for t in td.nodes.terms_in_id_order()) \
            + sum(len(t.encode()) for t in td.preds.terms_in_id_order())
        plain_bytes = 2 * raw + 16 * n_terms
        dict_bytes = td.size_in_bytes()
        hdt_per_term = (24 * td.n_nodes + 28 * td.n_preds) / max(n_terms, 1)
        bench["ingestion"] = {
            "rows": stats.rows,
            "batches": stats.batches,
            "rows_per_s": stats.rows_per_s,
            "terms_minted": stats.new_nodes + stats.new_preds,
            "terms_per_s": (stats.new_nodes + stats.new_preds) / stats.seconds
            if stats.seconds > 0 else float("inf"),
            "dict_bytes": int(dict_bytes),
            "plain_dict_bytes": int(plain_bytes),
            "dict_vs_plain_bytes": dict_bytes / plain_bytes
            if plain_bytes > 0 else float("inf"),
            "dict_bytes_per_term": td.bytes_per_term(),
            "hdt_model_bytes_per_term": hdt_per_term,
        }
        if not quiet:
            b = bench["ingestion"]
            print(f"ingestion rows={b['rows']} "
                  f"({b['rows_per_s']:,.0f}rows/s, "
                  f"{b['terms_per_s']:,.0f}terms/s) "
                  f"dict={b['dict_bytes']}B vs plain={b['plain_dict_bytes']}B "
                  f"({b['dict_vs_plain_bytes']:.3f}x) "
                  f"{b['dict_bytes_per_term']:.1f}B/term "
                  f"(hdt model {b['hdt_model_bytes_per_term']:.1f}B/term)")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _finalize_throughput(bench: dict, n_queries: int) -> None:
    """Aggregate qps = total batched queries / total batched wall time."""
    total_q = 0
    total_s = 0.0
    for pat, p in bench["patterns"].items():
        nq = min(n_queries, BATCH_QUERIES_PER_PATTERN.get(pat, n_queries))
        total_q += nq
        total_s += p["batched_us"] * nq / 1e6
    bench["batch_throughput_qps"] = total_q / total_s if total_s > 0 else 0.0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
