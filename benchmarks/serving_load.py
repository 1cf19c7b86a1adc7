"""Traffic-realistic serving load harness for the sharded tier.

Where `benchmarks.query_latency` measures closed-loop latency (one request
at a time, the next issued when the previous returns), this harness drives
the :class:`~repro.serve.sharded.ShardedTripleService` the way production
traffic actually arrives — **open loop**: requests are scheduled by a
Poisson process at a fixed *offered* rate whether or not the service has
kept up, so queueing delay is part of every latency sample instead of
being silently absorbed by the generator. Three sections land in
``BENCH_serving_load.json`` (schema: ``docs/BENCHMARKS.md``):

* ``latency`` — p50/p95/p99 at a sub-saturation offered rate, measured
  from each request's *scheduled arrival* to its completion, under a
  hot/cold pattern mix (a small hot set of repeated lookups over a cold
  random tail, plus occasional unselective ``?P?`` scans) with background
  mutation traffic running the whole time;
* ``saturation`` — a sweep over increasing offered rates; the saturation
  QPS is the highest rate the service still clears (achieved ≥ 90% of
  offered);
* ``scatter_fanout`` — the same unselective scatter workload executed
  sequentially (``serve_threads=1``) and threaded (one thread per core),
  whose ``speedup`` is the dimensionless signal the CI smoke gate tracks
  (on a single-core runner it sits at ~1.0 by construction);
* ``replica_scaling`` — closed-loop read QPS over a durable tier as the
  replica-group count grows 0 -> N (``enable_replication``, see
  docs/ARCHITECTURE.md §11): owned point lookups under concurrent
  clients, where extra replica groups dilute per-engine lock contention.
  ``replica_scaling_speedup`` (QPS at max replicas over QPS at one) is
  the smoke-gated signal; like the fan-out section it sits at ~1.0 on a
  single-core runner (``cpu_count`` is recorded alongside).

Knobs (flags override env, env overrides defaults): ``ITR_LOAD_DURATION``
(seconds per measured window), ``ITR_LOAD_RATES`` (comma-separated offered
QPS sweep), ``ITR_LOAD_CLIENTS`` (worker threads draining the arrival
queue), ``ITR_LOAD_HOT`` (hot-set fraction of the mix),
``ITR_LOAD_MUTATIONS`` (background mutation ops/second), ``ITR_LOAD_SEED``.

Run ``python -m benchmarks.serving_load --smoke`` for a seconds-long pass
on a tiny graph (no tracked JSON overwritten), or without ``--smoke`` to
refresh ``BENCH_serving_load.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import queue
import threading
import time
from pathlib import Path

import numpy as np

from repro.data.synthetic import PAPER_DATASETS
from repro.serve.concurrency import resolve_serve_threads
from repro.serve.sharded import ShardedTripleService

BENCH_JSON = "BENCH_serving_load.json"

HOT_SET_SIZE = 16


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    try:
        return float(raw) if raw else default
    except ValueError:
        return default


def _env_rates(default: tuple) -> tuple:
    raw = os.environ.get("ITR_LOAD_RATES", "").strip()
    if not raw:
        return default
    try:
        rates = tuple(float(r) for r in raw.split(",") if r.strip())
        return rates or default
    except ValueError:
        return default


# ------------------------------------------------------------- workload
def _pattern_stream(triples: np.ndarray, rng, hot_frac: float, n: int) -> list:
    """The hot/cold request mix: `hot_frac` of requests re-look-up one of
    ``HOT_SET_SIZE`` hot rows (the dashboard/entity-page shape); the cold
    tail is point lookups and subject scans over random live rows, with a
    thin slice of unselective ``?P?`` scatter scans."""
    hot = triples[rng.integers(0, len(triples), HOT_SET_SIZE)]
    out = []
    for _ in range(n):
        if rng.random() < hot_frac:
            s, p, _ = hot[int(rng.integers(0, HOT_SET_SIZE))]
            out.append((int(s), int(p), None))
            continue
        s, p, o = triples[int(rng.integers(0, len(triples)))]
        r = rng.random()
        if r < 0.45:
            out.append((int(s), None, None))
        elif r < 0.85:
            out.append((int(s), int(p), int(o)))
        else:
            out.append((None, int(p), None))  # unselective: scatters
    return out


class _Mutator(threading.Thread):
    """Background write traffic: ~`rate` mutation calls/second, each
    inserting or deleting a few random rows (valid predicate ids, so the
    tier applies them for real)."""

    def __init__(self, svc, triples, n_nodes, n_preds, rate, stop, seed):
        super().__init__(name="load-mutator", daemon=True)
        self.svc, self.stop, self.rate = svc, stop, rate
        self.n_nodes, self.n_preds = n_nodes, n_preds
        self.triples = triples
        self.rng = np.random.default_rng(seed)
        self.ops = 0

    def run(self):
        while not self.stop.is_set() and self.rate > 0:
            k = int(self.rng.integers(1, 4))
            rows = np.stack([self.rng.integers(0, self.n_nodes, k),
                             self.rng.integers(0, self.n_preds, k),
                             self.rng.integers(0, self.n_nodes, k)], axis=1)
            if self.rng.integers(0, 2):
                self.svc.insert_triples(rows)
            else:
                self.svc.delete_triples(rows)
            self.ops += 1
            self.stop.wait(1.0 / self.rate)


def _open_loop(svc, requests: list, rate: float, clients: int, rng) -> dict:
    """Drive one measured window at offered `rate` QPS.

    Arrivals follow a Poisson process (exponential gaps); `clients`
    worker threads drain the arrival queue. Latency is measured from the
    request's SCHEDULED arrival, not its dequeue — when the service falls
    behind, queueing delay lands in the percentiles, which is the whole
    point of the open loop.
    """
    gaps = rng.exponential(1.0 / rate, len(requests))
    arrivals = np.cumsum(gaps)
    work: queue.Queue = queue.Queue()
    lats: list[float] = []
    lock = threading.Lock()
    t0 = time.perf_counter()

    def worker():
        while True:
            item = work.get()
            if item is None:
                return
            sched, (s, p, o) = item
            svc.query(s, p, o)
            done = time.perf_counter() - t0
            with lock:
                lats.append(done - sched)

    threads = [threading.Thread(target=worker, name=f"load-client-{i}")
               for i in range(clients)]
    for t in threads:
        t.start()
    for sched, req in zip(arrivals, requests):
        wait = sched - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        work.put((float(sched), req))
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    lat = np.asarray(lats)
    return {
        "offered_qps": float(rate),
        "achieved_qps": float(len(lat) / wall) if wall > 0 else 0.0,
        "n_requests": int(len(lat)),
        "p50_ms": float(np.percentile(lat, 50) * 1e3),
        "p95_ms": float(np.percentile(lat, 95) * 1e3),
        "p99_ms": float(np.percentile(lat, 99) * 1e3),
        "max_ms": float(lat.max() * 1e3),
    }


# -------------------------------------------------------- fan-out section
def _scatter_fanout(triples, n_nodes, n_preds, *, n_shards, reps,
                    threads: int, quiet: bool) -> dict:
    """Unselective scatter workload, sequential vs threaded fan-out.

    Cache disabled (a warm merged entry would answer without fanning out
    at all) and the same service instance re-timed under both widths, so
    the only variable is `serve_threads`.
    """
    svc = ShardedTripleService.build(
        triples, n_nodes, n_preds, n_shards=n_shards,
        strategy="predicate_hash", cache=None, rebalance_skew=None,
        serve_threads=1)
    patterns = [(None, p, None) for p in range(n_preds)] \
        + [(None, None, int(o)) for o in range(0, n_nodes, max(1, n_nodes // 8))]

    def measure() -> float:
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            svc.query_many(patterns)
            best = min(best, time.perf_counter() - t0)
        return best

    seq = measure()
    svc.set_serve_threads(threads)
    thr = measure()
    svc.close()
    out = {
        "threads": int(threads),
        "n_patterns": len(patterns),
        "sequential_s": seq,
        "threaded_s": thr,
        "speedup": seq / thr if thr > 0 else 0.0,
    }
    if not quiet:
        print(f"scatter fan-out x{threads}: sequential {seq * 1e3:.1f}ms, "
              f"threaded {thr * 1e3:.1f}ms -> {out['speedup']:.2f}x")
    return out


# -------------------------------------------------- replica scaling section
def _closed_loop_qps(svc, patterns: list, clients: int, reps: int) -> float:
    """Best-of-`reps` closed-loop QPS: `clients` threads each drain their
    slice of `patterns` flat out; QPS = total requests / wall."""
    chunks = [patterns[i::clients] for i in range(clients)]
    best = 0.0
    for _ in range(reps):
        start = threading.Barrier(clients + 1)

        def worker(chunk):
            start.wait()
            for s, p, o in chunk:
                svc.query(s, p, o)

        threads = [threading.Thread(target=worker, args=(c,)) for c in chunks]
        for t in threads:
            t.start()
        start.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if wall > 0:
            best = max(best, len(patterns) / wall)
    return best


def _replica_scaling(triples, n_nodes, n_preds, *, n_shards, clients,
                     n_queries, reps, counts, quiet: bool) -> dict:
    """Read QPS vs replica-group count over one durable tier.

    Cache disabled (a warm entry would answer without touching any
    engine) and the workload is subject-bound ``sp?`` lookups — each
    flush routes to exactly one shard's engine, so with replicas off the
    clients contend on the primary's per-engine locks and each added
    group dilutes that contention. The tier is quiesced (lag 0, no
    mutations), isolating dispatch width as the only variable.
    """
    import tempfile

    from repro.persist.service import DurableShardedService

    rng = np.random.default_rng(7)
    rows = triples[rng.integers(0, len(triples), n_queries)]
    patterns = [(int(s), int(p), None) for s, p, _ in rows]
    qps: list[dict] = []
    with tempfile.TemporaryDirectory() as root:
        svc = DurableShardedService.build(
            triples, n_nodes, n_preds, root=os.path.join(root, "tier"),
            n_shards=n_shards, strategy="node_range", cache=None,
            rebalance_skew=None, serve_threads=1, fsync=False, replicas=0)
        try:
            for n in counts:
                svc.enable_replication(n)
                measured = _closed_loop_qps(svc, patterns, clients, reps)
                qps.append({"replicas": int(n), "qps": measured})
                if not quiet:
                    print(f"replica scaling x{n}: {measured:.0f} qps "
                          f"({clients} clients)")
        finally:
            svc.close()
    by_count = {w["replicas"]: w["qps"] for w in qps}
    base = by_count.get(1) or by_count[min(by_count)]
    top = by_count[max(by_count)]
    return {
        "cpu_count": os.cpu_count(),
        "clients": int(clients),
        "n_queries": len(patterns),
        "counts": [w["replicas"] for w in qps],
        "read_qps": [w["qps"] for w in qps],
        "speedup": top / base if base > 0 else 0.0,
    }


# ----------------------------------------------------------------- driver
def run(dataset: str = "geo-coordinates-en", *, scale=None,
        duration: float | None = None, rates: tuple | None = None,
        clients: int | None = None, hot_frac: float | None = None,
        mutation_rate: float | None = None, seed: int | None = None,
        n_shards: int = 4, fanout_reps: int = 3,
        replica_counts: tuple = (0, 1, 2, 4), replica_queries: int = 1500,
        quiet: bool = False, json_path: str | None = BENCH_JSON) -> dict:
    """Run the load harness; returns (and optionally writes) the bench dict.

    Defaults resolve through the ``ITR_LOAD_*`` environment; pass
    ``json_path=None`` to skip writing (the smoke path — the caller merges
    the dict into the smoke artifact instead).
    """
    duration = _env_float("ITR_LOAD_DURATION", 2.0) \
        if duration is None else float(duration)
    rates = _env_rates((100.0, 200.0, 400.0)) if rates is None else rates
    clients = int(_env_float("ITR_LOAD_CLIENTS", 4)) \
        if clients is None else int(clients)
    hot_frac = _env_float("ITR_LOAD_HOT", 0.5) \
        if hot_frac is None else float(hot_frac)
    mutation_rate = _env_float("ITR_LOAD_MUTATIONS", 50.0) \
        if mutation_rate is None else float(mutation_rate)
    seed = int(_env_float("ITR_LOAD_SEED", 0)) if seed is None else int(seed)

    ds = PAPER_DATASETS[dataset]() if scale is None \
        else PAPER_DATASETS[dataset](scale=scale)
    rng = np.random.default_rng(seed)
    svc = ShardedTripleService.build(
        ds.triples, ds.n_nodes, ds.n_preds, n_shards=n_shards,
        strategy="predicate_hash", rebalance_skew=None)
    bench: dict = {
        "dataset": dataset,
        "duration_s": duration,
        "clients": clients,
        "hot_fraction": hot_frac,
        "mutation_rate": mutation_rate,
        "n_shards": n_shards,
        "serve_threads": svc.serve_threads,
    }

    stop = threading.Event()
    mutator = _Mutator(svc, ds.triples, ds.n_nodes, ds.n_preds,
                       mutation_rate, stop, seed + 1)
    mutator.start()
    try:
        # saturation sweep: short open-loop windows at rising offered rates
        sweep = []
        for rate in rates:
            reqs = _pattern_stream(ds.triples, rng, hot_frac,
                                   max(1, int(rate * duration)))
            sweep.append(_open_loop(svc, reqs, rate, clients, rng))
            if not quiet:
                w = sweep[-1]
                print(f"offered {rate:.0f} qps: achieved "
                      f"{w['achieved_qps']:.0f} qps, p50 {w['p50_ms']:.2f}ms "
                      f"p95 {w['p95_ms']:.2f}ms p99 {w['p99_ms']:.2f}ms")
        cleared = [w for w in sweep
                   if w["achieved_qps"] >= 0.9 * w["offered_qps"]]
        bench["saturation"] = {
            "rates": [w["offered_qps"] for w in sweep],
            "achieved": [w["achieved_qps"] for w in sweep],
            "saturation_qps": cleared[-1]["achieved_qps"] if cleared
            else sweep[0]["achieved_qps"],
        }
        # the headline percentiles: the lowest (sub-saturation) rate window
        bench["latency"] = sweep[0]
    finally:
        stop.set()
        mutator.join(timeout=30)
    bench["mutation_ops"] = mutator.ops
    svc.close()

    bench["scatter_fanout"] = _scatter_fanout(
        ds.triples, ds.n_nodes, ds.n_preds, n_shards=n_shards,
        reps=fanout_reps, threads=resolve_serve_threads(None), quiet=quiet)
    bench["replica_scaling"] = _replica_scaling(
        ds.triples, ds.n_nodes, ds.n_preds, n_shards=n_shards,
        clients=clients, n_queries=replica_queries, reps=fanout_reps,
        counts=replica_counts, quiet=quiet)

    # dimensionless signals for the CI smoke gate (benchmarks.run --check):
    # achieved/offered collapses when the request plane stops keeping up,
    # fan-out and replica speedups collapse when parallel serving stops
    # helping (or breaks)
    lat = bench["latency"]
    bench["smoke_signals"] = {
        "achieved_vs_offered": lat["achieved_qps"] / lat["offered_qps"],
        "scatter_fanout_speedup": bench["scatter_fanout"]["speedup"],
        "replica_scaling_speedup": bench["replica_scaling"]["speedup"],
    }
    if not quiet:
        print(f"saturation: {bench['saturation']['saturation_qps']:.0f} qps "
              f"({bench['mutation_ops']} background mutation ops)")
    if json_path is not None:
        Path(json_path).write_text(json.dumps(bench, indent=2))
        if not quiet:
            print(f"wrote {json_path}")
    return bench


def run_smoke(quiet: bool = True) -> dict:
    """Seconds-long pass on a tiny graph: same code path end to end, no
    tracked JSON. The dict lands in the smoke artifact via benchmarks.run."""
    return run(scale=0.02, duration=0.4, rates=(60.0, 150.0), clients=2,
               hot_frac=0.5, mutation_rate=25.0, seed=0, n_shards=4,
               fanout_reps=2, replica_counts=(0, 1, 2), replica_queries=300,
               quiet=quiet, json_path=None)


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="tiny graph, sub-second windows, no JSON write")
    parser.add_argument("--json", default=BENCH_JSON,
                        help=f"output path (default {BENCH_JSON})")
    parser.add_argument("--quiet", action="store_true")
    args = parser.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke:
        bench = run_smoke(quiet=args.quiet)
        print(json.dumps(bench["smoke_signals"], indent=2))
    else:
        run(quiet=args.quiet, json_path=args.json)
