"""Benchmark harness entry point — one section per paper table/figure.

Prints `name,value,derived` CSV lines per benchmark so results are grep-able
(`python -m benchmarks.run > bench_output.txt`).

`--smoke` runs every section on tiny inputs with one repetition and never
overwrites the tracked BENCH_*.json artifacts — it exists so CI can prove
the harness still executes end to end without paying full benchmark time.

`--smoke --check` is the CI benchmark-regression gate: the smoke run's
*dimensionless* metrics (speedups, dispatch ratios — absolute µs vary too
much across machines to gate on) are compared against the `smoke_baseline`
section committed in BENCH_query_latency.json, with a generous tolerance
(default 3x, `--tolerance`) so timing noise never fails a build but a real
regression — a speedup collapsing, dispatch suddenly slower than scalar —
does. The smoke metrics are written to BENCH_smoke_query_latency.json for
upload as a workflow artifact. `--smoke --update-baseline` re-records the
committed baseline from the current machine.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BASELINE_JSON = "BENCH_query_latency.json"
SMOKE_JSON = "BENCH_smoke_query_latency.json"
GATE_TOLERANCE = 3.0

# metric name suffixes where LOWER is better (ratios of our-time / reference)
_LOWER_IS_BETTER = ("dispatched_vs_scalar", "sharded_vs_single",
                    "overhead_vs_clean", "skew_after_vs_before",
                    "dict_vs_plain_bytes")


def gate_metrics(bench: dict) -> dict[str, float]:
    """Flatten a query-latency bench dict to the dimensionless metrics the
    regression gate compares. Only ratio-style numbers qualify: absolute
    latencies depend on the machine, ratios mostly cancel it out."""
    out: dict[str, float] = {}
    for pat, p in bench.get("patterns", {}).items():
        if pat == "???":
            # full-decompression pattern: capped at 5 scalar queries and
            # bounded by result-materialization volume, not engine speed —
            # too few samples to gate on without flakiness
            continue
        out[f"patterns.{pat}.speedup_vs_scalar"] = p["speedup_vs_scalar"]
    wc = bench.get("warm_cache", {})
    for pat, p in wc.get("patterns", {}).items():
        out[f"warm_cache.{pat}.warm_speedup_vs_uncached"] = \
            p["warm_speedup_vs_uncached"]
    if "point_lookup" in wc:
        out["warm_cache.point_lookup.warm_speedup"] = \
            wc["point_lookup"]["warm_speedup"]
    for pat, p in bench.get("crossover_dispatch", {}).get("patterns", {}).items():
        out[f"crossover_dispatch.{pat}.dispatched_vs_scalar"] = \
            p["dispatched_vs_scalar"]
    sharded = bench.get("sharded", {})
    if "warm_view" in sharded:
        out["sharded.warm_view.speedup_vs_materialized"] = \
            sharded["warm_view"]["speedup_vs_materialized"]
    for pat, p in sharded.get("scatter_gather", {}).items():
        out[f"sharded.scatter_gather.{pat}.sharded_vs_single"] = \
            p["sharded_vs_single"]
    mutation = bench.get("mutation", {})
    for tier, t in mutation.get("overlay", {}).get("tiers", {}).items():
        out[f"mutation.overlay.{tier}.overhead_vs_clean"] = \
            t["overhead_vs_clean"]
    if "rebuild" in mutation:
        out["mutation.rebuild.full_vs_incremental"] = \
            mutation["rebuild"]["full_vs_incremental"]
    rebalance = bench.get("rebalance", {})
    if rebalance:
        # deterministic balance gain of the online re-cut (lower = better)
        out["rebalance.skew_after_vs_before"] = \
            rebalance["skew_after_vs_before"]
        # migration must stay cheaper than a full re-partition
        out["rebalance.full_vs_migration"] = rebalance["full_vs_migration"]
    bgp = bench.get("bgp", {})
    if "chain3" in bgp:
        # the planned id-array join must keep beating the naive
        # per-pattern-then-Python-join baseline on a 3-pattern chain
        out["bgp.chain3.planned_vs_naive"] = bgp["chain3"]["planned_vs_naive"]
        # whole-BGP cache hits must keep short-circuiting repeat queries
        out["bgp.chain3.warm_speedup"] = bgp["chain3"]["warm_speedup"]
    recovery = bench.get("recovery", {})
    if "cold_start_speedup" in recovery:
        # snapshot cold start must stay cheaper than a RePair rebuild
        out["recovery.cold_start_speedup"] = recovery["cold_start_speedup"]
    ingestion = bench.get("ingestion", {})
    if "dict_vs_plain_bytes" in ingestion:
        # the front-coded term dictionary must stay smaller than a plain
        # forward+reverse Python mapping; size ratio is deterministic for
        # a given dataset, so it gates tightly despite the 3x tolerance
        out["ingestion.dict_vs_plain_bytes"] = \
            ingestion["dict_vs_plain_bytes"]
    load = bench.get("serving_load", {}).get("smoke_signals", {})
    if "achieved_vs_offered" in load:
        # open-loop throughput ratio at a sub-saturation offered rate:
        # collapses when the concurrent request plane stops keeping up
        out["serving_load.achieved_vs_offered"] = load["achieved_vs_offered"]
    if "scatter_fanout_speedup" in load:
        # threaded vs sequential scatter fan-out (~1.0 on 1-core runners)
        out["serving_load.scatter_fanout_speedup"] = \
            load["scatter_fanout_speedup"]
    if "replica_scaling_speedup" in load:
        # read QPS at max replica groups vs one (~1.0 on 1-core runners):
        # collapses when replica dispatch breaks or stops spreading load
        out["serving_load.replica_scaling_speedup"] = \
            load["replica_scaling_speedup"]
    return {k: float(v) for k, v in out.items()}


def _load_bench_json(path: str, remedy: str) -> dict | None:
    """Read one bench JSON artifact; on any failure print an actionable
    `gate ERROR` (what is wrong + how to fix it) and return None."""
    try:
        doc = json.loads(Path(path).read_text())
    except FileNotFoundError:
        print(f"gate ERROR: {path} not found — {remedy}", file=sys.stderr)
        return None
    except json.JSONDecodeError as exc:
        print(f"gate ERROR: {path} is not valid JSON ({exc}) — {remedy}",
              file=sys.stderr)
        return None
    if not isinstance(doc, dict):
        print(f"gate ERROR: {path} must hold a JSON object, got "
              f"{type(doc).__name__} — {remedy}", file=sys.stderr)
        return None
    return doc


def check_regressions(smoke_path: str = SMOKE_JSON,
                      baseline_path: str = BASELINE_JSON,
                      tolerance: float | None = None) -> int:
    """Compare smoke gate metrics against the committed smoke baseline.

    Metrics only on the smoke side are skipped (new metrics don't fail
    the gate until a baseline is recorded for them), but a metric the
    BASELINE has and the smoke run no longer emits is a FAILURE — a
    renamed/dropped section silently losing its gates is exactly the
    coverage loss this gate exists to catch. `tolerance` defaults to the
    one recorded alongside the baseline (so re-recording with
    `--update-baseline --tolerance N` actually changes the gate).
    Returns the number of regressions; prints one `gate ...` line each.
    Every malformed-input path (missing file, invalid JSON, missing
    `smoke_baseline` section, a section metric that lost its value)
    fails with an actionable `gate ERROR` line instead of a traceback.
    """
    smoke_doc = _load_bench_json(
        smoke_path, "re-run `python -m benchmarks.run --smoke --check` "
        "(the smoke run writes it)")
    baseline_doc = _load_bench_json(
        baseline_path, "restore the tracked artifact or re-record it with "
        "`python -m benchmarks.run` then `--smoke --update-baseline`")
    if smoke_doc is None or baseline_doc is None:
        return 1
    try:
        smoke = gate_metrics(smoke_doc)
    except (KeyError, TypeError) as exc:
        print(f"gate ERROR: {smoke_path} has a bench section missing its "
              f"expected metric ({exc!r}); the smoke run and the gate "
              f"disagree about the schema — re-run "
              f"`python -m benchmarks.run --smoke --check` from this "
              f"checkout", file=sys.stderr)
        return 1
    section = baseline_doc.get("smoke_baseline")
    if not isinstance(section, dict):
        print(f"gate ERROR: no smoke_baseline section in {baseline_path}; "
              f"record one with "
              f"`python -m benchmarks.run --smoke --update-baseline`",
              file=sys.stderr)
        return 1
    if tolerance is None:
        tolerance = float(section.get("tolerance", GATE_TOLERANCE))
    base = section.get("metrics")
    if not isinstance(base, dict) or not base:
        print(f"gate ERROR: smoke_baseline in {baseline_path} has no "
              f"metrics mapping; re-record it with "
              f"`python -m benchmarks.run --smoke --update-baseline`",
              file=sys.stderr)
        return 1
    bad = {k: v for k, v in base.items()
           if not isinstance(v, (int, float)) or isinstance(v, bool)}
    if bad:
        print(f"gate ERROR: smoke_baseline metrics in {baseline_path} "
              f"must be numbers; offending entries: "
              f"{', '.join(sorted(bad))} — re-record with "
              f"`python -m benchmarks.run --smoke --update-baseline`",
              file=sys.stderr)
        return 1
    failures = 0
    for name in sorted(set(smoke) & set(base)):
        got, want = smoke[name], base[name]
        if name.endswith(_LOWER_IS_BETTER):
            ok = got <= want * tolerance
            bound = f"<= {want * tolerance:.2f}"
        else:
            ok = got >= want / tolerance
            bound = f">= {want / tolerance:.2f}"
        failures += not ok
        print(f"gate {name}: smoke={got:.2f} baseline={want:.2f} "
              f"({bound}) {'PASS' if ok else 'FAIL'}")
    for name in sorted(set(base) - set(smoke)):
        failures += 1
        print(f"gate {name}: MISSING from smoke run (baseline gates it) FAIL")
    fresh = sorted(set(smoke) - set(base))
    if fresh:
        print(f"gate # {len(fresh)} new metric(s) skipped until a baseline "
              f"is recorded: {', '.join(fresh)}")
    print(f"gate summary: {failures} regression(s) at {tolerance:g}x tolerance")
    return failures


def conservative_envelope(metric_dicts: list[dict]) -> dict[str, float]:
    """Fold several runs' gate metrics into one baseline, taking each
    metric's WORST observed side (min for higher-is-better, max for
    lower-is-better). Gating against the envelope means the tolerance
    band absorbs run-to-run timing noise instead of flagging it — only a
    regression beyond (worst observed) / tolerance fails."""
    out: dict[str, float] = {}
    for m in metric_dicts:
        for k, v in m.items():
            if k not in out:
                out[k] = v
            elif k.endswith(_LOWER_IS_BETTER):
                out[k] = max(out[k], v)
            else:
                out[k] = min(out[k], v)
    return out


def update_baseline_from(bench_dicts: list[dict],
                         baseline_path: str = BASELINE_JSON,
                         tolerance: float | None = None) -> None:
    """Record the conservative envelope of smoke bench dicts as the
    committed gate baseline (with the tolerance future `--check` runs
    will gate at). Refreshing without --tolerance keeps any previously
    recorded custom tolerance."""
    doc = json.loads(Path(baseline_path).read_text())
    if tolerance is None:
        tolerance = doc.get("smoke_baseline", {}).get("tolerance", GATE_TOLERANCE)
    doc["smoke_baseline"] = {
        "tolerance": float(tolerance),
        "runs": len(bench_dicts),
        "note": "conservative envelope of dimensionless smoke metrics for "
                "`benchmarks.run --smoke --check`; refresh with "
                "--smoke --update-baseline",
        "metrics": conservative_envelope([gate_metrics(b) for b in bench_dicts]),
    }
    Path(baseline_path).write_text(json.dumps(doc, indent=2))
    print(f"smoke_baseline updated in {baseline_path} "
          f"({len(bench_dicts)} run(s), tolerance {tolerance:g}x)")


def update_baseline(smoke_path: str = SMOKE_JSON,
                    baseline_path: str = BASELINE_JSON,
                    tolerance: float | None = None) -> None:
    """Single-run convenience wrapper around :func:`update_baseline_from`."""
    update_baseline_from([json.loads(Path(smoke_path).read_text())],
                         baseline_path, tolerance)


def main(smoke: bool = False, check: bool = False,
         update: bool = False, tolerance: float | None = None) -> None:
    from benchmarks import (
        compression_ratio,
        compression_speed,
        itr_plus_bench,
        kernels_bench,
        query_latency,
        serving_load,
    )

    def _merge_serving_load(quiet: bool = True) -> dict:
        """Run the load-harness smoke pass and fold it into the smoke
        artifact, so the gate sees its dimensionless signals alongside the
        query-latency ones."""
        load = serving_load.run_smoke(quiet=quiet)
        doc = json.loads(Path(SMOKE_JSON).read_text())
        doc["serving_load"] = load
        Path(SMOKE_JSON).write_text(json.dumps(doc, indent=2))
        return doc

    print("== Table 1b / Figure 3: compression ratio per dataset ==")
    fig3 = compression_ratio.run(datasets=["ttt-win"] if smoke else compression_ratio.DATASETS)
    print("\n== Figure 4: triple-query latency (500 queries/pattern) ==")
    if smoke:
        # the gate needs the smoke bench dict on disk; plain smoke runs
        # stay write-free (BENCH_*.json artifacts are never overwritten)
        smoke_json = SMOKE_JSON if (check or update) else None
        fig4 = query_latency.run(n_queries=25, scale=0.02, json_path=smoke_json)
        print("\n== serving load (open-loop smoke) ==")
        if smoke_json:
            _merge_serving_load(quiet=False)
        else:
            serving_load.run_smoke(quiet=False)
    else:
        fig4 = query_latency.run()
        print("\n== serving load (open-loop) ==")
        load_bench = serving_load.run()
    print("\n== §ITR+: node-label hyperedges (ttt-win) ==")
    plus = itr_plus_bench.run()
    print("\n== ablations: §Handling loops + mfd selection ==")
    from benchmarks import ablations

    abl = ablations.run()
    print("\n== compression throughput ==")
    speed = compression_speed.run(sizes=(2000,) if smoke else (2000, 8000, 32000))
    print("\n== kernel micro-bench (CPU: interpret / XLA:CPU, not device numbers) ==")
    kerns = kernels_bench.run()

    print("\n== CSV ==")
    print("name,value,derived")
    for row in fig3:
        for m in ("ITR", "ITR+", "k2-triples", "HDT-BT"):
            if m in row:
                print(f"fig3/{row['dataset']}/{m},{row[m]:.6f},ratio")
    for row in fig4:
        for m, v in row.items():
            if m != "pattern":
                print(f"fig4/{row['pattern']}/{m},{v:.1f},us_per_query")
    # batched-engine trajectory (written by query_latency.run; in smoke mode
    # the tracked file is not rewritten, so skip rather than report stale)
    if not smoke:
        try:
            bench = json.loads(Path(BASELINE_JSON).read_text())
            print(f"fig4/batch_throughput_qps,{bench['batch_throughput_qps']:.0f},qps")
            for pat, p in bench["patterns"].items():
                print(f"fig4/{pat}/speedup_vs_scalar,{p['speedup_vs_scalar']:.2f},x")
            for pat, p in bench.get("warm_cache", {}).get("patterns", {}).items():
                print(f"fig4/{pat}/warm_speedup_vs_uncached,{p['warm_speedup_vs_uncached']:.2f},x")
            for pat, p in bench.get("crossover_dispatch", {}).get("patterns", {}).items():
                print(f"fig4/{pat}/dispatched_vs_scalar,{p['dispatched_vs_scalar']:.2f},x")
            sharded = bench.get("sharded", {})
            for strat, per in sharded.get("strategies", {}).items():
                for n_shards, v in per.items():
                    print(f"sharded/{strat}/P{n_shards}/warm_qps,{v['warm_qps']:.0f},qps")
            if "warm_view" in sharded:
                print(f"sharded/warm_view/speedup_vs_materialized,"
                      f"{sharded['warm_view']['speedup_vs_materialized']:.2f},x")
            mutation = bench.get("mutation", {})
            for tier, t in mutation.get("overlay", {}).get("tiers", {}).items():
                print(f"mutation/overlay/{tier}/overhead_vs_clean,"
                      f"{t['overhead_vs_clean']:.2f},x")
            if "rebuild" in mutation:
                print(f"mutation/rebuild/full_vs_incremental,"
                      f"{mutation['rebuild']['full_vs_incremental']:.2f},x")
            rebalance = bench.get("rebalance", {})
            if rebalance:
                print(f"rebalance/skew_after_vs_before,"
                      f"{rebalance['skew_after_vs_before']:.3f},x")
                print(f"rebalance/full_vs_migration,"
                      f"{rebalance['full_vs_migration']:.2f},x")
                print(f"rebalance/migrated_rows,"
                      f"{rebalance['migrated_rows']},rows")
            recovery = bench.get("recovery", {})
            if recovery:
                print(f"recovery/cold_start_speedup,"
                      f"{recovery['cold_start_speedup']:.2f},x")
                print(f"recovery/wal_replay_records_per_s,"
                      f"{recovery['wal_replay_records_per_s']:.0f},rec_per_s")
                print(f"recovery/first_query_after_open_us,"
                      f"{recovery['first_query_after_open_us']:.1f},us")
            ingestion = bench.get("ingestion", {})
            if ingestion:
                print(f"ingestion/dict_vs_plain_bytes,"
                      f"{ingestion['dict_vs_plain_bytes']:.4f},ratio")
                print(f"ingestion/terms_per_s,"
                      f"{ingestion['terms_per_s']:.0f},terms_per_s")
                print(f"ingestion/rows_per_s,"
                      f"{ingestion['rows_per_s']:.0f},rows_per_s")
                print(f"ingestion/dict_bytes_per_term,"
                      f"{ingestion['dict_bytes_per_term']:.2f},bytes")
        except Exception as e:
            print(f"# {BASELINE_JSON} unavailable: {e}", file=sys.stderr)
        lat = load_bench.get("latency", {})
        for q in ("p50_ms", "p95_ms", "p99_ms"):
            print(f"serving_load/{q},{lat.get(q, 0.0):.3f},ms")
        print(f"serving_load/saturation_qps,"
              f"{load_bench['saturation']['saturation_qps']:.0f},qps")
        print(f"serving_load/scatter_fanout_speedup,"
              f"{load_bench['scatter_fanout']['speedup']:.2f},x")
        print(f"serving_load/replica_scaling_speedup,"
              f"{load_bench['replica_scaling']['speedup']:.2f},x")
    p = plus[0]
    print(f"itr_plus/ttt-win/gain,{p['plus_gain']:.4f},fraction")
    for row in abl["loop_rules"]:
        print(f"ablation/loop_rules/{row['dataset']},{row['loop_rule_bytes']/row['index_fn_bytes']:.4f},vs_index_fn")
    for row in abl["selection"]:
        print(f"ablation/selection/{row['dataset']},{row['savings_gain']:.4f},savings_vs_count")
    for row in speed:
        print(f"speed/E{row['edges']},{row['edges_per_s']:.0f},edges_per_s")
    for row in kerns:
        print(f"kernel/{row['kernel']},{row['kernel_us']:.1f},us_per_call")

    # roofline summary if the dry-run has produced results (skipped in smoke:
    # it only reports on artifacts a TPU dry-run would have left behind)
    if not smoke:
        try:
            from benchmarks import roofline_report

            rows = roofline_report.run(quiet=True)
            ok = [r for r in rows if r.get("ok")]
            if ok:
                print(f"roofline/cells_ok,{len(ok)},count")
                for r in ok:
                    print(f"roofline/{r['arch']}/{r['shape']}/dominant,{r['dominant']},bottleneck")
        except Exception as e:  # dry-run not yet executed
            print(f"# roofline skipped: {e}", file=sys.stderr)

    if smoke and update:
        print("\n== gate baseline ==")
        # envelope over extra latency-section runs: smoke ratios jitter by
        # ~2-3x run to run, so a single-shot baseline plus 3x tolerance
        # would flag noise; the worst observed side per metric won't
        runs = [json.loads(Path(SMOKE_JSON).read_text())]
        for _ in range(2):
            # query_latency.run rewrites SMOKE_JSON from scratch, so the
            # serving_load section must be re-run and re-merged per pass
            query_latency.run(n_queries=25, scale=0.02, json_path=SMOKE_JSON,
                              quiet=True)
            runs.append(_merge_serving_load())
        update_baseline_from(runs, tolerance=tolerance)
    if smoke and check:
        print("\n== benchmark-regression gate ==")
        if check_regressions(tolerance=tolerance):
            sys.exit(1)


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="tiny graphs, 1 repetition, no tracked-JSON overwrite")
    parser.add_argument("--check", action="store_true",
                        help="with --smoke: fail on regression vs the committed "
                             "smoke_baseline (writes BENCH_smoke_query_latency.json)")
    parser.add_argument("--update-baseline", action="store_true",
                        help="with --smoke: re-record the committed smoke_baseline")
    parser.add_argument("--tolerance", type=float, default=None,
                        help="gate tolerance factor (default: the one recorded "
                             f"in the baseline, else {GATE_TOLERANCE:g})")
    args = parser.parse_args()
    if (args.check or args.update_baseline) and not args.smoke:
        parser.error("--check/--update-baseline require --smoke")
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main(smoke=args.smoke, check=args.check, update=args.update_baseline,
         tolerance=args.tolerance)
