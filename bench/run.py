#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine whose chips JAX sees. The cell
(``BENCHMARK.json``: one configuration under one traffic mix) is run in
this one process:

1. it refuses, with no result line, a machine without a TPU or with fewer
   chips than the cell asks for, or a device kind ``bench/peaks.json``
   does not list;
2. it turns on JAX's persistent compilation cache;
3. it generates the configuration's triples from the seed;
4. it opens the store (``bench/store.py``): the first run of a
   (configuration, seed) builds and snapshots it, every run then reopens
   the snapshot through the store's own restart path;
5. it warms up on the mix's own shapes (a separate draw of the seed) and
   measures for ``--seconds``; with ``--trace 1`` the window runs under
   the profiler and the per-layer metrics are reported instead of the
   end-to-end ones. ``setup_s`` runs from the process's start to the
   window's, less the one-off build of step 4: what a restart costs;
6. it checks every answer the window returned against the plain
   reference (``bench/check.py``) and prints the result as the last line
   of standard output; the numbers compared, with their limits, are the
   last lines of standard error and the last key of the result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, replace  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import check, spec, store, trace_reduce  # noqa: E402
from bench import traffic as tr  # noqa: E402

GRACE_S = 60.0  # how long past the window's close an answer is waited for


class NoChip(RuntimeError):
    """The machine lacks the chips the cell asks for."""


def platforms_allow_tpu() -> bool:
    """False where ``JAX_PLATFORMS`` leaves the TPU out: no need to build
    a store before JAX itself says there is no chip."""
    wanted = os.environ.get("JAX_PLATFORMS", "")
    return not wanted or "tpu" in wanted.split(",")


def require_chips(n: int) -> list:
    """The local devices, which must be at least `n` TPUs."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found {len(devices)}")
    return devices


@dataclass
class RunContext:
    """What the metric readers (``bench/end_to_end``, ``bench/layers``) read."""

    seconds: float
    setup_s: float
    window: tr.Window
    trace: trace_reduce.TraceSummary | None = None
    cache_delta: dict | None = None     # result-cache counters over the window
    rank_calls: dict | None = None      # k²-tree rank calls over the window

    @property
    def completed(self) -> list:
        return [r for r in self.window.records if r.done and r.error is None]


def _readers(root: Path, metrics: list, sub: str) -> dict:
    return {m["name"]: spec.load_module(
        Path(root) / spec.BENCH_DIR / sub / f"{m['name']}.py", m["name"]).read
        for m in metrics}


# -- the program's counters, read from outside ------------------------------

def _trees(svc) -> list:
    out = []
    for eng in getattr(svc, "engines", []):
        enc = getattr(eng, "encoded", None)
        for t in (getattr(enc, "incidence", None), getattr(eng, "nt_k2", None)):
            if t is not None:
                out.append(t)
    return out


def rank_calls(svc) -> dict:
    total = {"device": 0, "host": 0}
    for t in _trees(svc):
        for key, n in getattr(t, "rank_calls", {}).items():
            side = key[1] if isinstance(key, tuple) else key
            total[side] = total.get(side, 0) + int(n)
    return total


def device_level_bytes(svc) -> int:
    return sum(int(getattr(t.device, "nbytes", 0)) for t in _trees(svc)
               if getattr(t, "device", None) is not None)


def cache_counters(svc) -> dict | None:
    stats = svc.cache_stats() if hasattr(svc, "cache_stats") else None
    if stats is None:
        return None
    return {k: int(getattr(stats, k)) for k in
            ("hits", "misses", "predicate_hits", "inserts", "evictions",
             "oversize_skips")
            if hasattr(stats, k)}


def compiled_programs() -> int | None:
    try:
        from repro.core.succinct import device_rank
        return int(device_rank.compiled_programs())
    except (ImportError, AttributeError):
        return None


def _delta(after: dict | None, before: dict | None) -> dict | None:
    if after is None or before is None:
        return None
    return {k: after[k] - before.get(k, 0) for k in after}


class CompileCounter:
    """JAX compile events (compiles and persistent-cache loads) while on."""

    def __init__(self):
        import jax.monitoring as mon

        self.events: dict = {}
        self.on = False
        self._mon = mon
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _note(self, name: str) -> None:
        if self.on and ("compil" in name):
            self.events[name] = self.events.get(name, 0) + 1

    def _duration(self, name, secs, **kw):
        self._note(name)

    def _event(self, name, **kw):
        self._note(name)

    def close(self) -> None:
        self._mon.unregister_event_duration_listener(self._duration)
        self._mon.unregister_event_listener(self._event)


def memory_peak_bytes() -> int:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


# -- one cell ---------------------------------------------------------------

def _say(text: str) -> None:
    print(text, flush=True)


def run_cell(root: Path, workload: str, seed: int, seconds: float, trace: bool,
             *, store_root: Path | None = None, t_start: float | None = None,
             prepare=None, traffic_overrides: dict | None = None, records_out=None) -> dict:
    """Run `workload` once; returns the result object (the last line).

    `prepare(svc)` is called on the opened store before the warm-up (the
    control uses it to switch on a degraded path). Raises NoChip where
    the machine lacks the cell's chips."""
    t_start = time.perf_counter() if t_start is None else t_start
    root = Path(root)
    cell = spec.load_cell(root, workload)
    if traffic_overrides:
        cell = replace(cell, traffic={**cell.traffic, **traffic_overrides})
    store_root = Path(store_root) if store_root else root / spec.BENCH_DIR / ".store"
    if not platforms_allow_tpu():
        raise NoChip(f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} leaves out the TPU")
    # the build, where this (configuration, seed) has no store yet, runs in
    # a child on the CPU before this process touches JAX
    info = store.ensure_store(root, store_root, cell.config_file, cell.config, seed)
    devices = require_chips(cell.chips)

    dev = devices[0]
    spec.peaks(root, dev.device_kind)  # an unknown device kind is an error
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    _say(f"device: {json.dumps(device)}")
    from repro.compile_cache import enable_compile_cache
    _say(f"compile cache: {enable_compile_cache()}")

    cfg = cell.config
    ds = cfg["dataset"]
    t = time.perf_counter()
    triples, n_nodes, n_preds = spec.generator(root, ds["generator"])(seed, **ds["params"])
    _say(f"dataset: {cell.config_name} seed={seed}: {n_nodes} nodes, "
         f"{len(triples)} triples, {n_preds} predicates, generated in "
         f"{time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    svc = store.open_store(info["path"], cfg)
    open_s = time.perf_counter() - t
    try:
        _say(f"store: {'built' if info['built'] else 'reused'} "
             f"(build {info['build_s']:.3f} s in a child on the CPU), "
             f"reopened in {open_s:.3f} s")
        _say(f"k2-tree levels on device: {device_level_bytes(svc)} bytes")
        if prepare is not None:
            prepare(svc)
        result = _serve_cell(root, cell, svc, triples, n_preds, seed, seconds, trace,
                             t_start + info["build_s"], device)
    finally:
        svc.close()
    del svc
    gc.collect()
    t = time.perf_counter()
    records = result.pop("_records")
    if records_out is not None:
        records_out(records)
    verdict = check.compare(records, triples)
    _say(f"check: {verdict['patterns_counted']} pattern counts and "
         f"{verdict['patterns_compared_whole']} whole answers against the "
         f"reference in {time.perf_counter() - t:.3f} s")
    checks = verdict["checks"]
    result["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    result["checks"] = checks  # last key: the numbers compared and their limits
    return result


def _serve_cell(root, cell, svc, triples, n_preds, seed, seconds, trace, t_start,
                device) -> dict:
    """Warm up, measure and reduce; `t_start` is when set-up began, moved
    on by any store build, which ``setup_s`` leaves out."""
    import jax

    mix = cell.traffic
    call = svc.query_many
    # warm-up: the mix's own shapes, from a draw of its own
    t = time.perf_counter()
    by_kind: dict = {}
    if mix["loop"] == "open":
        n = max(1, round(float(mix["rate_per_s"]) * seconds))
        schedule = tr.rng_for(0, tr.SCHEDULE)
        due = tr.arrivals(n, seconds, schedule)
        requests = tr.open_requests(mix["mix"], triples, n, tr.rng_for(seed, tr.WINDOW),
                                    schedule)
        warm_rng = tr.rng_for(seed, tr.WARMUP)
        warm = tr.open_requests(mix["mix"], triples, int(mix["warmup_requests"]),
                                warm_rng, warm_rng)
    else:
        streams = [tr.RoundStream(mix["round"], triples, n_preds,
                                  tr.rng_for(seed, tr.WINDOW * 100 + c))
                   for c in range(int(mix["clients"]))]
        warm_stream = tr.RoundStream(mix["round"], triples, n_preds,
                                     tr.rng_for(seed, tr.WARMUP))
        warm = [r for _ in range(int(mix["warmup_rounds"])) for r in warm_stream.round()]
    for req in warm:
        before = rank_calls(svc)
        call(req.patterns)
        d = _delta(rank_calls(svc), before)
        k = by_kind.setdefault(req.kind, {"requests": 0, "device": 0, "host": 0})
        k["requests"] += 1
        k["device"] += d.get("device", 0)
        k["host"] += d.get("host", 0)
    _say(f"warm-up: {len(warm)} requests in {time.perf_counter() - t:.3f} s; "
         f"rank calls by kind: {json.dumps(by_kind, sort_keys=True)}")

    if mix["loop"] == "open":
        def keep(i):  # every answer is kept whole
            return True
    else:
        share = float(mix.get("check", {}).get("whole_share", 1.0))
        samplers = [tr.rng_for(seed, tr.SAMPLE * 100 + c) for c in range(len(streams))]

        def keep(c, n):  # each client's first request, then a seeded share
            return n == 0 or samplers[c].random() < share

    counter = CompileCounter()
    cache0, ranks0, progs0 = cache_counters(svc), rank_calls(svc), compiled_programs()
    gc.collect()
    gc.freeze()  # what set-up made stays out of the window's collections
    setup_s = time.perf_counter() - t_start
    _say(f"setup: {setup_s:.3f} s (the store build, where there was one, left out)")

    trace_dir = root / spec.BENCH_DIR / ".traces" / f"{cell.name}-{seed}"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)

        def span(kind):
            return jax.profiler.TraceAnnotation(trace_reduce.REQUEST_SPAN + kind)
        window_span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
    else:
        def span(kind):
            return contextlib.nullcontext()
        window_span = contextlib.nullcontext()
    counter.on = True
    try:
        with window_span:
            if mix["loop"] == "open":
                win = tr.run_open(call, requests, due, seconds, int(mix["workers"]),
                                  GRACE_S, keep, span)
            else:
                win = tr.run_closed(call, streams, seconds, GRACE_S, keep, span)
    finally:
        counter.on = False
        counter.close()
        gc.unfreeze()
    summary = None
    if trace:
        jax.profiler.stop_trace()
        t = time.perf_counter()
        xplane = trace_reduce.find_xplane(trace_dir)
        size = xplane.stat().st_size
        summary = trace_reduce.reduce(trace_reduce.load(xplane), spec.kernels(root))
        shutil.rmtree(trace_dir, ignore_errors=True)  # traces are large: none stay
        _say(f"trace: {size} bytes, reduced in {time.perf_counter() - t:.3f} s: window "
             f"{summary.window_s:.3f} s, busy {summary.busy_s:.6f} s on "
             f"{summary.devices} device(s), kernels "
             f"{json.dumps(summary.kernel_launches, sort_keys=True)}")
    peak = memory_peak_bytes()
    ctx = RunContext(seconds, setup_s, win, summary,
                     _delta(cache_counters(svc), cache0), _delta(rank_calls(svc), ranks0))
    progs1 = compiled_programs()
    recs = win.records
    done = ctx.completed
    failed = [r for r in recs if r.error is not None]
    lost = [r for r in recs if not r.done]
    lag = sorted(win.lag_s) or [0.0]
    _say(f"requests: {len(recs)} attempted, {len(done)} completed, "
         f"{len(failed)} failed, {len(lost)} never returned; last ended at "
         f"{win.closed_s:.3f} s of a {seconds} s window")
    if failed:
        _say(f"first failure: {failed[0].error}")
    if mix["loop"] == "open":
        _say(f"generator lag: median {lag[len(lag) // 2] * 1e3:.3f} ms, "
             f"max {lag[-1] * 1e3:.3f} ms")
    _say(f"result cache over the window: {json.dumps(ctx.cache_delta)}")
    _say(f"rank calls over the window: {json.dumps(ctx.rank_calls)}")
    _say(f"compiled rank programs: {progs0} before the window, {progs1} after; "
         f"compile events in the window: {json.dumps(counter.events)}")
    _say(f"device memory: peak_bytes_in_use {peak}")

    metrics = cell.per_layer if trace else cell.end_to_end
    values = {}
    for name, read in _readers(root, metrics, "layers" if trace else "end_to_end").items():
        v = read(ctx)
        if v is not None:
            unit = next(m["unit"] for m in metrics if m["name"] == name)
            values[name] = {"value": float(v), "unit": unit}
    device = dict(device, memory_peak_bytes=peak)
    if trace:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    out = {"correct": None, "attempted": len(recs), "failed": len(failed) + len(lost),
           "metrics": values, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    out["_records"] = recs
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except (NoChip, spec.SpecError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
