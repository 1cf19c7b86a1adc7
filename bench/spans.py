#!/usr/bin/env python3
"""Where a request's host time goes: the program's ``itr.*`` spans, reduced.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> \\
        [--trace 0|1] [--spans 0|1]

from the root of a checkout, on the chip, runs the cell as
``bench/run.py`` does (``run.run_cell``: same store, warm-up, traffic and
check). With ``--trace 1 --spans 1`` (the default) the program's own
tracing (``repro.tracing``) is on for exactly the profiler's window, and
the reduction below reads the same trace as ``trace_reduce.reduce``. It
prints one JSON line: the run's result line, then ``p50_ms`` and
``service_ms`` (median due-to-answer and call time of the window's
completed requests), ``rank_positions`` (``K2Tree.rank_positions`` over
the window), and, with spans, ``spans`` and ``span_idle_gaps``.
``--trace 0`` or ``--spans 0`` runs the same cell without them, to price
the instrumentation.

The reduction:

* ``span_table``: for each ``itr.*`` name, its duration summed over host
  threads, its self time (the duration less what its child spans on the
  same thread cover), the spans that ended in the window, and the median
  share of a span's duration that is its own; all clipped to the window;
* ``name_idle_gaps``: the device's idle gaps named by the innermost
  ``itr.*`` span that covers most of each, on any host thread, or, where
  none covers it, as ``trace_reduce.reduce`` names it
  (``bench.request.<kind>`` or ``no request in flight``).

``bench/run.py`` does not read these yet (see ``PERF.md``, Open questions).
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from collections import Counter, defaultdict
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import trace_reduce as tr  # noqa: E402

SPAN_PREFIX = "itr."


def _host_events(profile, keep) -> list:
    """Per host line (thread), the events whose name `keep` accepts, as
    (start, end, name) sorted by start, outermost first."""
    out = []
    for plane in profile.planes:
        if tr._DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            evs = sorted(((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                          for ev in line.events if keep(ev.name)),
                         key=lambda e: (e[0], -e[1]))
            if evs:
                out.append(evs)
    return out


def window(profile):
    """The harness's ``bench.window`` span, or None."""
    for evs in _host_events(profile, lambda n: n == tr.WINDOW_SPAN):
        s, e, _ = evs[0]
        return s, e
    return None


def _innermost(events: list):
    """Walk one thread's properly nested spans; yields (span index, start,
    end) for each stretch in which that span is the innermost one."""
    stack: list = []
    cursor = None
    for i, (s, e, _) in enumerate(events + [(float("inf"), float("inf"), None)]):
        while stack and events[stack[-1]][1] <= s:
            top = stack.pop()
            yield top, cursor, events[top][1]
            cursor = events[top][1]
        if i == len(events):
            return
        if stack:
            yield stack[-1], cursor, s
        stack.append(i)
        cursor = s


def span_table(profile, win=None) -> dict:
    """name -> {"total_s", "self_s", "count", "median_self_share"} of the
    ``itr.*`` spans, clipped to `win` (default: the ``bench.window``
    span; the whole trace without one)."""
    win = win or window(profile) or (float("-inf"), float("inf"))
    lo, hi = win
    total: dict = defaultdict(float)
    own: dict = defaultdict(float)
    count: Counter = Counter()
    shares: dict = defaultdict(list)
    for events in _host_events(profile, lambda n: n.startswith(SPAN_PREFIX)):
        self_ns = [0] * len(events)
        for i, s, e in _innermost(events):
            s, e = tr._clip(s, e, lo, hi)
            if e > s:
                self_ns[i] += e - s
        for (s, e, name), mine in zip(events, self_ns):
            cs, ce = tr._clip(s, e, lo, hi)
            if ce < cs:
                continue
            total[name] += (ce - cs) * 1e-9
            own[name] += mine * 1e-9
            if lo <= e <= hi:
                count[name] += 1
            if ce > cs:
                shares[name].append(mine / (ce - cs))
    return {n: {"total_s": total[n], "self_s": own[n], "count": count[n],
                "median_self_share": statistics.median(shares[n]) if shares[n] else 0.0}
            for n in sorted(total)}


def name_idle_gaps(profile, win=None) -> list:
    """[[what, seconds]] of the device's idle time in the window, the ten
    largest, averaged over device planes as ``trace_reduce.reduce`` does."""
    win = win or window(profile)
    inner = []
    for events in _host_events(profile, lambda n: n.startswith(SPAN_PREFIX)):
        inner += [(s, e, events[i][2]) for i, s, e in _innermost(events) if e > s]
    requests = [(s, e, name[len(tr.REQUEST_SPAN):]) for evs in
                _host_events(profile, lambda n: n.startswith(tr.REQUEST_SPAN))
                for s, e, name in evs]
    inner, requests = tr._Spans(inner), tr._Spans(requests)
    planes = []
    for plane in profile.planes:
        if tr._DEVICE_PLANE.match(plane.name):
            ops = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                   for line in plane.lines if line.name == tr.OPS_LINE
                   for ev in line.events]
            if ops:
                planes.append(ops)
    if win is None:
        flat = [x for ops in planes for iv in ops for x in iv]
        win = (min(flat), max(flat)) if flat else (0, 0)
    lo, hi = win
    gaps: dict = defaultdict(float)
    for ops in planes:
        busy = tr._union([iv for iv in (tr._clip(s, e, lo, hi) for s, e in ops)
                          if iv[1] > iv[0]])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps[_gap_name(gs, ge, inner, requests)] += (ge - gs) * 1e-9 / len(planes)
    return [[n, s] for n, s in sorted(gaps.items(), key=lambda x: -x[1])[:10]]


def _gap_name(gs, ge, inner, requests) -> str:
    cover: dict = defaultdict(int)
    for s, e, name in inner.overlapping(gs, ge):
        cover[name] += min(e, ge) - max(s, gs)
    if cover:
        return max(cover, key=cover.get)
    return tr._host_activity(gs, ge, requests)


# -- the run ----------------------------------------------------------------

def _positions(svc) -> Counter:
    from bench import run

    total: Counter = Counter()
    for t in run._trees(svc):
        total.update(getattr(t, "rank_positions", {}))
    return total


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        spans: bool, **kw) -> dict:
    """``run.run_cell`` with the program's tracing on around the window
    (where `trace` and `spans`); returns its result with the additions."""
    import jax

    from bench import run as bench_run
    from repro import tracing

    seen: dict = {}
    start, stop, load = jax.profiler.start_trace, jax.profiler.stop_trace, tr.load

    def prepare(svc):
        seen["svc"] = svc

    def start_window(*a, **k):
        start(*a, **k)
        seen["before"] = _positions(seen["svc"])
        tracing.enable(spans)

    def stop_window():
        tracing.enable(False)
        seen["after"] = _positions(seen["svc"])
        stop()

    def keep(path):
        seen["profile"] = load(path)
        return seen["profile"]

    records = []
    with mock.patch.object(jax.profiler, "start_trace", start_window), \
            mock.patch.object(jax.profiler, "stop_trace", stop_window), \
            mock.patch.object(tr, "load", keep):
        out = bench_run.run_cell(root, workload, seed, seconds, trace, prepare=prepare,
                                 records_out=records.extend, **kw)
    done = [r for r in records if r.done and r.error is None]
    out["p50_ms"] = statistics.median(r.end - r.due for r in done) * 1e3
    out["service_ms"] = statistics.median(r.end - r.start for r in done) * 1e3
    out["completed"] = len(done)
    if "after" in seen:
        d = seen["after"] - seen["before"]
        out["rank_positions"] = {f"{side}.{kind}": n for (side, kind), n in sorted(d.items())}
    if trace and spans:
        prof = seen["profile"]
        out["spans"] = span_table(prof)
        out["span_idle_gaps"] = name_idle_gaps(prof)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--spans", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    from bench import run as bench_run
    from bench import spec

    try:
        out = run(ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
                  bool(args.spans))
    except (bench_run.NoChip, spec.SpecError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
