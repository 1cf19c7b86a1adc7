"""From a profiler trace to the numbers the benchmark reports.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX's own reader. Device planes are ``/device:TPU:<n>``. On each:

* busy time is the union of the intervals in which an operation of the
  ``XLA Ops`` line runs, clipped to the window;
* a device program is an event of the ``XLA Modules`` line; each kernel
  file (``bench/kernels/<name>.json``) claims the programs whose name
  its ``pattern`` matches, and its time and launches are summed;
* the gaps between busy intervals are named by what the host was doing:
  the ``bench.request.<kind>`` span that covers most of the gap, or
  ``no request in flight``.

The window is the host span ``bench.window`` that the harness opens
around its measured window; a trace without one is taken whole.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "bench.window"
REQUEST_SPAN = "bench.request."
NO_REQUEST = "no request in flight"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_OP = re.compile(r"^(\S+ = (?:\([^()]*\)|\S+) [\w\-]+)")


@dataclass
class TraceSummary:
    window_s: float
    devices: int                       # device planes with any operation
    busy_s: float                      # averaged over those planes
    kernel_s: dict = field(default_factory=dict)        # kernel -> seconds
    kernel_launches: dict = field(default_factory=dict)  # kernel -> count
    layer_s: dict = field(default_factory=dict)          # layer -> seconds
    layer_launches: dict = field(default_factory=dict)   # layer -> count
    device_ops: list = field(default_factory=list)       # [[name, seconds]]
    idle_gaps: list = field(default_factory=list)        # [[what, seconds]]
    requests: int = 0                  # request spans that ended in the window


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: Path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce(profile, kernels: dict) -> TraceSummary:
    """`kernels`: name -> {"pattern": regex, "layer": name}."""
    requests = []
    window = None
    device_planes = []
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            device_planes.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(REQUEST_SPAN):
                    requests.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                     ev.name[len(REQUEST_SPAN):]))
    per_plane = []
    for plane in device_planes:
        ops, mods = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                       for ev in line.events]
            elif line.name == MODULES_LINE:
                mods = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in line.events]
        if ops or mods:
            per_plane.append((ops, mods))
    if window is None:
        ends = [e for ops, mods in per_plane for _, e, _ in ops + mods]
        starts = [s for ops, mods in per_plane for s, _, _ in ops + mods]
        window = (min(starts), max(ends)) if starts else (0, 0)
    lo, hi = window
    out = TraceSummary(window_s=(hi - lo) * 1e-9, devices=len(per_plane), busy_s=0.0)
    out.requests = sum(1 for s, e, _ in requests if lo <= e <= hi)
    spans = _Spans(requests)
    compiled = {k: re.compile(v["pattern"]) for k, v in kernels.items()}
    op_s: dict = defaultdict(float)
    gaps: dict = defaultdict(float)
    busy_total = 0.0
    for ops, mods in per_plane:
        clipped = []
        for s, e, name in ops:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                clipped.append((s, e))
                op_s[short_op_name(name)] += (e - s) * 1e-9
        busy = _union(clipped)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        for s, e, name in mods:
            s, e = _clip(s, e, lo, hi)
            if e < s or (e == s and not lo <= s <= hi):
                continue
            for k, pat in compiled.items():
                if pat.search(name):
                    layer = kernels[k]["layer"]
                    out.kernel_s[k] = out.kernel_s.get(k, 0.0) + (e - s) * 1e-9
                    out.kernel_launches[k] = out.kernel_launches.get(k, 0) + 1
                    out.layer_s[layer] = out.layer_s.get(layer, 0.0) + (e - s) * 1e-9
                    out.layer_launches[layer] = out.layer_launches.get(layer, 0) + 1
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps[_host_activity(gs, ge, spans)] += (ge - gs) * 1e-9
    if per_plane:
        out.busy_s = busy_total / len(per_plane)
        for k in gaps:
            gaps[k] /= len(per_plane)
    out.device_ops = [[n, s] for n, s in sorted(op_s.items(), key=lambda x: -x[1])[:10]]
    out.idle_gaps = [[n, s] for n, s in sorted(gaps.items(), key=lambda x: -x[1])[:10]]
    return out


def short_op_name(hlo: str) -> str:
    """``%fusion.1 = u32[65536]{0:T(1024)} fusion(...), ...`` ->
    ``%fusion.1 = u32[65536] fusion``: the op, its result shape without
    layout, and its kind. Names of another form are kept as they are."""
    bare = re.sub(r"\{[^{}]*\}", "", hlo)
    m = _OP.match(bare)
    return m.group(1) if m else hlo[:120]


def _host_activity(gs: int, ge: int, requests: "_Spans") -> str:
    """The request kind whose spans cover most of the gap [gs, ge)."""
    cover: dict = defaultdict(int)
    for s, e, kind in requests.overlapping(gs, ge):
        cover[kind] += min(e, ge) - max(s, gs)
    if not cover:
        return NO_REQUEST
    return REQUEST_SPAN + max(cover, key=cover.get)


class _Spans:
    """Host request spans, sorted by start, for overlap queries."""

    def __init__(self, spans: list):
        self.spans = sorted(spans)
        self.starts = [s for s, _, _ in self.spans]
        self.longest = max((e - s for s, e, _ in self.spans), default=0)

    def overlapping(self, gs: int, ge: int):
        i = bisect.bisect_left(self.starts, ge)
        while i > 0:
            i -= 1
            s, e, kind = self.spans[i]
            if s < gs - self.longest:
                break
            if e > gs:
                yield s, e, kind
