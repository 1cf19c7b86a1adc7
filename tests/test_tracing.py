"""The query path's spans (`repro.tracing`): a shared no-op while off, and
while on every boundary of one served `query_many` in a CPU profiler
capture, nested as ``docs/ARCHITECTURE.md`` ("Tracing") lists them."""
import gc
from pathlib import Path

import numpy as np
import pytest

from repro import tracing
from repro.core.succinct import device_rank
from repro.data.synthetic import rdf_like
from repro.serve.sharded import ShardedTripleService

# span -> the spans it may sit directly inside, on its own thread
# (None: the outermost span of its thread)
PARENTS = {
    "itr.request": {None},
    "itr.flush": {"itr.request"},
    "itr.wait.rwlock": {"itr.flush"},
    "itr.route": {"itr.flush"},
    "itr.wait.shards": {"itr.flush"},
    "itr.merge": {"itr.flush"},
    "itr.tuples": {"itr.request"},
    "itr.wait.engine": {None},
    "itr.shard.exec": {None},
    "itr.engine.cache": {"itr.shard.exec"},
    "itr.engine.seed": {"itr.shard.exec"},
    "itr.engine.frontier": {"itr.shard.exec"},
    "itr.engine.scalar": {"itr.shard.exec"},
    "itr.engine.overlay": {"itr.shard.exec"},
    "itr.k2.descent": {"itr.engine.seed", "itr.engine.scalar"},
    "itr.k2.rank": {"itr.k2.descent"},
    "itr.k2.fused": {"itr.k2.descent"},
    "itr.k2.compile": {"itr.k2.rank", "itr.k2.fused"},
}
POOL_SPANS = {"itr.wait.engine", "itr.shard.exec"}


@pytest.fixture
def tracing_off():
    tracing.enable(False)
    yield
    tracing.enable(False)


def _store(monkeypatch):
    """Four predicate_hash shards of a small graph, its k²-trees placed on
    the device path, the fan-out on a pool, one triple in an overlay."""
    monkeypatch.setattr(device_rank, "enabled", lambda: True)
    data = rdf_like(n_nodes=400, n_edges=1500, n_preds=6, seed=3)
    svc = ShardedTripleService.build(data.triples, data.n_nodes, data.n_preds,
                                     n_shards=4, crossover=1, serve_threads=4)
    assert svc.insert_triples([(data.n_nodes - 1, 0, data.n_nodes - 2)]) == 1
    return svc, data.triples


def _patterns(triples, svc):
    """120 `sp?` lookups that one shard owns (its frontier), and one `s??`
    scattered to every shard: alone on the others, it takes the scalar
    worklist."""
    t = triples[:, :2]
    routes = svc.plan.route_batch(t[:, 0], t[:, 1], np.full(len(t), -1))
    busiest = np.bincount(routes).argmax()
    own = t[routes == busiest][:120]
    return [(int(s), int(p), None) for s, p in own] + [(int(t[0, 0]), None, None)]


def _wide_patterns(triples, svc):
    """One `sp?` lookup for each subject the busiest shard holds, at least
    DEVICE_MIN_BATCH seeds so that shard descends in one fused launch, and
    an `s??` the first batch did not cache, scattered to every shard."""
    t = triples[:, :2]
    routes = svc.plan.route_batch(t[:, 0], t[:, 1], np.full(len(t), -1))
    own = t[routes == np.bincount(routes).argmax()]
    _, first = np.unique(own[:, 0], return_index=True)
    assert len(first) >= device_rank.DEVICE_MIN_BATCH
    other = int(t[t[:, 0] != t[0, 0], 0][0])
    return [(int(s), int(p), None) for s, p in own[first]] + [(other, None, None)]


def _capture(tmp_path: Path, run):
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    return ProfileData.from_file(str(path))


def _itr_lines(profile) -> list:
    """Per host thread, its ``itr.*`` events as (start, end, name, stats)."""
    out = []
    for plane in profile.planes:
        for line in plane.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, dict(ev.stats))
                   for ev in line.events if ev.name.startswith("itr.")]
            if evs:
                out.append(sorted(evs, key=lambda e: (e[0], -e[1])))
    return out


def _parent(events, i):
    s, e = events[i][:2]
    inner = None
    for j, (ps, pe, name, _) in enumerate(events):
        if j != i and ps <= s and e <= pe and (ps, -pe) < (s, -e):
            if inner is None or ps >= inner[0]:
                inner = (ps, name)
    return None if inner is None else inner[1]


@pytest.mark.time_limit(60)
def test_span_is_one_shared_no_op_while_off(tracing_off):
    assert not tracing.enabled()
    a, b = tracing.span("itr.request"), tracing.span("itr.flush", flush=1)
    assert a is b
    with a:
        pass


@pytest.mark.time_limit(120)
def test_no_itr_event_while_off(tmp_path, monkeypatch, tracing_off):
    svc, triples = _store(monkeypatch)
    try:
        prof = _capture(tmp_path, lambda: svc.query_many(_patterns(triples, svc)))
    finally:
        svc.close()
    assert _itr_lines(prof) == []


@pytest.mark.time_limit(120)
def test_every_span_fires_nested_with_flush_ids(tmp_path, monkeypatch, tracing_off):
    svc, triples = _store(monkeypatch)
    pats, wide = _patterns(triples, svc), _wide_patterns(triples, svc)
    device_rank._program.cache_clear()  # the first device rank compiles
    device_rank._descent_program.cache_clear()  # and the first fused descent
    # the first batch's few seeds walk level by level (ranks on both sides),
    # the wide batch's descend fused
    monkeypatch.setattr(device_rank, "FUSED_MIN_BATCH", device_rank.DEVICE_MIN_BATCH)

    def run():
        tracing.enable(True)
        try:
            svc.query_many(pats)
            svc.query_many(wide)
            gc.collect()
        finally:
            tracing.enable(False)
    try:
        prof = _capture(tmp_path, run)
    finally:
        svc.close()
    lines = _itr_lines(prof)
    seen = {name for evs in lines for _, _, name, _ in evs}
    assert seen == set(PARENTS) | {"itr.gc"}
    assert any(name == "itr.gc" and st["generation"] == 2  # the forced one
               for evs in lines for _, _, name, st in evs)
    flushes = {st["flush"] for evs in lines for _, _, name, st in evs
               if name == "itr.flush"}
    assert len(flushes) == 2
    ranks, fused = set(), []
    for evs in lines:
        for i, (_, _, name, st) in enumerate(evs):
            if name == "itr.gc":
                continue
            assert _parent(evs, i) in PARENTS[name], name
            if name in POOL_SPANS:
                assert st["flush"] in flushes and "shard" in st, name
            if name == "itr.k2.rank":
                ranks.add(st["side"])
            if name == "itr.k2.fused":
                fused.append(st)
    assert ranks == {"device", "host"}
    assert fused and all(st["queries"] >= device_rank.DEVICE_MIN_BATCH
                         and st["capacity"] > 0 for st in fused)
