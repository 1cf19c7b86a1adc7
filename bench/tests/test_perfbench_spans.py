"""The reduction of the program's ``itr.*`` spans (``bench/spans.py``):
totals, self times and counts clipped to the window and summed over host
threads, and idle gaps named by the innermost span that covers them."""
from pathlib import Path

import pytest

from bench import spans, spec, trace_reduce
from bench.tests.tiny import REPO

HERE = Path(__file__).resolve().parent
NS = 1e-9


def _from_text(path: Path):
    from jax.profiler import ProfileData

    text = "\n".join(l for l in path.read_text().splitlines() if not l.startswith("#"))
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


@pytest.mark.time_limit(60)
def test_span_table_clips_to_the_window_and_sums_threads():
    t = spans.span_table(_from_text(HERE / "small_trace_spans.pbtxt"))
    # itr.wait.rwlock [700, 900) lies wholly before the window [1000, 11000)
    assert set(t) == {"itr.request", "itr.flush", "itr.route", "itr.wait.shards",
                      "itr.tuples", "itr.shard.exec", "itr.engine.seed",
                      "itr.k2.descent", "itr.k2.rank", "itr.engine.frontier"}
    want = {  # name: (total ns, self ns, spans that ended in the window)
        "itr.request": (8400, 300, 1),      # [1000, 9400); self [8000, 8200), [9300, 9400)
        "itr.flush": (7000, 1300, 1),       # self [1000, 1800), [7500, 8000)
        "itr.route": (1200, 1200, 1),
        "itr.wait.shards": (4500, 4500, 1),
        "itr.tuples": (1100, 1100, 1),
        "itr.shard.exec": (6900, 1200, 1),  # 6700 + 200 clipped; the second ends past it
        "itr.engine.seed": (2000, 200, 1),
        "itr.k2.descent": (1800, 1300, 1),
        "itr.k2.rank": (500, 500, 1),
        "itr.engine.frontier": (3700, 3700, 1),
    }
    for name, (total, own, count) in want.items():
        assert t[name]["total_s"] == pytest.approx(total * NS), name
        assert t[name]["self_s"] == pytest.approx(own * NS), name
        assert t[name]["count"] == count, name
    assert t["itr.request"]["median_self_share"] == pytest.approx(300 / 8400)
    assert t["itr.shard.exec"]["median_self_share"] == pytest.approx((1000 / 6700 + 1) / 2)


@pytest.mark.time_limit(60)
def test_idle_gaps_are_named_by_the_innermost_span():
    gaps = spans.name_idle_gaps(_from_text(HERE / "small_trace_spans.pbtxt"))
    # [4600, 10000): frontier 3700 ns of it against wait.shards' 2900;
    # [1000, 4000): route 1200 against wait.shards 1000 and flush 800;
    # [10700, 11000): the second shard.exec; [10500, 10600): no itr span
    assert gaps == [["itr.engine.frontier", pytest.approx(5400 * NS)],
                    ["itr.route", pytest.approx(3000 * NS)],
                    ["itr.shard.exec", pytest.approx(300 * NS)],
                    ["bench.request.?p?", pytest.approx(100 * NS)]]


@pytest.mark.time_limit(120)
@pytest.mark.parametrize("trace", ["small_trace.pbtxt", "recorded_lookup_trace.pbtxt"])
def test_without_itr_spans_gaps_are_named_as_trace_reduce_names_them(trace):
    prof = _from_text(HERE / trace)
    assert spans.span_table(prof) == {}
    want = trace_reduce.reduce(prof, spec.kernels(REPO)).idle_gaps
    got = spans.name_idle_gaps(prof)
    assert [n for n, _ in got] == [n for n, _ in want]
    assert [s for _, s in got] == pytest.approx([s for _, s in want])


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    from bench.tests.tiny import tiny_root

    return tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.time_limit(600)
@pytest.mark.parametrize("cell", ["jamendo.lookup", "jamendo.scan"])
def test_a_cell_run_with_spans_reads_every_layer(root, tmp_path, cell, monkeypatch):
    """At a tiny size on the CPU: the window's spans cover the service
    tier, the engine and (in the lookup cell) the descent, the program's
    tracing is off again afterwards, and the answers stay correct."""
    from bench.tests.tiny import SEED, on_cpu
    from repro import tracing

    on_cpu(monkeypatch)
    out = spans.run(root, cell, SEED, 1.0, True, True, store_root=tmp_path)
    assert out["correct"] is True and out["completed"] > 0
    assert not tracing.enabled()
    t = out["spans"]
    want = {"itr.request", "itr.flush", "itr.wait.rwlock", "itr.route", "itr.merge",
            "itr.tuples", "itr.wait.engine", "itr.shard.exec", "itr.engine.frontier"}
    if cell == "jamendo.lookup":
        want |= {"itr.engine.seed", "itr.k2.descent", "itr.k2.rank"}
        n = out["rank_positions"]
        assert n["host.asked"] == n["host.launched"] > 0
    assert want <= set(t)
    for name in want:
        assert t[name]["total_s"] > 0 and t[name]["count"] > 0, name
    assert out["span_idle_gaps"] == []  # the CPU has no device plane
    assert out["p50_ms"] > 0 and out["service_ms"] > 0
