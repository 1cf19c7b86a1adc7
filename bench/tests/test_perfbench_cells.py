"""Every cell of BENCHMARK.json runs end to end at a tiny size on the CPU,
with and without the trace, and its result line keeps the contract."""
import json
import time

import pytest

from bench import run, spec, store, sweep
from bench.traffic import Record
from bench.tests.tiny import REPO, SEED, on_cpu, tiny_root

CELLS = [w["name"] for w in spec.load_benchmark(REPO)["workloads"]]
DEVICE_TRACE = {m["name"] for m in spec.load_benchmark(REPO)["per_layer"]
                if m["source"] == "device_trace"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(scope="module")
def stores(tmp_path_factory):
    return tmp_path_factory.mktemp("stores")


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_at_tiny_size(root, stores, cell, trace, monkeypatch):
    on_cpu(monkeypatch)
    out = run.run_cell(root, cell, SEED, 1.0, bool(trace), store_root=stores)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    c = spec.load_cell(root, cell)
    want = {m["name"] for m in (c.per_layer if trace else c.end_to_end)}
    if trace:  # the CPU has no device plane: those readers find nothing
        want -= DEVICE_TRACE
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert set(out["metrics"]) == want
    for name, m in out["metrics"].items():
        assert m["value"] > 0, name
    assert out["device"]["count"] >= 1
    json.dumps(out)  # the result line is plain JSON


def test_a_second_run_reuses_the_store(root, monkeypatch, tmp_path, capsys):
    on_cpu(monkeypatch)
    cell = CELLS[0]
    run.run_cell(root, cell, SEED, 0.5, False, store_root=tmp_path)
    assert "store: built" in capsys.readouterr().out
    run.run_cell(root, cell, SEED, 0.5, False, store_root=tmp_path)
    assert "store: reused" in capsys.readouterr().out


def test_same_seed_same_traffic(root, stores, monkeypatch):
    on_cpu(monkeypatch)
    got = []
    for _ in range(2):
        run.run_cell(root, CELLS[0], SEED, 0.5, False, store_root=stores,
                     records_out=lambda recs: got.append(
                         [(r.kind, r.due, r.patterns) for r in recs]))
    assert got[0] == got[1] and got[0]


def test_main_refuses_a_machine_without_tpu(capsys, monkeypatch):
    # JAX sees only the CPU here: no result line, a non-zero exit, and no
    # store built first
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and "leaves out the TPU" in out.err
    assert "correct" not in out.out
    # past the platform check, JAX itself finds no TPU
    monkeypatch.setattr(run, "platforms_allow_tpu", lambda: True)
    with pytest.raises(run.NoChip, match="needs a TPU"):
        run.require_chips(1)


def test_unknown_device_kind_is_an_error(root, monkeypatch, tmp_path):
    on_cpu(monkeypatch)
    table = json.loads((root / "bench" / "peaks.json").read_text())
    assert "cpu" in table["devices"]
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks(REPO, "cpu")


def test_open_loop_seeds_share_arrivals_and_shapes(root, stores, monkeypatch):
    """Every seed offers the same load in the same rhythm; the seed draws
    the data and the bound terms."""
    on_cpu(monkeypatch)
    cell = next(c for c in CELLS if spec.load_cell(root, c).traffic["loop"] == "open")
    got = []
    for seed in (SEED, SEED + 1):
        run.run_cell(root, cell, seed, 0.5, False, store_root=stores,
                     records_out=lambda recs: got.append(recs))
    a, b = got
    assert [(r.due, r.kind) for r in a] == [(r.due, r.kind) for r in b]
    assert [r.patterns for r in a] != [r.patterns for r in b]


def test_setup_leaves_out_the_store_build(root, stores, monkeypatch):
    """``setup_s`` is what a restart costs: a store build, made once per
    (configuration, seed) in a checkout, is not counted in it."""
    on_cpu(monkeypatch)
    real = store.ensure_store
    build_s = 6.0

    def slow_build(*args, **kwargs):
        info = real(*args, **kwargs)
        time.sleep(build_s)
        return dict(info, built=True, build_s=info["build_s"] + build_s)
    monkeypatch.setattr(store, "ensure_store", slow_build)
    t0 = time.perf_counter()
    out = run.run_cell(root, CELLS[0], SEED, 0.5, False, store_root=stores, t_start=t0)
    elapsed = time.perf_counter() - t0
    assert 0 < out["metrics"]["setup_s"]["value"] <= elapsed - build_s


def _records(rate: float, seconds: float, service: float, sustained: bool) -> list:
    """Requests due at `rate`; each takes `service` seconds, and when not
    `sustained` each waits behind the last, so the queue grows."""
    recs, free = [], 0.0
    for i in range(int(rate * seconds)):
        due = i / rate
        start = max(due, free) if not sustained else due
        rec = Record(i, "spo", [], due, start, start + service)
        free = rec.end
        recs.append(rec)
    return recs


@pytest.mark.parametrize("knee", ["queue_grows", "service_inflates"])
def test_sweep_stops_at_the_first_rate_not_sustained(monkeypatch, capsys, knee):
    """The knee is where the queue grows, or where requests slow each other
    so that p50 passes twice the light-load service time."""
    ran = []

    def run_cell(root, workload, seed, seconds, trace, *, traffic_overrides,
                 records_out):
        rate = traffic_overrides["rate_per_s"]
        ran.append(rate)
        if knee == "queue_grows":
            records_out(_records(rate, seconds, 0.2, sustained=rate < 6))
        else:
            records_out(_records(rate, seconds, 0.2 if rate < 6 else 0.45, sustained=True))
        return {"correct": True}
    monkeypatch.setattr(run, "run_cell", run_cell)
    assert sweep.main(["--workload", "w", "--seed", "1", "--seconds", "20",
                       "--rates", "7,4,5,6,8"]) == 0
    assert ran == [4.0, 5.0, 6.0]
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["sustained"] for x in lines[:-1]] == [True, True, knee != "queue_grows"]
    assert lines[-1]["capacity_per_s"] == 5.0 and lines[-1]["first_failed_per_s"] == 6.0
    assert lines[-1]["p50_limit_ms"] == pytest.approx(400.0)
