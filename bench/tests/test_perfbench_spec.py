"""BENCHMARK.json keeps the benchmark's contract, everything a cell needs
is found by name in files of its own, and a new cell, mix, kernel or
metric is added by files alone."""
import json
import re

import pytest

from bench import run, spec
from bench.tests.tiny import REPO, SEED, on_cpu, tiny_root

BENCH = spec.load_benchmark(REPO)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in BENCH["configs"]] + \
        [w["name"] for w in BENCH["workloads"]] + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]] + \
        [w["traffic"] for w in BENCH["workloads"]] + \
        [k for c in BENCH["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [x["name"] for x in BENCH[group]]
        assert len(seen) == len(set(seen)), group
    for text in [c["why"] for c in BENCH["configs"]] + [c["source"] for c in BENCH["configs"]] \
            + [w["why"] for w in BENCH["workloads"]] + [m["layer"] for m in BENCH["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_entries_have_just_the_contract_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (REPO / c["file"]).is_file()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer",
                                          "moves"}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {c["name"] for c in BENCH["configs"]} == {w["config"] for w in BENCH["workloads"]}


def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(REPO, w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert cell.per_layer, w["name"]


def test_every_layer_metric_moves_an_end_to_end_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            reported = {x["name"] for x in spec.load_cell(REPO, cell).end_to_end}
            assert m["moves"] in reported, (m["name"], cell)
    layers = {}
    for m in BENCH["per_layer"]:  # one layer, one spelling
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_every_named_file_exists():
    for w in BENCH["workloads"]:
        assert (REPO / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in BENCH["end_to_end"]:
        assert (REPO / "bench" / "end_to_end" / f"{m['name']}.py").is_file()
    for m in BENCH["per_layer"]:
        assert (REPO / "bench" / "layers" / f"{m['name']}.py").is_file()
    for c in BENCH["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert (REPO / "bench" / "generators" / f"{cfg['dataset']['generator']}.py").is_file()
    assert spec.peaks(REPO, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert spec.kernels(REPO)["bitvec_rank"]["layer"] == "k2-tree descent"


def test_no_environment_knob_of_the_program_is_named_under_bench():
    marker = "ITR" + "_"
    hits = [str(p.relative_to(REPO)) for p in (REPO / "bench").rglob("*")
            if p.is_file() and p.suffix in (".py", ".json", ".md", ".txt")
            and ".store" not in p.parts and marker in p.read_text()]
    assert hits == []


def test_a_new_cell_is_added_by_files_alone(tmp_path, monkeypatch):
    """A throwaway configuration, mix, kernel and per-layer metric, added
    as new files and BENCHMARK.json entries in a copy, run end to end."""
    on_cpu(monkeypatch)
    root = tiny_root(tmp_path / "checkout")
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "jamendo.json").read_text())
    cfg["name"] = "throwaway"
    cfg["dataset"]["params"] = {"n_nodes": 700, "n_triples": 1500, "n_preds": 9}
    (bench / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "points.json").write_text(json.dumps({
        "why": "point lookups", "loop": "open", "rate_per_s": 20, "workers": 2,
        "mix": [{"kind": "patterns", "shapes": ["spo", "?po"], "patterns": 40}],
        "warmup_requests": 4}))
    (bench / "layers" / "results_per_req.points.py").write_text(
        "def read(ctx):\n"
        "    done = ctx.completed\n"
        "    return sum(r.n_triples for r in done) / len(done) if done else None\n")
    (bench / "kernels" / "fused_descent.json").write_text(json.dumps(
        {"pattern": "fused_descent", "layer": "k2-tree descent"}))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "throwaway", "source": "https://example.org/x",
                         "file": "bench/configs/throwaway.json", "reduced": [],
                         "why": "a test"})
    b["workloads"].append({"name": "throwaway.points", "config": "throwaway",
                           "traffic": "points", "chips": 1, "why": "a test"})
    for m in b["end_to_end"]:
        if m["name"] in ("p95_ms", "p50_ms"):
            m["workloads"].append("throwaway.points")
    b["per_layer"].append({"name": "results_per_req.points", "unit": "triples",
                           "better": "higher", "source": "host_clock",
                           "layer": "service tier", "moves": "p95_ms",
                           "workloads": ["throwaway.points"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    assert "fused_descent" in spec.kernels(root)
    plain = run.run_cell(root, "throwaway.points", SEED, 1.0, False, store_root=tmp_path)
    traced = run.run_cell(root, "throwaway.points", SEED, 1.0, True, store_root=tmp_path)
    assert plain["correct"] is True and traced["correct"] is True
    assert set(plain["metrics"]) == {"p95_ms", "p50_ms", "setup_s"}
    assert traced["metrics"]["results_per_req.points"]["value"] > 0


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        spec.load_cell(REPO, "no.such.cell")
