"""``BENCHMARK.json`` and the files it names, resolved from a checkout root.

Everything that belongs to one configuration, one traffic mix, one
per-layer metric or one device program sits in a file of its own; this
module finds each by the name ``BENCHMARK.json`` gives it.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = "bench"


class SpecError(KeyError):
    """BENCHMARK.json or a file it names lacks what a run asks for."""


@dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    chips: int
    config_name: str
    config_file: Path
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list
    per_layer: list


def load_benchmark(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, workload: str) -> Cell:
    """The cell named `workload`; KeyError names the missing piece."""
    root = Path(root)
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names unknown config {w['config']!r}")
    config_file = root / configs[w["config"]]["file"]
    config = json.loads(config_file.read_text())
    traffic = json.loads(
        (root / BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    reported = {m["name"] for m in e2e}
    # a per-layer metric without `workloads` is read wherever its end-to-end
    # metric is reported
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return Cell(workload, int(w["chips"]), w["config"], config_file, config,
                w["traffic"], traffic, e2e, layer)


def load_module(path: Path, name: str):
    """Import a reader or generator file by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_plugin_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_reader(root: Path, metric: str):
    """``read(ctx)`` of ``bench/layers/<metric>.py``."""
    return load_module(Path(root) / BENCH_DIR / "layers" / f"{metric}.py",
                       metric).read


def generator(root: Path, name: str):
    """``generate(seed, **params)`` of ``bench/generators/<name>.py``."""
    return load_module(Path(root) / BENCH_DIR / "generators" / f"{name}.py",
                       name).generate


def kernels(root: Path) -> dict:
    """Every ``bench/kernels/<name>.json``: trace-name pattern and layer."""
    out = {}
    for f in sorted((Path(root) / BENCH_DIR / "kernels").glob("*.json")):
        out[f.stem] = json.loads(f.read_text())
    return out


def peaks(root: Path, device_kind: str) -> dict:
    """Published peaks of `device_kind`; an unknown kind is an error."""
    table = json.loads((Path(root) / BENCH_DIR / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise SpecError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table["devices"][device_kind]
