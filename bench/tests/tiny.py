"""A checkout copy of the benchmark at a size a test run holds.

``tiny_root(dest)`` copies ``BENCHMARK.json`` and ``bench/`` into `dest`,
links the repository's ``src``, divides every configuration's node and
triple counts by ``SHRINK`` and gives the CPU a row in the peaks table,
so a cell runs end to end on the CPU once the chip check is patched.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
SHRINK = 400
SEED = 2**31 + 11  # more than 32 signed bits hold: seeds of the benchmark may


def tiny_root(dest: Path) -> Path:
    dest = Path(dest)
    dest.mkdir(parents=True, exist_ok=True)
    shutil.copy(REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns(".store", ".traces", "__pycache__"))
    (dest / "src").symlink_to(REPO / "src")
    for f in (dest / "bench" / "configs").glob("*.json"):
        cfg = json.loads(f.read_text())
        params = cfg["dataset"]["params"]
        params["n_nodes"] //= SHRINK
        params["n_triples"] //= SHRINK
        f.write_text(json.dumps(cfg))
    peaks = dest / "bench" / "peaks.json"
    table = json.loads(peaks.read_text())
    table["devices"]["cpu"] = {}
    peaks.write_text(json.dumps(table))
    return dest


def on_cpu(monkeypatch) -> None:
    """Let the harness run on the CPU: its chip check returns the CPU, and
    the persistent compilation cache stays off, as the rest of the test
    process expects."""
    import jax

    import repro.compile_cache
    from bench import run
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(run, "platforms_allow_tpu", lambda: True)
    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache", lambda: "off")
