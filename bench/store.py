#!/usr/bin/env python3
"""The store a cell serves, built once per (configuration, seed, code).

The first run of a (configuration, seed) in a checkout builds the durable
tier with ``DurableShardedService.build`` and keeps its snapshot under
``bench/.store/<config>/<seed>-<digest>/``. The digest covers every file
under ``src/repro`` and the configuration's file, so a store is never
read by code, or under settings, other than those that wrote it. Every run
then opens the snapshot with ``DurableShardedService.open`` — the store's
own restart path — and the window is served by that reopened store.

The build runs in a child process on the CPU (``JAX_PLATFORMS=cpu``),
started before the run touches JAX: the snapshot holds host arrays only,
so it is the same wherever it was built, and the process that measures
never carries the build's heap. As a script, this file is that child:

    python3 bench/store.py <root> <store_root> <config_file> <seed>
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

KEEP_STORES = 12  # per configuration; the oldest go first
READY = "READY"


def code_digest(root: Path, config: dict) -> str:
    h = hashlib.sha256(json.dumps(config, sort_keys=True).encode())
    src = Path(root) / "src" / "repro"
    for f in sorted(p for p in src.rglob("*")
                    if p.is_file() and "__pycache__" not in p.parts):
        h.update(str(f.relative_to(src)).encode())
        h.update(f.read_bytes())
    return h.hexdigest()[:16]


def store_path(root: Path, store_root: Path, config: dict, seed: int) -> Path:
    return Path(store_root) / config["name"] / f"{seed}-{code_digest(root, config)}"


def ensure_store(root: Path, store_root: Path, config_file: Path, config: dict,
                 seed: int) -> dict:
    """Build the store of (config, seed) in a child process where this
    checkout has none. Returns ``{"path", "built", "build_s"}``."""
    path = store_path(root, store_root, config, seed)
    info = {"path": path, "built": False, "build_s": 0.0}
    if (path / READY).exists():
        return info
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, str(Path(root) / "bench" / "store.py"), str(root),
         str(store_root), str(config_file), str(seed)],
        env=env, capture_output=True, text=True)
    if proc.returncode != 0 or not (path / READY).exists():
        raise RuntimeError(f"store build failed (exit {proc.returncode}):\n"
                           f"{proc.stderr[-4000:]}")
    info.update(built=True, build_s=time.perf_counter() - t)
    return info


def open_store(path: Path, config: dict):
    """The durable tier reopened from its snapshot, as a restart would."""
    from repro.persist.service import DurableShardedService

    return DurableShardedService.open(path, **config["service"]["open"])


def build(root: Path, store_root: Path, config_file: Path, seed: int) -> Path:
    """Generate the configuration's triples from `seed` and build the store."""
    from bench import spec
    from repro.persist.service import DurableShardedService

    config = json.loads(Path(config_file).read_text())
    path = store_path(root, store_root, config, seed)
    ds = config["dataset"]
    triples, n_nodes, n_preds = spec.generator(root, ds["generator"])(seed, **ds["params"])
    tmp = path.with_name(path.name + ".building")
    shutil.rmtree(tmp, ignore_errors=True)
    shutil.rmtree(path, ignore_errors=True)
    tmp.parent.mkdir(parents=True, exist_ok=True)
    svc = DurableShardedService.build(triples, n_nodes, n_preds, root=tmp,
                                      **config["service"]["build"])
    svc.close()
    (tmp / READY).write_text(json.dumps({"seed": seed, "triples": len(triples)}))
    os.replace(tmp, path)
    _prune(path.parent)
    return path


def _prune(parent: Path) -> None:
    stores = sorted((p for p in parent.iterdir() if (p / READY).exists()),
                    key=lambda p: (p / READY).stat().st_mtime)
    for old in stores[:-KEEP_STORES]:
        shutil.rmtree(old, ignore_errors=True)


if __name__ == "__main__":
    _root = Path(sys.argv[1]).resolve()
    for _p in (_root / "src", _root):
        sys.path.insert(0, str(_p))
    build(_root, Path(sys.argv[2]), Path(sys.argv[3]), int(sys.argv[4]))
