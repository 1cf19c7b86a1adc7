"""CPU rehearsal of ``chip_smoke.py``: the whole smoke at a tiny scale, with
the platform check patched out inside the test, plus its refusals."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_rehearsal_on_cpu(smoke, capsys, monkeypatch):
    """Build, all eight shapes, oracle sample, snapshot/reopen parity and
    device rank calls per S/O-bound shape, at scale 0.003 on XLA:CPU."""
    import jax

    from repro import compile_cache
    from repro.core.succinct import device_rank

    monkeypatch.setattr(smoke, "require_tpu", lambda: jax.devices()[0])
    monkeypatch.setattr(device_rank, "enabled", lambda: True)
    # keep this worker's JAX config free of a persistent cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "off")
    assert smoke.run(seed=0, scale=0.003) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    so_bound = [s for s in smoke.SHAPES if s[0] != "?" or s[2] != "?"]
    for p in ("pass 1", "pass 2"):
        for shape in so_bound:
            line = next(ln for ln in out if ln.startswith(f"{p} {shape}:"))
            assert "rank calls device=0 " not in line, line
    assert any(ln.startswith("compiled rank programs: ") for ln in out)
    assert not list((ROOT / ".smoke_store").glob("store_*"))


def test_chip_smoke_refuses_a_host_without_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_fails_without_the_repo(tmp_path):
    """Alone in a directory, even past the platform check, the smoke
    cannot import the store and prints no result."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    code = ("import jax, chip_smoke\n"
            "chip_smoke.require_tpu = lambda: jax.devices()[0]\n"
            "raise SystemExit(chip_smoke.main([]))\n")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "ModuleNotFoundError" in proc.stderr
    assert '"ok"' not in proc.stdout
