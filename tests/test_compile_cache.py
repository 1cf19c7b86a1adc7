"""Where the persistent compilation cache goes (``repro.compile_cache``)."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def test_cache_dir_defaults_to_the_checkout(monkeypatch):
    from repro import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.cache_dir() == str(ROOT / ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.cache_dir() == "/elsewhere"


def test_enable_without_env_points_jax_at_the_checkout():
    """Without JAX_COMPILATION_CACHE_DIR, JAX's cache directory becomes
    `<checkout>/.jax_cache` (checked in a fresh process that compiles
    nothing, so the checkout's cache is left alone)."""
    code = ("import jax\n"
            "from repro.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(ROOT / ".jax_cache")] * 2


def test_cache_entries_land_in_the_env_directory(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, a rank compile writes its entry
    there (in a fresh CPU process: the cache is process-global config)."""
    code = ("import numpy as np\n"
            "from repro.compile_cache import enable_compile_cache\n"
            "from repro.core.succinct import BitVector\n"
            "from repro.core.succinct.device_rank import DeviceLevels\n"
            "print(enable_compile_cache())\n"
            "bv = BitVector(np.ones(100, np.uint8))\n"
            "assert int(DeviceLevels([bv]).rank1(0, np.arange(40))[-1]) == 39\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "PYTHONPATH": str(ROOT / "src")}
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == str(tmp_path)
    assert any("bitvec_rank" in p.name for p in tmp_path.iterdir())
