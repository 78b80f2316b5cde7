"""The chip entry points, off the chip.

``chip_smoke.py`` must refuse to report success without a TPU or without
the package beside it, and JAX's persistent compilation cache must land
where ``JAX_COMPILATION_CACHE_DIR`` says, else in ``<checkout>/.jax_cache``.
"""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax

from repro.runtime import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _run(args, cwd, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=120)


def test_chip_smoke_fails_without_a_tpu():
    r = _run(["chip_smoke.py"], ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_chip_smoke_fails_alone(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = _run(["chip_smoke.py"], tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_compile_cache_defaults_to_checkout_dir(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_entries_land_in_env_dir(tmp_path):
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.runtime import compile_cache\n"
        "print(compile_cache.enable())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n")
    r = _run(["-c", code], ROOT, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
             PYTHONPATH=str(ROOT / "src"))
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir())
