"""The limb arithmetic compiled at XLA:CPU's default LLVM level.

The root ``conftest.py`` compiles every other test at LLVM level 1, which
keeps the td and qd programs cheap to compile.  Level 1 is not what a user
of XLA:CPU gets: XLA's default is level 3.  XLA:CPU contracts a multiply
feeding an add into an fma at any level, and which contractions form
depends on LLVM's passes, which differ between levels.  The error-free
transformations are built to be exact whatever contracts
(``core/efts.py``); these cases hold them to that at the default level by
running the arithmetic's test files again in a subprocess there.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
OPT_LEVEL_FLAG = "--xla_backend_optimization_level"
XLA_DEFAULT_OPT_LEVEL = 3


def _env(level):
    """This process's environment with the LLVM level ``level`` (None: no
    level given, so XLA's own default) in place of the caller's."""
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith(OPT_LEVEL_FLAG)]
    if level is not None:
        flags.append(f"{OPT_LEVEL_FLAG}={level}")
    path = os.pathsep.join(
        p for p in (str(ROOT / "src"), os.environ.get("PYTHONPATH")) if p)
    return dict(os.environ, XLA_FLAGS=" ".join(flags), PYTHONPATH=path)


def test_xla_default_level_is_three():
    """The level given below is XLA's own default, read where no
    ``XLA_FLAGS`` level is set."""
    r = subprocess.run(
        [sys.executable, "-c",
         "from jax._src.lib import xla_client as xc; print(xc.CompileOptions()"
         ".executable_build_options.debug_options"
         ".xla_backend_optimization_level)"],
        env=_env(None), capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert int(r.stdout.split()[-1]) == XLA_DEFAULT_OPT_LEVEL


@pytest.mark.parametrize("name", ["efts", "dd", "td", "qd"])
def test_arithmetic_exact_at_xla_default_level(name):
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", f"tests/test_{name}.py"],
        cwd=ROOT, env=_env(XLA_DEFAULT_OPT_LEVEL), capture_output=True,
        text=True, timeout=80)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out
    assert " passed" in out and " failed" not in out, out
