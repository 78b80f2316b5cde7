"""The per-test time limit of the root ``conftest.py``, run on itself.

Each case runs pytest in a subprocess on a two-test file beside a copy of
that conftest: the first test runs past ``timeout(1)``, the second passes.
"""

import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SLEEPS = """
import time
import pytest

@pytest.mark.timeout(1)
def test_sleeps_past_its_limit():
    time.sleep(30)

def test_after_it():
    pass
"""

# SIGALRM blocked: the alarm cannot interrupt the sleep, as it cannot a
# compile stuck in C++, so only the faulthandler backstop ends it
STUCK = """
import signal
import time
import pytest

@pytest.mark.timeout(1)
def test_sleeps_past_its_limit():
    signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
    time.sleep(30)

def test_after_it():
    pass
"""


def _pytest(tmp_path, source, *args):
    shutil.copy(ROOT / "conftest.py", tmp_path)
    (tmp_path / "test_limit.py").write_text(source)
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-rA", *args, "test_limit.py"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60)


@pytest.mark.parametrize("workers", [[], ["-p", "xdist", "-n", "2"]],
                         ids=["serial", "xdist"])
def test_sleeping_test_fails_by_name_and_the_run_goes_on(tmp_path, workers):
    r = _pytest(tmp_path, SLEEPS, *workers)
    out = r.stdout + r.stderr
    assert r.returncode == 1, out
    assert "FAILED test_limit.py::test_sleeps_past_its_limit" in out, out
    assert ("test_limit.py::test_sleeps_past_its_limit ran past its time "
            "limit of 1 s") in out, out
    assert "PASSED test_limit.py::test_after_it" in out, out
    assert "1 failed, 1 passed" in out, out


def test_backstop_ends_a_stuck_worker_and_xdist_replaces_it(tmp_path):
    # one worker: the test after the stuck one can pass only on its
    # replacement; "-v" undoes "-q", under which xdist does not say so
    r = _pytest(tmp_path, STUCK, "-v", "-p", "xdist", "-n", "1")
    out = r.stdout + r.stderr
    assert r.returncode == 1, out
    assert "test_limit.py::test_sleeps_past_its_limit" in out, out
    assert "replacing crashed worker gw0" in out, out
    assert "PASSED test_limit.py::test_after_it" in out, out
