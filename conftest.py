"""A time limit for every test of the repo (``tests/`` and ``bench/tests``).

Each test gets ``DEFAULT_LIMIT_S`` seconds for its setup, call and
teardown together, or the number a ``@pytest.mark.timeout(seconds)`` mark
gives it.  Two clocks hold it to that:

* ``signal.setitimer`` raises :class:`TimeLimitExceeded` in the main thread
  when the limit passes.  The test fails with its node id and the traceback
  of where it was, and the run goes on.  Python sees the signal only
  between bytecodes, so a compile stuck inside XLA does not.
* ``faulthandler.dump_traceback_later`` is the backstop for that case:
  a grace after the limit (as long as the limit, at most ``GRACE_S``) it
  writes every thread's stack to stderr and ends the process.  Under
  pytest-xdist the worker dies, xdist reports its test as failed and
  starts a new worker; without xdist the run ends there.

Both are cancelled when the test's teardown ends.

The tests also run XLA:CPU at LLVM's optimization level 1 in place of
XLA's default, 3 (``XLA_FLAGS``, set here before any backend starts, unless
the caller chose a level).  At the default level LLVM's passes took minutes
and many GB on the td and qd kernels and oracles, whose limb arithmetic
unrolls into long programs; the programs are tiny, so they run about as
fast at level 1.  The level changes the machine code: XLA:CPU contracts a
multiply feeding an add into an fma at every level, and which contractions
form depends on LLVM's passes.  The error-free transformations are built
to be exact whatever contracts (``core/efts.py``), and
``tests/test_xla_default_level.py`` runs the arithmetic's tests again at
the default level to hold them to that.  The TPU compile rehearsals
(``tests/test_tpu_compile.py``) ask for the default level themselves.
"""

import faulthandler
import os
import signal
import time

import pytest

DEFAULT_LIMIT_S = 90.0
GRACE_S = 30.0
OPT_LEVEL_FLAG = "--xla_backend_optimization_level"

if OPT_LEVEL_FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = \
        f"{os.environ.get('XLA_FLAGS', '')} {OPT_LEVEL_FLAG}=1".strip()

_deadline = None  # monotonic time at which the running test's limit passes
_stderr_fd = None  # the real stderr, whatever capture does to fd 2 later


class TimeLimitExceeded(BaseException):
    """A test ran past its time limit.

    A BaseException, so that neither hypothesis (which shrinks on
    ``Exception``) nor an ``except Exception`` in the code under test takes
    it for a failure of its own and carries on.
    """


def _limit_of(item) -> float:
    mark = item.get_closest_marker("timeout")
    if mark is None:
        return DEFAULT_LIMIT_S
    if len(mark.args) != 1 or mark.kwargs:
        raise pytest.UsageError(
            f"{item.nodeid}: use @pytest.mark.timeout(seconds)")
    return float(mark.args[0])


def pytest_configure(config):
    global _stderr_fd
    _stderr_fd = os.dup(2)


def pytest_unconfigure(config):
    os.close(_stderr_fd)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    global _deadline
    limit = _limit_of(item)
    _deadline = time.monotonic() + limit

    def on_alarm(signum, frame):
        raise TimeLimitExceeded(
            f"{item.nodeid} ran past its time limit of {limit:g} s")

    old_handler = signal.signal(signal.SIGALRM, on_alarm)
    faulthandler.dump_traceback_later(limit + min(limit, GRACE_S), exit=True,
                                      file=_stderr_fd)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old_handler)
        _deadline = None


def _alarm_during_phase():
    """Arm the alarm for what is left of the test's limit (setup, call and
    teardown share it), and only while a phase runs: outside them the
    signal would land in pytest's own reporting."""
    # a phase that starts past the deadline still gets a moment, so that
    # the teardown after a timed-out call can release what the test holds
    signal.setitimer(signal.ITIMER_REAL,
                     max(_deadline - time.monotonic(), 1.0))
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)


@pytest.hookimpl(hookwrapper=True, tryfirst=True)
def pytest_runtest_setup(item):
    yield from _alarm_during_phase()


@pytest.hookimpl(hookwrapper=True, tryfirst=True)
def pytest_runtest_call(item):
    yield from _alarm_during_phase()


@pytest.hookimpl(hookwrapper=True, tryfirst=True)
def pytest_runtest_teardown(item, nextitem):
    yield from _alarm_during_phase()
