"""Faults planted underneath the timed path, to see ``correct`` come out
false: one set per entry, each a wrapper of the program function that the
entry's window calls.  ``bench/tests/test_faults.py`` plants them at small
sizes on the CPU, ``bench/calibrate.py --faults`` at the cell's size on
the chip.  A cell on one chip has no exchange between chips to leave out.
"""

from __future__ import annotations

import contextlib
import importlib

import jax.numpy as jnp


def _last_limb_dropped(ls):
    from repro.core import mp

    return mp.from_limbs(ls[:-1] + [jnp.zeros_like(ls[-1])])


def _gemm(fault, orig):
    from repro.core import mp

    if fault == "unchanged":   # the accumulator returned as it started
        return lambda plan, a, b, **kw: mp.zeros(
            (a.shape[0], b.shape[1]), mp.precision_of(a),
            dtype=mp.limbs(a)[0].dtype)
    if fault == "half":        # half of the rows left out
        return lambda plan, a, b, **kw: orig(plan, mp.map_limbs(
            lambda l: l.at[l.shape[0] // 2:].set(0), a), b, **kw)
    # altered: every answer altered where it is produced
    return lambda plan, a, b, **kw: _last_limb_dropped(
        mp.limbs(orig(plan, a, b, **kw)))


def _lu(fault, orig):
    from repro.core import mp

    if fault == "unchanged":   # the right-hand side handed back
        return lambda lu, piv, b: b
    if fault == "half":        # half of the solution left out
        return lambda lu, piv, b: mp.map_limbs(
            lambda l: l.at[l.shape[0] // 2:].set(0), orig(lu, piv, b))
    return lambda lu, piv, b: _last_limb_dropped(mp.limbs(orig(lu, piv, b)))


# entry -> (module, function the window calls, wrapper)
TARGETS = {"gemm": ("repro.gemm", "execute", _gemm),
           "lu": ("repro.core.linalg", "lu_solve", _lu)}
FAULTS = ("unchanged", "half", "altered")


@contextlib.contextmanager
def planted(entry: str, fault: str):
    """The entry's program function broken by ``fault`` while inside."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    module, attr, wrap = TARGETS[entry]
    mod = importlib.import_module(module)
    orig = getattr(mod, attr)
    setattr(mod, attr, wrap(fault, orig))
    try:
        yield
    finally:
        setattr(mod, attr, orig)
