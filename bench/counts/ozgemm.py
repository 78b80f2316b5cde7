"""Operations and bytes of one launch of ``kernels/ozgemm.py``.

Operations: the kernel's slice-pair products on the matrix unit, 2·m·n·k
each, bf16 in and f32 out.  Without ``full`` a plan keeps the triangular
set of pairs, s(s+1)/2 of s slices; with it all s².  ``n_slices`` and
``full`` are read from the plan that ran.  The recombination on the vector
unit is not counted: the peak table has no peak for it.

Bytes: the grid (m/bm, n/bn, k/bk) reads each A block once per column of
C blocks and each B block once per row of C blocks, all limbs, and writes
C once; the ``full`` epilogue also reads C.
"""

from __future__ import annotations

import math

import numpy as np

# the kernel's events in a TPU trace
EVENT = r"ozgemm"


def slice_pairs(n_slices: int, full: bool) -> int:
    return n_slices * n_slices if full else n_slices * (n_slices + 1) // 2


def counts(plan, m: int, n: int, k: int, epilogue: str = "none") -> dict:
    bm, bn, bk = min(plan.bm, m), min(plan.bn, n), min(plan.bk, k)
    mp_, np_, kp = (math.ceil(x / b) * b for x, b in ((m, bm), (n, bn),
                                                      (k, bk)))
    limb = np.dtype(plan.limb_dtype).itemsize * plan.nlimbs
    reads = limb * (mp_ * kp * (np_ // bn) + kp * np_ * (mp_ // bm))
    c = limb * mp_ * np_ * (2 if epilogue == "full" else 1)
    return {"flops": 2 * m * n * k * slice_pairs(plan.n_slices,
                                                 bool(plan.full)),
            "bytes": reads + c}
