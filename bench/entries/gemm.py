"""Entry for GEMM configurations: C = A @ B, planned and executed.

Each call of the window does what ``repro.gemm.matmul`` does for a user's
call: ``gemm.make_plan`` (the default plan, from a fresh plan cache), then
``gemm.execute`` under that plan.  The plan of the last call is kept as
``Cell.plan``: the harness checks that it is the default one, and the
kernel counts read its slices from it.  The check compares one entry from
each cell of a grid over C with the exact product; the control is the
same product one tier lower.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

import exact
from operands import LOWER
from repro import gemm
from repro.core import mp


class Cell:
    def __init__(self, cfg: dict, traffic: dict, ops: dict):
        self.a, self.b = ops["a"], ops["b"]
        self.m, self.k = self.a.shape
        self.n = self.b.shape[1]
        self.precision = cfg["precision"]
        self.dtype = np.dtype(cfg["limb_dtype"])
        self.plan = None
        self.flops = 2 * self.m * self.n * self.k  # Eq. 4, whatever runs it
        self.grid = traffic["check_grid"]

    def call(self):
        self.plan = gemm.make_plan(self.m, self.k, self.n, dtype=self.dtype,
                                   precision=self.precision)
        return gemm.execute(self.plan, self.a, self.b)

    def describe(self) -> dict:
        p = self.plan
        return {"m": self.m, "n": self.n, "k": self.k, "backend": p.backend,
                "blocks": [p.bm, p.bn, p.bk], "tier": p.precision,
                "limb_dtype": p.limb_dtype, "n_slices": p.n_slices,
                "slice_beta": p.slice_beta, "full": bool(p.full),
                "interpret": p.interpret}

    def kernel_calls(self) -> list:
        """(kernel, shape and epilogue) of each kernel launch per call."""
        return [("ozgemm", {"m": self.m, "n": self.n, "k": self.k,
                            "epilogue": "none"})]

    def check(self, answers, rng) -> dict:
        """The worst relative error of the sampled entries of each answer."""
        worst = 0.0
        for c in answers:
            rows, cols = exact.stratified_entries(rng, self.m, self.n,
                                                  self.grid)
            r, q = jnp.asarray(rows), jnp.asarray(cols)
            errs = exact.product_errors(
                [l[r] for l in mp.limbs(self.a)],
                [l[:, q] for l in mp.limbs(self.b)],
                [l[r, q] for l in mp.limbs(c)])
            worst = max(worst, float(errs.max()))
        return {"gemm_err": worst}

    def control(self):
        """The product one tier lower, in this tier's format."""
        lower = LOWER[self.precision]
        c = gemm.matmul(mp.promote(self.a, lower), mp.promote(self.b, lower))
        return mp.promote(c, self.precision)
