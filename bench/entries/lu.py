"""Entry for LU configurations: ``rgetrf`` + ``lu_solve`` on one system.

The window runs whole solves through the normal path: the blocked
factorization with the trailing updates under the engine's default plan
(the plan of the first, largest update; plans do not depend on shape),
then the two triangular solves.  The check is the exact normwise backward
error of a seeded sample of rows; the control is the same solve one tier
lower (the program's own path at that tier).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

import exact
from operands import LOWER
from repro import gemm
from repro.core import mp
from repro.core.linalg import lu_solve, rgetrf


class Cell:
    def __init__(self, cfg: dict, traffic: dict, ops: dict):
        self.a, self.b = ops["a"], ops["b"]
        self.n = self.a.shape[0]
        if self.n != cfg["n"]:
            raise ValueError(f"the traffic's A is {self.n} square, the "
                             f"configuration says n = {cfg['n']}")
        self.block = cfg["block"]
        self.precision = cfg["precision"]
        self.plan = self.plan_for(self.precision, np.dtype(cfg["limb_dtype"]))
        self.rows = traffic["check_rows"]

    def plan_for(self, precision: str, dtype):
        nb = self.block
        return gemm.make_plan(self.n - nb, nb, self.n - nb, dtype=dtype,
                              precision=precision)

    def solve(self, a, b, plan):
        lu, piv = rgetrf(a, block=self.block, plan=plan)
        return lu_solve(lu, piv, b)

    def call(self):
        return self.solve(self.a, self.b, self.plan)

    def describe(self) -> dict:
        p = self.plan
        return {"n": self.n, "block": self.block, "backend": p.backend,
                "blocks": [p.bm, p.bn, p.bk], "tier": p.precision,
                "limb_dtype": p.limb_dtype, "n_slices": p.n_slices,
                "full": bool(p.full), "interpret": p.interpret}

    def check(self, answers, rng) -> dict:
        """The worst normwise backward error of the sampled rows of each
        solution."""
        hi = [mp.limbs(v)[0] for v in (self.a, self.b)]
        a_norm = float(jnp.max(jnp.sum(jnp.abs(hi[0]), axis=1)))
        b_norm = float(jnp.max(jnp.abs(hi[1])))
        worst = 0.0
        for x in answers:
            rows = jnp.asarray(np.sort(rng.choice(
                self.n, size=min(self.rows, self.n), replace=False)))
            x_norm = float(jnp.max(jnp.abs(mp.limbs(x)[0])))
            errs = exact.solve_errors(
                [l[rows] for l in mp.limbs(self.a)],
                [l[:, 0] for l in mp.limbs(x)],
                [l[rows, 0] for l in mp.limbs(self.b)],
                a_norm * x_norm + b_norm)
            worst = max(worst, float(errs.max()))
        return {"solve_err": worst}

    def control(self):
        """The same solve one tier lower, in this tier's format."""
        lower = LOWER[self.precision]
        x = self.solve(mp.promote(self.a, lower), mp.promote(self.b, lower),
                       self.plan_for(lower, mp.limbs(self.a)[0].dtype))
        return mp.promote(x, self.precision)
