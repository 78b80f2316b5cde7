"""SDP via PDIPM in double vs binary128-class precision (paper §V-B).

Solves a Lovasz-theta problem (the paper's SDPLIB 'theta*' family) both
ways and prints the Table-V-style comparison: double stalls near 1e-8..
1e-12 relative gap, binary128-class pushes to ~1e-23.

    PYTHONPATH=src python examples/sdp_solver.py
"""

import time

from repro.core.sdp import solve_sdp, theta_problem
from repro.runtime import compile_cache


def main():
    compile_cache.enable()
    prob = theta_problem(8, 0.4, seed=2)
    print(f"Lovasz theta SDP: n={prob.n}, m={prob.m} constraints\n")

    rows = []
    for precision, iters in (("double", 40), ("binary128", 80)):
        t0 = time.time()
        res = solve_sdp(prob, precision=precision, max_iters=iters)
        rows.append((precision, res, time.time() - t0))

    print(f"{'':16s}{'double':>14s}{'binary128':>14s}   (paper Table V)")
    labels = [
        ("relative gap", lambda r: f"{r.relative_gap:.2e}", "1e-24 vs 1e-08"),
        ("p.feas.error", lambda r: f"{r.p_feas_err:.2e}", "1e-32 vs 1e-15"),
        ("d.feas.error", lambda r: f"{r.d_feas_err:.2e}", "1e-25 vs 1e-14"),
        ("# iterations", lambda r: str(r.iterations), "45-94 vs 17-47"),
        ("theta number", lambda r: f"{-r.primal_obj:.6f}", ""),
    ]
    for name, fn, paper in labels:
        print(f"{name:16s}{fn(rows[0][1]):>14s}{fn(rows[1][1]):>14s}   {paper}")
    print(f"{'seconds/iter':16s}"
          f"{rows[0][2] / rows[0][1].iterations:>14.2f}"
          f"{rows[1][2] / rows[1][1].iterations:>14.2f}")


if __name__ == "__main__":
    main()
