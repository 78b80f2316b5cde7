"""Small stand-ins for the cells, for runs on the CPU."""

import run

SMALL = {
    "gemm_td32.sq4096": {"traffic": {
        "operands": [{"name": "a", "shape": [128, 128], "dist": "normal"},
                     {"name": "b", "shape": [128, 128], "dist": "normal"}],
        "check_grid": 8}},
    "lu_td32.n4096": {"config": {"n": 96, "block": 32}, "traffic": {
        "operands": [{"name": "a", "shape": [96, 96], "dist": "uniform"},
                     {"name": "b", "shape": [96, 1], "dist": "normal"}],
        "check_rows": 32}},
}


def run_small(cell: str, seed: int = 2 ** 33 + 1, seconds: float = 0.3,
              control: bool = False) -> dict:
    """A run of the cell's own harness and entry at a small size, past the
    look for a chip."""
    args = run.parse(["--workload", cell, "--seed", str(seed), "--seconds",
                      str(seconds)])
    return run.run(args, check_chip=False, overrides=SMALL[cell],
                   control=control)
