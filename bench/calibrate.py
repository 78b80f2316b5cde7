"""Read a cell's compared numbers, its control's and its planted faults',
over many seeds in one process (one set-up's compile serves them all).

    python bench/calibrate.py --workload <cell> --seeds 11,12,13 --seconds 4 \
        [--faults]

Each seed is a whole run of the cell's timed path (operands from the seed,
a short window at the cell's own size and load, the check), followed by
the control (``Cell.control``: the reference one precision lower, in the
program's place) judged by the same limits as the program.  With
``--faults`` each fault of ``bench/faults.py`` is then planted under the
timed path for one more run of the first seed.  One JSON line per run goes
to standard output.  The limits in ``bench/configs`` are set from these
readings; ``PERF.md`` gives them.  The benchmark's own runs never run the
control or a fault.
"""

from __future__ import annotations

import argparse
import json
import sys

import faults
import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    def one(seed, control):
        return run.run(run.parse(["--workload", args.workload, "--seed",
                                  str(seed), "--seconds", str(args.seconds)]),
                       control=control)

    def values(checks):
        return {k: c["value"] for k, c in checks.items()}

    for seed in seeds:
        r = one(seed, control=True)
        print(json.dumps({
            "seed": seed, "attempted": r["attempted"],
            "correct": r["correct"], "checks": values(r["checks"]),
            "control_correct": r["control_correct"],
            "control": values(r["control_checks"]), "metrics": r["metrics"],
            "device": r["device"], "log": r["_log"]}), flush=True)
    if args.faults:
        entry = run.cell_files(run.registry(), args.workload)[1]["entry"]
        for fault in faults.FAULTS:
            with faults.planted(entry, fault):
                r = one(seeds[0], control=False)
            print(json.dumps({"fault": fault, "seed": seeds[0],
                              "attempted": r["attempted"],
                              "correct": r["correct"],
                              "checks": values(r["checks"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
