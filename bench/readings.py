"""The readings that a cell's limits are set from, many seeds in one process.

    python3 bench/readings.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--faults token,half_batch] \
        [--fault-seeds 4,5,6] [--seconds 3] [--rehearse]

For each seed: one run of the cell through ``harness.run_cell`` -- its
set-up, a short window at its own load, its check -- which prints every
compared number (the lower reading is the largest over the program's
seeds).  On ``--control-seeds`` the check also runs the control -- the
reference computed with fp8 operands in the program's place -- on the
same inputs, and the control's numbers decide ``correct`` (the upper
reading is the smallest over the control's seeds).  Each fault of ``--faults``
(``benchlib/faults.py``) is planted under the timed path on
``--fault-seeds``.  One JSON line per run on standard output.  The
benchmark's own runs never run the control or a fault.
"""

import argparse
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def _ints(text):
    return [int(x) for x in text.split(",") if x]


def one(cell, seed, seconds, rehearse, control=False, fault=None):
    """One run of the cell through the harness, with the control computed
    or a fault planted; prints and returns its line."""
    import contextlib

    from benchlib import faults, harness
    planted = (faults.plant(fault, cell.traffic["job"]) if fault
               else contextlib.nullcontext())
    with planted:
        out = harness.run_cell(cell.name, seed, seconds, False,
                               rehearse=rehearse, control=control)
    line = {"workload": cell.name, "seed": seed, "fault": fault,
            "control": control, "correct": out["correct"],
            "metrics": {k: m["value"] for k, m in out["metrics"].items()},
            "memory_peak_bytes": out["device"]["memory_peak_bytes"],
            "readings": out["readings"]}
    print(json.dumps(line), flush=True)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=_ints, default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchlib import spec
    cell = spec.load_cell(args.workload)
    for seed in args.seeds:
        one(cell, seed, args.seconds, args.rehearse,
            control=seed in args.control_seeds)
    for seed in args.control_seeds:
        if seed not in args.seeds:
            one(cell, seed, args.seconds, args.rehearse, control=True)
    for fault in [f for f in args.faults.split(",") if f]:
        for seed in args.fault_seeds:
            one(cell, seed, args.seconds, args.rehearse, fault=fault)
    return 0


if __name__ == "__main__":
    sys.exit(main())
