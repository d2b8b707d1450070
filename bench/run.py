"""One measured run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, limits and metrics are found by
name from ``BENCHMARK.json`` (``bench/benchlib/spec.py``).  The run makes
its weights and inputs from the seed, compiles and warms up (set-up),
measures for ``--seconds``, checks what the measured window produced
against the configuration's plain reference, and prints one JSON line as
the last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device`` and, traced, ``breakdown``; ``checks``,
each compared number beside its limit, comes last and is also printed as
the last lines of standard error.

With no TPU, or fewer chips than the cell asks for, it exits 2 and prints
no result.  ``--rehearse`` runs the same path at the reduced configuration
off the chip (Pallas in interpret mode); it prints no result and exits 3
when the run was correct, 1 when it was not.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the reduced configuration off the chip; never "
                         "prints a result")
    args = ap.parse_args(argv)
    from benchlib import harness
    return harness.main(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
