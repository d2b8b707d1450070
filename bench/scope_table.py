"""Device ms per execution of one program, by the program's own scopes,
and the program's host spans, in the trace that the last ``--trace 1`` run
of ``bench/run.py`` left.

    python3 bench/scope_table.py serve_step [prefill_step ...]
"""

import argparse
import glob
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("programs", nargs="+")
    args = ap.parse_args(argv)
    from benchlib import harness, scopes, trace
    found = glob.glob(str(harness.TRACE_DIR / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        print(f"bench: {len(found)} traces under {harness.TRACE_DIR}",
              file=sys.stderr)
        return 2
    scoped = scopes.Scoped.attach(trace.Reduced.from_xplane(found[0]),
                                  found[0])
    for program in args.programs:
        print("\n".join(scopes.table(scoped, program)))
    spans: dict = {}
    for s, e, name in scoped.program_spans:
        spans.setdefault(name, []).append((e - s) * 1e-6)
    for name, took in sorted(spans.items()):
        print(f"span {name}: {len(took)}, {sum(took) / len(took):.4f} ms "
              "each")
    return 0


if __name__ == "__main__":
    sys.exit(main())
