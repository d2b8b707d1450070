"""The control -- the reference computed with fp8 operands in the
program's place -- goes through the harness's own decision and comes out
not correct, against the cell's limits file, at the reduced size.  (On the
chip, at each cell's own size, the same runs set the limits:
``bench/readings.py``, PERF.md section 2.)"""
import _paths  # noqa: F401

import json

import pytest

from benchlib import harness, spec

# one-chip cells; a mesh cell's control runs on forced host devices
# (test_bench_mesh.py)
CELLS = [w["name"] for w in json.load(open(spec.ROOT / "BENCHMARK.json"))[
    "workloads"] if w["chips"] == 1]


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_worse_than_the_program(cell):
    limits = spec.load_cell(cell, rehearse=True).limits
    out = harness.run_cell(cell, 3_000_000_031, 0.5, False, rehearse=True,
                           control=True)
    prog, ctrl = out["readings"]["program"], out["readings"]["control"]
    assert set(ctrl) == set(prog) >= set(limits)
    # the control's numbers, not the program's, are the ones judged
    assert {k: c["value"] for k, c in out["checks"].items()} == {
        k: ctrl[k] for k in limits}
    assert out["correct"] is False, out["checks"]
    assert all(prog[k] <= limits[k] for k in limits), prog
    assert [k for k in prog if ctrl[k] > 0 and ctrl[k] >= 3 * prog[k]]
