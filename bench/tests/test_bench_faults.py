"""With the timed path broken underneath, the check comes out not
correct: once for each fault each one-chip cell can have.  (A mesh
cell's faults, the exchange between chips among them, are planted on
forced host devices: ``test_bench_mesh.py``.)"""
import _paths  # noqa: F401

import json

import pytest

from benchlib import faults, harness, spec

CELLS = {w["name"]: w for w in json.load(open(spec.ROOT / "BENCHMARK.json"))[
    "workloads"] if w["chips"] == 1}
CASES = [(name, fault) for name in CELLS
         for fault in faults.of(spec.load_cell(name))]


@pytest.mark.parametrize("cell,fault", CASES,
                         ids=[f"{c}-{f}" for c, f in CASES])
def test_fault_is_not_correct(cell, fault):
    kind = spec.load_cell(cell).traffic["job"]
    with faults.plant(fault, kind):
        out = harness.run_cell(cell, 3_000_000_021, 0.5, False,
                               rehearse=True)
    assert out["correct"] is False, out["checks"]
