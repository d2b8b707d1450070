"""A cell on a mesh, rehearsed at the reduced size on four forced host
devices, each in a process of its own (the device count is fixed when
JAX starts): the weights are made sharded as the program lays them, the
program comes out correct, and the control and every planted fault --
the exchange between chips left out among them -- do not."""
import _paths  # noqa: F401

import json
import os
import subprocess
import sys

import pytest

from benchlib import faults, harness, spec

MESH_CELLS = [w["name"] for w in json.load(open(spec.ROOT / "BENCHMARK.json"))[
    "workloads"] if w["chips"] > 1]


def _forced(chips: int) -> dict:
    return dict(os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")


def _python(code: str, chips: int) -> str:
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=_forced(chips), timeout=600,
                       cwd=spec.ROOT)
    assert p.returncode == 0, p.stderr[-4000:]
    return p.stdout


WEIGHTS = """
import json, sys
sys.path[:0] = ["bench", "src"]
import jax
from benchlib import jobs, spec
from repro.models import model as M
from repro.parallel import api as par
cell = spec.load_cell({cell!r}, rehearse=True)
chips = cell.workload["chips"]
model = jobs.make_model(cell.config, True, jax.devices()[:chips])
w = jobs.make_weights(model, 3_000_000_041)
axes = M.param_axes(model.arch)
out = {{}}
for (path, leaf), ax in zip(jax.tree_util.tree_leaves_with_path(w),
                            jax.tree.leaves(axes, is_leaf=lambda x:
                                            isinstance(x, tuple))):
    out[jax.tree_util.keystr(path)] = {{
        "spec": str(leaf.sharding.spec),
        "want": str(par.param_spec(leaf.shape, ax, model.rules)),
        "shape": list(leaf.shape),
        "shards": sorted({{tuple(s.data.shape) for s in
                          leaf.addressable_shards}}),
        "devices": len(leaf.sharding.device_set)}}
print(json.dumps(out))
"""


@pytest.mark.parametrize("cell", MESH_CELLS)
def test_weights_are_made_sharded_as_the_program_lays_them(cell):
    chips = spec.load_cell(cell).workload["chips"]
    got = json.loads(_python(WEIGHTS.format(cell=cell), chips)
                     .strip().splitlines()[-1])
    for name, leaf in got.items():
        assert leaf["spec"] == leaf["want"], name
        assert leaf["devices"] == chips, name
    w1 = got["['layers']['mlp']['w1']"]
    # no device holds the whole weight: each holds a quarter of its columns
    assert [list(s) for s in w1["shards"]] == [
        w1["shape"][:2] + [w1["shape"][2] // chips]]


@pytest.mark.parametrize("cell", MESH_CELLS)
def test_mesh_rehearsal_program_control_and_faults(cell):
    """One process runs the cell four ways through ``bench/readings.py``:
    the program, the control (its numbers decide ``correct``), and each
    fault the cell can have planted under the timed path."""
    c = spec.load_cell(cell)
    kinds = faults.of(c)
    assert "exchange" in kinds
    p = subprocess.run(
        [sys.executable, str(spec.BENCH / "readings.py"), "--workload", cell,
         "--rehearse", "--seconds", "0.3", "--seeds", "3000000501",
         "--control-seeds", "3000000502", "--faults", ",".join(kinds),
         "--fault-seeds", "3000000503"],
        capture_output=True, text=True, env=_forced(c.workload["chips"]),
        timeout=900, cwd=spec.ROOT)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]
    by = {(x["control"], x["fault"]): x for x in lines}
    assert by[(False, None)]["correct"] is True, by[(False, None)]
    assert by[(True, None)]["correct"] is False, by[(True, None)]
    for fault in kinds:
        assert by[(False, fault)]["correct"] is False, fault
    limits = spec.load_cell(cell, rehearse=True).limits
    prog = by[(False, None)]["readings"]["program"]
    ctrl = by[(True, None)]["readings"]["control"]
    assert all(prog[k] <= limits[k] < ctrl[k] for k in limits)


@pytest.mark.parametrize("cell", MESH_CELLS)
def test_a_mesh_cell_needs_its_devices(cell, monkeypatch):
    one = harness.jax.devices()[:1]
    monkeypatch.setattr(harness.jax, "devices", lambda: one)
    with pytest.raises(RuntimeError, match="needs 4 devices"):
        harness.run_cell(cell, 1, 0.1, False, rehearse=True)


def test_a_mesh_must_span_the_cells_chips():
    bm = json.load(open(spec.ROOT / "BENCHMARK.json"))
    w = next(w for w in bm["workloads"] if w["chips"] > 1)
    w["chips"] = 1
    with pytest.raises(ValueError, match="spans 4 chips"):
        spec.load_cell(w["name"], bm)
