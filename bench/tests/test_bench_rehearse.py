"""Each cell's rehearsal at the reduced size, Pallas in interpret mode:
the whole run goes through, comes out correct, and prints no result."""
import _paths  # noqa: F401

import argparse
import json
import os
import subprocess
import sys

import pytest

from benchlib import harness, spec

CELLS = [w["name"] for w in json.load(open(spec.ROOT / "BENCHMARK.json"))[
    "workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_and_prints_no_result(cell, capsys):
    args = argparse.Namespace(workload=cell, seed=3_000_000_019,
                              seconds=0.5, trace=0, rehearse=True)
    assert harness.main(args, 0.0) == 3
    out, err = capsys.readouterr()
    assert out.strip() == ""
    assert "check " in err and "rehearsal correct" in err


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
