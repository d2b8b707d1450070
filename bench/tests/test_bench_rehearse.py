"""Each one-chip cell's rehearsal at the reduced size, Pallas in interpret
mode: the whole run goes through, comes out correct, prints no result,
and compiles nothing inside its window.  (A cell on a mesh rehearses on
forced host devices: ``test_bench_mesh.py``.)"""
import _paths  # noqa: F401

import argparse
import json
import os
import re
import subprocess
import sys

import pytest

from benchlib import harness, spec

CELLS = [w["name"] for w in json.load(open(spec.ROOT / "BENCHMARK.json"))[
    "workloads"] if w["chips"] == 1]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_and_prints_no_result(cell, capsys):
    args = argparse.Namespace(workload=cell, seed=3_000_000_019,
                              seconds=0.5, trace=0, rehearse=True)
    assert harness.main(args, 0.0) == 3
    out, err = capsys.readouterr()
    assert out.strip() == ""
    assert "check " in err and "rehearsal correct" in err
    line = re.search(r"^window compiles: .*$", err, re.M).group(0)
    assert line.startswith("window compiles: backend_compiles 0,"), line


def test_the_window_compiles_line_names_every_counter():
    line = harness.compiles_line({"backend_compiles": 2,
                                  "backend_compile_s": 1.25,
                                  "cache_hits": 1, "cache_misses": 1,
                                  "cache_retrieval_s": 0.5})
    assert line == ("window compiles: backend_compiles 2, backend_compile_s "
                    "1.25, cache_hits 1, cache_misses 1, cache_retrieval_s "
                    "0.5")


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
