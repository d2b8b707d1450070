"""Finds what a cell is made of, by the names in ``BENCHMARK.json``.

    BENCHMARK.json                  the cells, metrics and bounds
    bench/configs/<config>.json     a configuration's sizes, as run, and
                                    its ``mesh`` where it spans chips
    bench/traffic/<traffic>.json    a traffic mix or job: its parameters
    bench/limits/<workload>.json    the limits that decide ``correct``
                                    (none yet: the cell is never correct);
                                    its ``rehearse`` block replaces some
                                    in a rehearsal
    bench/metrics/<metric>.py       the reader of one per-layer metric
    bench/references/<name>.py      a plain reference, named by a config

A new cell, mix or metric is new files and new entries: nothing here
changes.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    workload: dict            # the BENCHMARK.json entry
    config: dict              # bench/configs/<config>.json
    traffic: dict             # bench/traffic/<traffic>.json
    limits: dict              # bench/limits/<workload>.json
    end_to_end: list          # metric entries this cell reports
    per_layer: list

    @property
    def name(self) -> str:
        return self.workload["name"]


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, benchmark: dict | None = None, *,
              rehearse: bool = False) -> Cell:
    bm = benchmark if benchmark is not None else _json(ROOT /
                                                       "BENCHMARK.json")
    by_name = {w["name"]: w for w in bm["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(by_name)}")
    w = by_name[workload]
    configs = {c["name"]: c for c in bm["configs"]}
    config = _json(ROOT / configs[w["config"]]["file"])
    mesh = config.get("mesh")
    if mesh and math.prod(mesh.values()) != w["chips"]:
        raise ValueError(f"{workload}: the mesh {mesh} of its configuration "
                         f"spans {math.prod(mesh.values())} chips, the cell "
                         f"asks for {w['chips']}")
    path = BENCH / "limits" / f"{workload}.json"
    limits = _json(path) if path.exists() else {}
    at_size = limits.pop("rehearse", {})
    if rehearse:
        limits.update(at_size)
    return Cell(
        workload=w,
        config=config,
        traffic=_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=limits,
        end_to_end=[m for m in bm["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bm["per_layer"] if _reports(m, workload)])


def reference(name: str):
    """The plain reference module a configuration names."""
    return importlib.import_module(f"references.{name}")


def metric_reader(name: str):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
