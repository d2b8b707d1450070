"""One run of a cell: set-up, the measured window, the check, the result.

Set-up is everything from process start to the first measured work:
making the weights, compiling, warming up.  The window measures for the
given seconds with the profiler off, or, traced, with it on for the
first ``trace_units`` units of work of the traffic file.  Then the peak
memory is read, the program's state is freed, and the check runs.
"""

from __future__ import annotations

import collections
import gc
import json
import statistics
import sys
import time

import jax

from benchlib import device, jobs, readers, spec, trace

from repro.launch import compile_cache

TRACE_DIR = spec.ROOT / ".bench_trace"
COMPILE_KEYS = ("backend_compiles", "backend_compile_s", "cache_hits",
                "cache_misses", "cache_retrieval_s")


def prepare_process():
    """The program's persistent compile cache, fixed inside the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says); every program is cached,
    however quickly it compiled, so a warm set-up compiles nothing.  Block
    plans come from an in-checkout autotune store only (absent: the tiling
    heuristic), never from a cache elsewhere on the machine."""
    import os

    from repro.core import autotune
    os.environ[autotune.DEFAULT_CACHE_ENV] = str(spec.ROOT /
                                                 ".autotune.json")
    compile_cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             rehearse: bool = False, t_start: float | None = None,
             control: bool = False) -> dict:
    """One run of ``workload``.  With ``control`` (tests and
    ``bench/readings.py`` only) the check also computes the control, the
    reference in the precision below the configuration's, and the
    control's numbers stand in for the program's in ``correct``.  Every
    number read, the program's and the control's, is under ``readings``."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = spec.load_cell(workload, rehearse=rehearse)
    chips = cell.workload["chips"]
    if rehearse:
        devs = jax.devices()[:chips]
        if len(devs) < chips:
            raise RuntimeError(f"the rehearsal of {workload} needs {chips} "
                               f"devices, JAX sees {len(devs)}")
        peaks = None
    else:
        devs = device.find_chips(chips)
        peaks = device.peaks(devs[0].device_kind)
        prepare_process()
    compile_cache.count_compiles()
    job = jobs.make_job(cell, seed, rehearse, devs)
    job.setup()
    tracer = trace.Tracer(job.trace_units if traced else 0, TRACE_DIR)
    info = job.window(seconds, tracer)
    tracer.finish()
    compiled = collections.Counter(compile_cache.COMPILE_COUNTS)
    compiled.subtract(info["compiles_at_open"])
    setup_s = info["opened"] - t_start

    reduced = tracer.reduce() if traced else None
    if reduced is not None and not rehearse and not reduced.ops:
        raise RuntimeError("the trace holds no operation of the chip")
    dev = device.describe(devs, reduced.device_times() if reduced else None)
    job.free()
    gc.collect()
    program = job.check(control=control)
    checks = program.pop("control") if control else program

    limits = cell.limits
    missing = sorted(set(limits) - set(checks))
    if missing:
        raise KeyError(f"no reading for the limits {missing}")
    # a cell with no limits yet (its readings still to be taken) is never
    # correct
    correct = (bool(limits) and info["failed"] == 0
               and all(checks[k] <= limits[k] for k in limits))

    if traced:
        ctx = readers.Context(kind=job.kind, trace=reduced, peaks=peaks,
                              unit_work=info["unit_work"],
                              units=tracer.units_done, chips=len(devs))
        metrics = {}
        for m in cell.per_layer:
            value = (spec.metric_reader(m["name"])(ctx)
                     if reduced is not None and peaks else None)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(info["end_to_end"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    out = {"correct": bool(correct), "attempted": info["attempted"],
           "failed": info["failed"], "metrics": metrics, "device": dev}
    if reduced is not None:
        out["breakdown"] = reduced.breakdown()
    out["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                     for k in sorted(limits)}
    out["units"] = info["ends"]
    out["compiles"] = {k: compiled[k] for k in COMPILE_KEYS}
    out["readings"] = dict(program=program, **({"control": checks}
                                                if control else {}))
    return out


def compiles_line(compiled: dict) -> str:
    """The program's compile counters over the measured window, as
    ``launch/compile_cache.COMPILE_COUNTS`` counts them: every program
    handed to the backend (compiled, or loaded from the persistent
    cache) and its seconds.  A warm window reads 0 throughout."""
    return "window compiles: " + ", ".join(
        f"{k} {compiled[k]:g}" for k in COMPILE_KEYS)


def main(args, t_start: float) -> int:
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), rehearse=args.rehearse,
                       t_start=t_start)
    except device.NoChip as e:
        print(f"bench: {e}; nothing measured", file=sys.stderr)
        return 2
    ends = out.pop("units")
    compiled = out.pop("compiles")
    del out["readings"]
    took = [b - a for a, b in zip(ends, ends[1:])]
    if took:
        slow = max(range(len(took)), key=took.__getitem__)
        print(f"window: {len(took)} units, median "
              f"{statistics.median(took):.4f} s, slowest {took[slow]:.4f} s "
              f"(unit {slow})", file=sys.stderr)
    print(compiles_line(compiled), file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    if args.rehearse:
        print(f"bench: rehearsal {'correct' if out['correct'] else 'NOT correct'}"
              f"; no result is reported off the chip", file=sys.stderr)
        return 3 if out["correct"] else 1
    print(json.dumps(out), flush=True)
    return 0
