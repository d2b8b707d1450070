"""The profiler's trace, reduced to what the per-layer metrics read.

A traced run records the harness's own spans (``bench.<name>``, around
prefill, hand-off, decode, client work, batch making and train steps) on
the host, and every operation the device ran.  The reduction keeps:

* the window: from the first harness span's start to the last one's end;
* per chip, the operations that ran in it, each with its class -- a
  contraction (a Pallas gemm kernel, or an XLA fusion around a dot or a
  convolution), attention (the flash-attention kernel), an exchange
  between chips (a collective), or other;
* per chip, the executions of each compiled program (XLA module);
* the harness's spans.

Busy time is the union of the operations' intervals; the idle gaps are
what is left of the window, each named by the harness span that overlaps
it most.  ``Reduced.to_json``/``from_json`` hold all of this, so the
reduction is checked against a small recorded trace without a chip.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
import shutil
import statistics

import jax

SPAN_PREFIX = "bench."

# Operations that hold other operations (their events span their bodies'):
# they count as busy time but are no operation of their own.
CONTAINERS = ("while", "conditional", "call")

# XLA's collectives; each also runs as an async pair, ``<op>-start`` and
# ``<op>-done``, whose own durations are the time the chip spends on it.
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


class Tracer:
    """Runs the profiler over the first ``units`` units of the window
    (0: never), and puts the harness's spans into its trace."""

    def __init__(self, units: int, directory):
        self.units, self.dir = units, str(directory)
        self.on = False
        self.units_done = 0
        self.path = None

    def span(self, name: str):
        if self.on:
            return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
        import contextlib
        return contextlib.nullcontext()

    def before(self, unit: int):
        if self.units and unit == 0 and not self.on and self.path is None:
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            self.on = True

    def after(self, unit: int):
        if self.on:
            self.units_done = unit + 1
            if self.units_done >= self.units:
                self.finish()

    def finish(self):
        if self.on:
            jax.profiler.stop_trace()
            self.on = False
            found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            self.path = found[0] if found else None

    def reduce(self) -> "Reduced | None":
        return None if self.path is None else Reduced.from_xplane(self.path)


def compact(text: str) -> str:
    """An operation's event name on the TPU is its HLO instruction; keep
    ``%name = type op [fusion kind]`` with the layouts dropped."""
    head, eq, rest = text.partition(" = ")
    m = re.match(r"(.*?)\s([a-z][a-z0-9_\-]*)\(", rest) if eq else None
    if not m:
        return text[:120]
    typ = re.sub(r"\{[^{}]*\}", "", m.group(1))
    typ = typ if len(typ) <= 80 else typ[:77] + "..."
    kind = re.search(r"kind=(k\w+)", rest)
    return f"{head} = {typ} {m.group(2)}" + (f" {kind.group(1)}" if kind
                                             else "")


def _collective(op: str) -> bool:
    return op.removesuffix("-start").removesuffix("-done") in COLLECTIVES


def classify(name: str) -> str:
    """'gemm', 'attention', 'collective', 'container' or 'other' for one
    operation, from its compact name: a Pallas custom call by the name the
    program gives its implementation (``_pallas_gemm_impl``,
    ``_pallas_attn_impl``), XLA's own contractions as dot or convolution
    instructions or the output fusions built around them
    (``kind=kOutput``), and a collective as its instruction or a fusion
    named for the collective at its root (``%all-gather-fusion.3``)."""
    head, _, rest = name.partition(" = ")
    words = rest.split()
    op = words[-2] if len(words) >= 2 and words[-1].startswith("k") else (
        words[-1] if words else "")
    kind = words[-1] if words and words[-1].startswith("k") else ""
    short = head.lstrip("%").lower()
    if op in CONTAINERS:
        return "container"
    if op == "custom-call":
        if "attn" in short or "flash" in short:
            return "attention"
        return "gemm" if "gemm" in short else "other"
    if _collective(op):
        return "collective"
    if op in ("dot", "convolution") or (op == "fusion" and
                                        kind == "kOutput"):
        return "gemm"
    if op == "fusion" and _collective(re.sub(r"[-.]?fusion.*", "", short)):
        return "collective"
    return "other"


@dataclasses.dataclass
class Reduced:
    window: tuple                  # (start_ns, end_ns)
    ops: list                      # per chip: [[start, end, name, class]]
    modules: list                  # per chip: [[start, end, name]]
    spans: list                    # [[start, end, name]]

    # ------------------------------------------------------------ read
    @classmethod
    def from_xplane(cls, path: str) -> "Reduced":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        ops, modules, spans = [], [], []
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                chip_ops, chip_mods = [], []
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        for e in line.events:
                            name = compact(e.name)
                            chip_ops.append([e.start_ns, e.end_ns, name,
                                             classify(name)])
                    elif line.name == "XLA Modules":
                        chip_mods.extend([e.start_ns, e.end_ns, e.name]
                                         for e in line.events)
                ops.append(chip_ops)
                modules.append(chip_mods)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans.extend([e.start_ns, e.end_ns,
                                  e.name[len(SPAN_PREFIX):]]
                                 for e in line.events
                                 if e.name.startswith(SPAN_PREFIX))
        if not spans:
            raise ValueError(f"no harness spans in {path}")
        window = (min(s[0] for s in spans), max(s[1] for s in spans))
        return cls(window, ops, modules, sorted(spans))

    @classmethod
    def from_json(cls, d: dict) -> "Reduced":
        return cls(tuple(d["window"]), d["ops"], d["modules"], d["spans"])

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    # ---------------------------------------------------------- reduce
    def _clip(self, start, end):
        lo, hi = self.window
        return max(start, lo), min(end, hi)

    def busy_intervals(self, chip: int) -> list:
        """The union of the chip's operation intervals, in the window."""
        out = []
        for s, e, _, _ in sorted(self.ops[chip] if self.ops else []):
            s, e = self._clip(s, e)
            if e <= s:
                continue
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return out

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips."""
        per = [sum(e - s for s, e in self.busy_intervals(c))
               for c in range(len(self.ops))]
        return sum(per) / max(len(per), 1) * 1e-9

    def device_times(self) -> dict:
        return {"busy_s": self.busy_s(), "window_s": self.window_s()}

    def class_seconds(self, cls_name: str) -> float:
        """Device seconds of one class of operation, averaged over chips."""
        per = []
        for chip_ops in self.ops:
            t = 0
            for s, e, _, c in chip_ops:
                if c == cls_name:
                    s, e = self._clip(s, e)
                    t += max(e - s, 0)
            per.append(t)
        return sum(per) / max(len(per), 1) * 1e-9

    def module_seconds(self, key: str) -> list:
        """Durations of the executions of the programs whose name holds
        ``key``, inside the window, on chip 0.  An execution that the
        trace's end cut off reads 0 and is left out."""
        lo, hi = self.window
        return [(e - s) * 1e-9 for s, e, name in
                (self.modules[0] if self.modules else [])
                if key in name and lo <= s < e <= hi]

    def executions(self, key: str) -> list:
        """[(start, end)] of the executions, on chip 0, of the programs
        whose name holds ``key`` that ran whole inside the window.  The
        one the profiler's stop cut short is left out: its recorded span
        is under half the median execution's."""
        lo, hi = self.window
        runs = sorted((s, e) for s, e, name in
                      (self.modules[0] if self.modules else [])
                      if key in name and lo <= s < e <= hi)
        if runs:
            half = statistics.median(e - s for s, e in runs) / 2
            runs = [(s, e) for s, e in runs if e - s >= half]
        return runs

    def class_seconds_per_run(self, key: str, cls_name: str) -> list:
        """Device seconds of the operations of one class, on chip 0, in
        each of :meth:`executions`; an execution whose recorded ops fill
        less than half of it is left out, as the trace holds it only in
        part."""
        runs = self.executions(key)
        starts = [s for s, _ in runs]
        filled, per = [0] * len(runs), [0] * len(runs)
        for s, e, _, c in (self.ops[0] if self.ops else []):
            i = bisect.bisect_right(starts, s) - 1
            if c == "container" or i < 0 or e > runs[i][1]:
                continue
            filled[i] += e - s
            if c == cls_name:
                per[i] += e - s
        return [t * 1e-9 for t, f, (s, e) in zip(per, filled, runs)
                if 2 * f >= e - s]

    def idle_gaps(self, chip: int = 0) -> list:
        """[(seconds, span name)] of every gap between busy intervals in
        the window, named by the harness span that overlaps it most."""
        lo, hi = self.window
        edges, t = [], lo
        for s, e in self.busy_intervals(chip):
            if s > t:
                edges.append((t, s))
            t = max(t, e)
        if t < hi:
            edges.append((t, hi))
        out = []
        for s, e in edges:
            best, who = 0, "none"
            for ss, se, name in self.spans:
                ov = min(e, se) - max(s, ss)
                if ov > best:
                    best, who = ov, name
            out.append(((e - s) * 1e-9, who))
        return out

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (summed by name, on
        chip 0), and the longest idle gaps with what the host was doing."""
        by_name: dict = {}
        for s, e, name, c in (self.ops[0] if self.ops else []):
            s, e = self._clip(s, e)
            if e > s and c != "container":
                by_name[name] = by_name.get(name, 0) + (e - s) * 1e-9
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(), key=lambda g: -g[0])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[who, s] for s, who in gaps]}


def save_small(reduced: Reduced, path: str, spans: int):
    """A copy of a reduced trace small enough to keep as a test fixture:
    its first ``spans`` harness spans, and what ran on the chips until the
    last of them ended."""
    d = reduced.to_json()
    d["spans"] = d["spans"][:spans]
    lo, hi = d["spans"][0][0], max(e for _, e, _ in d["spans"])
    d["window"] = [lo, hi]
    d["ops"] = [[o for o in chip if o[0] < hi and o[1] > lo]
                for chip in d["ops"]]
    d["modules"] = [[m for m in chip if m[0] < hi and m[1] > lo]
                    for chip in d["modules"]]
    with open(path, "w") as f:
        json.dump(d, f)
