"""The program's own names in a traced run: its scopes on the device and
its spans on the host.

The program names its work (``src/repro``): every device op traced under
``jax.named_scope`` carries the scope in its HLO ``op_name`` --
``contract.<op class>.<backend>`` around each facility dispatch, the block
scopes (``embed``, ``head``, ``block.*``) and the cost sites (``ssm.state``,
``ssm.conv``, ``kv.write``, ``weights.cast``, ``serve.handoff``, ``loss``,
``optim.adamw``).  The trainer loop writes host spans named
``repro.trainer.*`` into the profiler's trace.

The TPU's trace events do not carry ``op_name``; the compiled HLO of each
program does, and the trace holds it (the ``/host:metadata`` plane, one HLO
proto per program).  :class:`Scoped` is a :class:`trace.Reduced` that also
keeps, per chip and parallel to ``ops``, each op's innermost program scope
(``None``: unscoped), and the program spans.  An op takes its own
``op_name`` -- a fusion its root's -- except that a fusion holding a
contraction under a ``contract.*`` scope takes that scope, as the fusion
is the contraction's.  A program whose ops carry no scope reads ``None``
in every reader here.

    python3 bench/scope_table.py <program>    # after a --trace 1 run

prints the device ms per execution of one program by scope.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re

from benchlib import readers, trace

SCOPES = frozenset({
    "embed", "head", "block.ssm", "block.attn", "block.mlp", "block.norm",
    "ssm.state", "ssm.conv", "kv.write", "weights.cast", "serve.handoff",
    "loss", "optim.adamw"})
CONTRACT = "contract."
PROGRAM_SPAN = "repro."
TRAINER = "repro.trainer."
CONTRACTIONS = (b"dot", b"convolution", b"custom-call")

_WRAPPED = re.compile(r"^(?:[\w-]+\()+")


def innermost(op_name: str):
    """The innermost program scope in an HLO ``op_name``, or ``None``.  A
    scope entered under a transformation reads e.g.
    ``transpose(jvp(block.ssm))``."""
    for part in reversed(op_name.split("/")):
        name = _WRAPPED.sub("", part).rstrip(")")
        if name in SCOPES or name.startswith(CONTRACT):
            return name
    return None


# ------------------------------------------------ the trace's HLO protos

def _varint(b: bytes, i: int):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b: bytes):
    """(field number, value) of one protobuf message: ints for varints,
    bytes for length-delimited fields, fixed-width fields skipped."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, v


def _packed(b: bytes) -> list:
    out, i = [], 0
    while i < len(b):
        v, i = _varint(b, i)
        out.append(v)
    return out


def hlo_protos(path: str) -> dict:
    """{program name: serialized HloProto} from the trace's
    ``/host:metadata`` plane (XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .bytes_value = 6)."""
    with open(path, "rb") as f:
        space = f.read()
    for field, plane in _fields(space):
        if field != 1:
            continue
        if next((v for k, v in _fields(plane) if k == 2),
                None) != b"/host:metadata":
            continue
        stat_names, events = {}, []
        for k, v in _fields(plane):
            if k in (4, 5):
                entry = dict(_fields(v))
                meta = dict((a, c) for a, c in _fields(entry.get(2, b""))
                            if a != 5)
                if k == 5:
                    stat_names[meta.get(1)] = meta.get(2, b"")
                else:
                    events.append(entry.get(2, b""))
        out = {}
        for ev in events:
            name, protos = None, []
            for a, c in _fields(ev):
                if a == 2:
                    name = c.decode()
                elif a == 5:
                    stat = dict(_fields(c))
                    if stat_names.get(stat.get(1)) == b"Hlo Proto":
                        protos.append(stat.get(6, b""))
            if name and protos:
                out[name] = protos[0]
        return out
    return {}


def instruction_scopes(proto: bytes) -> dict:
    """{instruction name: innermost program scope or None} of one
    HloProto (.hlo_module = 1; HloModuleProto.computations = 3;
    HloComputationProto.name = 1, .instructions = 2, .id = 5;
    HloInstructionProto.name = 1, .opcode = 2, .metadata = 7,
    .called_computation_ids = 38; OpMetadata.op_name = 2)."""
    module = dict(_fields(proto)).get(1, b"")
    comps = {}                      # id -> [(name, opcode, scope, calls)]
    for k, comp in _fields(module):
        if k != 3:
            continue
        cid, instrs = None, []
        for a, c in _fields(comp):
            if a == 5:
                cid = c
            elif a == 2:
                name = opcode = op_name = b""
                calls = []
                for f, v in _fields(c):
                    if f == 1:
                        name = v
                    elif f == 2:
                        opcode = v
                    elif f == 7:
                        op_name = dict(_fields(v)).get(2, b"")
                    elif f == 38:
                        calls += _packed(v) if isinstance(v, bytes) else [v]
                instrs.append((name.decode(), opcode,
                               innermost(op_name.decode()), calls))
        comps[cid] = instrs
    out = {}
    for instrs in comps.values():
        for name, opcode, scope, calls in instrs:
            if opcode == b"fusion":
                inner = [s for cid in calls for _, op, s, _ in
                         comps.get(cid, ()) if op in CONTRACTIONS and s
                         and s.startswith(CONTRACT)]
                scope = inner[0] if inner else scope
            out[name] = scope
    return out


def _instruction(event_name: str) -> str:
    """``%fusion.56 = bf16[...] fusion(...)`` -> ``fusion.56``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def _base(program: str) -> str:
    return program.partition("(")[0]


# ------------------------------------------------------------ reduction

@dataclasses.dataclass
class Scoped(trace.Reduced):
    scopes: list | None = None        # per chip, parallel to ops
    program_spans: list | None = None  # [[start, end, name]]

    @classmethod
    def attach(cls, reduced: trace.Reduced, path: str) -> "Scoped | None":
        """``reduced`` (the harness's reduction of the trace at ``path``)
        with its ops' scopes and the program's spans; ``None`` where the
        trace is not the one ``reduced`` was made from."""
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        try:
            protos = hlo_protos(path)
        except (ValueError, IndexError):
            protos = {}
        tables: dict = {}

        def table(program: str, seen: set) -> dict:
            if program not in tables:
                names = ([program] if program in protos else
                         [n for n in protos if _base(n) == _base(program)])
                found = [instruction_scopes(protos[n]) for n in names]
                best = max(found, key=lambda t: len(seen & t.keys()),
                           default={})
                tables[program] = best if seen & best.keys() else {}
            return tables[program]

        scopes, spans, chip = [], [], 0
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                events, mods = [], []
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        events = [(e.start_ns, e.end_ns, e.name)
                                  for e in line.events]
                    elif line.name == "XLA Modules":
                        mods = sorted((e.start_ns, e.end_ns, e.name)
                                      for e in line.events)
                if chip >= len(reduced.ops) or [
                        (s, e) for s, e, _ in events] != [
                        (o[0], o[1]) for o in reduced.ops[chip]]:
                    return None
                starts = [m[0] for m in mods]
                owner = []
                for s, _, name in events:
                    i = bisect.bisect_right(starts, s) - 1
                    owner.append(mods[i][2] if i >= 0 and s < mods[i][1]
                                 else None)
                seen: dict = {}
                for prog, (_, _, name) in zip(owner, events):
                    seen.setdefault(prog, set()).add(_instruction(name))
                scopes.append([
                    table(prog, seen[prog]).get(_instruction(name))
                    if prog is not None else None
                    for prog, (_, _, name) in zip(owner, events)])
                chip += 1
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans.extend([e.start_ns, e.end_ns, e.name]
                                 for e in line.events
                                 if e.name.startswith(PROGRAM_SPAN))
        if chip != len(reduced.ops):
            return None
        return cls(reduced.window, reduced.ops, reduced.modules,
                   reduced.spans, scopes, sorted(spans))

    @classmethod
    def from_json(cls, d: dict) -> "Scoped":
        return cls(tuple(d["window"]), d["ops"], d["modules"], d["spans"],
                   d.get("scopes"), d.get("program_spans"))

    # ---------------------------------------------------------- reduce
    def by_scope(self, program: str):
        """({scope: device seconds}, executions): the ops of chip 0 inside
        the executions of the program whose name holds ``program``
        (:meth:`trace.Reduced.executions`), summed by innermost scope
        (``None``: unscoped); containers are left out, as their bodies'
        ops count.  An execution the trace holds only in part (the one
        running when the profiler stopped: a few microseconds and one op)
        is left out too, as one whose recorded ops fill less than half of
        it."""
        runs = self.executions(program)
        starts = [s for s, _ in runs]
        per_run: list = [{} for _ in runs]
        for (s, e, _, cls_name), scope in zip(
                self.ops[0] if self.ops else [],
                self.scopes[0] if self.scopes else []):
            i = bisect.bisect_right(starts, s) - 1
            if cls_name == "container" or i < 0 or e > runs[i][1]:
                continue
            per_run[i][scope] = per_run[i].get(scope, 0) + e - s
        whole = [t for t, (s, e) in zip(per_run, runs)
                 if 2 * sum(t.values()) >= e - s]
        out: dict = {}
        for t in whole:
            for scope, ns in t.items():
                out[scope] = out.get(scope, 0.0) + ns * 1e-9
        return out, len(whole)

    def program_idle_s(self, prefix: str, chip: int = 0) -> float | None:
        """Idle seconds of the chip in the window while a program span
        whose name starts with ``prefix`` (other than the step) was open;
        ``None`` where there is no such span."""
        lo, hi = self.window
        spans = sorted([max(s, lo), min(e, hi)] for s, e, name in
                       (self.program_spans or [])
                       if name.startswith(prefix) and
                       name != prefix + "step" and min(e, hi) > max(s, lo))
        if not spans:
            return None
        union: list = []
        for s, e in spans:
            if union and s <= union[-1][1]:
                union[-1][1] = max(union[-1][1], e)
            else:
                union.append([s, e])
        gaps, t = [], lo
        for s, e in self.busy_intervals(chip):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        return sum(max(0, min(ge, ue) - max(gs, us))
                   for gs, ge in gaps for us, ue in union) * 1e-9


def save_small(scoped: Scoped, path: str, start: int, end: int):
    """A copy of a scoped trace small enough to keep as a test fixture:
    the window cut to [start, end] ns, the spans clipped to it, and the
    ops (with their scopes) and executions that overlap it, whole."""
    d = scoped.to_json()
    d["window"] = [start, end]

    def cut(rows):
        return [[max(r[0], start), min(r[1], end), *r[2:]] for r in rows
                if r[0] < end and r[1] > start]
    d["spans"] = cut(d["spans"])
    d["program_spans"] = cut(d["program_spans"] or [])
    keep = [[i for i, o in enumerate(chip) if o[0] < end and o[1] > start]
            for chip in d["ops"]]
    d["scopes"] = [[chip[i] for i in k] for chip, k in
                   zip(d["scopes"], keep)]
    d["ops"] = [[chip[i] for i in k] for chip, k in zip(d["ops"], keep)]
    d["modules"] = [[m for m in chip if m[0] < end and m[1] > start]
                    for chip in d["modules"]]
    with open(path, "w") as f:
        json.dump(d, f)


# -------------------------------------------------------------- readers

def of(ctx) -> Scoped | None:
    """The scoped reduction of the run's trace: ``ctx.trace`` itself where
    it is one, else the harness's trace file read again and checked
    against ``ctx.trace``."""
    t = ctx.trace
    if t is None or isinstance(t, Scoped):
        return t
    from benchlib import harness
    found = glob.glob(os.path.join(str(harness.TRACE_DIR), "**",
                                   "*.xplane.pb"), recursive=True)
    return Scoped.attach(t, found[0]) if len(found) == 1 else None


def _by_scope(ctx, kind: str, program: str):
    if not readers._ready(ctx, kind):
        return None
    scoped = of(ctx)
    if scoped is None or not scoped.scopes:
        return None
    seconds, runs = scoped.by_scope(program)
    # a program that names nothing (the parent's) reads nothing
    return (seconds, runs) if runs and set(seconds) != {None} else None


def contract_ms(ctx, kind: str, program: str):
    """Device ms per execution of ``program`` in ops under a facility
    dispatch scope (``contract.*``)."""
    got = _by_scope(ctx, kind, program)
    if got is None:
        return None
    seconds, runs = got
    return 1e3 * sum(v for k, v in seconds.items()
                     if k is not None and k.startswith(CONTRACT)) / runs


def unscoped_ms(ctx, kind: str, program: str):
    """Device ms per execution of ``program`` in ops under no program
    scope."""
    got = _by_scope(ctx, kind, program)
    if got is None:
        return None
    seconds, runs = got
    return 1e3 * seconds.get(None, 0.0) / runs


def trainer_idle_ms(ctx):
    """Idle ms of chip 0 per traced step while the trainer loop was in a
    span of its own other than the step (batch, dispatch, wait, log,
    checkpoint)."""
    if not readers._ready(ctx, "train"):
        return None
    scoped = of(ctx)
    idle = scoped.program_idle_s(TRAINER) if scoped is not None else None
    return None if idle is None else 1e3 * idle / ctx.units


def table(scoped: Scoped, program: str) -> list[str]:
    """Lines of device ms per execution of ``program`` by scope, the
    largest first."""
    seconds, runs = scoped.by_scope(program)
    if not runs:
        return [f"no whole execution of {program} in the window"]
    rows = sorted(seconds.items(), key=lambda kv: -kv[1])
    total = sum(seconds.values())
    return [f"{program}: {runs} executions, {1e3 * total / runs:.4f} ms "
            "of ops each"] + [
        f"  {scope or '(unscoped)':<28} {1e3 * s / runs:10.4f} ms "
        f"{100 * s / total:6.2f}%" for scope, s in rows]
