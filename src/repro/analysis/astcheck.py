"""Import-alias-aware AST rules engine.

Replaces the grep lint blocks ``scripts/ci.sh`` carried since PR 2.  The
greps string-matched one spelling per contract (``jnp\\.dot\\(``); this
pass parses every file, resolves import aliases first, and then matches
*meaning*: ``from jax.numpy import dot as d; d(a, b)``, ``x.dot(y)``
method calls, and the ``@`` operator all resolve to the same
facility-purity finding.

Entry points:

- :func:`check_source` — lint one source string under a pretend path
  (what the test fixtures use).
- :func:`check_paths` — walk files/directories and lint each ``.py``.

Findings carry ``path:line``, the rule id, and a message; a finding is
suppressed by ``# repro: allow(<rule-id>)`` on the flagged line or the
line directly above it.
"""

from __future__ import annotations

import ast
import dataclasses
import pathlib
import re

from repro.analysis import rules

_ALLOW_RE = re.compile(r"#\s*repro:\s*allow\(([^)]*)\)")


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def module_name(relpath: str) -> str:
    """Derive the dotted module name from a (pretend or real) path.

    Anything from the ``repro`` path component onward is the module;
    ``__init__.py`` names the package itself.  Files outside a ``repro``
    tree fall back to their stem so fixtures still get *a* name.
    """
    parts = list(pathlib.PurePosixPath(relpath.replace("\\", "/")).parts)
    if parts and parts[-1].endswith(".py"):
        parts[-1] = parts[-1][:-3]
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    if "repro" in parts:
        parts = parts[parts.index("repro"):]
    return ".".join(parts[-4:]) if parts else "<string>"


def _suppressions(source: str) -> dict[int, set]:
    out: dict[int, set] = {}
    for i, text in enumerate(source.splitlines(), start=1):
        m = _ALLOW_RE.search(text)
        if m:
            out[i] = {tok.strip() for tok in m.group(1).split(",")
                      if tok.strip()}
    return out


class Checker(ast.NodeVisitor):
    """One pass over one module; collects findings for every rule."""

    def __init__(self, source: str, relpath: str):
        self.path = relpath
        self.module = module_name(relpath)
        self.is_pkg = relpath.endswith("__init__.py")
        self.is_test = any(p in ("tests", "test") for p in
                           pathlib.PurePosixPath(
                               relpath.replace("\\", "/")).parts)
        self.allow = _suppressions(source)
        self.aliases: dict[str, str] = {}
        self.findings: list[Finding] = []
        # Precomputed scoping decisions for this module.
        self.purity_sanctioned = self.module in rules.PURITY_SANCTIONED
        self.lax_sanctioned = any(
            self.module == p or self.module.startswith(p + ".")
            for p in rules.LAX_SANCTIONED_PREFIXES)
        self.no_vmap = self.module in rules.GRID_OWNS_BATCH_MODULES
        self.pack_once_lowering = self.module in rules.PACK_ONCE_LOWERING
        self.pack_once_kernel = self.module in rules.PACK_ONCE_KERNELS
        self.attn_client = (
            self.module == rules.ATTN_FORBIDDEN_PREFIX
            or self.module.startswith(rules.ATTN_FORBIDDEN_PREFIX + "."))
        self.collective_sanctioned = (
            self.module in rules.COLLECTIVE_SANCTIONED)
        self.stratum = rules.stratum_of(self.module)

    # -- plumbing ------------------------------------------------------

    def report(self, rule_id: str, node: ast.AST, message: str) -> None:
        line = getattr(node, "lineno", 0)
        for probe in (line, line - 1):
            if rule_id in self.allow.get(probe, ()):
                return
        self.findings.append(Finding(rule_id, self.path, line, message))

    def qualify(self, node: ast.AST) -> str | None:
        """Resolve an attribute chain through the alias table.

        ``jnp.dot`` -> ``jax.numpy.dot`` after ``import jax.numpy as
        jnp``.  Returns None when the chain bottoms out in something
        that is not an imported name (a local variable, a call result).
        """
        chain: list[str] = []
        while isinstance(node, ast.Attribute):
            chain.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        root = self.aliases.get(node.id)
        if root is None:
            return None
        return ".".join([root] + list(reversed(chain)))

    def _resolve_from(self, node: ast.ImportFrom) -> str:
        if not node.level:
            return node.module or ""
        base = self.module.split(".")
        strip = node.level if not self.is_pkg else node.level - 1
        if strip:
            base = base[:-strip] if strip < len(base) else []
        return ".".join(base + ([node.module] if node.module else []))

    # -- imports -------------------------------------------------------

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            local = alias.asname or alias.name.split(".")[0]
            self.aliases[local] = (alias.name if alias.asname
                                   else alias.name.split(".")[0])
            self._check_import_target(node, alias.name)
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        mod = self._resolve_from(node)
        shims = rules.DEPRECATED_SHIMS.get(mod, ())
        for alias in node.names:
            if alias.name == "*":
                continue
            target = f"{mod}.{alias.name}" if mod else alias.name
            self.aliases[alias.asname or alias.name] = target
            # facility-purity: `from jax.numpy import dot` is itself a
            # finding (the alias table then also catches every call).
            if (mod in rules.CONTRACTION_MODULES
                    and alias.name in rules.CONTRACTION_FNS
                    and not self.purity_sanctioned):
                self.report("facility-purity", node,
                            f"import of contraction `{target}` — route "
                            "through facility.contract")
            if (alias.name in shims and mod != self.module
                    and not self.is_test):
                self.report("deprecated-shim", node,
                            f"import of deprecated shim `{target}` — "
                            "call facility.contract instead")
            if (target in rules.COLLECTIVE_FNS
                    and not self.collective_sanctioned):
                self.report("collective-purity", node,
                            f"import of raw collective `{target}` — the "
                            "mesh-native dispatch surface (parallel/api, "
                            "core/lowering, runtime/pipeline) owns it")
            # The per-name candidate prefix-subsumes the module itself,
            # so `from repro.kernels import epilogue` is checked once as
            # `repro.kernels.epilogue`, not again as `repro.kernels`.
            self._check_import_target(node, target)
        if not node.names:
            self._check_import_target(node, mod)
        self.generic_visit(node)

    def _check_import_target(self, node: ast.AST, target: str) -> None:
        if not target or not target.startswith("repro"):
            return
        # attn-op-class: models never import an op-class kernel module.
        for kernel, spec in rules.OP_CLASS_KERNEL_MODULES.items():
            if self.attn_client and (target == kernel
                                     or target.startswith(kernel + ".")):
                self.report("attn-op-class", node,
                            "models must dispatch through "
                            f"facility.contract({spec}, ...), not "
                            f"import `{target}`")
        # layer-stratification over the mapped spine.
        r, t = self.stratum, rules.stratum_of(target)
        if r is None or t is None:
            return
        here = rules.STRATUM_NAMES[r]
        there = rules.STRATUM_NAMES[t]
        if t > r:
            self.report("layer-stratification", node,
                        f"upward import: {here} module imports "
                        f"`{target}` ({there})")
        elif t < r - 1:
            self.report("layer-stratification", node,
                        f"layer-skipping import: {here} module imports "
                        f"`{target}` ({there}) — go through "
                        f"{rules.STRATUM_NAMES[r - 1]}")

    # -- calls and references ------------------------------------------

    def visit_Call(self, node: ast.Call) -> None:
        q = self.qualify(node.func)
        if q is not None:
            self._check_qualified_call(node, q)
        elif isinstance(node.func, ast.Attribute):
            self._check_method_call(node, node.func.attr)
        elif isinstance(node.func, ast.Name):
            self._check_bare_call(node, node.func.id)
        self.generic_visit(node)

    def _check_qualified_call(self, node: ast.Call, q: str) -> None:
        mod, _, fn = q.rpartition(".")
        if (mod in rules.CONTRACTION_MODULES
                and fn in rules.CONTRACTION_FNS
                and not self.purity_sanctioned):
            self.report("facility-purity", node,
                        f"`{q}(...)` outside the sanctioned lowering "
                        "modules — route through facility.contract")
        if (mod in ("jax.lax", "lax") and fn in rules.LAX_CONTRACTION_FNS
                and not self.lax_sanctioned):
            self.report("lax-purity", node,
                        f"raw `{q}(...)` belongs to the lowering layer "
                        "— route through facility.contract")
        if fn in rules.DEPRECATED_SHIMS.get(mod, ()):
            if mod != self.module and not self.is_test:
                self.report("deprecated-shim", node,
                            f"call to deprecated shim `{q}` — call "
                            "facility.contract instead")
        if mod == rules.FAULT_MODULE and fn in rules.FAULT_HOOKS:
            self._check_fault_point(node, fn)
        if q in rules.COLLECTIVE_FNS and not self.collective_sanctioned:
            self.report("collective-purity", node,
                        f"raw collective `{q}(...)` outside the "
                        "mesh-native dispatch surface — annotate with "
                        "parallel.api.shard or bind the contract's mesh")
        self._check_pack_once(node, fn)

    def _check_fault_point(self, node: ast.Call, fn: str) -> None:
        # Only literal strings are checkable statically; named constants
        # (`_faults.CONTRACT_DISPATCH`) resolve to Attribute nodes and
        # validate at runtime through FaultSpec.__post_init__ anyway.
        arg = node.args[0] if node.args else next(
            (kw.value for kw in node.keywords if kw.arg == "point"), None)
        if (isinstance(arg, ast.Constant) and isinstance(arg.value, str)
                and arg.value not in rules.FAULT_POINTS):
            self.report("fault-point-literal", node,
                        f"`faults.{fn}({arg.value!r})` — not a "
                        "registered injection point; use a member of "
                        "faults.POINTS (a typo'd literal never fires)")

    def _check_method_call(self, node: ast.Call, attr: str) -> None:
        if (attr in rules.CONTRACTION_FNS and node.args
                and not self.purity_sanctioned):
            self.report("facility-purity", node,
                        f"method-call contraction `.{attr}(...)` — "
                        "route through facility.contract")
        self._check_pack_once(node, attr)

    def _check_bare_call(self, node: ast.Call, name: str) -> None:
        q = self.aliases.get(name)
        if q is not None:
            self._check_qualified_call(node, q)
        else:
            self._check_pack_once(node, name)

    def _check_pack_once(self, node: ast.Call, fn: str) -> None:
        relayout = fn in rules.RELAYOUT_FNS
        base = fn.lstrip("_")
        packish = base.startswith("unpack") or base.startswith("pack_")
        if self.pack_once_lowering and (packish or fn == "swapaxes"):
            self.report("pack-once", node,
                        f"`{fn}(...)` in the lowering dispatch path — "
                        "layout is paid once, in core/packing.py")
        elif self.pack_once_kernel and (packish or relayout):
            self.report("pack-once", node,
                        f"`{fn}(...)` inside a GEMM/conv kernel — "
                        "operands arrive pre-tiled; no per-call "
                        "relayout")

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if self.no_vmap:
            q = self.qualify(node)
            if q in rules.VMAP_NAMES:
                self.report("grid-owns-batch", node,
                            f"`{q}` in kernel dispatch — fold the batch "
                            "axis into the Pallas grid instead")
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name) -> None:
        if self.no_vmap and isinstance(node.ctx, ast.Load):
            if self.aliases.get(node.id) in rules.VMAP_NAMES:
                self.report("grid-owns-batch", node,
                            f"`{self.aliases[node.id]}` (as "
                            f"`{node.id}`) in kernel dispatch — fold "
                            "the batch axis into the Pallas grid")

    def visit_BinOp(self, node: ast.BinOp) -> None:
        if isinstance(node.op, ast.MatMult) and not self.purity_sanctioned:
            self.report("facility-purity", node,
                        "`@` matmul operator — route through "
                        "facility.contract")
        self.generic_visit(node)

    # -- defaults and excepts ------------------------------------------

    def _check_defaults(self, node) -> None:
        a = node.args
        for default in list(a.defaults) + [d for d in a.kw_defaults if d]:
            if isinstance(default, (ast.List, ast.Dict, ast.Set)):
                self.report("mutable-default-arg", default,
                            "mutable literal default argument — use "
                            "None and construct inside the body")
            elif isinstance(default, ast.Call):
                fn = default.func
                name = (fn.attr if isinstance(fn, ast.Attribute)
                        else fn.id if isinstance(fn, ast.Name) else "")
                if name not in rules.IMMUTABLE_DEFAULT_CTORS:
                    self.report("mutable-default-arg", default,
                                f"call default `{name}(...)` is "
                                "evaluated once at def time — use None "
                                "and construct inside the body")

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._check_defaults(node)
        self.generic_visit(node)

    def visit_AsyncFunctionDef(self, node) -> None:
        self._check_defaults(node)
        self.generic_visit(node)

    def visit_Lambda(self, node: ast.Lambda) -> None:
        self._check_defaults(node)
        self.generic_visit(node)

    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        names = []
        if node.type is None:
            names = [""]
        else:
            elts = (node.type.elts if isinstance(node.type, ast.Tuple)
                    else [node.type])
            for e in elts:
                if isinstance(e, ast.Name):
                    names.append(e.id)
                elif isinstance(e, ast.Attribute):
                    names.append(e.attr)
        for n in names:
            if n == "":
                self.report("overbroad-except", node,
                            "bare `except:` — catch LOWERING_ERRORS or "
                            "narrower")
            elif n in rules.OVERBROAD_EXCEPTIONS:
                self.report("overbroad-except", node,
                            f"`except {n}:` — catch LOWERING_ERRORS or "
                            "narrower")
        self.generic_visit(node)


def check_source(source: str, relpath: str) -> list[Finding]:
    """Lint one source string as if it lived at ``relpath``."""
    try:
        tree = ast.parse(source, filename=relpath)
    except SyntaxError as e:
        return [Finding("syntax-error", relpath, e.lineno or 0, str(e))]
    checker = Checker(source, relpath)
    checker.visit(tree)
    return sorted(set(checker.findings),
                  key=lambda f: (f.path, f.line, f.rule))


def iter_py_files(paths):
    for p in paths:
        path = pathlib.Path(p)
        if path.is_dir():
            yield from sorted(path.rglob("*.py"))
        elif path.suffix == ".py":
            yield path


def check_paths(paths) -> list[Finding]:
    findings: list[Finding] = []
    for f in iter_py_files(paths):
        findings.extend(check_source(f.read_text(), str(f)))
    return findings
