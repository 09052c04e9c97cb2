"""AST linter passes: dtype-parity (DP), host-sync (HS), rng-discipline (RNG).

All three passes share one per-module index (`ModuleIndex`): function ranges
and qualnames, and an intra-module name-based call graph for
x64-reachability. They are heuristic by design -- the point is to name the
*likely* parity hazards at PR time, with pragmas/suppressions (see
`pragmas.py`) carrying the justification whenever a hazard is intentional
(the documented tier boundaries, integer hash/key lanes).

Device-array dataflow is a per-scope name heuristic: a name assigned from a
``jnp.*``/``jax.*`` call, from a call whose terminal name matches
``(_traced|_jit|_jnp|_pallas)$`` or ``epoch_step``/``epoch_scan``, or from
another device name, is treated as device-resident. That is exactly the
vocabulary this repo uses for its traced entry points, which is what makes
a repo-specific linter worth having over a generic one.
"""
from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from typing import Optional

from repro.analysis.lint.findings import Finding
from repro.analysis.lint.pragmas import FilePragmas

# identifiers carrying protocol time quantities (the float64 plane)
_TIME_WORDS = ("deadline", "arriv", "release", "stamp", "owd", "clock",
               "commit", "latenc", "dies_at", "floor", "horizon",
               "watermark")
_TIME_RE = re.compile("|".join(_TIME_WORDS))

# terminal call names that produce device arrays in this repo
_DEVICE_FN_RE = re.compile(r"(_traced|_jit|_jnp|_pallas)$|^epoch_(step|scan)$")

# np.random.<attr> entries that are NOT global-state RNG use
_NP_RANDOM_OK = {"default_rng", "Generator", "SeedSequence", "PCG64",
                 "Philox", "SFC64", "BitGenerator", "RandomState"}
# (RandomState is allowed as a *type*; instantiating it seeds an owned
# generator, which is legacy but not global state.)

_HOST_CAST_FNS = {"float", "int", "bool"}
_NP_PULL_FNS = {"asarray", "array", "ascontiguousarray"}
_JAX_KEY_FNS = {"PRNGKey", "key"}
_JAX_KEY_SAFE = {"split", "fold_in", "clone"}


def _terminal_name(node: ast.AST) -> str:
    """foo -> 'foo';  a.b.c -> 'c';  anything else -> ''."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return ""


def _attr_chain(node: ast.AST) -> str:
    """a.b.c -> 'a.b.c' (or '' when not a pure name/attribute chain)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _names_in(node: ast.AST) -> set[str]:
    out = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            out.add(n.id)
        elif isinstance(n, ast.Attribute):
            out.add(n.attr)
        elif isinstance(n, ast.arg):
            out.add(n.arg)
    return out


def _mentions_time(node: ast.AST) -> bool:
    return any(_TIME_RE.search(name.lower()) for name in _names_in(node))


def _f32_marker(node: ast.AST) -> Optional[ast.AST]:
    """The float32 literal/cast node in ``node``'s subtree, if any."""
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr == "float32":
            return n
        if isinstance(n, ast.Name) and n.id == "float32":
            return n
        if isinstance(n, ast.Constant) and n.value == "float32":
            return n
    return None


# ---------------------------------------------------------------------------
# module index: function ranges, x64 reachability, span-f32 annotations
# ---------------------------------------------------------------------------
@dataclass
class FunctionInfo:
    qualname: str
    node: ast.AST
    start: int
    end: int
    params: list[str] = field(default_factory=list)
    calls: set[str] = field(default_factory=set)     # bare callee names
    has_x64: bool = False       # contains an enable_x64 usage itself
    traced: bool = False        # jit-decorated or *_traced by name
    parent: Optional[str] = None


class ModuleIndex(ast.NodeVisitor):
    """One walk collecting per-function facts for all passes."""

    def __init__(self, tree: ast.Module, pragmas: FilePragmas):
        self.functions: dict[str, FunctionInfo] = {}
        self._stack: list[str] = []
        self.visit(tree)
        self._propagate_x64()

    # -- collection ----------------------------------------------------------
    def _visit_function(self, node) -> None:
        qual = ".".join(self._stack + [node.name])
        info = FunctionInfo(
            qualname=qual, node=node, start=node.lineno,
            end=getattr(node, "end_lineno", node.lineno),
            params=[a.arg for a in (node.args.posonlyargs + node.args.args
                                    + node.args.kwonlyargs)],
            parent=self._stack[-1] if self._stack else None,
        )
        for dec in node.decorator_list:
            name = _attr_chain(dec if not isinstance(dec, ast.Call)
                               else dec.func)
            if name.split(".")[-1] == "jit":
                info.traced = True
            info.start = min(info.start, dec.lineno)
        if node.name.endswith("_traced"):
            info.traced = True
        # `enable_x64(False)` scopes switch 64-bit types OFF (the Pallas
        # wrappers trace their int32 kernels that way): not an x64 entry
        x64_off = {id(c.func) for c in ast.walk(node)
                   if isinstance(c, ast.Call)
                   and _terminal_name(c.func) == "enable_x64"
                   and c.args and isinstance(c.args[0], ast.Constant)
                   and c.args[0].value is False}
        for n in ast.walk(node):
            if isinstance(n, ast.Call):
                callee = _terminal_name(n.func)
                if callee:
                    info.calls.add(callee)
                # function references passed as arguments (vmap(f), scan(f),
                # partial(f)) are callees too for x64 reachability
                for a in list(n.args) + [k.value for k in n.keywords]:
                    ref = _terminal_name(a)
                    if ref:
                        info.calls.add(ref)
            if id(n) not in x64_off and (
                    _terminal_name(n) == "enable_x64"
                    or (isinstance(n, ast.Name) and n.id == "enable_x64")):
                info.has_x64 = True
        self.functions[qual] = info
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _visit_function
    visit_AsyncFunctionDef = _visit_function

    def visit_ClassDef(self, node) -> None:
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    # -- x64 reachability ----------------------------------------------------
    def _propagate_x64(self) -> None:
        """Safety (some enable_x64 on every intra-module path) propagates
        from functions that enter the context to their same-module callees
        by bare name, and from enclosing to nested functions."""
        by_bare: dict[str, list[FunctionInfo]] = {}
        for info in self.functions.values():
            by_bare.setdefault(info.qualname.split(".")[-1], []).append(info)
        safe = {q for q, i in self.functions.items() if i.has_x64}
        work = list(safe)
        while work:
            q = work.pop()
            info = self.functions[q]
            nested = [o for o in self.functions.values()
                      if o.qualname.startswith(q + ".")]
            callees = [c for name in info.calls
                       for c in by_bare.get(name, [])]
            for o in nested + callees:
                if o.qualname not in safe:
                    safe.add(o.qualname)
                    work.append(o.qualname)
        self.x64_safe = safe

    # -- lookup --------------------------------------------------------------
    def enclosing(self, line: int) -> Optional[FunctionInfo]:
        best = None
        for info in self.functions.values():
            if info.start <= line <= info.end:
                if best is None or info.start >= best.start:
                    best = info
        return best


# ---------------------------------------------------------------------------
# the combined per-module linter
# ---------------------------------------------------------------------------
class ModuleLinter(ast.NodeVisitor):
    """Runs DP/HS/RNG checks in one source-order walk.

    Pragma handling happens here (findings are emitted pre-suppressed with
    the pragma's justification); the suppression *file* is applied later by
    the runner.
    """

    def __init__(self, path: str, tree: ast.Module, pragmas: FilePragmas):
        self.path = path
        self.pragmas = pragmas
        self.index = ModuleIndex(tree, pragmas)
        self.findings: list[Finding] = []
        # scope stacks: device-name sets and jax-PRNG-key use counts;
        # index 0 is module scope
        self._device: list[set[str]] = [set()]
        self._keys: list[dict[str, int]] = [{}]
        self._fstack: list[FunctionInfo] = []
        self.visit(tree)
        self._dedup()

    # -- emission ------------------------------------------------------------
    def _emit(self, rule: str, node: ast.AST, message: str,
              extra: Optional[dict] = None) -> None:
        line, col = node.lineno, node.col_offset
        fn = self._fstack[-1] if self._fstack else None
        symbol = fn.qualname if fn else ""
        suppressed, justification = False, ""
        reason = self.pragmas.allows(rule, line)
        if reason is not None:
            suppressed, justification = True, reason
        self.findings.append(Finding(
            rule=rule, path=self.path, line=line, col=col, message=message,
            symbol=symbol, suppressed=suppressed,
            justification=justification, extra=extra or {}))

    def _dedup(self) -> None:
        seen, out = set(), []
        for f in self.findings:
            key = (f.rule, f.line)
            if key not in seen:
                seen.add(key)
                out.append(f)
        self.findings = sorted(out, key=lambda f: (f.line, f.rule))

    # -- scope management ----------------------------------------------------
    def _visit_function(self, node) -> None:
        qual = ".".join(
            ([self._fstack[-1].qualname] if self._fstack else [])
            + [node.name])
        info = self.index.functions.get(qual)
        if info is None:        # method: qualname includes the class
            info = self.index.enclosing(node.lineno)
        self._fstack.append(info)
        self._device.append(set(self._device[-1]))
        self._keys.append({})
        self.generic_visit(node)
        self._keys.pop()
        self._device.pop()
        self._fstack.pop()

    visit_FunctionDef = _visit_function
    visit_AsyncFunctionDef = _visit_function

    def _is_device(self, node: ast.AST) -> bool:
        dev = self._device[-1]
        for n in ast.walk(node):
            if isinstance(n, ast.Name) and n.id in dev:
                return True
            if isinstance(n, ast.Call) and self._device_call(n):
                return True
        return False

    def _device_call(self, call: ast.Call) -> bool:
        chain = _attr_chain(call.func)
        root = chain.split(".")[0] if chain else ""
        if root in ("jnp", "jax") and chain not in ("jnp", "jax"):
            return True
        name = _terminal_name(call.func)
        if name and _DEVICE_FN_RE.search(name):
            return True
        if isinstance(call.func, ast.Name) and call.func.id in self._device[-1]:
            return True
        return False

    # -- statements: dataflow + DP001-on-assign ------------------------------
    def visit_Assign(self, node) -> None:
        self.generic_visit(node)
        targets = [n.id for t in node.targets for n in ast.walk(t)
                   if isinstance(n, ast.Name)]
        if self._is_device(node.value):
            self._device[-1].update(targets)
        # jax PRNG keys: register ownership
        if isinstance(node.value, ast.Call):
            chain = _attr_chain(node.value.func)
            if chain.split(".")[-1] in _JAX_KEY_FNS and "random" in chain:
                for t in targets:
                    self._keys[-1][t] = 0
            elif chain.split(".")[-1] in _JAX_KEY_SAFE:
                for t in targets:      # key, sub = jax.random.split(key)
                    self._keys[-1][t] = 0
        # DP001: f32 literal/cast assigned into a time-valued name
        marker = _f32_marker(node.value)
        if marker is not None and (
                _mentions_time(node.value)
                or any(_TIME_RE.search(t.lower()) for t in targets)):
            self._emit("DP001", marker,
                       "float32 literal/cast on a time-valued expression")

    def visit_comprehension(self, node) -> None:
        # iterating a device value makes the comprehension target a device
        # name within the current scope (good enough for the list-comp pull
        # patterns this repo uses)
        if self._is_device(node.iter):
            self._device[-1].update(
                n.id for n in ast.walk(node.target)
                if isinstance(n, ast.Name))
        self.generic_visit(node)

    def visit_ListComp(self, node) -> None:
        for gen in node.generators:
            self.visit(gen)
        self.visit(node.elt)

    visit_SetComp = visit_ListComp
    visit_GeneratorExp = visit_ListComp

    # -- calls: DP001, HS001-003, RNG001/002 ---------------------------------
    def visit_Call(self, node) -> None:
        self.generic_visit(node)
        chain = _attr_chain(node.func)
        term = _terminal_name(node.func)

        # DP001: f32 cast with time-valued operands
        marker = _f32_marker(node)
        if marker is not None and _mentions_time(node):
            self._emit("DP001", marker,
                       "float32 literal/cast on a time-valued expression")

        # DP002: jnp compute on time quantities without enable_x64 on any
        # intra-module path
        root = chain.split(".")[0] if chain else ""
        if root == "jnp" or chain.startswith("jax.numpy"):
            fn = self._fstack[-1] if self._fstack else None
            safe = fn is not None and fn.qualname in self.index.x64_safe
            if not safe and _mentions_time(node):
                self._emit(
                    "DP002", node,
                    f"jnp op `{chain}` on time-valued operands in a "
                    "function with no enable_x64 on any intra-module path")

        # HS001: .item()
        if term == "item" and isinstance(node.func, ast.Attribute) \
                and not node.args:
            self._emit("HS001", node, ".item() device->host sync")

        # HS002: float()/int() on device values
        if isinstance(node.func, ast.Name) \
                and node.func.id in _HOST_CAST_FNS and node.args \
                and self._is_device(node.args[0]):
            self._emit("HS002", node,
                       f"{node.func.id}() on a device-array value forces "
                       "a host sync")

        # HS003: np.asarray/np.array on device values
        if root in ("np", "numpy") and term in _NP_PULL_FNS and node.args \
                and self._is_device(node.args[0]):
            self._emit("HS003", node,
                       f"np.{term}() on a device-array value forces a "
                       "device->host transfer")

        # RNG001: global numpy RNG state
        if ".random." in f".{chain}." and root in ("np", "numpy") \
                and term not in _NP_RANDOM_OK and chain.count(".") == 2:
            self._emit("RNG001", node,
                       f"global numpy RNG state `{chain}`; use an owned "
                       "np.random.Generator")

        # RNG002: PRNG key reuse without split
        if "random" in chain and term not in _JAX_KEY_SAFE:
            keys = self._keys[-1]
            for arg in node.args:
                if isinstance(arg, ast.Name) and arg.id in keys:
                    keys[arg.id] += 1
                    if keys[arg.id] > 1:
                        self._emit(
                            "RNG002", arg,
                            f"PRNG key `{arg.id}` consumed "
                            f"{keys[arg.id]} times without split")

    # -- HS004: branching on traced values inside traced functions -----------
    def _check_branch(self, node, test: ast.AST) -> None:
        fn = self._fstack[-1] if self._fstack else None
        if fn is None or not (fn.traced or (
                fn.parent and any(
                    p.traced for p in self.index.functions.values()
                    if fn.qualname.startswith(p.qualname + ".")))):
            return
        # `x is None` / `x is not None` are trace-time Python tests
        if isinstance(test, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
            return
        traced_names = set(self._device[-1])
        traced_names.update(p for p in fn.params
                            if _TIME_RE.search(p.lower()))
        hit = [n.id for n in ast.walk(test)
               if isinstance(n, ast.Name) and n.id in traced_names]
        if hit:
            self._emit("HS004", node,
                       f"Python branch on traced value `{hit[0]}` inside "
                       "jitted code")

    def visit_If(self, node) -> None:
        self._check_branch(node, node.test)
        self.generic_visit(node)

    def visit_While(self, node) -> None:
        self._check_branch(node, node.test)
        self.generic_visit(node)

    def visit_IfExp(self, node) -> None:
        self._check_branch(node, node.test)
        self.generic_visit(node)


def lint_module(path: str, source: str, pragmas: FilePragmas) -> list[Finding]:
    """All AST-pass findings for one file (pragmas applied, file
    suppressions not)."""
    tree = ast.parse(source, filename=path)
    return ModuleLinter(path, tree, pragmas).findings


__all__ = ["ModuleIndex", "ModuleLinter", "FunctionInfo", "lint_module"]
