"""Pass 1 — trace-safety / host-sync (RA101-RA104).

Enforces the engine's one-readback-per-step contract over the serving hot
paths: the ONLY unannotated `jax.device_get` lives in the engine's deferred
harvest; every implicit sync — `.item()`, `float()/int()/bool()` on a device
value, `np.asarray` on a device value, `block_until_ready` — is a violation
unless explicitly waived with a pragma.

The pass is a lightweight per-function taint analysis: names assigned from
device-producing calls (jnp.*, jax.* transforms, jitted callables, the
engine's sampler helpers) are "device"; subscripts/attribute loads/arithmetic
propagate the taint; `jax.device_get` results are host values and clear it.
It is deliberately syntactic — it proves the *presence* of known sync
patterns, not their absence.
"""
from __future__ import annotations

import ast
from typing import List, Set

from repro.analysis import rules
from repro.analysis.common import (SourceFile, Violation, apply_waivers,
                                   dotted, load_files)

_SCALAR_CASTS = {"float", "int", "bool"}
_NP_TRANSFER = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
                "onp.asarray", "onp.array"}
# attribute-call suffixes on `self.` / locals that return device arrays
_DEVICE_METHOD_PREFIXES = ("_decode_run", "_prefill", "_score", "_fork",
                           "_insert", "_feed_chunk")
_DEVICE_FN_NAMES = {"sample", "token_logprob"}


def _is_device_get(call: ast.Call) -> bool:
    return dotted(call.func) in ("jax.device_get", "device_get")


def _is_jit_expr(node: ast.AST) -> bool:
    """`jax.jit(...)` (possibly keyword-heavy) producing a jitted callable."""
    return (isinstance(node, ast.Call)
            and dotted(node.func) in ("jax.jit", "functools.partial")
            and any(dotted(getattr(a, "func", None)) == "jax.jit"
                    for a in ast.walk(node) if isinstance(a, ast.Call))
            or (isinstance(node, ast.Call)
                and dotted(node.func) == "jax.jit"))


class _FnChecker(ast.NodeVisitor):
    def __init__(self, sf: SourceFile, fn: ast.FunctionDef,
                 jitted_names: Set[str], allowlisted: bool):
        self.sf = sf
        self.fn = fn
        self.jitted = jitted_names
        self.allowlisted = allowlisted
        self.taint: Set[str] = set()
        self.violations: List[Violation] = []

    # -- taint machinery -------------------------------------------------
    def _producer_call(self, call: ast.Call) -> bool:
        d = dotted(call.func)
        if not d:
            return False
        if _is_device_get(call):
            return False                      # readback: result is host
        if d.startswith("jnp.") or d.startswith("jax."):
            return True
        if d.startswith("transformer."):
            return True
        last = d.split(".")[-1]
        if last in _DEVICE_FN_NAMES or last in self.jitted:
            return True
        return any(last.startswith(p) for p in _DEVICE_METHOD_PREFIXES)

    def _tainted(self, node: ast.AST) -> bool:
        for n in ast.walk(node):
            if isinstance(n, (ast.Name, ast.Attribute)):
                if dotted(n) in self.taint:
                    return True
            elif isinstance(n, ast.Call) and self._producer_call(n):
                return True
        return False

    def _mark(self, target: ast.AST, on: bool):
        for n in ast.walk(target):
            if isinstance(n, (ast.Name, ast.Attribute)):
                d = dotted(n)
                if d:
                    (self.taint.add if on else self.taint.discard)(d)

    # -- sinks -----------------------------------------------------------
    def _report(self, node: ast.AST, code: str, msg: str):
        self.violations.append(Violation(
            file=self.sf.rel, line=node.lineno, code=code, message=msg))

    def visit_Call(self, node: ast.Call):
        d = dotted(node.func)
        if _is_device_get(node):
            if not self.allowlisted:
                self._report(node, "RA103",
                             "jax.device_get outside the sanctioned harvest "
                             f"site (in `{self.fn.name}`)")
            # arguments are read, result is host: fall through to visit args
        elif d in _SCALAR_CASTS and node.args \
                and self._tainted(node.args[0]):
            self._report(node, "RA101",
                         f"`{d}()` on a device value forces a host sync")
        elif d.endswith(".item") and isinstance(node.func, ast.Attribute) \
                and self._tainted(node.func.value):
            self._report(node, "RA101",
                         "`.item()` on a device value forces a host sync")
        elif d in _NP_TRANSFER and node.args \
                and self._tainted(node.args[0]):
            self._report(node, "RA102",
                         f"`{d}` on a device value forces a transfer")
        elif d.endswith(".block_until_ready"):
            self._report(node, "RA104",
                         "block_until_ready stalls the dispatch pipeline")
        self.generic_visit(node)

    # -- statement-order taint updates ----------------------------------
    def visit_Assign(self, node: ast.Assign):
        self.visit(node.value)
        # a readback result is a host value, also through [slices]
        root = node.value
        while isinstance(root, ast.Subscript):
            root = root.value
        clean = isinstance(root, ast.Call) and _is_device_get(root)
        on = (not clean) and self._tainted(node.value)
        for t in node.targets:
            self._mark(t, on)

    def visit_AugAssign(self, node: ast.AugAssign):
        self.visit(node.value)
        if self._tainted(node.value):
            self._mark(node.target, True)

    def visit_For(self, node: ast.For):
        self.visit(node.iter)
        if self._tainted(node.iter):
            self._mark(node.target, True)
        for stmt in node.body + node.orelse:
            self.visit(stmt)

    def _visit_comp(self, node):
        for gen in node.generators:
            self.visit(gen.iter)
            if self._tainted(gen.iter):
                self._mark(gen.target, True)
        for field in ("elt", "key", "value"):
            sub = getattr(node, field, None)
            if sub is not None:
                self.visit(sub)

    visit_ListComp = visit_SetComp = visit_DictComp = _visit_comp
    visit_GeneratorExp = _visit_comp

    def visit_FunctionDef(self, node: ast.FunctionDef):
        if node is not self.fn:
            return                             # nested defs: checked separately
        for stmt in node.body:
            self.visit(stmt)

    visit_AsyncFunctionDef = visit_FunctionDef


def _jitted_names(tree: ast.AST) -> Set[str]:
    """Names bound (module- or function-level) to jitted callables, plus
    functions decorated with @jax.jit."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and _is_jit_expr(node.value):
            for t in node.targets:
                for n in ast.walk(t):
                    if isinstance(n, (ast.Name, ast.Attribute)):
                        d = dotted(n)
                        if d:
                            names.add(d.split(".")[-1])
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if "jit" in dotted(dec) or "jit" in dotted(
                        getattr(dec, "func", ast.Pass())):
                    names.add(node.name)
    return names


def check_file(sf: SourceFile) -> List[Violation]:
    jitted = _jitted_names(sf.tree)
    out: List[Violation] = []
    for node in ast.walk(sf.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        allow = any(sf.rel.endswith(path) and node.name == fn
                    for path, fn in rules.HOST_SYNC_ALLOWLIST)
        checker = _FnChecker(sf, node, jitted, allow)
        checker.visit_FunctionDef(node)
        out.extend(checker.violations)
    return apply_waivers(sf, out)


def run(root) -> List[Violation]:
    out: List[Violation] = []
    for sf in load_files(root, rules.HOST_SYNC_SCOPE):
        out.extend(check_file(sf))
    return out
