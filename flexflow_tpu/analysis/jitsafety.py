"""jit-discipline: host-Python constructs that break the fixed-shape
single-program contract inside jit-traced function bodies.

The whole serving design (SURVEY.md §2.2) rests on ONE fixed-shape
decode/verify program and zero steady-state retraces — the retrace tests
and the benchmark's ``correct`` measure that invariant, this rule prevents
the code shapes that violate it from landing at all.

Which functions are "jitted": a function is in scope when it

* contains a ``...note_trace(...)`` call (the engine's traced bodies
  self-register in the ProgramRegistry from INSIDE the trace), or
* is passed by name to ``<registry>.instrument(name, fn)`` (the
  executor's train/eval/forward programs), or
* is referenced by name in a ``jax.jit(...)`` call or decorated with
  ``jax.jit`` / ``partial(jax.jit, ...)``.

Inside such a function the rule flags:

* ``.item()`` — host sync (and a concretization error at trace time),
* ``int(x)`` / ``float(x)`` / ``bool(x)`` on a traced value — host
  concretization; per-value retraces if hoisted to a static,
* ``np.*``/``numpy.*`` calls — host numpy inside a traced body forces
  materialization; use ``jnp``/``jax.lax``,
* ``if``/``while`` on a traced value — Python control flow on tensors
  is a trace-time concretization error (or a retrace per branch when
  fed via a static),
* ``for`` iterating a traced value — unrolls or syncs.

"Traced value" is a lexical taint: the function's parameters, spread
through assignments — except through ``.shape``/``.dtype``/``.ndim``/
``len()``, which yield static Python values at trace time (bucketed
shapes are the engine's dispatch keys and are fine to branch on).
"""
from __future__ import annotations

import ast
from typing import List, Optional, Set

from .core import Context, Finding, Rule, SourceFile, attr_chain, call_name

_STATIC_ATTRS = {"shape", "dtype", "ndim", "size"}
_CONCRETIZERS = {"int", "float", "bool", "len"}
_NP_ROOTS = {"np", "numpy"}


def _param_names(args: ast.arguments) -> Set[str]:
    """EVERY parameter name: positional-only, positional, keyword-only,
    *args, **kwargs — all are traced values inside a jitted body."""
    names = {a.arg for a in args.posonlyargs + args.args + args.kwonlyargs}
    if args.vararg is not None:
        names.add(args.vararg.arg)
    if args.kwarg is not None:
        names.add(args.kwarg.arg)
    return names


def _jit_function_names(tree: ast.AST) -> Set[str]:
    """Names of functions registered for jit elsewhere in the module:
    ``reg.instrument("prog", fn)`` second args and ``jax.jit(fn)`` /
    ``jax.jit(self.fn)`` arguments."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        cn = call_name(node)
        if cn == "instrument" and len(node.args) >= 2:
            target = node.args[1]
            if isinstance(target, ast.Name):
                names.add(target.id)
            elif isinstance(target, ast.Attribute):
                names.add(target.attr)
        elif cn == "jit" and attr_chain(node.func) in ("jax.jit", "jit"):
            for target in node.args[:1]:
                if isinstance(target, ast.Name):
                    names.add(target.id)
                elif isinstance(target, ast.Attribute):
                    names.add(target.attr)
    return names


def _has_note_trace(fn: ast.AST) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call) and call_name(node) == "note_trace":
            return True
    return False


def _jit_decorated(fn) -> bool:
    for dec in fn.decorator_list:
        chain = attr_chain(dec)
        if chain in ("jax.jit", "jit"):
            return True
        if isinstance(dec, ast.Call):
            if attr_chain(dec.func) in ("jax.jit", "jit"):
                return True
            if attr_chain(dec.func) in ("partial", "functools.partial"):
                for a in dec.args[:1]:
                    if attr_chain(a) in ("jax.jit", "jit"):
                        return True
    return False


class _TaintChecker(ast.NodeVisitor):
    """Single forward pass over one jitted function body."""

    def __init__(self, rule: "JitRule", src: SourceFile, fn_name: str,
                 tainted: Set[str]):
        self.rule = rule
        self.src = src
        self.fn_name = fn_name
        self.tainted = set(tainted)
        self.findings: List[Finding] = []

    def _flag(self, node: ast.AST, what: str) -> None:
        self.findings.append(Finding(
            self.rule.name, self.src.relpath, node.lineno,
            f"in jit-traced `{self.fn_name}`: {what}",
        ))

    def _expr_tainted(self, node: Optional[ast.AST]) -> bool:
        """Any tainted Name reachable without crossing a static-shape
        attribute (.shape/.dtype/...) or len()."""
        if node is None:
            return False
        stack = [node]
        while stack:
            n = stack.pop()
            if isinstance(n, ast.Name):
                if n.id in self.tainted:
                    return True
                continue
            if isinstance(n, ast.Attribute) and n.attr in _STATIC_ATTRS:
                continue  # static at trace time
            if (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Name)
                and n.func.id == "len"
            ):
                continue  # len() of anything is a static int
            stack.extend(ast.iter_child_nodes(n))
        return False

    def _taint_targets(self, target: ast.AST) -> None:
        for n in ast.walk(target):
            if isinstance(n, ast.Name):
                self.tainted.add(n.id)

    # ------------------------------------------------------- statements
    def visit_Assign(self, node: ast.Assign) -> None:
        self.visit(node.value)
        if self._expr_tainted(node.value):
            for t in node.targets:
                self._taint_targets(t)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self.visit(node.value)

    def visit_If(self, node: ast.If) -> None:
        if self._expr_tainted(node.test):
            self._flag(node, "Python `if` on a traced value (host "
                             "concretization / retrace risk); use jnp.where "
                             "or lax.cond")
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        if self._expr_tainted(node.test):
            self._flag(node, "Python `while` on a traced value; use "
                             "lax.while_loop")
        self.generic_visit(node)

    def visit_For(self, node: ast.For) -> None:
        if self._expr_tainted(node.iter):
            self._flag(node, "Python iteration over a traced value "
                             "(unrolls the trace or syncs); use lax.scan "
                             "or vmap")
            self._taint_targets(node.target)  # elements are traced too
        self.generic_visit(node)

    # ------------------------------------------------------------ calls
    def visit_Call(self, node: ast.Call) -> None:
        cn = call_name(node)
        if cn == "item" and isinstance(node.func, ast.Attribute):
            self._flag(node, "`.item()` forces a host sync")
        elif (
            isinstance(node.func, ast.Name)
            and node.func.id in _CONCRETIZERS
            and node.func.id != "len"
            and any(self._expr_tainted(a) for a in node.args)
        ):
            self._flag(node, f"`{node.func.id}()` on a traced value "
                             "concretizes at trace time")
        else:
            chain = attr_chain(node.func)
            if chain is not None and chain.split(".")[0] in _NP_ROOTS:
                self._flag(node, f"host numpy call `{chain}` inside a "
                                 "traced body; use jnp/jax.lax")
        self.generic_visit(node)

    # nested defs/lambdas trace inline with the enclosing program: their
    # parameters are traced values too (vmap/scan bodies)
    def _visit_nested(self, node) -> None:
        prev = set(self.tainted)
        self.tainted |= _param_names(node.args)
        if isinstance(node, ast.Lambda):
            self.visit(node.body)
        else:
            for stmt in node.body:
                self.visit(stmt)
        self.tainted = prev

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._visit_nested(node)

    def visit_Lambda(self, node: ast.Lambda) -> None:
        self._visit_nested(node)


class JitRule(Rule):
    name = "jit-discipline"
    description = (
        "host sync / retrace-risk constructs (.item, int()/float() on "
        "traced values, np.*, Python control flow on tensors) inside "
        "jit-traced functions"
    )

    def run(self, ctx: Context) -> List[Finding]:
        out: List[Finding] = []
        for f in ctx.files:
            if f.tree is None:
                continue
            registered = _jit_function_names(f.tree)
            for node in ast.walk(f.tree):
                if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                if not (
                    node.name in registered
                    or _has_note_trace(node)
                    or _jit_decorated(node)
                ):
                    continue
                params = _param_names(node.args) - {"self", "cls"}
                checker = _TaintChecker(self, f, node.name, params)
                for stmt in node.body:
                    checker.visit(stmt)
                out.extend(checker.findings)
        return out
