"""PL007 use-after-donate: reading a buffer after a dispatch donated it.

``jax.jit(..., donate_argnums=...)`` hands the argument buffers to XLA:
after the dispatch the Python bindings still *name* them, but the device
memory is gone (reads raise ``RuntimeError`` on TPU, ``ValueError``
INVALID_ARGUMENT on CPU — and only when the timing loses, which is why this
bug class ships). The engine's contract is the runner.py rebind idiom:
every dispatch that donates the KV pools returns the new buffers and the
call site rebinds them **in the same statement** —

    self.kv_k, self.kv_v = ... = self._decode(..., self.kv_k, self.kv_v, ...)

(or, the same operands as a tuple, ``self._dispatch("decode", program,
(..., self.kv_k, self.kv_v, ...), static)``, which calls the binding named
by its first argument or the loaded executable compiled from it).

This rule makes that idiom the checked contract. Per module it builds the
jit binding graph (tools/pstpu_lint/jaxmodel.py): which bindings hold a
donating dispatch (direct ``jax.jit`` assignments, decorated defs, and
one-level factories), with which ``donate_argnums``. Then, per function
body, statements are scanned in source order:

  * a call through a donating binding marks the argument bindings at the
    donated positions (locals and ``self.*`` attrs) as *consumed* — unless
    the same statement's assignment targets rebind them;
  * any later read of a consumed binding is flagged, until a rebinding
    assignment clears it;
  * reads inside a ``try`` whose handler catches ``RuntimeError`` or
    ``ValueError`` are exempt — that is the linted donation-retry guard
    (``runner.read_blocks_retry``); a bare ``except Exception`` guard is
    NOT accepted (type it, or waive with a reason).

The analysis is intra-function and flow-insensitive across branches
(statements in source order), which is exactly the shape of the real
dispatch sites; cross-function donation would mean a dispatch's caller
holds stale pool refs across frames — worth a human's eyes, not a
heuristic's.
"""

import ast
from typing import List, Optional, Set

from tools.pstpu_lint import jaxmodel
from tools.pstpu_lint.core import Finding

_RETRYISH = {"RuntimeError", "ValueError"}


def _walk_pruned(node: ast.AST):
    """ast.walk that does not descend into nested function/class bodies
    (they are separate execution contexts with their own scan)."""
    stack = [node]
    while stack:
        n = stack.pop()
        yield n
        for child in ast.iter_child_nodes(n):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                continue
            stack.append(child)


def _read_key(node: ast.AST) -> Optional[str]:
    """Binding key of a Name/self-attr expression ('wk' / 'self.kv_k')."""
    if isinstance(node, ast.Name):
        return node.id
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id in ("self", "cls")):
        return f"self.{node.attr}"
    return None


def _flatten_targets(target: ast.AST, out: Set[str]) -> None:
    if isinstance(target, (ast.Tuple, ast.List)):
        for e in target.elts:
            _flatten_targets(e, out)
    elif isinstance(target, ast.Starred):
        _flatten_targets(target.value, out)
    else:
        key = _read_key(target)
        if key is not None:
            out.add(key)


def _stmt_targets(stmt: ast.stmt) -> Set[str]:
    out: Set[str] = set()
    if isinstance(stmt, ast.Assign):
        for t in stmt.targets:
            _flatten_targets(t, out)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        if stmt.target is not None:
            _flatten_targets(stmt.target, out)
    elif isinstance(stmt, (ast.For, ast.AsyncFor)):
        _flatten_targets(stmt.target, out)
    return out


def _catches_retryish(try_node: ast.Try) -> bool:
    for handler in try_node.handlers:
        t = handler.type
        names = []
        if isinstance(t, ast.Tuple):
            names = [e.id for e in t.elts if isinstance(e, ast.Name)]
        elif isinstance(t, ast.Name):
            names = [t.id]
        if any(n in _RETRYISH for n in names):
            return True
    return False


class _BodyScan:
    """Source-order scan of one function body, tracking consumed bindings."""

    def __init__(self, relpath: str, model: jaxmodel.JaxModel):
        self.relpath = relpath
        self.model = model
        self.consumed: dict = {}          # key -> (dispatch line, binding key)
        self.findings: List[Finding] = []

    # ------------------------------------------------------------- helpers
    def _donating_calls(self, stmt: ast.stmt):
        """(call, binding) pairs for donating-jit calls inside ``stmt``."""
        for node in _walk_pruned(stmt):
            if not isinstance(node, ast.Call):
                continue
            key = _read_key(node.func)
            if key is None:
                continue
            if key == "self._dispatch" and len(node.args) >= 3 \
                    and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[2], ast.Tuple):
                # runner._dispatch("decode", program, (operands), static):
                # the operands go to self._decode or to the loaded
                # executable compiled from it, which donates the same.
                binding = self.model.bindings.get(
                    f"self._{node.args[0].value}")
                if binding is not None and binding.donate:
                    yield ast.copy_location(
                        ast.Call(func=node.func, args=node.args[2].elts,
                                 keywords=[]), node), binding
                continue
            binding = self.model.bindings.get(key)
            if binding is None and key.startswith("self."):
                binding = self.model.bindings.get(key[len("self."):])
            if binding is not None and binding.donate:
                yield node, binding

    def _check_reads(self, stmt: ast.stmt, exempt: bool) -> None:
        if exempt or not self.consumed:
            return
        for node in _walk_pruned(stmt):
            key = None
            if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
                key = node.id
            elif (isinstance(node, ast.Attribute)
                  and isinstance(node.ctx, ast.Load)
                  and isinstance(node.value, ast.Name)
                  and node.value.id in ("self", "cls")):
                key = f"self.{node.attr}"
            if key is None or key not in self.consumed:
                continue
            disp_line, disp_key = self.consumed[key]
            self.findings.append(Finding(
                "PL007", self.relpath, node.lineno,
                f"{key} was donated to the dispatch through {disp_key} "
                f"(line {disp_line}) and never rebound from its outputs — "
                f"the buffer is deleted; rebind it from the dispatch's "
                f"returns or guard the read with the donation-retry idiom "
                f"(except (RuntimeError, ValueError))",
            ))

    def _apply_stmt_effects(self, stmt: ast.stmt) -> None:
        donated_now: Set[str] = set()
        for call, binding in self._donating_calls(stmt):
            for pos in binding.donate:
                if pos < len(call.args):
                    key = _read_key(call.args[pos])
                    if key is not None:
                        donated_now.add(key)
            if donated_now:
                for key in donated_now:
                    self.consumed.setdefault(key, (call.lineno, binding.key))
        # Assignment targets of the SAME statement rebind (the idiom);
        # later assignments clear earlier donations.
        for key in _stmt_targets(stmt):
            self.consumed.pop(key, None)

    # ---------------------------------------------------------------- walk
    @staticmethod
    def _headers(stmt: ast.stmt) -> List[ast.AST]:
        """The expressions a compound statement evaluates BEFORE its body
        (its bodies are scanned recursively with their own exemption)."""
        if isinstance(stmt, (ast.If, ast.While)):
            return [stmt.test]
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            return [stmt.iter]
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            return [item.context_expr for item in stmt.items]
        return []

    def scan(self, body: List[ast.stmt], exempt: bool = False) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            if isinstance(stmt, (ast.Try, ast.If, ast.For, ast.AsyncFor,
                                 ast.While, ast.With, ast.AsyncWith)):
                for header in self._headers(stmt):
                    self._check_reads(header, exempt)
                    self._apply_stmt_effects(header)
                if isinstance(stmt, (ast.For, ast.AsyncFor)):
                    for key in _stmt_targets(stmt):
                        self.consumed.pop(key, None)
                if isinstance(stmt, ast.Try):
                    sub_exempt = exempt or _catches_retryish(stmt)
                    self.scan(stmt.body, sub_exempt)
                    for handler in stmt.handlers:
                        self.scan(handler.body, exempt)
                    self.scan(stmt.orelse, exempt)
                    self.scan(stmt.finalbody, exempt)
                elif isinstance(stmt, (ast.If, ast.For, ast.AsyncFor,
                                       ast.While)):
                    self.scan(stmt.body, exempt)
                    self.scan(stmt.orelse, exempt)
                else:
                    self.scan(stmt.body, exempt)
                continue
            self._check_reads(stmt, exempt)
            self._apply_stmt_effects(stmt)


def check(relpath: str, tree: ast.AST, source: str) -> List[Finding]:
    model = jaxmodel.build(tree)
    if not any(b.donate for b in model.bindings.values()):
        return []
    findings: List[Finding] = []
    for qual, info in model.graph.functions.items():
        body = getattr(info.node, "body", None)
        if not body:
            continue
        scan = _BodyScan(relpath, model)
        scan.scan(body)
        findings.extend(scan.findings)
    return findings
