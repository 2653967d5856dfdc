"""donation-safety: donated buffers are dead after the call.

``donate_argnums`` lets XLA reuse an input buffer for the output — the
arena scatters depend on it — but the donated python reference then
points at freed memory: touching it later raises on strict backends and
silently reads garbage where donation is a no-op (CPU), so the bug only
fires on the accelerator.  This pass finds every surface callable that
donates (directly via decorator/`jax.jit(...)`, or transitively: a
wrapper that forwards its own parameter into a donated position
donates that parameter too), then checks each call site: a donated
``Name``/``self.attr`` argument must not be *loaded* again in a later
statement of the same block unless rebound first.  The idiomatic safe
shape — ``self.vectors = arena_scatter(self.vectors, ...)`` — rebinds
in the same statement and passes, also where that statement sits inside
a ``with`` or loop body; a donation inside such a body is still checked
against the statements after the block.
"""
from __future__ import annotations

import ast

from ..callgraph import FuncInfo, ModuleFile, RepoIndex, dotted
from ..findings import Finding

NAME = "donation-safety"
DESCRIPTION = "donated jit arguments referenced after the call"
SCOPE = None


def _donating_map(index: RepoIndex) -> dict[str, set[int]]:
    """qualname -> donated positional indices, with one transitive step
    per fixpoint round for forwarding wrappers."""
    don: dict[str, set[int]] = {
        fi.qualname: set(fi.donated)
        for fi in index.functions.values() if fi.donated
    }
    for _ in range(8):
        changed = False
        for fi in index.functions.values():
            for call in (n for n in ast.walk(fi.node)
                         if isinstance(n, ast.Call)):
                callee = index.resolve_call(fi.mod, call.func, fi.cls)
                if callee is None or callee.qualname not in don:
                    continue
                for pos in don[callee.qualname]:
                    if pos >= len(call.args):
                        continue
                    arg = call.args[pos]
                    if (isinstance(arg, ast.Name)
                            and arg.id in fi.params):
                        p = fi.params.index(arg.id)
                        cur = don.setdefault(fi.qualname, set())
                        if p not in cur:
                            cur.add(p)
                            changed = True
        if not changed:
            break
    return don


def _target_names(stmt: ast.stmt) -> set[str]:
    """Dotted names rebound by this statement."""
    out: set[str] = set()
    targets: list[ast.AST] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
        targets = [stmt.target]
    for t in targets:
        d = dotted(t)
        if d:
            out.add(d)
        if isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                de = dotted(e)
                if de:
                    out.add(de)
    return out


def _loads_in(stmt: ast.stmt, name: str) -> ast.AST | None:
    """First Load of dotted ``name`` inside ``stmt`` (excluding stores)."""
    for sub in ast.walk(stmt):
        if isinstance(sub, (ast.Name, ast.Attribute)):
            if not isinstance(getattr(sub, "ctx", None), ast.Load):
                continue
            if dotted(sub) == name:
                return sub
    return None


def _calls_with_owner(stmt: ast.stmt):
    """Each call inside ``stmt`` with the innermost statement holding it,
    whose targets decide whether the call rebinds what it donated."""
    stack: list[ast.AST] = [stmt]
    while stack:
        owner = stack.pop()
        for node in ast.iter_child_nodes(owner):
            if isinstance(node, (ast.stmt, ast.excepthandler)):
                stack.append(node)
                continue
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    yield sub, owner


def _check_block(index: RepoIndex, fi: FuncInfo, body: list[ast.stmt],
                 don: dict[str, set[int]], out: list[Finding]) -> None:
    for i, stmt in enumerate(body):
        for call, owner in _calls_with_owner(stmt):
            callee = index.resolve_call(fi.mod, call.func, fi.cls)
            if callee is None or callee.qualname not in don:
                continue
            rebound = _target_names(owner)
            for pos in don[callee.qualname]:
                if pos >= len(call.args):
                    continue
                name = dotted(call.args[pos])
                if name is None or name in rebound:
                    continue  # non-name arg, or safe same-stmt rebind
                for later in body[i + 1:]:
                    if name in _target_names(later):
                        break  # rebound before any load
                    hit = _loads_in(later, name)
                    if hit is not None:
                        out.append(Finding(
                            pass_name=NAME, path=fi.mod.rel,
                            line=hit.lineno,
                            message=(
                                f"`{name}` was donated to "
                                f"`{callee.name}` (line {call.lineno}) "
                                f"and is referenced afterwards")))
                        break
        # recurse into nested blocks
        for attr in ("body", "orelse", "finalbody"):
            sub = getattr(stmt, attr, None)
            if isinstance(sub, list) and sub and isinstance(sub[0], ast.stmt):
                _check_block(index, fi, sub, don, out)
        for h in getattr(stmt, "handlers", []) or []:
            _check_block(index, fi, h.body, don, out)


def run(index: RepoIndex, files: list[ModuleFile]) -> list[Finding]:
    don = _donating_map(index)
    wanted = {f.module for f in files}
    out: list[Finding] = []
    for fi in index.functions.values():
        if fi.mod.module not in wanted:
            continue
        # note: functions that *transitively* donate (forwarding
        # wrappers) are still checked — a wrapper that touches its own
        # donated param after forwarding it is exactly the bug
        _check_block(index, fi, fi.node.body, don, out)
    return sorted(set(out))
