"""Rule ``alias-push``: pushing a host buffer that the pusher mutates.

The PR 5 heisenbug, verbatim: ``jnp.asarray`` (and ``jax.device_put``) on
CPU can ALIAS the numpy buffer instead of copying it; if the same
function then mutates that buffer in place (``buf[...] = x``), the
"device" value silently changes under an already-enqueued computation —
a bit-flip that reproduces only under scheduler-dependent timing.  The
fix (kept in ``scheduler.serve_scheduled``'s pushes) is to push
``buf.copy()``.

Flagged: ``jnp.asarray(X)`` / ``jax.device_put(X)`` where ``X`` is a bare
name the SAME function also mutates via subscript assignment, augmented
assignment, or ``X.fill(...)`` — unless the pushed expression is already
``X.copy()``.
"""
from __future__ import annotations

import ast

from tools.reprolint.core import FileContext, Violation, call_name

RULE = "alias-push"

_PUSH = {"jnp.asarray", "jnp.array", "jax.numpy.asarray", "jax.device_put"}
_MUTATORS = {"fill", "sort", "put", "setfield"}


def _mutated_names(fn: ast.AST):
    out = set()
    for n in ast.walk(fn):
        targets = []
        if isinstance(n, ast.Assign):
            targets = n.targets
        elif isinstance(n, ast.AugAssign):
            targets = [n.target]
        for t in targets:
            if isinstance(t, ast.Subscript) and isinstance(t.value, ast.Name):
                out.add(t.value.id)
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) \
                and n.func.attr in _MUTATORS \
                and isinstance(n.func.value, ast.Name):
            out.add(n.func.value.id)
    return out


def check(ctx: FileContext):
    out = []
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        mutated = _mutated_names(fn)
        if not mutated:
            continue
        for n in ast.walk(fn):
            if isinstance(n, ast.Call) and call_name(n.func) in _PUSH \
                    and n.args and isinstance(n.args[0], ast.Name) \
                    and n.args[0].id in mutated:
                out.append(Violation(
                    RULE, ctx.path, n.lineno,
                    f"`{call_name(n.func)}({n.args[0].id})` pushes a host "
                    f"buffer `{fn.name}` also mutates in place: on CPU the "
                    f"push may alias, so the enqueued value changes under "
                    f"the computation (PR 5 heisenbug); push "
                    f"`{n.args[0].id}.copy()` instead"))
    return out
