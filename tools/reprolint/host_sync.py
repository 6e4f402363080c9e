"""Rule ``host-sync``: host synchronization reachable from a hot path.

The serving decode loop and the recon engine's scanned step are timed,
device-resident code: one stray ``float(x)`` / ``np.asarray(x)`` /
``block_until_ready`` forces a device->host round trip per step and turns
a pipelined loop into a lock-step one (the class of regression PR 5's
push-a-copy aliasing fix and the scheduler's sync accounting guard
against).

Hot roots come from ``config.HOT_ROOTS`` plus any def carrying a
``# reprolint: hot`` pragma; the pass closes over same-module callees by
simple name, then flags every sync-shaped call in those scopes.
Intentional syncs (admission-time argmax, timing boundaries) carry
``ok[host-sync]`` pragmas with the reason inline.
"""
from __future__ import annotations

import ast

from tools.reprolint.config import (HOT_ROOTS, SYNC_BUILTINS, SYNC_CALLS,
                                    SYNC_METHODS)
from tools.reprolint.core import FileContext, Violation, call_name

RULE = "host-sync"


def _hot_roots(ctx: FileContext):
    names = set()
    for suffix, roots in HOT_ROOTS.items():
        if ctx.path.endswith(suffix):
            names |= set(roots)
    defs = ctx.module_defs()
    for name, node in defs.items():
        if node.lineno in ctx.hot_lines or node.lineno - 1 in ctx.hot_lines:
            names.add(name)
    return names, defs


def _closure(roots, defs):
    """Transitively reachable module-level defs, by simple call name."""
    seen, work = set(), [r for r in roots if r in defs]
    while work:
        name = work.pop()
        if name in seen:
            continue
        seen.add(name)
        for n in ast.walk(defs[name]):
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Name) \
                    and n.func.id in defs and n.func.id not in seen:
                work.append(n.func.id)
    return seen


def _is_sync(node: ast.Call) -> str:
    name = call_name(node.func)
    if name in SYNC_CALLS:
        return name
    if isinstance(node.func, ast.Attribute) and node.func.attr in SYNC_METHODS:
        return f".{node.func.attr}()"
    if name in SYNC_BUILTINS and node.args \
            and any(isinstance(n, ast.Name) and n.id in ("jnp", "jax")
                    for a in node.args for n in ast.walk(a)):
        # float()/int() force the device value to the host; only flagged on
        # expressions visibly rooted in jax/jnp (plain-host ints are fine)
        return f"{name}()"
    return ""


def check(ctx: FileContext):
    roots, defs = _hot_roots(ctx)
    if not roots:
        return []
    out = []
    for name in sorted(_closure(roots, defs)):
        for n in ast.walk(defs[name]):
            if isinstance(n, ast.Call):
                what = _is_sync(n)
                if what:
                    out.append(Violation(
                        RULE, ctx.path, n.lineno,
                        f"host sync `{what}` reachable from hot path "
                        f"`{name}`; keep the timed loop device-resident or "
                        f"tag the site with an ok[host-sync] reason"))
    return out
