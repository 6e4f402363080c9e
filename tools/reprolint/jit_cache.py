"""Rule ``jit-cache``: jit/shard_map constructions that defeat the cache.

Four shapes of the PR 4 bug class:

1. ``jax.jit``/``shard_map`` constructed INSIDE a loop — a fresh traced
   callable (and a fresh compile) per iteration.
2. ``jax.jit`` constructed per call of a function and then invoked in a
   loop in that same function, without a memoized cache.  Recognized
   guards: the construction sits under an ``if <x> is None:`` /
   ``if <key> not in <cache>:`` test (the ``cache.get(key)`` idiom used by
   ``core/tesseraq.py`` and ``launch/mesh.py``), or the enclosing function
   is itself ``functools.lru_cache``/``cache``-decorated.
3. A Mesh constructed locally (``jax.sharding.Mesh``/``make_mesh``/
   ``make_production_mesh``) flowing into a ``jit``/``shard_map`` built in
   the same function: the wrapper is rebuilt on every call of that
   function, so every call retraces and recompiles — the exact 24x
   regression PR 4 debugged.  ``make_data_mesh``/``pod_submeshes`` return
   memoized meshes for the callers' keyed caches and are exempt.
4. A serve-step BUILDER (``make_serve_steps``/``make_sched_steps``/
   ``_make_tp_serve_steps``) invoked in a loop without a cache guard: each
   call constructs fresh (possibly shard_map-wrapped) step closures, so
   every iteration re-traces and recompiles — the same regression class
   reachable again through the serving ``mesh=`` plumbing.  Go through
   ``compile_serve_steps``/``compile_sched_steps`` instead: they memoize
   per (cfg, backend, mesh, tp_shard) key.
"""
from __future__ import annotations

import ast

from tools.reprolint.config import MESH_CONSTRUCTORS, SERVE_STEP_BUILDERS
from tools.reprolint.core import (FileContext, Violation, call_name,
                                  name_refs)

RULE = "jit-cache"

_WRAP_LAST = {"jit", "shard_map"}


def _is_wrap(node: ast.Call) -> bool:
    name = call_name(node.func)
    if not name:
        return False
    last = name.split(".")[-1]
    if last == "shard_map":
        return True
    return last == "jit" and name in ("jit", "jax.jit")


def _is_guard_if(test: ast.AST) -> bool:
    """A cache-miss test: ``x is None`` / ``key not in CACHE`` (comparing
    against a cache object, not a literal tuple of options)."""
    for n in ast.walk(test):
        if not isinstance(n, ast.Compare):
            continue
        for op, comp in zip(n.ops, n.comparators, strict=True):
            if isinstance(op, (ast.Is, ast.IsNot)) \
                    and isinstance(comp, ast.Constant) and comp.value is None:
                return True
            if isinstance(op, (ast.In, ast.NotIn)) \
                    and isinstance(comp, (ast.Name, ast.Attribute)):
                return True
    return False


def _in_body(branch, node) -> bool:
    return any(node is stmt or any(node is d for d in ast.walk(stmt))
               for stmt in branch)


def _guarded(ctx: FileContext, node: ast.AST, fn: ast.AST) -> bool:
    for a in ctx.ancestors(node):
        if a is fn:
            break
        if isinstance(a, ast.If) and _is_guard_if(a.test) \
                and _in_body(a.body, node):
            return True
    deco = getattr(fn, "decorator_list", [])
    for d in deco:
        name = call_name(d.func if isinstance(d, ast.Call) else d)
        if name.split(".")[-1] in ("lru_cache", "cache"):
            return True
    return False


def _functions(ctx: FileContext):
    return [n for n in ast.walk(ctx.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]


def _owned(ctx: FileContext, fn, node) -> bool:
    return ctx.enclosing_function(node) is fn


def check(ctx: FileContext):
    out = []

    # 1. construction inside a loop
    for n in ast.walk(ctx.tree):
        if isinstance(n, ast.Call) and _is_wrap(n) and ctx.in_loop(n) \
                and ctx.enclosing_function(n) is not None \
                and not _guarded(ctx, n, ctx.enclosing_function(n)):
            out.append(Violation(
                RULE, ctx.path, n.lineno,
                f"`{call_name(n.func)}` constructed inside a loop: a fresh "
                f"trace (and compile) every iteration; hoist it behind a "
                f"keyed cache"))

    # 4. serve-step builder invoked in a loop without a memoization guard
    for n in ast.walk(ctx.tree):
        if not isinstance(n, ast.Call):
            continue
        name = call_name(n.func)
        if name and name.split(".")[-1] in SERVE_STEP_BUILDERS \
                and ctx.in_loop(n) \
                and ctx.enclosing_function(n) is not None \
                and not _guarded(ctx, n, ctx.enclosing_function(n)):
            out.append(Violation(
                RULE, ctx.path, n.lineno,
                f"serve-step builder `{name}` called inside a loop: each "
                f"call builds fresh step closures (a re-trace and recompile "
                f"per iteration); use compile_serve_steps/"
                f"compile_sched_steps, which memoize per "
                f"(cfg, backend, mesh, tp_shard)"))

    for fn in _functions(ctx):
        # jitted callables built per call of fn: name = jax.jit(...) or a
        # nested @jax.jit def
        built = {}
        for n in ast.walk(fn):
            if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                    and isinstance(n.targets[0], ast.Name) \
                    and isinstance(n.value, ast.Call) and _is_wrap(n.value) \
                    and _owned(ctx, fn, n):
                built[n.targets[0].id] = n
            elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and n is not fn and ctx.enclosing_function(n) is fn:
                for d in n.decorator_list:
                    target = d.func if isinstance(d, ast.Call) else d
                    if isinstance(target, ast.Call):
                        target = target.func
                    if call_name(target) in ("jit", "jax.jit"):
                        built[n.name] = n

        # 2. rebuilt-per-call jit invoked in a loop without a guard
        for name, site in built.items():
            if _guarded(ctx, site, fn):
                continue
            for n in ast.walk(fn):
                if isinstance(n, ast.Call) and isinstance(n.func, ast.Name) \
                        and n.func.id == name and ctx.in_loop(n, stop=fn):
                    out.append(Violation(
                        RULE, ctx.path, site.lineno,
                        f"jit-compiled `{name}` is rebuilt on every call of "
                        f"`{fn.name}` and invoked in a loop (line "
                        f"{n.lineno}); memoize it behind a keyed cache "
                        f"(`cache.get(key)` + `if ... is None:`)"))
                    break

        # 3. per-call Mesh captured by a jit/shard_map built here
        mesh_names = {
            n.targets[0].id: n for n in ast.walk(fn)
            if isinstance(n, ast.Assign) and len(n.targets) == 1
            and isinstance(n.targets[0], ast.Name)
            and isinstance(n.value, ast.Call)
            and call_name(n.value.func) in MESH_CONSTRUCTORS
            and _owned(ctx, fn, n)}
        if not mesh_names:
            continue
        for n in ast.walk(fn):
            if isinstance(n, ast.Call) and _is_wrap(n):
                used = name_refs(n) & set(mesh_names)
                if used and not _guarded(ctx, n, fn):
                    name = sorted(used)[0]
                    out.append(Violation(
                        RULE, ctx.path, n.lineno,
                        f"`{call_name(n.func)}` closes over Mesh `{name}` "
                        f"constructed in `{fn.name}` (line "
                        f"{mesh_names[name].lineno}): the wrapper is rebuilt "
                        f"and recompiled on every call — build it once "
                        f"behind a keyed cache over a memoized mesh "
                        f"(make_data_mesh/pod_submeshes)"))
    return out
