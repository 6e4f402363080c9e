"""tools/reprolint: per-rule known-bad / known-good fixtures, pragma
grammar, and the whole-repo-clean contract.

Each rule is exercised twice: a fixture reproducing the historical bug
class it exists for (PR 3's module-global env read, PR 4's per-call
Mesh + jit recompiles, PR 5's aliased numpy push) must FAIL, and the
repo's blessed spelling of the same operation must PASS.
"""
import os
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)          # conftest adds ../src only

from tools.reprolint.core import lint_source, lint_paths  # noqa: E402


def rules_at(src: str, path: str = "pkg/launch/scheduler.py"):
    return [v.rule for v in lint_source(textwrap.dedent(src), path)]


# -- host-sync ---------------------------------------------------------------

def test_host_sync_flags_sync_in_hot_root():
    bad = """
    import numpy as np

    def serve_scheduled(xs):
        out = []
        for x in xs:
            out.append(np.asarray(x))     # d2h per step
        return out
    """
    assert "host-sync" in rules_at(bad)


def test_host_sync_flags_float_of_jax_value():
    bad = """
    import jax.numpy as jnp

    def serve_scheduled(x):
        return float(jnp.sum(x))
    """
    assert "host-sync" in rules_at(bad)


def test_host_sync_follows_same_module_callees():
    bad = """
    def _drain(x):
        return x.tolist()

    def serve_scheduled(x):
        return _drain(x)
    """
    assert "host-sync" in rules_at(bad)


def test_host_sync_ignores_cold_functions():
    good = """
    import numpy as np

    def build_report(x):
        return np.asarray(x)
    """
    assert rules_at(good) == []


def test_host_sync_pragma_with_reason_suppresses():
    good = """
    import jax

    def serve_scheduled(x):
        jax.block_until_ready(x)   # reprolint: ok[host-sync] — timing boundary
        return x
    """
    assert rules_at(good) == []


def test_hot_pragma_marks_extra_root():
    bad = """
    def my_inner_loop(x):  # reprolint: hot
        return x.item()
    """
    assert "host-sync" in rules_at(bad, path="pkg/whatever.py")


# -- jit-cache ---------------------------------------------------------------

def test_jit_cache_flags_jit_in_loop():
    bad = """
    import jax

    def run(xs, step):
        for x in xs:
            f = jax.jit(step)
            x = f(x)
        return x
    """
    assert "jit-cache" in rules_at(bad)


def test_jit_cache_flags_per_call_mesh_pr4_bug():
    # PR 4 bug class: a fresh Mesh per call misses the tracing cache and
    # every invocation recompiles.
    bad = """
    import jax
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    def run(x, step, devs):
        mesh = jax.sharding.Mesh(devs, ("dp",))
        f = shard_map(step, mesh=mesh, in_specs=P(), out_specs=P())
        return f(x)
    """
    assert "jit-cache" in rules_at(bad)


def test_jit_cache_flags_serve_step_builder_in_loop():
    # Shape 4: the serve-step builders return fresh (shard_map-wrapped)
    # closures, so looping over configs/meshes through them recompiles
    # per iteration — the memoized compile_* entry points are the guard.
    bad = """
    from pkg.launch.steps import make_sched_steps

    def sweep(cfgs, mesh):
        outs = []
        for cfg in cfgs:
            outs.append(make_sched_steps(cfg, mesh, tp_shard=True))
        return outs
    """
    assert "jit-cache" in rules_at(bad)


def test_jit_cache_accepts_memoized_compile_in_loop():
    # compile_sched_steps memoizes per (cfg, width, backend, mesh,
    # tp_shard) — looping over it is the blessed spelling and must pass.
    good = """
    from pkg.launch.scheduler import compile_sched_steps

    def sweep(cfgs, mesh):
        outs = []
        for cfg in cfgs:
            outs.append(compile_sched_steps(cfg, mesh, tp_shard=True))
        return outs
    """
    assert "jit-cache" not in rules_at(good)


def test_jit_cache_accepts_cache_get_guard():
    good = """
    import jax

    def run(xs, step, cache):
        f = cache.get("step")
        if f is None:
            f = jax.jit(step)
            cache["step"] = f
        for x in xs:
            x = f(x)
        return x
    """
    assert "jit-cache" not in rules_at(good)


# -- env-read ----------------------------------------------------------------

def test_env_read_flags_module_scope_pr3_bug():
    # PR 3 bug class: the backend env var frozen at first import.
    bad = """
    import os

    KERNEL_BACKEND = os.environ.get("REPRO_KERNEL_BACKEND", "xla")
    """
    assert "env-read" in rules_at(bad)


def test_env_read_accepts_call_time_read():
    good = """
    import os

    def kernel_backend():
        return os.environ.get("REPRO_KERNEL_BACKEND", "xla")
    """
    assert "env-read" not in rules_at(good)


# -- donation-guard ----------------------------------------------------------

def test_donation_flags_bare_literal():
    bad = """
    import jax

    def build(step):
        return jax.jit(step, donate_argnums=(0, 1))
    """
    assert "donation-guard" in rules_at(bad)


def test_donation_accepts_helper_and_backend_guard():
    good = """
    import jax
    from pkg.launch.steps import cache_donate_argnums

    def build(step, run):
        donate = jax.default_backend() != "cpu"
        a = jax.jit(step, donate_argnums=cache_donate_argnums(1))
        b = jax.jit(run, donate_argnums=(0, 1) if donate else ())
        return a, b
    """
    assert "donation-guard" not in rules_at(good)


# -- alias-push --------------------------------------------------------------

def test_alias_push_flags_pr5_heisenbug_verbatim():
    # PR 5 bug class: jnp.asarray may alias the numpy buffer on CPU; the
    # later in-place write mutates the "device" value under a dispatched
    # step.
    bad = """
    import jax.numpy as jnp

    def admit(active_h, s):
        active_d = jnp.asarray(active_h)
        active_h[s] = True
        return active_d
    """
    assert "alias-push" in rules_at(bad)


def test_alias_push_accepts_copy():
    good = """
    import jax.numpy as jnp

    def admit(active_h, s):
        active_d = jnp.asarray(active_h.copy())
        active_h[s] = True
        return active_d
    """
    assert "alias-push" not in rules_at(good)


# -- pallas-contract ---------------------------------------------------------

def test_pallas_flags_unguarded_grid_division():
    bad = """
    from jax.experimental import pallas as pl

    def launch(x, n):
        return pl.pallas_call(kern, grid=(n // 8,), out_shape=x)(x)
    """
    assert "pallas-contract" in rules_at(bad, path="pkg/kernels/k.py")


def test_pallas_accepts_guarded_grid_division():
    good = """
    from jax.experimental import pallas as pl

    def launch(x, n):
        if n % 8:
            raise ValueError("n must divide the 8-wide grid tile")
        return pl.pallas_call(kern, grid=(n // 8,), out_shape=x)(x)
    """
    assert "pallas-contract" not in rules_at(good, path="pkg/kernels/k.py")


# -- pragma grammar ----------------------------------------------------------

def test_pragma_without_reason_is_itself_a_violation():
    # assembled so this file's own lint doesn't see a reason-less pragma
    marker = "# reprolint" + ": ok[host-sync]"
    bad = """
    import jax

    def serve_scheduled(x):
        jax.block_until_ready(x)   {}
        return x
    """.format(marker)
    assert "pragma" in rules_at(bad)


def test_pragma_suppresses_only_named_rule():
    bad = """
    import numpy as np

    def serve_scheduled(x):
        return np.asarray(x)   # reprolint: ok[jit-cache] — wrong rule named
    """
    assert "host-sync" in rules_at(bad)


# -- the repo itself ---------------------------------------------------------

@pytest.mark.parametrize("tree", ["src", "tests", "benchmarks"])
def test_repo_tree_is_clean(tree):
    violations = lint_paths([os.path.join(ROOT, tree)])
    assert violations == [], "\n".join(str(v) for v in violations)
