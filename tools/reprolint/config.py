"""Repo-specific knobs for the reprolint passes.

``HOT_ROOTS`` names the functions/classes whose transitive (same-module)
callees form the timed serving and reconstruction paths — the scopes the
host-sync rule patrols.  Fixture files can mark additional roots inline
with a ``# reprolint: hot`` pragma on the def line.
"""

# path suffix (posix) -> names of hot root defs/classes in that module
HOT_ROOTS = {
    "launch/scheduler.py": {"serve_scheduled"},
    "launch/steps.py": {"make_sched_steps", "make_serve_steps",
                        "_make_tp_serve_steps",
                        "make_paged_install_step"},
    "core/recon_engine.py": {"ReconstructionEngine"},
}

# serve-step builders that construct fresh (shard_map-wrapped) step closures
# per call: calling one inside a loop rebuilds and recompiles per iteration
# (the PR 4 recompile class, reachable again via the serve `mesh=` plumbing).
# compile_sched_steps is memoized behind the per-(cfg, width, backend,
# mesh, tp_shard) step-set cache and deliberately absent — it is the guard
# the rule points offenders at.
SERVE_STEP_BUILDERS = {"make_serve_steps", "make_sched_steps",
                       "_make_tp_serve_steps"}

# calls that synchronize with (or copy to) the host
SYNC_CALLS = {
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "jax.device_get", "jax.block_until_ready",
}
SYNC_METHODS = {"item", "tolist", "block_until_ready"}
SYNC_BUILTINS = {"float", "int", "bool"}

# constructors that build a NEW Mesh object per call; make_data_mesh and
# pod_submeshes are memoized in launch/mesh.py and deliberately absent
MESH_CONSTRUCTORS = {"Mesh", "jax.sharding.Mesh", "make_mesh",
                     "make_production_mesh"}

# per-core VMEM budget the pallas-contract pass estimates block residency
# against (TPU v4/v5 class: 16 MiB, f32-conservative)
VMEM_BUDGET_BYTES = 16 * 2 ** 20
VMEM_BYTES_PER_ELEM = 4
