"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run forces a
512-device host platform while tests/benches run on the single real device.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import PartitionSpec as P


def _mk(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-propagated), the
    mode every sharding rule in ``launch/sharding.py`` is written for."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single v5e pod (256 chips) or 2x16x16 (2 pods, 512 chips).

    The ``pod`` axis is pure data parallelism: only gradient all-reduce
    crosses the DCN between pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape, axes=None):
    """Arbitrary mesh for tests / elastic restarts (e.g. (2, 4))."""
    if axes is None:
        axes = ("pod", "data", "model")[-len(shape):] if len(shape) == 3 \
            else ("data", "model")[:len(shape)]
    return _mk(tuple(shape), tuple(axes))


_DATA_MESH_CACHE: dict = {}


def make_data_mesh(n_devices=None):
    """1-D pure data-parallel mesh over ``n_devices`` (default: all visible
    devices).  The default mesh for ``engine="sharded"`` reconstruction when
    the caller does not hand one in — on a host platform forced to N devices
    this is the N-way calibration mesh the CI multi-device job exercises.

    Memoized per device set, so every caller that resolves the default
    mesh (e.g. one reconstruction per block) gets the SAME object back and
    the per-mesh engine and step caches keyed on it stay at one entry."""
    n = len(jax.devices()) if n_devices is None else int(n_devices)
    key = (n, tuple(d.id for d in jax.devices()[:n]))
    if key not in _DATA_MESH_CACHE:
        _DATA_MESH_CACHE[key] = _mk((n,), ("data",))
    return _DATA_MESH_CACHE[key]


_SERVE_MESH_CACHE: dict = {}


def serve_mesh(tp: int = 1, n_devices=None):
    """THE serve-mesh constructor: a ``("data", "model")`` mesh whose
    ``model`` axis carries the serve-time tensor-parallel degree (the
    ``--tp N`` flag on the serve CLI and bench), remaining devices on
    ``data``.  CLI, bench and tests all build the serve mesh through here
    so they agree on shape and axis names — and on identity: memoized per
    (device set, tp) like :func:`make_data_mesh`."""
    n = len(jax.devices()) if n_devices is None else int(n_devices)
    tp = int(tp)
    if tp < 1:
        raise ValueError(f"serve_mesh: tp must be >= 1, got {tp}")
    if n % tp:
        raise ValueError(f"serve_mesh: tp={tp} does not divide the "
                         f"{n} visible devices")
    key = (n, tp, tuple(d.id for d in jax.devices()[:n]))
    if key not in _SERVE_MESH_CACHE:
        _SERVE_MESH_CACHE[key] = _mk((n // tp, tp), ("data", "model"))
    return _SERVE_MESH_CACHE[key]


def dp_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def dp_size(mesh, axes=None) -> int:
    """Total data-parallel degree (product of the DP axis extents; pass
    ``axes`` to honor a caller-resolved axis set, e.g. ``Ctx.dp_axes``)."""
    n = 1
    for a in (dp_axes(mesh) if axes is None else axes):
        n *= mesh.shape[a]
    return n


def tp_axis(mesh):
    """Name of the tensor-parallel mesh axis, or None when the mesh has no
    ``model`` axis.  NOTE: this reports the axis *name* even at extent 1 —
    callers that branch on "is TP actually on?" should use :func:`tp_size`
    instead of special-casing degree-1 TP."""
    return "model" if "model" in mesh.axis_names else None


def tp_size(mesh) -> int:
    """Tensor-parallel degree (extent of the ``model`` axis; 1 when the mesh
    has no such axis or is None — 0/1-safe, mirroring ``dp_size``)."""
    if mesh is None:
        return 1
    ax = tp_axis(mesh)
    return int(mesh.shape[ax]) if ax is not None else 1


def pod_axis(mesh):
    """Name of the cross-pod (pipeline) mesh axis, or None."""
    return "pod" if mesh is not None and "pod" in mesh.axis_names else None


def pod_count(mesh) -> int:
    """Number of pods (extent of the ``pod`` axis; 1 when absent)."""
    ax = pod_axis(mesh)
    return int(mesh.shape[ax]) if ax is not None else 1


_POD_SUBMESH_CACHE: dict = {}


def pod_submeshes(mesh) -> list:
    """One ``("data", "model")``-shaped submesh per pod, carved out of a
    ``("pod", "data", "model")`` mesh's device grid.

    The pipelined block walk places block k's reconstruction on submesh
    ``k % n_pods`` and block k+1's capture forward on the next one, so the
    two phases run on disjoint device sets and genuinely overlap.  Memoized
    per device grid like ``make_data_mesh`` (the walk resolves the same
    pod's submesh once per block)."""
    ax = pod_axis(mesh)
    if ax is None:
        return [mesh]
    rest = tuple(a for a in mesh.axis_names if a != ax)
    key = (tuple(d.id for d in mesh.devices.flat), mesh.axis_names)
    if key not in _POD_SUBMESH_CACHE:
        pod_dim = mesh.axis_names.index(ax)
        devs = np.moveaxis(mesh.devices, pod_dim, 0)
        _POD_SUBMESH_CACHE[key] = [
            jax.sharding.Mesh(devs[p], rest) for p in range(devs.shape[0])]
    return _POD_SUBMESH_CACHE[key]


def reshard_between_pods(x, dst_mesh, spec=None):
    """Move an array (or pytree) onto another pod's submesh — the explicit
    cross-mesh transfer seam of the pipelined block walk (the analog of
    alpa's pipeshard ``send_recv`` resharding: device-to-device transfers
    between disjoint device sets, here expressed as a ``device_put`` onto
    the destination mesh so XLA's transfer engine picks the route).

    ``spec`` defaults to ``batch_spec(dst_mesh)`` — activation streams move
    batch-sharded over the destination's DP axes.  Pass ``P()`` (or a
    per-leaf spec pytree) for replicated/parameter payloads."""
    from jax.sharding import NamedSharding

    dspec = batch_spec(dst_mesh) if spec is None else spec

    def put(leaf, s):
        if leaf is None:
            return None
        target = s
        if not isinstance(target, jax.sharding.Sharding):
            target = NamedSharding(dst_mesh, target)
        return jax.device_put(leaf, target)

    if isinstance(dspec, (P, jax.sharding.Sharding)):
        return jax.tree_util.tree_map(lambda leaf: put(leaf, dspec), x)
    return jax.tree_util.tree_map(put, x, dspec)


def validate_single_pod(mesh, what: str) -> None:
    """Serving paths are single-mesh: they have no cross-pod resharding
    seam, so a multi-pod mesh would silently mis-shard (the ``pod`` axis
    would be treated as one more data axis).  Fail loudly instead."""
    if mesh is not None and pod_count(mesh) > 1:
        raise ValueError(
            f"{what} runs on a single-pod mesh, but was handed a multi-pod "
            f"mesh with axes {mesh.axis_names} (pod extent "
            f"{pod_count(mesh)}); quantization's pipelined block walk is "
            "the only multi-pod consumer — serve each pod with its own "
            "submesh (launch.mesh.pod_submeshes) instead, building it via "
            "launch.mesh.serve_mesh(tp=N) (the serve CLI/bench --tp N "
            "path) for tensor-parallel serving within the pod")


def batch_spec(mesh) -> P:
    """PartitionSpec that shards a leading batch dimension over the mesh's
    data-parallel axes (the one spec every batch-sharded path — capture
    streams, the sharded reconstruction engine's calibration pool — shares,
    so they always agree on the placement)."""
    dp = dp_axes(mesh)
    if not dp:
        return P()
    return P(dp if len(dp) > 1 else dp[0])
