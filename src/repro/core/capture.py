"""Eager-mode capture of per-linear input activations inside a block, plus
the activation-stream utilities the pipelined ``quantize_model`` walk uses.

AWQ/GPTQ need, for every linear W in a block, statistics of that linear's own
input X (mean |X| per channel; a token subsample for the reconstruction
objective; optionally X^T X for GPTQ's Hessian).  We obtain them by running
the block *uncompiled* with ``layers.matmul`` / ``layers.expert_matmul``
temporarily patched to record (weight-identity -> stats); weight identities
are mapped back to param paths.

MoE expert weights see their own capacity-gathered (or, for the dropless
layer, expert-sorted and tile-padded) inputs: zero-padded slots dilute
``mean_abs`` by a uniform factor that cancels under AWQ's relative scale
search — documented approximation.

Stream utilities (``split_minibatches`` / ``shard_stream`` /
``capture_minibatch``) keep the calibration streams device-resident between
blocks and, on a mesh, place every minibatch with its batch dim sharded over
the data-parallel axes so the capture forward passes run mesh-parallel —
the whole block walk stays mesh-resident.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.blocks import get_path, quant_leaf_paths
from repro.launch.mesh import batch_spec, dp_size
from repro.models import layers as L

MAX_ROWS = 1024          # token subsample kept per linear for objectives


def stage_calibration(X, Y=None, aux=None, *, mesh=None) -> Tuple:
    """Move a block's calibration streams to device *once*.

    The reconstruction inner loop gathers minibatches out of these staged
    arrays with a device-side ``take``; all host->device traffic for a block
    happens here, before the first optimization step, instead of one transfer
    per step.  Y is promoted to float32 (the reconstruction-loss dtype).

    With ``mesh`` each stream is placed with its batch dim sharded over the
    mesh's data-parallel axes (``shard_stream``): every device holds only
    its 1/D slice of the pool, which is exactly the slice the sharded
    reconstruction engine's local index plan reads — the streams never need
    to be replicated.

    The transfers are EXPLICIT ``jax.device_put`` calls (dtype promotion on
    host first): this is the one sanctioned host->device staging point, and
    the sanitizer's ``transfer_guard("disallow")`` holds it to that."""
    Xd = jax.device_put(X)
    Yd = (jax.device_put(np.asarray(Y, np.float32))
          if Y is not None else None)
    auxd = jax.device_put(aux) if aux is not None else None
    if mesh is not None:
        Xd = shard_stream(Xd, mesh)
        Yd = shard_stream(Yd, mesh) if Yd is not None else None
        auxd = shard_stream(auxd, mesh) if auxd is not None else None
    return Xd, Yd, auxd


def capture_minibatch(mesh=None, base: int = 4) -> int:
    """Minibatch size for the stream forward passes: ``base`` on a single
    device, lifted to the mesh's DP degree when sharding so every device
    owns at least one sample per capture dispatch."""
    return base if mesh is None else max(base, dp_size(mesh))


def shard_stream(x, mesh):
    """Place one activation minibatch mesh-resident with its batch dim (0)
    sharded over the DP axes; batch sizes that don't divide the DP degree
    fall back to replication (same contract as ``sharding.resolve_spec``)."""
    spec = batch_spec(mesh)
    if spec != P() and x.shape[0] % dp_size(mesh):
        spec = P()
    return jax.device_put(x, NamedSharding(mesh, spec))


def split_minibatches(x, mb: int, mesh=None) -> list:
    """Split a (N, ...) stream into device-resident minibatches of ``mb``
    rows (last one may be short); with ``mesh``, each part is placed with
    its batch dim sharded over the DP axes so jitted forwards over the
    parts run data-parallel."""
    parts = [jnp.asarray(x[j:j + mb]) for j in range(0, x.shape[0], mb)]
    if mesh is not None:
        parts = [shard_stream(p, mesh) for p in parts]
    return parts


class LinearStats:
    def __init__(self):
        self.abs_sum = None
        self.count = 0
        self.rows = []
        self.row_count = 0
        self.hessian = None

    def update(self, x: np.ndarray, want_hessian: bool):
        x2d = x.reshape(-1, x.shape[-1]).astype(np.float32)
        a = np.abs(x2d).sum(0)
        self.abs_sum = a if self.abs_sum is None else self.abs_sum + a
        self.count += x2d.shape[0]
        if self.row_count < MAX_ROWS:
            take = min(MAX_ROWS - self.row_count, x2d.shape[0])
            idx = np.linspace(0, max(x2d.shape[0] - 1, 0), take).astype(int)
            self.rows.append(x2d[idx])
            self.row_count += take
        if want_hessian:
            h = x2d.T @ x2d
            self.hessian = h if self.hessian is None else self.hessian + h

    @property
    def mean_abs(self) -> np.ndarray:
        return self.abs_sum / max(self.count, 1)

    @property
    def sample(self) -> np.ndarray:
        return np.concatenate(self.rows, 0) if self.rows else np.zeros((0, 1))


def capture_block_inputs(apply: Callable, bp, xs, auxs=None, *,
                         want_hessian: bool = False) -> Dict[tuple, LinearStats]:
    """Run ``apply(bp, x, aux)`` eagerly over minibatches, recording inputs of
    every quantizable linear.  xs/auxs: lists of minibatch arrays."""
    paths = quant_leaf_paths(bp)
    by_id = {id(get_path(bp, p)): p for p in paths}
    stats = {p: LinearStats() for p in paths}

    orig_mm, orig_emm, orig_gmm = L.matmul, L.expert_matmul, L.grouped_matmul

    def rec(w, x):
        p = by_id.get(id(w))
        if p is not None:
            stats[p].update(np.asarray(x), want_hessian)

    def patched_mm(x, w, backend=None):
        rec(w, x)
        return orig_mm(x, w, backend)

    def patched_emm(a, w, backend=None):
        rec(w, a)
        return orig_emm(a, w, backend)

    def patched_gmm(x, w, layout, backend=None):
        rec(w, x)
        return orig_gmm(x, w, layout, backend)

    L.matmul, L.expert_matmul, L.grouped_matmul = (patched_mm, patched_emm,
                                                   patched_gmm)
    try:
        for i, x in enumerate(xs):
            aux = auxs[i] if auxs is not None else None
            apply(bp, x, aux)
    finally:
        L.matmul, L.expert_matmul, L.grouped_matmul = (orig_mm, orig_emm,
                                                       orig_gmm)
    return stats
