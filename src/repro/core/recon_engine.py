"""On-device block reconstruction engine shared by TesseraQ / OmniQuant /
SignRound.

The per-block inner loop is the cost center of every reconstruction-style PTQ
method (paper Sec. 3.2/3.3, Algorithm 1): thousands of gradient steps per
block, each tiny.  Run naively (one jitted grad call per step, batches
gathered on the host, optimizer stepped eagerly) the wall clock is dominated
by dispatch overhead and host<->device ping-pong, not math.  This module
keeps the whole loop on the device:

  * **Batch pre-staging** — the calibration streams X / Y / aux are moved to
    the device once per block (``capture.stage_calibration``; batch-sharded
    over the mesh when one is given) and the entire minibatch index plan for
    all K*T steps is drawn up front by ``draw_index_plan`` — the *same*
    canonical draw sequence the host-loop engines consume, so every path
    sees identical batches.  Inside the loop, minibatches are device-side
    ``take`` gathers.

  * **Scanned soften phase** — the T Adam (or SignSGD) steps of one PAR
    iteration run as a single ``jax.lax.scan``; trainables and optimizer
    state are donated so backends that support aliasing update them in
    place.  One dispatch per PAR iteration instead of T.

  * **Jitted global-threshold hardening** — the block-wide HS quantile
    (Algorithm 1's joint sort over every rounding variable in the block) is
    computed with a device-side sort; frozen variables participate as +inf
    sentinels, which pins the quantile to the fixed index ``want_soft`` of
    the ascending sort and reproduces the NumPy reference's tie handling
    exactly.

  * **Host-sync accounting** — the only blocking device->host read per PAR
    iteration is the optional log line, and it is routed through
    ``host_read`` so tests and benchmarks can count syncs.

  * **Canonical (device-count-invariant) chunked batch gradients** — the
    batch dimension is the only dimension the sharded engine splits across
    devices, so the reduction over it is associativity-pinned as a
    two-level *chunked ordered mean*: the minibatch's per-sample gradient
    lanes (``vmap`` lanes, whose arithmetic does not depend on how many
    lanes run together) are grouped into ``C = grad_chunk_count(bs, N)``
    fixed contiguous chunks, each chunk is reduced with one ordered lane
    sum, and the C chunk partials are combined with one ordered sum in
    chunk order, then divided by the batch size.  C is a pure function of
    the minibatch size and the pool size (never of the device count), so
    the same minibatch yields bit-identical gradients whether the chunks
    are computed on one device or spread across a mesh — up to compiler
    scheduling: XLA may still compile a lane's GEMMs differently inside
    different surrounding programs, which injects ~1-ulp noise at long
    horizons.  The DISCRETE artifacts (hardened mask + packed codes)
    absorb that noise and stay bit-identical at the reduced widths and
    calibration horizons the tests and benchmark gates pin (see
    ``tests/test_recon_engine.py`` and ``benchmarks/recon_speed.py``).
    At published widths they do not: among millions of rounding variables
    per block a few sit within that noise of the freeze threshold, and the
    ``shard_map`` program differs from the single-device one in a few
    masks/codes (ROADMAP C2).

  * **Mesh-sharded soften phase** — with a ``mesh``, the same scanned step
    runs under ``shard_map``, hierarchically: each device owns C/D of the
    canonical chunks (device r takes rows [r*bs/D, (r+1)*bs/D) of the
    step's index-plan row), computes its per-sample lanes and reduces them
    LOCALLY into its per-chunk partial sums, and only those partials — one
    flattened (C/D, |params|) array per device, O(C x |params|) total, not
    the O(bs x |params|) per-sample lane stacks — cross the interconnect in
    a single fused ``all_gather``.  Every device then applies the same
    rank-ordered combine over the C gathered partials the single-device
    engine applies to its own chunk partials.  Rounding variables, DST
    variables and Adam state stay REPLICATED — every device applies the
    identical reduced gradient, so the trainables never desynchronize
    across the mesh and the hardened mask is computed from a single
    consistent copy.  The calibration pool itself is SHARDED over the DP
    axes (``in_specs`` carry a batch-dim ``PartitionSpec``): the canonical
    index plan draws chunk j's samples from pool shard j, so device r's
    chunks read only rows it already owns — per-device calibration-stream
    memory shrinks by the DP degree and the per-step gather stays local.

The host-loop paths are kept alongside: ``TesseraQConfig.engine =
"reference"`` (NumPy harden + fused jitted step — the oracle
``tests/test_recon_engine.py`` pins against the device engine: masks and
codes bit-for-bit, folded scales to 1 ulp)
and ``engine = "legacy"`` (the original eager-optimizer loop, the
``benchmarks/recon_speed.py`` baseline).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.capture import stage_calibration
from repro.launch.mesh import (batch_spec, dp_axes, dp_size, make_data_mesh,
                               tp_axis, tp_size)

# ---------------------------------------------------------------------------
# host-sync accounting
# ---------------------------------------------------------------------------

_SYNC_COUNT = 0


def host_read(x):
    """Blocking device->host read, counted.  Every code path that pulls a
    value out of the reconstruction loop goes through here so benchmarks can
    assert the engine's <=1-sync-per-iteration guarantee — and it reads via
    the explicit ``jax.device_get`` form ``transfer_guard("disallow")``
    permits, so sanitized bench runs see only counted syncs."""
    global _SYNC_COUNT
    _SYNC_COUNT += 1
    return np.asarray(jax.device_get(x))


def sync_count() -> int:
    return _SYNC_COUNT


def reset_sync_count() -> None:
    global _SYNC_COUNT
    _SYNC_COUNT = 0


# ---------------------------------------------------------------------------
# jitted global-threshold hardening
# ---------------------------------------------------------------------------

def _hardness_score(nu: jax.Array) -> jax.Array:
    return jnp.abs(jax.nn.sigmoid(nu) - 0.5)          # HS (paper Eq. 6)


@functools.partial(jax.jit, static_argnames=("use_inf",))
def _harden_jit(states, want_soft, use_inf: bool):
    """Freeze the HIGHEST-HS soft variables (those already nearly binary, so
    rounding them perturbs the block least) until only ``want_soft``
    variables remain soft across the WHOLE block (joint threshold over all
    leaves).

    Equivalence with the NumPy reference (``tesseraq.harden``): the reference
    takes the k-th largest score *among currently-soft variables* (k =
    n_soft_now - want_soft) and freezes every soft variable with
    ``hs >= thresh``.  Mapping frozen slots to +inf and sorting the full
    concatenated vector ascending puts the soft scores at positions
    [0, n_soft_now), so that same threshold lives at index ``want_soft`` —
    no host round-trip to count how many are already frozen.  When nothing
    needs freezing (n_soft_now <= want_soft) that index lands on a +inf
    sentinel and the ``hs >= thresh`` mask is empty, reproducing the
    reference's early return."""
    scores = jnp.concatenate([
        jnp.where(st["hard"] == 0, _hardness_score(st["nu"]),
                  jnp.inf).ravel()
        for st in states.values()])
    thresh = jnp.take(jnp.sort(scores), want_soft)

    new = {}
    for p, st in states.items():
        hs = _hardness_score(st["nu"])
        freeze = (st["hard"] == 0) & (hs >= thresh)
        sign = jnp.where(st["nu"] > 0, 1, -1).astype(jnp.int8)
        hard = jnp.where(freeze, sign, st["hard"])
        st = dict(st)
        st["hard"] = hard
        if use_inf:
            st["nu"] = jnp.where(hard != 0, hard.astype(jnp.float32) * 40.0,
                                 st["nu"])
        new[p] = st
    return new


def harden_device(states, target_soft_rate: float, use_inf: bool, *,
                  mesh=None):
    """Device-side counterpart of ``tesseraq.harden`` (same freeze sets,
    including ties — verified bit-for-bit by tests/test_recon_engine.py).

    With ``mesh`` the threshold scalar is placed onto the mesh so the jit
    sees colocated args when ``states`` lives there (mesh runs keep the
    whole state tree mesh-resident between PAR iterations)."""
    total = sum(int(np.prod(st["hard"].shape)) for st in states.values())
    want_soft = int(total * target_soft_rate)
    if want_soft >= total:
        return states                                  # nothing to freeze
    # explicit device_put: a bare jnp.asarray(int, int32) is an implicit
    # scalar transfer the sanitizer's transfer_guard would reject
    want = np.int32(want_soft)
    if mesh is None:
        want_d = jax.device_put(want)
    else:
        from jax.sharding import NamedSharding
        want_d = jax.device_put(want, NamedSharding(mesh, P()))
    return _harden_jit(states, want_d, use_inf)


# ---------------------------------------------------------------------------
# optimizers beyond AdamW (duck-typed: .init(params), .update(g, st, p))
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SignSGD:
    """Signed gradient descent with linear lr decay (SignRound's optimizer).
    State is just the global step counter."""
    lr: float = 5e-3
    total_steps: int = 200
    clip: float = 0.5

    def init(self, params):
        return jnp.zeros((), jnp.int32)

    def state_specs(self, param_specs):
        """State is a replicated scalar step counter whatever the params'
        placement (same protocol as ``AdamW.state_specs``)."""
        return P()

    def update(self, grads, state, params):
        frac = state.astype(jnp.float32) / max(self.total_steps, 1)
        cur_lr = self.lr * (1.0 - frac)
        new = jax.tree_util.tree_map(
            lambda p, g: jnp.clip(p - cur_lr * jnp.sign(g),
                                  -self.clip, self.clip),
            params, grads)
        return new, state + 1


# ---------------------------------------------------------------------------
# mesh plumbing for the sharded engine
# ---------------------------------------------------------------------------

def resolve_mesh(mesh=None):
    """The mesh for ``engine="sharded"``: the caller's, or a 1-D pure
    data-parallel mesh over every visible device (what the CI multi-device
    job gets under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``)."""
    return mesh if mesh is not None else make_data_mesh()


def _dp_rank(mesh, dp):
    """Linearized data-parallel rank inside a shard_map body (row-major over
    the DP axes, matching how ``P(dp)`` would lay a dim over them)."""
    r = jnp.zeros((), jnp.int32)
    for a in dp:
        r = r * mesh.shape[a] + jax.lax.axis_index(a)
    return r


# ---------------------------------------------------------------------------
# tensor-parallel gather/scatter (ParamSpec-driven)
# ---------------------------------------------------------------------------

def _tp_dim(spec, axis_name):
    """Index of the dim a PartitionSpec shards over ``axis_name`` (None when
    the leaf is not TP-sharded — replicated fallback or a non-split leaf)."""
    if spec is None:
        return None
    for d, entry in enumerate(spec):
        if entry == axis_name or (isinstance(entry, tuple)
                                  and axis_name in entry):
            return d
    return None


def _tp_gather(tree, specs, axis_name):
    """Reassemble full per-block arrays from their TP shards inside a
    shard_map body: one tiled ``all_gather`` along each leaf's ParamSpec
    split dim (ZeRO-3 semantics — persistent storage stays 1/TP per device,
    the full array exists only transiently inside the step).  Leaves whose
    spec carries no TP axis pass through untouched, so replicated-fallback
    leaves cost nothing."""
    def g(x, spec):
        d = _tp_dim(spec, axis_name)
        if d is None:
            return x
        return jax.lax.all_gather(x, axis_name, axis=d, tiled=True)
    return jax.tree_util.tree_map(g, tree, specs)


def _tp_shard(tree, specs, axis_name, size):
    """Inverse of ``_tp_gather`` for the *gradients*: every device computed
    the identical full-size gradient (the calibration batch is replicated
    over the TP axis), so each keeps the contiguous slice of its own shard —
    a static-width ``dynamic_slice``, no collective, and elementwise
    optimizer updates on the slice are bit-identical to slicing after a
    full-array update (the TP=1 / device-engine equivalence)."""
    def s(x, spec):
        d = _tp_dim(spec, axis_name)
        if d is None:
            return x
        w = x.shape[d] // size
        r = jax.lax.axis_index(axis_name)
        return jax.lax.dynamic_slice_in_dim(x, r * w, w, axis=d)
    return jax.tree_util.tree_map(s, tree, specs)


# ---------------------------------------------------------------------------
# canonical (device-count-invariant) chunked batch gradients
# ---------------------------------------------------------------------------

# The canonical gradient association groups a minibatch's per-sample lanes
# into at most this many contiguous chunks.  8 matches the CI multi-device
# job's DP degree: any power-of-2 mesh up to 8-way owns a whole number of
# chunks, so the chunk grid — and therefore every bit of the rounding
# trajectory — is identical on 1 device and on the mesh.
CANONICAL_LANE_CHUNKS = 8


def grad_chunk_count(batch_size: int, pool: int) -> int:
    """Number of chunks in the canonical gradient association for a
    ``batch_size`` minibatch drawn from a ``pool``-sample calibration pool.

    A pure function of (batch_size, pool) — NEVER of the device count —
    so every engine (device / reference / sharded, any mesh) reduces with
    the identical association.  It must divide the batch (equal chunks)
    and the pool (the index plan draws chunk j from pool shard j), hence
    the gcd; ``CANONICAL_LANE_CHUNKS`` caps it so the cross-device
    exchange stays O(chunks x |params|).  A sharded engine additionally
    requires its DP degree to divide this count (checked in ``run``)."""
    return math.gcd(math.gcd(batch_size, CANONICAL_LANE_CHUNKS), pool)


def make_per_sample_grad(loss_fn: Callable) -> Callable:
    """Per-sample (lane) value-and-grad of a minibatch ``loss_fn``.

    Returns ``f(tr, frozen, xb, yb, auxb) -> (loss_lanes, grad_lanes)`` where
    both outputs carry a leading sample axis of length ``xb.shape[0]``.  Each
    lane evaluates ``loss_fn`` on a size-1 slice of the minibatch, so lane
    arithmetic is independent of how many lanes are vmapped together — the
    property that makes the reduction below device-count invariant."""
    vg = jax.value_and_grad(loss_fn)

    def f(tr, frozen, xb, yb, auxb):
        if auxb is None:
            return jax.vmap(
                lambda x1, y1: vg(tr, frozen, x1[None], y1[None], None)
            )(xb, yb)
        return jax.vmap(
            lambda x1, y1, a1: vg(tr, frozen, x1[None], y1[None], a1[None])
        )(xb, yb, auxb)
    return f


def _chunk_partials(loss_lanes, grad_lanes, chunks: int):
    """First level of the canonical association: group the lanes into
    ``chunks`` contiguous chunks and reduce each with one ordered lane sum
    (one batched reduce over the chunk-width axis — a fixed association
    for a given chunk width).

    Note the cross-PROGRAM caveat: when the chunk width exceeds one lane,
    XLA may lower this reduce marginally differently for a (C, c, ...)
    device-engine stack than for a (C/D, c, ...) local shard, which can
    inject ~1-ulp noise into the continuous state exactly like the
    per-lane GEMM scheduling noise the engine already documents; the
    discrete artifacts (hardened mask + packed codes) absorb it, and the
    parity gates pin them bit-for-bit."""
    def csum(s):
        return jnp.sum(
            s.reshape((chunks, s.shape[0] // chunks) + s.shape[1:]), axis=1)
    return csum(loss_lanes), jax.tree_util.tree_map(csum, grad_lanes)


def _combine_partials(loss_partials, grad_partials, batch_size: int):
    """Second level: one ordered sum over the chunk partials in chunk order
    — identical (C, ...) operand shape on every engine, so the final
    association never depends on where the partials were computed —
    divided by the GLOBAL minibatch size."""
    grads = jax.tree_util.tree_map(
        lambda s: jnp.sum(s, axis=0) / batch_size, grad_partials)
    return jnp.sum(loss_partials) / batch_size, grads


def _flatten_partials(loss_partials, grad_partials):
    """Pack the per-chunk loss + gradient partials into ONE (chunks, width)
    float32 matrix, so the sharded engine exchanges a single fused
    ``all_gather`` per step instead of one collective per pytree leaf."""
    leaves, treedef = jax.tree_util.tree_flatten(grad_partials)
    cols = [loss_partials[:, None].astype(jnp.float32)]
    cols += [leaf.reshape(leaf.shape[0], -1) for leaf in leaves]
    shapes = [leaf.shape[1:] for leaf in leaves]
    return jnp.concatenate(cols, axis=1), treedef, shapes


def _unflatten_partials(flat, treedef, shapes):
    """Inverse of ``_flatten_partials`` after the gather: the leading dim is
    now the FULL canonical chunk count, restored per leaf to the exact
    (C, *param_shape) arrays the single-device engine reduces."""
    loss_partials = flat[:, 0]
    leaves, col = [], 1
    for shp in shapes:
        n = int(np.prod(shp)) if shp else 1
        leaves.append(flat[:, col:col + n].reshape((flat.shape[0],) + shp))
        col += n
    return loss_partials, jax.tree_util.tree_unflatten(treedef, leaves)


def make_canonical_grad(loss_fn: Callable, *, chunks: int) -> Callable:
    """``value_and_grad`` with the canonical chunked per-sample reduction —
    the exact gradient HLO inside the device engine's scanned step, exposed
    so the host-loop reference oracle can pin against it bit-for-bit.
    ``chunks`` must be ``grad_chunk_count(bs, pool)`` for the caller's
    minibatch/pool sizes."""
    per_sample = make_per_sample_grad(loss_fn)

    def grad_fn(tr, frozen, xb, yb, auxb):
        lv, grads = per_sample(tr, frozen, xb, yb, auxb)
        lp, gp = _chunk_partials(lv, grads, chunks)
        return _combine_partials(lp, gp, xb.shape[0])
    return grad_fn


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BatchPlan:
    """Per-block staged calibration data + the full minibatch index plan.

    The plan is drawn once by ``draw_index_plan`` — identical draws, in the
    same order, as the host-loop engines, which is what pins the device and
    sharded engines to the reference path batch-for-batch.  With a mesh the
    streams are staged batch-sharded over the DP axes (``chunks`` is the
    canonical gradient chunk count the plan's draws are stratified over)."""
    X: Any
    Y: Any
    aux: Any
    index_plan: Any        # (total_steps, bs) int32, on device
    total_steps: int
    chunks: int = 1


def draw_index_plan(N: int, batch_size: int, total_steps: int,
                    seed: int = 0) -> np.ndarray:
    """The canonical minibatch index plan every engine consumes.

    Draws are STRATIFIED over the canonical chunk grid: the pool is split
    into ``C = grad_chunk_count(bs, N)`` equal contiguous shards and chunk
    j of each step's minibatch draws its ``bs/C`` samples (without
    replacement) from pool shard j, in one fixed rng sequence
    (step-major, chunk-major).  Chunk j's rows therefore always live on
    the device that owns pool shard j when the pool is batch-sharded over
    a mesh — the sharded engine never has to move calibration data — while
    the plan itself is a pure function of (N, bs, steps, seed), so every
    engine at every device count sees identical global minibatches."""
    bs = min(batch_size, N)
    if total_steps <= 0:
        return np.empty((0, bs), np.int32)
    C = grad_chunk_count(bs, N)
    c, Ns = bs // C, N // C
    rng = np.random.default_rng(seed)
    plan = np.stack([
        np.concatenate([j * Ns + rng.choice(Ns, c, replace=False)
                        for j in range(C)])
        for _ in range(total_steps)])
    return plan.astype(np.int32)


def stage_plan(X, Y, aux=None, *, batch_size: int, total_steps: int,
               seed: int = 0, mesh=None) -> BatchPlan:
    Xd, Yd, auxd = stage_calibration(X, Y, aux, mesh=mesh)
    N = Xd.shape[0]
    bs = min(batch_size, N)
    plan = draw_index_plan(N, bs, total_steps, seed)
    return BatchPlan(Xd, Yd, auxd, jnp.asarray(plan, jnp.int32), total_steps,
                     grad_chunk_count(bs, N))


def _mesh_place(mesh, tree, specs):
    """Explicitly ``device_put`` every leaf of ``tree`` onto ``mesh`` per
    ``specs`` (a full PartitionSpec tree, or one prefix ``P()`` for the
    whole tree).  Without this, the first sharded ``run`` after ``init``
    reshards single-device carries implicitly at dispatch — a silent
    device-to-device broadcast the sanitizer's ``transfer_guard``
    (correctly) rejects.  Already-placed leaves are a no-op."""
    if tree is None:
        return None
    from jax.sharding import NamedSharding
    if isinstance(specs, P):
        sh = NamedSharding(mesh, specs)
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), tree)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P))
    return jax.device_put(tree, shardings)


class ReconstructionEngine:
    """Scanned, donated inner loop over a pre-staged :class:`BatchPlan`.

    ``loss_fn(trainables, frozen, xb, yb, auxb) -> scalar`` is the block
    reconstruction objective; ``frozen`` is an arbitrary pytree of
    non-trainable side state (e.g. TesseraQ's hardened masks AND the block
    params themselves) threaded through unchanged.  ``optimizer`` is AdamW /
    SignSGD / anything with the same ``init`` / ``update`` protocol.

    The engine is data-free: everything per-block (weights, calibration
    streams, index plan) enters ``run`` as arguments, so ONE engine — and
    one XLA compilation of its scanned step — is reused for every
    identically-shaped block in a stage.  Callers hold the engine in a
    per-stage cache; compilation amortizes over the model's depth.

    With ``mesh`` the scanned step runs under ``shard_map``, data-parallel
    over the mesh's DP axes, as a hierarchical chunked ordered reduction:
    each device owns a contiguous slice of the canonical chunk grid
    (``grad_chunk_count``), computes its per-sample gradient lanes from its
    OWN shard of the batch-sharded calibration pool, reduces them locally
    into per-chunk partial sums, and exchanges only those partials — one
    fused ``all_gather`` of a (C/D, |params|+1) float32 matrix per step,
    O(C x |params|) traffic instead of the O(bs x |params|) per-sample lane
    stacks.  Every device then applies the SAME rank-ordered combine over
    the C gathered chunk partials the single-device engine applies to its
    own — so ``engine="sharded"`` reproduces ``engine="device"`` hardened
    masks and packed codes bit-for-bit at the tests' reduced widths and
    pinned calibration horizons (folded scales within 1 ulp at long
    horizons, where XLA's per-program compilation choices inject
    lane-level rounding noise the discrete artifacts absorb).  At
    published widths a few masks/codes per block differ (ROADMAP C2).  Trainables, optimizer state and the frozen
    side state enter and leave replicated (``P()`` specs); the per-step
    update is identical on every device, so replication is an invariant of
    the scan, not something that needs re-synchronizing.  The canonical
    chunk count must divide by the DP degree (``run`` raises otherwise).
    """

    def __init__(self, loss_fn: Callable, optimizer, *, donate: bool = True,
                 mesh=None, param_specs=None):
        self.opt = optimizer
        self.mesh = mesh
        self.dp_degree = D = 1 if mesh is None else dp_size(mesh)
        per_sample = make_per_sample_grad(loss_fn)
        opt = optimizer

        # tensor-parallel placement (ParamSpec contract): ``param_specs`` is
        # {"tr": <spec tree matching trainables>, "frozen": <spec tree
        # matching the frozen side state>} of PartitionSpecs whose TP-axis
        # entry names each leaf's split dim.  Only meaningful with a mesh
        # that has a model axis; TP degree 1 keeps the specs (and the
        # gather/scatter no-ops they induce) so the code path is identical.
        tp_name = tp_axis(mesh) if (mesh is not None
                                    and param_specs is not None) else None
        tp_n = tp_size(mesh) if tp_name is not None else 1
        self.tp_degree = tp_n if tp_name is not None else 1
        tr_specs = param_specs["tr"] if tp_name is not None else None
        frozen_specs = param_specs["frozen"] if tp_name is not None else None

        if mesh is None:
            def grad_fn(tr, frozen, xb, yb, auxb, chunks):
                lv, grads = per_sample(tr, frozen, xb, yb, auxb)
                lp, gp = _chunk_partials(lv, grads, chunks)
                return _combine_partials(lp, gp, xb.shape[0])

            def pick(i, r, n_local):
                return i
        else:
            dp = dp_axes(mesh)
            if not dp:
                raise ValueError(f"mesh {mesh.axis_names} has no "
                                 "data-parallel axes ('pod'/'data')")

            def grad_fn(tr, frozen, xb, yb, auxb, chunks):
                # local lanes -> LOCAL per-chunk ordered lane sums -> one
                # fused all_gather of the per-shard chunk partials -> the
                # same rank-ordered combine over all C partials the
                # single-device engine applies: a hierarchical ordered
                # reduction, deterministic where a raw lax.psum would leave
                # the association to the backend, and O(C x |params|) on
                # the wire where gathering the lane stacks was O(bs x ...)
                lv, grads = per_sample(tr, frozen, xb, yb, auxb)
                lp, gp = _chunk_partials(lv, grads, chunks // D)
                flat, treedef, shapes = _flatten_partials(lp, gp)
                flat = jax.lax.all_gather(flat, dp, axis=0, tiled=True)
                lp, gp = _unflatten_partials(flat, treedef, shapes)
                return _combine_partials(lp, gp, xb.shape[0] * D)

            def pick(i, r, n_local):
                # device r takes rows [r*bs_local, (r+1)*bs_local) of the
                # step's (replicated) index-plan row: the global minibatch
                # is identical to the single-device engine's, only its rows
                # are computed on different devices.  The plan's stratified
                # draws guarantee those rows live in this device's pool
                # shard, so the global indices rebase to local ones by
                # subtracting the shard offset.
                bs_local = i.shape[0] // D
                li = jax.lax.dynamic_slice_in_dim(i, r * bs_local, bs_local)
                return li - r * n_local

        def run(tr, opt_state, frozen, X, Y, aux, idx):
            rank = None if mesh is None else _dp_rank(mesh, dp_axes(mesh))
            # static under jit: inside shard_map X is the LOCAL pool shard,
            # so the global pool size is its length times the DP degree
            chunks = grad_chunk_count(idx.shape[1], X.shape[0] * D)
            if tp_name is not None:
                # frozen side state (block weights, hardened masks, bases)
                # is read-only across the scan: gather its TP shards once —
                # XLA hoists the loop-invariant gathers out of the scan
                frozen = _tp_gather(frozen, frozen_specs, tp_name)

            def step(carry, i):
                tr, opt_state = carry
                li = pick(i, rank, X.shape[0])
                xb = jnp.take(X, li, axis=0)
                yb = jnp.take(Y, li, axis=0)
                auxb = jnp.take(aux, li, axis=0) if aux is not None else None
                # TP: the loss sees the full rounding/DST variables
                # (transient per-step gather); the carry — and the Adam
                # state the update touches — stays a 1/TP shard.  The batch
                # is replicated over the TP axis, so grads come out
                # full-size and identical on every TP peer; each keeps its
                # own slice, which makes the per-element trajectory — and
                # therefore the hardened mask — independent of the TP
                # degree.
                tr_f = (tr if tp_name is None
                        else _tp_gather(tr, tr_specs, tp_name))
                lv, grads = grad_fn(tr_f, frozen, xb, yb, auxb, chunks)
                if tp_name is not None:
                    grads = _tp_shard(grads, tr_specs, tp_name, tp_n)
                tr, opt_state = opt.update(grads, opt_state, tr)
                return (tr, opt_state), lv
            (tr, opt_state), losses = jax.lax.scan(step, (tr, opt_state),
                                                   idx)
            return tr, opt_state, losses[-1]

        if mesh is not None:
            # index plan replicated; the calibration streams X / Y / aux are
            # SHARDED over the DP axes on their batch dim — each device
            # stages and reads only its 1/D of the pool.  Trainables /
            # optimizer state / frozen side state are replicated (P())
            # without a ParamSpec, or sharded over the TP axis per its
            # placement contract (out-channel for q/k/v/up, in-channel for
            # o/down) when one is given — they enter AND leave sharded, so
            # between PAR iterations the persistent rounding/Adam state
            # occupies 1/TP per device.  Replication checking is off
            # (check_vma=False) because axis_index makes intermediate values
            # device-varying even though the gather restores replication
            # before the update.
            bspec = batch_spec(mesh)
            if tp_name is None:
                tr_in, opt_in, frz_in = P(), P(), P()
            else:
                tr_in = tr_specs
                frz_in = frozen_specs
                opt_in = (opt.state_specs(tr_specs)
                          if hasattr(opt, "state_specs") else P())
            run = jax.shard_map(
                run, mesh=mesh,
                in_specs=(tr_in, opt_in, frz_in, bspec, bspec, bspec, P()),
                out_specs=(tr_in, opt_in, P()), check_vma=False)
            # run() re-places carries onto the mesh explicitly with these
            # (no-op once sharded; see _mesh_place)
            self._carry_specs = (tr_in, opt_in, frz_in)

        # trainables + optimizer state are loop carries: donate them so the
        # update happens in place where the backend supports aliasing —
        # except on CPU, where XLA cannot alias and donation only emits
        # unusable-donation warnings (same guard as adam.jitted_update)
        donate = donate and jax.default_backend() != "cpu"
        self._run = jax.jit(run, donate_argnums=(0, 1) if donate else ())
        self._init = jax.jit(self.opt.init)

    def init(self, trainables):
        # compiled: the optimizer's zero-state builder runs eager jnp.zeros
        # (a scalar-constant device_put per leaf) which the sanitizer's
        # transfer_guard rejects; under jit it is part of the program
        return self._init(trainables)

    def run(self, trainables, opt_state, frozen, plan: BatchPlan, *,
            start: int = 0, steps: Optional[int] = None):
        """Execute ``steps`` optimization steps (plan rows [start,
        start+steps)) in one dispatch.  Returns (trainables, opt_state,
        last_loss) with the loss still on device — reading it is the
        caller's (counted) choice."""
        steps = plan.total_steps - start if steps is None else steps
        # static slice, not basic indexing: eager `x[a:b]` lowers to a
        # dynamic_slice whose scalar index operands are fresh host->device
        # transfers every call — the sanitizer's transfer_guard rejects it
        idx = jax.lax.slice_in_dim(plan.index_plan, start, start + steps,
                                   axis=0)
        chunks = grad_chunk_count(idx.shape[1], plan.X.shape[0])
        if chunks != plan.chunks:
            raise ValueError(
                f"plan was staged for {plan.chunks} canonical gradient "
                f"chunks but the engine now derives {chunks} — "
                "CANONICAL_LANE_CHUNKS changed after stage_plan drew the "
                "stratified index plan; re-stage the plan (a mismatched "
                "grid would read rows outside a device's pool shard)")
        if chunks % self.dp_degree:
            raise ValueError(
                f"canonical gradient chunk count {chunks} (minibatch "
                f"{idx.shape[1]}, pool {plan.X.shape[0]}, cap "
                f"{CANONICAL_LANE_CHUNKS}) does not divide by the mesh's "
                f"data-parallel degree {self.dp_degree}; pick a batch_size "
                "and calibration pool that are multiples of it (or shrink "
                "the mesh).  For a DP degree that does not divide "
                f"{CANONICAL_LANE_CHUNKS} (e.g. 6- or 16-way), set "
                "recon_engine.CANONICAL_LANE_CHUNKS to a multiple of it "
                "before building engines — note this changes the canonical "
                "rounding trajectory for batches wider than the cap")
        if self.mesh is not None:
            tr_s, opt_s, frz_s = self._carry_specs
            trainables = _mesh_place(self.mesh, trainables, tr_s)
            opt_state = _mesh_place(self.mesh, opt_state, opt_s)
            frozen = _mesh_place(self.mesh, frozen, frz_s)
            idx = _mesh_place(self.mesh, idx, P())
        return self._run(trainables, opt_state, frozen,
                         plan.X, plan.Y, plan.aux, idx)
