"""Logical-axis sharding rules with divisibility fallback.

Parameters and activations are annotated with *logical* dim names; a single
table maps logical names to mesh axes.  Any dim that does not divide by its
mesh-axis extent silently falls back to replication — so the same model code
runs on 8-chip test meshes and 512-chip production meshes unmodified
(elastic scaling = restore under a different mesh).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.qtensor import PACK_FACTOR, QTensor
from repro.launch.mesh import dp_axes, tp_axis, tp_size
from repro.models.common import LEAF_FIXED, LEAF_TOKEN
from repro.models.layers import PsumWeight

# logical name -> tuple of mesh axes (joined when multiple)
def logical_table(mesh, overrides=None):
    dp = dp_axes(mesh)
    tp = ("model",) if "model" in mesh.axis_names else ()
    table = {
        "batch": dp,
        "fsdp": ("data",) if "data" in mesh.axis_names else (),
        "tensor": tp,
        "expert": tp,
        "vocab": tp,
        "heads": tp,
        "kv_heads": tp,
        None: (),
        "seq": (),
        "res_seq": (),      # residual-stream sequence dim; -> ("model",)
                            # enables sequence parallelism (perf knob)
        "embed": (),
    }
    if overrides:
        table.update(overrides)
    return table


def _axis_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def resolve_spec(mesh, logical: tuple, shape, overrides=None) -> P:
    """Logical dim names -> PartitionSpec with divisibility fallback and
    axis-reuse guard (first dim wins)."""
    table = logical_table(mesh, overrides)
    out = []
    used = set()
    # zip-to-shortest is the contract: a spec may name fewer dims than
    # the tensor's rank (trailing dims replicate)
    for name, dim in zip(logical, shape, strict=False):
        axes = table.get(name, ())
        if axes and dim % _axis_size(mesh, axes) == 0 \
                and not (set(axes) & used):
            out.append(axes if len(axes) > 1 else axes[0])
            used.update(axes)
        else:
            out.append(None)
    return P(*out)


# --------------------------------------------------------------------------
# parameter rules: leaf name -> logical dims of the *trailing* (in, out) dims
# (leading stacked layer/expert dims handled structurally)
# --------------------------------------------------------------------------

PARAM_RULES = {
    # dense attention / mlp: 2D-shard (fsdp x tensor)
    "wq": ("fsdp", "tensor"), "wk": ("fsdp", "tensor"), "wv": ("fsdp", "tensor"),
    "wo": ("tensor", "fsdp"),
    "w_gate": ("fsdp", "tensor"), "w_up": ("fsdp", "tensor"),
    "w_down": ("tensor", "fsdp"),
    # rwkv
    "wr": ("fsdp", "tensor"), "wg": ("fsdp", "tensor"),
    "ck": ("fsdp", "tensor"), "cv": ("tensor", "fsdp"), "cr": ("fsdp", "tensor"),
    # mamba2
    "in_proj": ("fsdp", None), "out_proj": ("tensor", "fsdp"),
    # latent attention: the latent is shared by every head, its
    # decompression is per head
    "wkv_a": ("fsdp", None), "wkv_b": ("fsdp", "tensor"),
    # embeddings / head
    "embed": ("vocab", "fsdp"), "head": ("fsdp", "vocab"),
    "router": (None, None),
}

MOE_EXPERT_LEAVES = {"w_gate", "w_up", "w_down"}


def _leaf_logical(path, leaf, cfg: ModelConfig):
    name = path[-1]
    ndim = leaf.ndim if not isinstance(leaf, QTensor) else len(leaf.shape) + \
        (leaf.packed.ndim - 2)
    if name not in PARAM_RULES:
        return (None,) * _leaf_ndim(leaf)
    rule = PARAM_RULES[name]
    n = _leaf_ndim(leaf)
    lead = n - 2
    lead_names: list = [None] * lead
    # stacked MoE experts: (L, E, in, out) or (E, in, out) -> expert dim
    if cfg.family == "moe" and name in MOE_EXPERT_LEAVES and lead >= 1:
        lead_names[-1] = "expert"
        # EP over the tensor axis + FSDP over data on the reduction dim;
        # shard_map all-gathers the fsdp dim at entry (ZeRO-3 semantics)
        rule = ("fsdp", None)
    return tuple(lead_names) + rule


def _leaf_ndim(leaf):
    if isinstance(leaf, QTensor):
        return leaf.packed.ndim
    return leaf.ndim


def _qtensor_spec(mesh, qt: QTensor, logical, overrides=None) -> QTensor:
    """Spec pytree for a QTensor: packed/scale/zero (+act_scale) children."""
    lead = logical[:-2]
    in_l, out_l = logical[-2], logical[-1]
    packed_spec = resolve_spec(mesh, lead + (in_l, out_l), qt.packed.shape,
                               overrides)
    scale_spec = resolve_spec(mesh, lead + (None, out_l), qt.scale.shape,
                              overrides)
    zero_spec = resolve_spec(mesh, lead + (None, out_l), qt.zero.shape,
                             overrides)
    act_spec = (resolve_spec(mesh, lead + (None,), qt.act_scale.shape,
                             overrides)
                if qt.act_scale is not None else None)
    return QTensor(packed=NamedSharding(mesh, packed_spec),
                   scale=NamedSharding(mesh, scale_spec),
                   zero=NamedSharding(mesh, zero_spec),
                   bits=qt.bits, group_size=qt.group_size, shape=qt.shape,
                   act_scale=(NamedSharding(mesh, act_spec)
                              if act_spec is not None else None))


def param_shardings(mesh, params, cfg: ModelConfig, overrides=None):
    """NamedSharding pytree matching ``params`` (dict tree, QTensor-aware).

    ``overrides`` remaps logical axes — e.g. {"fsdp": ()} for serving, where
    weights must be TP-resident (an FSDP all-gather per decode step would
    dominate the collective roofline; see EXPERIMENTS.md §Perf)."""
    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        if isinstance(node, QTensor):
            return _qtensor_spec(mesh, node, _leaf_logical(path, node, cfg),
                                 overrides)
        logical = _leaf_logical(path, node, cfg)
        return NamedSharding(mesh, resolve_spec(mesh, logical, node.shape,
                                                overrides))
    return walk(params, ())


# --------------------------------------------------------------------------
# ParamSpec: the reconstruction stack's tensor-parallel placement contract
# --------------------------------------------------------------------------

# TesseraQ per-linear reconstruction state layouts (tesseraq._leaf_state):
# rounding variables and their frozen companions live in the GROUPED weight
# layout, the DST/scale family in the per-group layout.
RECON_GROUPED_KEYS = ("nu", "hard", "base")     # (..., ng, g, out)
RECON_GROUPVEC_KEYS = ("v", "scale", "zero")    # (..., ng, out)


def recon_split(name: str) -> Optional[str]:
    """Which weight channel a reconstruction leaf splits over the TP axis:
    ``"out"`` for output-channel-sharded linears (q/k/v/gate/up — their
    ``PARAM_RULES`` orientation puts ``tensor`` on the out dim), ``"in"``
    for input-channel-sharded ones (o/down), None for everything else."""
    rule = PARAM_RULES.get(name)
    if not rule or len(rule) < 2:
        return None
    if rule[-1] == "tensor":
        return "out"
    if rule[0] == "tensor":
        return "in"
    return None


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Tensor-parallel placement contract for block reconstruction.

    One object per mesh answers, for every per-block array the
    reconstruction stack carries — the weight itself, the rounding/DST
    variables (``nu``/``v``), their frozen companions (``hard``/``base``/
    ``scale``/``zero``/``act_scale``) and, structurally, the Adam moments —
    *which dim, if any, is sharded over* ``tp_axis(mesh)``:

      * out-split leaves (wq/wk/wv/w_gate/w_up, …): the ``out`` dim — last
        dim of the weight, of the grouped ``nu`` layout, and of the
        per-group ``scale``/``v`` layout.
      * in-split leaves (wo/w_down, …): the ``in`` dim — dim -2 of the
        weight, the group-count dim (-3) of ``nu``, dim -2 of ``scale``,
        and the only dim of ``act_scale`` (quant groups tile the in dim
        contiguously, so the three gathers concatenate consistently).

    Any dim that does not divide by the TP degree falls back to
    replication per leaf (``P()``), the same elastic-scaling contract as
    ``resolve_spec`` — the engine's gather/scatter treats a spec with no TP
    axis as a no-op, so mixed sharded/replicated blocks stay correct.
    ``pipeline.quantize_model`` (capture-forward weight placement),
    ``capture`` (stream placement next to them) and
    ``recon_engine.ReconstructionEngine`` (shard_map in/out specs +
    per-step gather/scatter dims) all consume the same object, so the
    placement never has to be re-derived — and at TP degree 1 every spec
    degenerates to the replicated layout, which is what keeps
    ``engine="sharded"`` bit-identical to ``engine="device"`` there."""

    mesh: Any
    axis: Optional[str]
    size: int

    @classmethod
    def for_mesh(cls, mesh) -> "ParamSpec":
        return cls(mesh, tp_axis(mesh) if mesh is not None else None,
                   tp_size(mesh))

    @classmethod
    def for_serving(cls, mesh, cfg: ModelConfig) -> "ServeSpec":
        """The serve-time side of the contract: same mesh/axis/degree
        resolution, grown with the family split tables, cfg/param
        localization and cache placement the serving stack needs (see
        :class:`ServeSpec`)."""
        return ServeSpec.for_mesh(mesh, cfg)

    @property
    def active(self) -> bool:
        return self.axis is not None

    def _split_at(self, ndim: int, dim: int, extent: int) -> P:
        if (self.axis is None or ndim + dim < 0
                or extent % max(self.size, 1)):
            return P()
        spec = [None] * ndim
        spec[dim] = self.axis
        return P(*spec)

    def weight_spec(self, name: str, shape) -> P:
        """Spec for a quantizable weight leaf ``(..., in, out)``."""
        split = recon_split(name)
        if split == "out":
            return self._split_at(len(shape), -1, shape[-1])
        if split == "in" and len(shape) >= 2:
            return self._split_at(len(shape), -2, shape[-2])
        return P()

    def state_spec(self, name: str, key: str, shape) -> P:
        """Spec for one reconstruction-state array of leaf ``name``."""
        split = recon_split(name)
        if split is None:
            return P()
        ndim = len(shape)
        if key in RECON_GROUPED_KEYS and ndim >= 3:
            dim = -1 if split == "out" else -3
        elif key in RECON_GROUPVEC_KEYS and ndim >= 2:
            dim = -1 if split == "out" else -2
        elif key == "act_scale" and ndim >= 1 and split == "in":
            dim = -1
        else:
            return P()
        return self._split_at(ndim, dim, shape[dim])

    def block_specs(self, bp):
        """Spec pytree matching a raw block-param tree (non-quantizable
        leaves — norms, routers — replicated)."""
        def walk(node, path):
            if isinstance(node, dict):
                return {k: walk(v, path + (k,)) for k, v in node.items()}
            if node is None or not hasattr(node, "shape"):
                return P()
            return self.weight_spec(path[-1], node.shape)
        return walk(bp, ())

    def state_specs(self, states):
        """Spec pytree matching a ``{path: {key: array}}`` reconstruction
        state tree (``None`` leaves — absent act_scale — mirrored)."""
        return {
            p: {k: (None if v is None
                    else self.state_spec(p[-1], k, v.shape))
                for k, v in st.items()}
            for p, st in states.items()}

    def place_block(self, bp):
        """Device_put a block-param tree per its ``block_specs`` — the
        capture-forward placement ``quantize_model`` applies so the FP
        target forwards partition over the TP axis too."""
        if not self.active:
            return bp
        specs = self.block_specs(bp)
        return jax.tree_util.tree_map(
            lambda leaf, s: jax.device_put(
                leaf, NamedSharding(self.mesh, s)), bp, specs)


# --------------------------------------------------------------------------
# activations
# --------------------------------------------------------------------------

def make_sharder(mesh, overrides=None):
    def shard(x, names):
        if x.ndim != len(names):
            return x
        spec = resolve_spec(mesh, tuple(names), x.shape, overrides)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return shard


def batch_shardings(mesh, batch_struct):
    """Batch dicts: shard dim 0 over the DP axes."""
    dp = dp_axes(mesh)

    def one(leaf):
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        spec = [None] * leaf.ndim
        if leaf.shape[0] % _axis_size(mesh, dp) == 0 and dp:
            spec[0] = dp if len(dp) > 1 else dp[0]
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map(one, batch_struct)


def cache_shardings(mesh, cache_struct, cfg: ModelConfig):
    """KV / state caches: (L, B, ...) -> batch over DP; heads over TP when
    divisible (GQA with few KV heads falls back to replication)."""
    dp = dp_axes(mesh)
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    tp = "model" if "model" in mesh.axis_names else None

    def one(leaf):
        spec = [None] * leaf.ndim
        if leaf.ndim >= 2:
            bdim = 1  # leading dim is stacked layers/sites
            if leaf.shape[bdim] % _axis_size(mesh, dp) == 0 and dp:
                spec[bdim] = dp_spec
        if leaf.ndim >= 4 and tp:
            # KV caches: (L,B,S,H,D) -> heads at -2; states: (L,B,H,K,V) -> 2
            hdim = leaf.ndim - 2
            if leaf.shape[hdim] % mesh.shape[tp] == 0:
                spec[hdim] = tp
            elif leaf.ndim == 5 and leaf.shape[2] % mesh.shape[tp] == 0:
                # GQA with kv_heads < TP degree: shard the *sequence* dim —
                # decode's row write is a scatter batched over slots and
                # its softmax a single row, which partition over seq with
                # only two small psums (§Perf iteration A1/A3)
                spec[2] = tp
            elif leaf.ndim == 5 and leaf.shape[-1] % mesh.shape[tp] == 0:
                spec[-1] = tp
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map(one, cache_struct)


# --------------------------------------------------------------------------
# ServeSpec: the serving stack's tensor-parallel placement contract
# --------------------------------------------------------------------------
#
# Serve-time TP is shard_map-based (the packed QTensor leaves must reach the
# kernels as LOCAL shards, not GSPMD-annotated global arrays): each family's
# prefill/decode step runs inside shard_map over ``tp_axis(mesh)`` with
# per-leaf specs derived here.  The split tables are FAMILY-keyed because
# leaf names collide across families with different layouts (rwkv's time-mix
# ``wk``/``wv`` are (d, d) mixers followed by a GLOBAL per-head group norm —
# sharding them like attention projections would be wrong, so rwkv shards
# only its channel-mix pair).
#
# Feasibility is decided per ATOMIC GROUP, not per leaf: an out-split
# producer and its in-split consumer must agree (wo consumes the local heads
# wq/wk/wv produced; w_down consumes the local d_ff w_gate/w_up produced),
# so if ANY member of a group cannot split — head counts or a QTensor's
# group-count/packed-row dims not dividing the TP degree — the WHOLE group
# falls back to replicated, the same elastic-scaling contract as
# ``resolve_spec``/``ParamSpec``.  Embedding/unembed stay replicated by
# design: vocab sharding would add an all-gather per step, and the serve
# HLO contract permits only all-reduce collectives (tools/reprolint --hlo).

# leaf name -> split ("out" | "in" | "expert"), per family.  Absent names
# (norms, routers, rwkv time-mix, mamba in/out_proj — the latter consumed
# via fixed-offset jnp.split) replicate.
SERVE_SPLIT_TABLES = {
    "dense": {"wq": "out", "wk": "out", "wv": "out", "wo": "in",
              "w_gate": "out", "w_up": "out", "w_down": "in"},
    "moe": {"wq": "out", "wk": "out", "wv": "out", "wo": "in",
            "w_gate": "expert", "w_up": "expert", "w_down": "expert"},
    "encdec": {"wq": "out", "wk": "out", "wv": "out", "wo": "in",
               "w_up": "out", "w_down": "in"},
    "rwkv": {"ck": "out", "cv": "in"},
}
SERVE_SPLIT_TABLES["vlm"] = SERVE_SPLIT_TABLES["dense"]
SERVE_SPLIT_TABLES["hybrid"] = SERVE_SPLIT_TABLES["dense"]

# atomic fallback groups per family (frozensets of leaf names)
SERVE_GROUPS = {
    "dense": (frozenset({"wq", "wk", "wv", "wo"}),
              frozenset({"w_gate", "w_up", "w_down"})),
    "moe": (frozenset({"wq", "wk", "wv", "wo"}),
            frozenset({"w_gate", "w_up", "w_down"})),
    "encdec": (frozenset({"wq", "wk", "wv", "wo"}),
               frozenset({"w_up", "w_down"})),
    "rwkv": (frozenset({"ck", "cv"}),),
}
SERVE_GROUPS["vlm"] = SERVE_GROUPS["dense"]
SERVE_GROUPS["hybrid"] = SERVE_GROUPS["dense"]

# the group whose sharding implies head-local attention (cfg/cache localize)
_ATTN_GROUP_MEMBER = "wq"


def _split_ok(leaf, split: str, tp: int) -> bool:
    """Can ``leaf`` split ``split``-wise over a TP degree of ``tp``?

    QTensor divisibility covers every K-keyed operand at once: an in-split
    shard must take whole quant groups (group-count dim ``ng % tp``) AND
    whole packed container rows (``(K // ppb) % tp``), or the kernels' padded
    dequant contract breaks on the shard boundary."""
    if tp <= 1:
        return True
    if isinstance(leaf, QTensor):
        K, N = leaf.shape[-2], leaf.shape[-1]
        ppb = PACK_FACTOR[leaf.bits]
        ng = leaf.scale.shape[-2]
        if split == "out":
            return N % tp == 0
        if split == "in":
            return ng % tp == 0 and (K // ppb) % tp == 0
        if split == "expert":
            return leaf.packed.ndim >= 3 and leaf.packed.shape[-3] % tp == 0
        return False
    if getattr(leaf, "ndim", 0) < 2:
        return False
    if split == "out":
        return leaf.shape[-1] % tp == 0
    if split == "in":
        return leaf.shape[-2] % tp == 0
    if split == "expert":
        return leaf.ndim >= 3 and leaf.shape[-3] % tp == 0
    return False


def serve_plan(cfg: ModelConfig, params, tp: int) -> dict:
    """The serve placement decision: ``{leaf name: split}`` for every leaf
    that SHARDS over the TP axis (absent = replicated).

    Pure function of (family, leaf shapes/QTensor layouts, tp) — computable
    at trace time inside a jitted step (QTensor aux and shapes are static)
    and directly pinnable by tests.  Group atomicity: the attention group
    additionally needs ``num_heads`` and ``num_kv_heads`` divisible by
    ``tp`` (the forward reshapes heads), the MoE expert group needs the
    expert dim divisible; W2/W3 grouped codes whose group-count dim does
    not divide ``tp`` push their whole group back to replicated."""
    if tp < 1:
        raise ValueError(f"serve_plan: TP degree must be >= 1, got {tp}")
    table = SERVE_SPLIT_TABLES.get(cfg.family, SERVE_SPLIT_TABLES["dense"])
    groups = SERVE_GROUPS.get(cfg.family, SERVE_GROUPS["dense"])

    found: dict = {}

    def walk(node, path):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, path + (k,))
            return
        name = path[-1]
        if name in table:
            found.setdefault(name, []).append(node)

    walk(params, ())
    plan: dict = {}
    for group in groups:
        members = sorted(n for n in group if n in found)
        if not members:
            continue
        ok = all(_split_ok(leaf, table[n], tp)
                 for n in members for leaf in found[n])
        if _ATTN_GROUP_MEMBER in group:
            ok = ok and cfg.num_heads % tp == 0 \
                and cfg.num_kv_heads % tp == 0
        if ok:
            for n in members:
                plan[n] = table[n]
    return plan


def _localize_qtensor(qt: QTensor) -> QTensor:
    """Rebuild a QTensor's STATIC aux from its (possibly shard-local) array
    shapes.  Inside shard_map the packed/scale/zero children are local but
    the aux (bits, group_size, logical shape) rides the treedef unchanged
    from the global tree — the kernels' row-count validation would reject
    the shard.  Out-split shrinks ``out``; in-split shrinks ``in`` by whole
    groups (``group_size`` itself is preserved: ``ng % tp == 0`` is a
    feasibility precondition); expert splits only touch leading dims, which
    never live in ``shape``."""
    ppb = PACK_FACTOR[qt.bits]
    k_local = qt.packed.shape[-2] * ppb
    n_local = qt.packed.shape[-1]
    if (k_local, n_local) == tuple(qt.shape[-2:]):
        return qt
    return QTensor(packed=qt.packed, scale=qt.scale, zero=qt.zero,
                   bits=qt.bits, group_size=qt.group_size,
                   shape=(k_local, n_local), act_scale=qt.act_scale)


def _spec_at(ndim: int, dim: int, axis) -> P:
    spec = [None] * ndim
    spec[dim] = axis
    return P(*spec)


def _serve_qtensor_spec(qt: QTensor, split, axis) -> QTensor:
    """shard_map spec node for a QTensor leaf: same treedef (aux included),
    PartitionSpec children."""
    rep = P()
    if split == "out":
        packed = _spec_at(qt.packed.ndim, -1, axis)
        scale = _spec_at(qt.scale.ndim, -1, axis)
        zero = _spec_at(qt.zero.ndim, -1, axis)
        act = rep if qt.act_scale is not None else None
    elif split == "in":
        packed = _spec_at(qt.packed.ndim, -2, axis)
        scale = _spec_at(qt.scale.ndim, -2, axis)
        zero = _spec_at(qt.zero.ndim, -2, axis)
        act = (_spec_at(qt.act_scale.ndim, -1, axis)
               if qt.act_scale is not None else None)
    elif split == "expert":
        packed = _spec_at(qt.packed.ndim, -3, axis)
        scale = _spec_at(qt.scale.ndim, -3, axis)
        zero = _spec_at(qt.zero.ndim, -3, axis)
        act = (_spec_at(qt.act_scale.ndim, -2, axis)
               if qt.act_scale is not None else None)
    else:
        packed = scale = zero = rep
        act = rep if qt.act_scale is not None else None
    return QTensor(packed=packed, scale=scale, zero=zero, bits=qt.bits,
                   group_size=qt.group_size, shape=qt.shape, act_scale=act)


def serve_param_specs(params, plan: dict, axis):
    """shard_map ``in_specs`` pytree for a param tree under ``plan``.

    QTensor leaves become QTensor spec NODES (matching aux, PartitionSpec
    children) so the spec tree's treedef matches the params'."""
    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        split = plan.get(path[-1]) if axis is not None else None
        if isinstance(node, QTensor):
            return _serve_qtensor_spec(node, split, axis)
        if node is None:
            return None
        if split == "out":
            return _spec_at(node.ndim, -1, axis)
        if split == "in":
            return _spec_at(node.ndim, -2, axis)
        if split == "expert":
            return _spec_at(node.ndim, -3, axis)
        return P()
    return walk(params, ())


def localize_serve_params(params, plan: dict, axis):
    """Inside-shard_map view of the param tree: QTensor aux rebuilt from the
    local array shapes, and in-split leaves wrapped in
    :class:`repro.models.layers.PsumWeight` so ``L.matmul`` adds the
    in-channel psum epilogue — the family forwards stay sharding-free."""
    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        split = plan.get(path[-1]) if axis is not None else None
        if isinstance(node, QTensor):
            node = _localize_qtensor(node) if split else node
        if split == "in":
            return PsumWeight(node, axis)
        return node
    return walk(params, ())


def localize_serve_cfg(cfg: ModelConfig, plan: dict, tp: int) -> ModelConfig:
    """Per-shard model config: head counts divided by the TP degree when the
    attention group is sharded (the forward reshapes q/k/v by them), with
    ``head_dim`` pinned to its resolved value so dividing ``num_heads`` does
    not silently change it.  ``d_ff`` never appears in a forward reshape and
    MoE ``num_experts`` stays GLOBAL (routing is over global expert ids;
    only the capacity gather is expert-local)."""
    if tp <= 1 or plan.get(_ATTN_GROUP_MEMBER) != "out":
        return cfg
    return cfg.replace(num_heads=cfg.num_heads // tp,
                       num_kv_heads=cfg.num_kv_heads // tp,
                       head_dim=cfg.resolved_head_dim)


def serve_cache_specs(cache_spec, cache, plan: dict, axis, tp: int):
    """shard_map specs for a family cache tree, keyed on the declared
    :class:`models.common.CacheSpec` leaf KIND:

      * token/fixed leaves (KV lanes ``(L, B, S, H, hd)``, paged pools
        ``(L, P, psz, H, hd)``, encdec cross caches) shard their KV-head
        dim — dim -2 in every in-tree layout — iff the attention group is
        sharded and the head count divides;
      * state leaves (rwkv shift/wkv, mamba conv/ssm) replicate: recurrent
        state channels are coupled through replicated mixers.

    Page tables / token / pos / active vectors replicate (specs for those
    ride in the step builder, not here)."""
    attn = plan.get(_ATTN_GROUP_MEMBER) == "out" and axis is not None

    def walk(tree, prefix=()):
        if isinstance(tree, dict):
            return {k: walk(v, prefix + (k,)) for k, v in tree.items()}
        ls = cache_spec.leaf("/".join(prefix))
        if (attn and ls.kind in (LEAF_TOKEN, LEAF_FIXED)
                and tree.ndim >= 2 and tree.shape[-2] % tp == 0):
            return _spec_at(tree.ndim, -2, axis)
        return P()
    return walk(cache)


@dataclasses.dataclass(frozen=True)
class ServeSpec:
    """One sharding contract from :class:`ParamSpec` to the decode kernels.

    The serving counterpart of ``ParamSpec`` (construct via
    ``ParamSpec.for_serving(mesh, cfg)`` or :meth:`for_mesh`): one object
    answers, for a family's packed params, its cache and its per-shard
    config, how serve-time placement works over ``tp_axis(mesh)``.
    ``launch.steps.make_serve_steps(tp_shard=True)`` is the sole consumer
    wiring it into shard_map; everything here is a pure function of static
    shapes so the whole contract resolves at trace time."""

    mesh: Any
    axis: Optional[str]
    size: int
    cfg: ModelConfig

    @classmethod
    def for_mesh(cls, mesh, cfg: ModelConfig) -> "ServeSpec":
        return cls(mesh, tp_axis(mesh) if mesh is not None else None,
                   tp_size(mesh), cfg)

    @property
    def active(self) -> bool:
        return self.axis is not None

    def plan(self, params) -> dict:
        return serve_plan(self.cfg, params, self.size)

    def local_cfg(self, plan: dict) -> ModelConfig:
        return localize_serve_cfg(self.cfg, plan, self.size)

    def param_specs(self, params, plan: dict):
        return serve_param_specs(params, plan, self.axis)

    def localize_params(self, params, plan: dict):
        return localize_serve_params(params, plan, self.axis)

    def cache_specs(self, cache_spec, cache, plan: dict):
        return serve_cache_specs(cache_spec, cache, plan, self.axis,
                                 self.size)

    # ---- explicit placement (transfer_guard-clean serving) -----------------
    # The shard-mapped steps declare in_specs, but jit dispatch RESHARDS any
    # operand not already committed to its contract placement — a full
    # device-0 -> mesh copy of the params EVERY step, which the serving
    # sanitizer's transfer_guard rightly rejects as an implicit transfer.
    # Callers place params/cache once, off the timed loop, with these.

    def shardings(self, spec_tree):
        """PartitionSpec tree -> NamedSharding tree (device_put targets)."""
        return jax.tree_util.tree_map(
            lambda s: jax.sharding.NamedSharding(self.mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))

    def place_params(self, params, plan: dict):
        """Commit the GLOBAL param tree to its contract placement (one
        explicit device_put; sharded leaves land split over the TP axis,
        the rest replicated across the mesh)."""
        if not self.active:
            return params
        return jax.device_put(params,
                              self.shardings(self.param_specs(params, plan)))

    def place_cache(self, cache_spec, cache, plan: dict):
        """Commit a freshly initialized cache tree to its contract
        placement (KV-head-sharded lanes, replicated state leaves)."""
        if not self.active:
            return cache
        return jax.device_put(
            cache, self.shardings(self.cache_specs(cache_spec, cache, plan)))

    def replicated(self):
        """Placement for mesh-replicated step operands (tokens, pos,
        active masks, page tables)."""
        return jax.sharding.NamedSharding(self.mesh, P())
