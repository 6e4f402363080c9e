"""Token-choice top-k MoE FFN with capacity-based dispatch.

Expert parallelism is TPU-adapted: instead of an a2a shuffle (the NCCL-era
pattern), tokens stay resident per data shard and are *replicated* across the
``model`` axis; each model shard capacity-gathers only the tokens routed to its
local experts, runs a batched (E_local, C, d)×(E_local, d, f) MXU matmul, and a
single ``psum`` over ``model`` combines expert outputs.  This trades one
all-reduce for two all-to-alls and keeps dispatch purely local — the better
deal on TPU ICI where reductions are native.

Two code paths share the same math:
  * ``ctx.ep_axis`` set  -> shard_map over the model axis (production)
  * ``ctx.ep_axis`` None -> single-shard local computation (tests / CPU)
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.qtensor import QTensor
from repro.launch import mesh as mesh_mod
from repro.models import layers as L
from repro.models.common import Ctx


def init_moe_ffn(cfg: ModelConfig, key, n_layers: int) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    e = cfg.moe.num_experts
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, (n_layers,) + shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    return {
        "router": w(ks[0], (d, e), d).astype(jnp.float32),
        "w_gate": w(ks[1], (e, d, f), d),
        "w_up": w(ks[2], (e, d, f), d),
        "w_down": w(ks[3], (e, f, d), f),
    }


def _route(x2d: jax.Array, router_w: jax.Array, top_k: int):
    """Returns (expert_idx (T,k), gate (T,k) fp32)."""
    logits = x2d.astype(jnp.float32) @ router_w.astype(jnp.float32)
    gate, idx = jax.lax.top_k(logits, top_k)
    gate = jax.nn.softmax(gate, axis=-1)              # normalize over chosen k
    return idx, gate


def _capacity(tokens: int, num_experts: int, top_k: int, cf: float) -> int:
    c = int(math.ceil(tokens * top_k / num_experts * cf))
    return max(8, -(-c // 8) * 8)                     # round up to 8


def _expert_compute(x2d, idx, gate, w_gate, w_up, w_down, *,
                    e_start: int, e_local: int, capacity: int, act_bits,
                    backend=None):
    """Capacity-gather tokens for experts [e_start, e_start+e_local), run the
    batched FFN, and scatter-combine.  Pure function used by both EP paths.

    x2d: (T, d); idx/gate: (T, k); w_*: (e_local, d, f) / (e_local, f, d).
    """
    T, d = x2d.shape
    k = idx.shape[1]
    flat_e = idx.reshape(-1)                                    # (T*k,)
    local = (flat_e >= e_start) & (flat_e < e_start + e_local)
    local_e = jnp.where(local, flat_e - e_start, e_local)       # OOB -> dropped
    # position of each (token, choice) within its expert queue
    onehot = jax.nn.one_hot(local_e, e_local, dtype=jnp.int32)  # (T*k, E_l)
    pos = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.sum(pos * onehot, axis=1)                         # (T*k,)
    keep = local & (pos < capacity)
    slot = jnp.where(keep, local_e * capacity + pos, e_local * capacity)

    buf = jnp.zeros((e_local * capacity + 1, d), x2d.dtype)
    tok_idx = jnp.arange(T * k) // k
    buf = buf.at[slot].set(x2d[tok_idx])                        # gather into slots
    h = buf[:-1].reshape(e_local, capacity, d)
    if act_bits:
        h = L.fake_quant_act(h, act_bits)

    g = (jax.nn.silu(L.expert_matmul(h, w_gate, backend))
         * L.expert_matmul(h, w_up, backend))
    if act_bits:
        g = L.fake_quant_act(g, act_bits)
    out = L.expert_matmul(g, w_down, backend)                   # (E_l, C, d)

    out_flat = jnp.concatenate(
        [out.reshape(e_local * capacity, d), jnp.zeros((1, d), out.dtype)], 0)
    contrib = out_flat[slot] * gate.reshape(-1)[:, None].astype(out.dtype)
    contrib = jnp.where(keep[:, None], contrib, 0)
    y = jnp.sum(contrib.reshape(T, k, d), axis=1)
    return y


def moe_ffn(mp: dict, x: jax.Array, cfg: ModelConfig, ctx: Ctx) -> jax.Array:
    """x: (B, S, d) -> (B, S, d)."""
    B, S, d = x.shape
    e, k = cfg.moe.num_experts, cfg.moe.top_k
    x2d = x.reshape(B * S, d)
    idx, gate = _route(x2d, mp["router"], k)

    if ctx.ep_inner is not None:
        # ---- inner expert parallelism: already inside a serve-time
        # shard_map (launch.sharding.ServeSpec), expert weights arrive
        # pre-sliced over ``ctx.ep_inner`` — no nested shard_map, just the
        # local-expert compute + psum.  Routing stays over GLOBAL expert
        # ids; capacity matches the TP=1 value (per-replica token count).
        ax = ctx.ep_inner
        wg = mp["w_gate"]
        arr = wg.packed if isinstance(wg, QTensor) else wg
        e_local = int(arr.shape[-3])
        sid = jax.lax.axis_index(ax)
        cap = _capacity(B * S, e, k, cfg.moe.capacity_factor)
        y = _expert_compute(x2d, idx, gate, mp["w_gate"], mp["w_up"],
                            mp["w_down"], e_start=sid * e_local,
                            e_local=e_local, capacity=cap,
                            act_bits=ctx.act_bits,
                            backend=ctx.kernel_backend)
        return jax.lax.psum(y, ax).reshape(B, S, d)

    if ctx.ep_axis is None:
        cap = _capacity(B * S, e, k, cfg.moe.capacity_factor)
        y = _expert_compute(x2d, idx, gate, mp["w_gate"], mp["w_up"],
                            mp["w_down"], e_start=0, e_local=e, capacity=cap,
                            act_bits=ctx.act_bits,
                            backend=ctx.kernel_backend)
        return y.reshape(B, S, d)

    # ---- expert-parallel path: shard_map over the EP mesh axis -------------
    mesh = ctx.mesh
    ax = ctx.ep_axis
    n_shards = mesh.shape[ax]
    assert e % n_shards == 0, f"{e} experts not divisible by {n_shards} EP shards"
    e_local = e // n_shards
    dp_degree = mesh_mod.dp_size(mesh, ctx.dp_axes)
    # capacity is per data shard: each shard routes its own resident tokens
    cap = _capacity(B * S // dp_degree, e, k, cfg.moe.capacity_factor)
    P = jax.sharding.PartitionSpec
    dp = tuple(ctx.dp_axes) or None

    def shard_fn(x2d, idx, gate, wg, wu, wd):
        sid = jax.lax.axis_index(ax)
        y = _expert_compute(x2d, idx, gate, wg, wu, wd,
                            e_start=sid * e_local, e_local=e_local,
                            capacity=cap, act_bits=ctx.act_bits,
                            backend=ctx.kernel_backend)
        return jax.lax.psum(y, ax)

    y = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(dp), P(dp), P(dp), P(ax), P(ax), P(ax)),
        out_specs=P(dp), check_vma=False,
    )(x2d, idx, gate, mp["w_gate"], mp["w_up"], mp["w_down"])
    return y.reshape(B, S, d)
