"""Jit-ready train/prefill/decode step builders over the production mesh,
plus ShapeDtypeStruct input specs for the dry-run (no allocation).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, QuantConfig, ShapeConfig
from repro.core.blocks import QUANT_LEAF_NAMES
from repro.core.qtensor import PACK_FACTOR, QTensor
from repro.core.quantizer import resolve_group
from repro.launch.sharding import batch_shardings, param_shardings
from repro.models import get_model
from repro.models.common import (Ctx, _get_leaf, _set_leaf, page_write_tokens)
from repro.models.common import make_ctx as _common_make_ctx
from repro.optim.adam import AdamW, clip_by_global_norm
from repro.optim.compression import compress_decompress, init_error


def make_ctx(cfg: ModelConfig, mesh=None, *, act_bits=None, decode=False,
             attn_chunk=512, remat=None, shard_overrides=None,
             kernel_backend=None, **overrides) -> Ctx:
    """Launch-layer shim over ``models.common.make_ctx`` — THE blessed Ctx
    constructor (kernel_backend/kv_bits/page_size validation, unknown-kwarg
    rejection) — keeping this module's historical positional-``mesh``
    signature for its many call sites.
    (shard_overrides: logical-axis remaps, e.g. {"seq": ("model",)} for
    attention sequence parallelism — the worst-fraction hillclimb knob)"""
    return _common_make_ctx(cfg, mesh=mesh, decode=decode,
                            shard_overrides=shard_overrides,
                            act_bits=act_bits, attn_chunk=attn_chunk,
                            remat=remat, kernel_backend=kernel_backend,
                            **overrides)


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrainHarness:
    cfg: ModelConfig
    step_fn: Any                 # (params, opt_state, batch) -> (p, s, metrics)
    init_params: Any
    init_opt: Any
    param_sharding: Any = None
    opt_sharding: Any = None
    batch_sharding: Any = None


def make_train_harness(cfg: ModelConfig, mesh=None, *, lr=3e-4,
                       grad_clip: float = 1.0,
                       grad_compression: bool = False,
                       attn_chunk: int = 512,
                       microbatches: int = 1,
                       seq_parallel: bool = False,
                       extra_overrides=None) -> TrainHarness:
    model = get_model(cfg)
    overrides = dict(extra_overrides or {})
    if seq_parallel:
        overrides["res_seq"] = ("model",)
    overrides = overrides or None
    ctx = make_ctx(cfg, mesh, attn_chunk=attn_chunk,
                   shard_overrides=overrides)
    opt = AdamW(lr=lr, state_dtype=jnp.dtype(cfg.optimizer_dtype))

    def init_opt(params):
        state = opt.init(params)
        if grad_compression:
            return {"adam": state, "ef": init_error(params)}
        return {"adam": state}

    def grad_of(params, batch):
        return jax.value_and_grad(model.loss_fn)(params, batch, ctx)

    def step_fn(params, opt_state, batch):
        if microbatches > 1:
            # gradient accumulation: scan over microbatches; activation
            # memory scales by 1/M at the cost of M sequential passes
            def split(leaf):
                return leaf.reshape(microbatches, leaf.shape[0] // microbatches,
                                    *leaf.shape[1:])
            ubatches = jax.tree_util.tree_map(split, batch)
            acc_dt = jnp.dtype(cfg.optimizer_dtype)

            def ub(carry, ubatch):
                l_acc, g_acc = carry
                loss, grads = grad_of(params, ubatch)
                g_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(acc_dt), g_acc, grads)
                return (l_acc + loss, g_acc), ()

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, acc_dt), params)
            (loss, grads), _ = jax.lax.scan(ub, (jnp.float32(0.0), g0),
                                            ubatches)
            loss = loss / microbatches
            grads = jax.tree_util.tree_map(lambda g: g / microbatches, grads)
        else:
            loss, grads = grad_of(params, batch)
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        if grad_compression:
            grads, new_ef = compress_decompress(grads, opt_state["ef"])
        new_p, new_adam = opt.update(grads, opt_state["adam"], params)
        new_state = {"adam": new_adam}
        if grad_compression:
            new_state["ef"] = new_ef
        return new_p, new_state, {"loss": loss, "grad_norm": gnorm}

    return TrainHarness(cfg, step_fn, model.init_params, init_opt)


def jit_train_step(harness: TrainHarness, mesh, params_struct, batch_struct):
    cfg = harness.cfg
    pspec = param_shardings(mesh, params_struct, cfg)
    opt_struct = jax.eval_shape(harness.init_opt, params_struct)
    ospec = opt_sharding_like(mesh, opt_struct, params_struct, cfg)
    bspec = batch_shardings(mesh, batch_struct)
    return jax.jit(
        harness.step_fn,
        in_shardings=(pspec, ospec, bspec),
        out_shardings=(pspec, ospec, None),
        donate_argnums=train_donate_argnums(0, 1),
    ), (pspec, ospec, bspec)


def opt_sharding_like(mesh, opt_struct, params_struct, cfg):
    """Adam m/v (and EF buffers) shard exactly like their parameters
    (ZeRO-1 falls out of the fsdp axis in the param rules)."""
    pspec = param_shardings(mesh, params_struct, cfg)

    def walk(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k in ("adam",):
                    out[k] = type(v)(
                        step=jax.sharding.NamedSharding(
                            mesh, jax.sharding.PartitionSpec()),
                        m=pspec, v=pspec)
                elif k == "ef":
                    out[k] = pspec
                else:
                    out[k] = walk(v)
            return out
        return node
    return walk(opt_struct)


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

def quantize_param_struct(params_struct, cfg: ModelConfig, qcfg: QuantConfig):
    """Map an eval_shape param tree to its QTensor deployment layout
    (ShapeDtypeStructs only — used by the dry-run for serve_step)."""
    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        name = path[-1]
        if name in QUANT_LEAF_NAMES and node.ndim >= 2 and node.shape[-2] >= 2:
            *lead, in_f, out_f = node.shape
            g = resolve_group(in_f, qcfg.group_size)
            ppb = PACK_FACTOR[qcfg.bits]
            if in_f % ppb:
                return node
            return QTensor(
                packed=jax.ShapeDtypeStruct((*lead, in_f // ppb, out_f),
                                            jnp.uint8),
                scale=jax.ShapeDtypeStruct((*lead, in_f // g, out_f),
                                           jnp.float32),
                zero=jax.ShapeDtypeStruct((*lead, in_f // g, out_f),
                                          jnp.float32),
                bits=qcfg.bits, group_size=g, shape=(in_f, out_f),
                act_scale=None)
        return node
    return walk(params_struct, ())


def make_serve_steps(cfg: ModelConfig, mesh=None, *, act_bits=None,
                     attn_chunk: int = 512, extra_overrides=None,
                     kv_bits=None, kernel_backend=None,
                     decode_attn_chunk: int = 1 << 30, page_size: int = 0,
                     tp_shard: bool = False):
    """``kernel_backend`` ("xla" | "pallas" | None = env/default) selects the
    QTensor matmul path for BOTH the prefill and decode steps — this is the
    explicit per-run dispatch the serving launcher and benchmarks use.

    ``decode_attn_chunk`` defaults to un-chunked decode attention (single
    scan trip — the score row is tiny and GSPMD can then partition the
    softmax reduction over a sequence-sharded KV cache); the dense-vs-paged
    pallas parity tests pin it to ``page_size`` so both kernels walk the
    same chunk grid.  ``page_size > 0`` builds paged-cache steps: prefill
    accepts ``start_pos``/``ptab`` (chunked prefill over a page table) and
    decode accepts ``ptab``.

    ``tp_shard=True`` routes both steps through the serve-time
    tensor-parallel contract (:class:`repro.launch.sharding.ServeSpec`):
    shard_map over ``tp_axis(mesh)`` with per-leaf specs derived from the
    contract, packed QTensor leaves reaching the kernels as LOCAL shards.
    This is opt-in — the default ``mesh=`` path keeps today's GSPMD
    annotation-only behavior (used by the dry-run's serve sharding cells)."""
    if tp_shard:
        if mesh is None:
            raise ValueError("make_serve_steps: tp_shard=True requires a "
                             "mesh (build one with launch.mesh.serve_mesh)")
        if extra_overrides:
            raise ValueError("make_serve_steps: shard_overrides do not "
                             "compose with tp_shard=True (the ServeSpec "
                             "contract owns serve-time placement)")
        return _make_tp_serve_steps(
            cfg, mesh, act_bits=act_bits, attn_chunk=attn_chunk,
            kv_bits=kv_bits, kernel_backend=kernel_backend,
            decode_attn_chunk=decode_attn_chunk, page_size=page_size)
    model = get_model(cfg)
    ctx = make_ctx(cfg, mesh, act_bits=act_bits, attn_chunk=attn_chunk,
                   remat=False, shard_overrides=extra_overrides,
                   kernel_backend=kernel_backend, kv_bits=kv_bits,
                   page_size=page_size)
    dctx = make_ctx(cfg, mesh, act_bits=act_bits,
                    attn_chunk=decode_attn_chunk,
                    remat=False, decode=True, shard_overrides=extra_overrides,
                    kernel_backend=kernel_backend, kv_bits=kv_bits,
                    page_size=page_size)

    def prefill_step(params, batch, cache, start_pos=0, ptab=None):
        return model.prefill(params, batch, cache, ctx,
                             start_pos=start_pos, ptab=ptab)

    def decode_step(params, cache, tokens, pos, active=None, ptab=None):
        return model.decode_step(params, cache, tokens, pos, dctx,
                                 active=active, ptab=ptab)

    return model, prefill_step, decode_step


def _make_tp_serve_steps(cfg: ModelConfig, mesh, *, act_bits=None,
                         attn_chunk: int = 512, kv_bits=None,
                         kernel_backend=None,
                         decode_attn_chunk: int = 1 << 30,
                         page_size: int = 0):
    """Serve steps under the tensor-parallel contract.

    Both steps run the family forward inside ``jax.shard_map`` over the
    FULL serve mesh: the ``model`` axis carries the contract's splits, any
    ``data`` axes replicate (P() specs).  Everything placement-related —
    the plan, the per-shard config, the spec trees — resolves at TRACE
    time from static shapes (``ServeSpec`` is a pure function of them), so
    the jitted step compiles to one shard_mapped program with no host
    round-trips.  Inside the body the param tree is LOCALIZED: QTensor aux
    rebuilt from shard shapes, in-split weights wrapped in ``PsumWeight``
    so ``L.matmul`` adds the psum epilogue — the family forwards never see
    sharding logic.  At TP=1 every spec is trivial and psum over the
    size-1 axis is the identity: bit-identical to the un-meshed path (the
    pinned ``tp_serve_parity`` guarantee)."""
    from jax.sharding import PartitionSpec as P

    from repro.launch import sharding as shp
    from repro.launch.mesh import validate_single_pod

    validate_single_pod(mesh, "make_serve_steps(tp_shard=True)")
    model = get_model(cfg)
    spec = shp.ServeSpec.for_mesh(mesh, cfg)
    ax = spec.axis
    if ax is None:
        raise ValueError("make_serve_steps: tp_shard=True needs a mesh "
                         "with a 'model' axis (launch.mesh.serve_mesh)")

    def replicate(tree):
        return jax.tree_util.tree_map(lambda _: P(), tree)

    def trace_ctx(params, *, decode):
        plan = spec.plan(params)
        lcfg = spec.local_cfg(plan)
        # the registry lambdas close over their cfg (head counts drive the
        # q/k/v reshapes), so the shard-local forward needs a model built
        # from the LOCALIZED config; the global `model` keeps describing
        # the global cache layout (init_cache / cache_spec)
        lmodel = model if lcfg is cfg else get_model(lcfg)
        ep_inner = ax if plan.get("w_gate") == "expert" else None
        ctx = make_ctx(lcfg, None, act_bits=act_bits,
                       attn_chunk=(decode_attn_chunk if decode
                                   else attn_chunk),
                       remat=False, decode=decode,
                       kernel_backend=kernel_backend, kv_bits=kv_bits,
                       page_size=page_size, ep_inner=ep_inner)
        return plan, ctx, lmodel

    def prefill_step(params, batch, cache, start_pos=0, ptab=None):
        plan, ctx, lmodel = trace_ctx(params, decode=False)
        pspecs = spec.param_specs(params, plan)
        cspecs = spec.cache_specs(model.cache_spec, cache, plan)
        start = jnp.asarray(start_pos, jnp.int32)

        def body(p, b, c, sp, pt):
            lp = spec.localize_params(p, plan)
            return lmodel.prefill(lp, b, c, ctx, start_pos=sp, ptab=pt)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspecs, replicate(batch), cspecs, P(),
                      replicate(ptab)),
            out_specs=(P(), cspecs), check_vma=False,
        )(params, batch, cache, start, ptab)

    def decode_step(params, cache, tokens, pos, active=None, ptab=None):
        plan, dctx, lmodel = trace_ctx(params, decode=True)
        pspecs = spec.param_specs(params, plan)
        cspecs = spec.cache_specs(model.cache_spec, cache, plan)

        def body(p, c, t, po, a, pt):
            lp = spec.localize_params(p, plan)
            return lmodel.decode_step(lp, c, t, po, dctx, active=a, ptab=pt)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspecs, cspecs, P(), P(), replicate(active),
                      replicate(ptab)),
            out_specs=(P(), cspecs), check_vma=False,
        )(params, cache, tokens, pos, active, ptab)

    return model, prefill_step, decode_step


def cache_donate_argnums(*argnums: int) -> tuple:
    """Donation argnums for serve-step cache buffers — the ONE place
    serve-path donation policy lives (the lock-step and scheduler step
    compilers both call it).  Unlike the recon engine's param/opt carries
    (which CPU XLA refuses to alias, hence the guard in
    ``adam.jitted_update``), KV/state caches alias cleanly on every
    backend INCLUDING CPU: no unusable-donation warnings, a measured
    ~15% decode win, and ``write_slot`` admission becomes an in-place
    slot update instead of a full cache copy."""
    return argnums


def train_donate_argnums(*argnums: int) -> tuple:
    """Donation argnums for train-step param/optimizer carries — the ONE
    place train-path donation policy lives.  Unlike the serve caches
    (``cache_donate_argnums``), CPU XLA cannot alias the param/Adam
    buffers, so donating them there only floods logs with
    unusable-donation warnings: donate on accelerators, skip on CPU (the
    same guard ``optim/adam.jitted_update`` applies inline)."""
    return argnums if jax.default_backend() != "cpu" else ()


def make_paged_install_step(model, *, page_size: int):
    """Admission step for the paged store, non-chunked path: move a B=1
    request cache (prefilled dense at full ``max_seq`` width — EXACTLY the
    computation dense admission runs, which is what makes paged admission
    trivially bit-identical) into the slot's pages.

    Token leaves scatter rows ``[0, plen)`` into the pool pages named by
    ``ptab_row``; state/fixed leaves take the classic ``write_slot`` path.
    ``plen`` is static (one jit specialization per distinct prefill length,
    the same compile cost profile as the per-length prefill itself)."""
    spec = model.cache_spec
    token_paths = set(spec.token_paths)

    def install(cache, c1, slot, ptab_row, *, plen: int):
        out = cache
        zero = jnp.zeros((1,), jnp.int32)
        for path, _ls in spec.leaves:
            src = _get_leaf(c1, path)
            dst = _get_leaf(out, path)
            if path in token_paths:
                # (lead, 1, max_seq, *tail) -> (lead, plen, *tail)
                vals = jax.lax.slice_in_dim(src, 0, plen, axis=2)[:, 0]
                new = jax.vmap(
                    lambda pool, v: page_write_tokens(
                        pool, v[None], ptab_row[None], zero, page_size)
                )(dst, vals)
            else:
                new = jax.lax.dynamic_update_slice_in_dim(
                    dst, src.astype(dst.dtype), slot, axis=spec.slot_axis)
            out = _set_leaf(out, path, new)
        return out

    return install


def make_sched_steps(cfg: ModelConfig, mesh=None, *, max_seq: int,
                     act_bits=None, attn_chunk: int = 512,
                     extra_overrides=None, kv_bits=None, kernel_backend=None,
                     decode_attn_chunk: int = 1 << 30, page_size: int = 0,
                     tp_shard: bool = False):
    """Step pair for the slot scheduler (``repro.launch.scheduler``).

    Returns ``(model, prefill_step, sched_decode_step)``.  The decode step
    wraps the family's ``decode_step`` with occupancy masking so ONE jit
    compilation (fixed slot count, ``active`` as a traced bool vector)
    serves every occupancy the scheduler passes through:

      * inactive slots write at position ``max_seq`` — out of range, so the
        masked cache write in ``models.common.update_cache`` is a no-op and
        a finished slot's KV state stops changing the moment it completes
        (recurrence families — rwkv/ssm state — ignore ``pos``; their slot
        state is simply dead weight until admission overwrites it whole);
      * the greedy next token is selected on device and frozen for inactive
        slots (``where(active, argmax, tok)``), as is ``pos`` — a finished
        request's token stream and write cursor never move again.

    Active rows see EXACTLY the arguments the plain serve loop passes
    (same pos, same kv_len), which is what makes scheduled decode
    bit-compatible with serving a request alone.
    """
    model, prefill_step, decode_step = make_serve_steps(
        cfg, mesh, act_bits=act_bits, attn_chunk=attn_chunk,
        extra_overrides=extra_overrides, kv_bits=kv_bits,
        kernel_backend=kernel_backend, decode_attn_chunk=decode_attn_chunk,
        page_size=page_size, tp_shard=tp_shard)

    def sched_decode_step(params, cache, tok, pos, active, ptab=None):
        write_pos = jnp.where(active, pos, max_seq)
        # occupancy reaches the kernel: the slot-aware decode attention
        # skips dead slots instead of computing-then-masking their rows.
        # (paged: write_pos == max_seq maps past the page table, where
        # page_write_tokens' sentinel index drops the write — the paged
        # analog of update_cache's out-of-range masked no-op)
        logits, cache = decode_step(params, cache, tok, write_pos,
                                    active=active, ptab=ptab)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        tok = jnp.where(active, nxt, tok)
        pos = jnp.where(active, pos + 1, pos)
        return logits, tok, pos, cache

    return model, prefill_step, sched_decode_step


# --------------------------------------------------------------------------
# dry-run input specs (ShapeDtypeStruct stand-ins, per arch x shape)
# --------------------------------------------------------------------------

def train_input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict:
    B, S = shape.global_batch, shape.seq_len
    toks = jax.ShapeDtypeStruct((B, S + 1), jnp.int32)
    batch = {"tokens": toks}
    if cfg.family == "encdec":
        F = cfg.frontend_len or S
        batch["frames"] = jax.ShapeDtypeStruct((B, F, cfg.d_model),
                                               jnp.dtype(cfg.dtype))
    if cfg.family == "vlm":
        batch["patches"] = jax.ShapeDtypeStruct(
            (B, cfg.num_patches, cfg.d_model), jnp.dtype(cfg.dtype))
        # patches + text = S tokens total
        batch["tokens"] = jax.ShapeDtypeStruct(
            (B, S - cfg.num_patches + 1), jnp.int32)
    return batch


def serve_input_specs(cfg: ModelConfig, shape: ShapeConfig,
                      kv_bits=None) -> Dict:
    """decode-step inputs: one new token against a seq_len KV cache."""
    model = get_model(cfg)
    B, S = shape.global_batch, shape.seq_len
    dt = jnp.int8 if kv_bits == 8 else jnp.bfloat16
    cache = jax.eval_shape(partial(model.init_cache, B, S, dtype=dt))
    return {
        "cache": cache,
        "tokens": jax.ShapeDtypeStruct((B,), jnp.int32),
        "pos": jax.ShapeDtypeStruct((B,), jnp.int32),
    }


def prefill_input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict:
    model = get_model(cfg)
    B, S = shape.global_batch, shape.seq_len
    cache = jax.eval_shape(partial(model.init_cache, B, S))
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    if cfg.family == "encdec":
        F = cfg.frontend_len or S
        batch["frames"] = jax.ShapeDtypeStruct((B, F, cfg.d_model),
                                               jnp.dtype(cfg.dtype))
    if cfg.family == "vlm":
        batch["patches"] = jax.ShapeDtypeStruct(
            (B, cfg.num_patches, cfg.d_model), jnp.dtype(cfg.dtype))
        batch["tokens"] = jax.ShapeDtypeStruct((B, S - cfg.num_patches),
                                               jnp.int32)
    return {"batch": batch, "cache": cache}
