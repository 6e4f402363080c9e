"""End-to-end PTQ pipeline (paper Algorithm 1 at model scope).

``quantize_model`` walks the architecture's stages block by block:
  1. collect the block's input stream X (from the progressively-quantized
     model — errors compose, as in OmniQuant/BRECQ) and the FP target
     block(theta_fp, X);
  2. initialize scale/zero (+ AWQ transformation) per linear;
  3. optimize rounding with TesseraQ (or LWC for the OmniQuant baseline);
  4. write the fake-quantized block back and advance the stream.

The walk is **pipelined**: activation streams stay device-resident between
blocks (no host round-trips), the FP targets of block k double as block
k+1's FP input stream (the same forward pass, computed once), and — in the
default ``input_source="fp"`` mode — block k+1's target forward is
DISPATCHED before block k's reconstruction starts, so the capture of the
next block's inputs overlaps the current block's optimization
(double-buffered streams; JAX async dispatch does the overlapping).  With
``engine="sharded"`` every capture minibatch is placed batch-sharded over
the mesh's data-parallel axes — and, when the mesh has a ``model`` axis,
the block weights are placed per the ``launch.sharding.ParamSpec``
tensor-parallel contract — so the forwards and the reconstruction loop are
all mesh-resident.

On a multi-pod mesh (``("pod", "data", "model")``) the time-domain double
buffering generalizes into SPACE: the walk round-robins blocks over the
per-pod submeshes (``launch.mesh.pod_submeshes``), so block k+1's prefetched
capture forward runs on pod p+1's devices while block k reconstructs on pod
p — disjoint device sets, genuine overlap rather than queue-order overlap.
The activation stream crosses pods through the explicit
``reshard_between_pods`` seam (alpa-pipeshard-style send/recv resharding),
and the walk records per-stage wall-clock profiling (reconstruction time,
residual prefetch wait, pipeline-fill captures) in ``report["pipeline"]``
with a ``pipeline_efficiency`` summary the recon benchmark gates on.

``pack_model`` then converts the calibrated model into the deployment form:
stacked packed QTensors per linear, with DST folded into the scales.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PS

from repro.configs.base import ModelConfig, QuantConfig
from repro.core import awq as awq_mod
from repro.core import gptq as gptq_mod
from repro.core import omniquant as omni_mod
from repro.core import recon_engine as re_mod
from repro.core import rtn as rtn_mod
from repro.core import signround as sr_mod
from repro.core import tesseraq as tq_mod
from repro.core.blocks import build_stages, get_path, set_path
from repro.core.capture import (capture_block_inputs, capture_minibatch,
                                split_minibatches, stage_calibration)
from repro.core.quantizer import resolve_group
from repro.core.qtensor import QTensor, pack
from repro.launch.mesh import (dp_size, pod_count, pod_submeshes,
                               reshard_between_pods, tp_size)
from repro.models.common import Ctx, DEFAULT_CTX


def _aux_part(auxs, j):
    return auxs[j] if auxs is not None else None


def quantize_model(cfg: ModelConfig, params: Dict, batches: List[Dict],
                   qcfg: QuantConfig, *, method: str = "tesseraq",
                   init: str = "awq",
                   tcfg: Optional[tq_mod.TesseraQConfig] = None,
                   omni_steps: int = 500,
                   ctx: Ctx = DEFAULT_CTX,
                   input_source: str = "fp",
                   verbose: bool = False):
    """Returns (params_fq, qmeta, report).

    ``batches``: list of batch dicts (calibration set, pre-minibatched).
    method: tesseraq | omniquant | signround | none (init only)
    init:   awq | rtn | gptq   (scale/zero/transform initialization)
    input_source: "fp" (paper Algorithm 1: block inputs collected from the
        FP model) or "quant" (BRECQ/OmniQuant-style compounding: inputs from
        the progressively-quantized stream, targets from the FP block)

    Parallelism (``engine="sharded"``): reconstruction runs data-parallel
    over the mesh's DP axes; a ``model`` axis additionally shards each
    block's weights, rounding/DST variables and Adam state per the
    ``launch.sharding.ParamSpec`` contract (tensor parallelism — TP=1
    reproduces ``engine="device"`` masks and codes at the tests' widths,
    not at published ones: ROADMAP C2); a ``pod`` axis pipelines the
    block walk itself across pods (block k+1's capture overlaps block k's
    reconstruction on the next pod's devices), with per-stage wall-clock
    profiling in ``report["pipeline"]``.
    """
    # lazy import: sharding.py pulls core.qtensor through the package root,
    # so a module-level import here would be circular whenever
    # launch.sharding is imported first
    from repro.launch.sharding import ParamSpec
    tcfg = tcfg or tq_mod.TesseraQConfig()
    mesh = emesh = None
    pods = None
    pipeline_prof = None
    if tcfg.engine == "sharded":
        # resolve ONCE so the reconstruction engines and the capture
        # forwards agree on the same mesh object; lift batch_size to a
        # DP-divisible multiple (mirroring capture_minibatch) so the
        # default config runs on any mesh, and clamp to the largest
        # DP-divisible size the calibration pool can fill (stage_plan
        # clamps to the pool, which would silently undo a bare lift) —
        # direct reconstruct_block callers keep the engine's strict check
        mesh = re_mod.resolve_mesh(tcfg.mesh)
        # multi-pod mesh: the pod axis is the walk's PIPELINE dimension,
        # never a data axis of any single engine — each block reconstructs
        # on one pod's ("data", "model") submesh and the walk round-robins
        # blocks over pods
        pods = pod_submeshes(mesh) if pod_count(mesh) > 1 else None
        emesh = pods[0] if pods else mesh
        D = dp_size(emesh)
        n_pool = sum(jax.tree_util.tree_leaves(b)[0].shape[0]
                     for b in batches)
        if n_pool < D:
            raise ValueError(
                f"calibration pool ({n_pool} samples) is smaller than the "
                f"mesh's data-parallel degree ({D}); add calibration data "
                "or shrink the mesh")
        bs = min(tcfg.batch_size + (-tcfg.batch_size % D),
                 n_pool - n_pool % D)
        if re_mod.grad_chunk_count(bs, n_pool) % D:
            raise ValueError(
                f"calibration pool size {n_pool} is incompatible with the "
                f"mesh's data-parallel degree {D}: the canonical gradient "
                f"chunk count gcd(batch={bs}, pool={n_pool}, "
                f"cap={re_mod.CANONICAL_LANE_CHUNKS}) must be a multiple "
                f"of {D} — use a calibration pool whose size is a multiple "
                "of the DP degree, or set recon_engine.CANONICAL_LANE_CHUNKS "
                "to a multiple of the DP degree (required for DP degrees "
                f"that do not divide {re_mod.CANONICAL_LANE_CHUNKS}, e.g. "
                "6-way), or shrink the mesh")
        tcfg = dataclasses.replace(tcfg, mesh=emesh, batch_size=bs)
        pipeline_prof = {"pods": len(pods) if pods else 1,
                         "dp": D, "tp": tp_size(emesh), "blocks": []}
    stages = build_stages(cfg, ctx)
    params_q = params
    saved: Dict[str, np.ndarray] = {}
    qmeta_all: Dict = {}
    report = {"blocks": [], "method": method, "init": init, "qcfg": qcfg.tag}

    def mesh_for(i):
        """Submesh block ``i`` reconstructs on (round-robin over pods)."""
        return pods[i % len(pods)] if pods else mesh

    X = X_fp = None
    for stage in stages:
        # stage input stream (None => continue the running stream)
        per_batch = [stage.init_x(params_q, b, saved) for b in batches]
        if per_batch[0] is not None:
            X = jnp.concatenate([jnp.asarray(x) for x in per_batch], 0)
            X_fp = X
        aux = None
        aux_parts = [stage.make_aux(params_q, b, saved) for b in batches]
        if aux_parts[0] is not None:
            aux = jnp.concatenate([jnp.asarray(a) for a in aux_parts], 0)

        # reprolint: ok[jit-cache] — one jit per STAGE (few, distinct apply fns), reused for every block in it
        napply = jax.jit(stage.apply)
        # the reconstruction inner loop compiles once per stage and is
        # reused for every identically-shaped block in it
        recon_cache: Dict = {}
        mb = capture_minibatch(emesh if emesh is not None else mesh)
        # aux is loop-invariant: split/place it once per submesh it visits
        aux_cache: Dict = {}

        def aux_for(m):
            if aux is None:
                return None
            k = id(m)
            if k not in aux_cache:
                aux_cache[k] = split_minibatches(aux, mb, m)
            return aux_cache[k]

        # double buffer (fp mode): the dispatched-but-unread FP outputs of
        # the CURRENT block over the FP stream — they are both the
        # reconstruction targets Y_i and the next FP inputs X_fp[i+1], and
        # for block i+1 they were enqueued while block i reconstructed.
        # On a multi-pod mesh fp_out lives on mesh_for(i)'s devices and
        # fp_src carries the already-resharded input parts alongside it,
        # so the stream crosses each pod boundary exactly once.
        fp_out = None
        fp_src = None

        for i in range(stage.n_blocks):
            t0 = time.time()
            bmesh = mesh_for(i)
            pspec = (ParamSpec.for_mesh(bmesh)
                     if bmesh is not None else None)
            bp_fp = stage.get_block(params_q, i)
            if pspec is not None and pspec.active:
                # capture forwards run with the block TP-placed per the
                # ParamSpec contract (GSPMD partitions the contractions)
                bp_fp = pspec.place_block(bp_fp)
            same_stream = X_fp is X
            out_q = None
            auxs = aux_for(bmesh)

            if stage.calibrate:
                src = X_fp if input_source == "fp" else X
                prefetched = fp_out is not None and input_source == "fp"
                wait_s = fill_s = None
                if prefetched and pods is not None:
                    # residual wait on the prefetched target forward — on a
                    # pipelined walk this is the bubble the efficiency gate
                    # measures (it ran on the previous iteration's NEXT pod,
                    # i.e. this one, while the previous block reconstructed)
                    tw = time.time()
                    jax.block_until_ready(fp_out)
                    wait_s = time.time() - tw
                if prefetched and fp_src is not None:
                    src_parts = fp_src
                    src = jnp.concatenate(src_parts, 0)
                else:
                    src_parts = split_minibatches(src, mb, bmesh)
                # FP target block(theta, X) on the selected input stream
                # (reused from the previous iteration's prefetch when the
                # stream carries over)
                if not prefetched:
                    tf = time.time()
                    fp_out = [napply(bp_fp, src_parts[j], _aux_part(auxs, j))
                              for j in range(len(src_parts))]
                    if pipeline_prof is not None:
                        jax.block_until_ready(fp_out)
                        fill_s = time.time() - tf
                # prefetch: dispatch block i+1's FP target forward NOW —
                # on the NEXT pod's submesh when pods are active — so it
                # executes while this block reconstructs below
                next_fp_out = next_fp_src = None
                if input_source == "fp" and i + 1 < stage.n_blocks:
                    nmesh = mesh_for(i + 1)
                    bp_fp_next = stage.get_block(params_q, i + 1)
                    npspec = (ParamSpec.for_mesh(nmesh)
                              if nmesh is not None else None)
                    if npspec is not None and npspec.active:
                        bp_fp_next = npspec.place_block(bp_fp_next)
                    naux = aux_for(nmesh)
                    if nmesh is bmesh:
                        next_fp_src = fp_out
                    else:
                        # the explicit cross-pod seam: block i's targets
                        # hop to pod (i+1) % P where they become inputs
                        next_fp_src = [reshard_between_pods(p_, nmesh)
                                       for p_ in fp_out]
                    next_fp_out = [napply(bp_fp_next, next_fp_src[j],
                                          _aux_part(naux, j))
                                   for j in range(len(next_fp_src))]
                Y = jnp.concatenate(fp_out, 0)

                want_h = init == "gptq"
                caps = (capture_block_inputs(stage.apply, bp_fp, src_parts,
                                             auxs, want_hessian=want_h)
                        if init in ("awq", "gptq") else None)
                if init == "awq":
                    bp_init, qmeta = awq_mod.quantize_block_awq(bp_fp, caps, qcfg)
                elif init == "gptq":
                    bp_init, qmeta = gptq_mod.quantize_block_gptq(bp_fp, caps, qcfg)
                else:
                    bp_init, qmeta = rtn_mod.quantize_block_rtn(bp_fp, qcfg)

                log: list = []
                # one host->device transfer per block: every engine gathers
                # its minibatches out of these staged streams (batch-sharded
                # over the mesh, so they land shard-resident straight out of
                # the pipelined capture — no replicated copies per device)
                Xd, Yd, auxd = stage_calibration(src, Y, aux, mesh=bmesh)
                btcfg = (tcfg if pods is None
                         else dataclasses.replace(tcfg, mesh=bmesh))
                tr0 = time.time()
                if method == "tesseraq":
                    bp_q, qmeta = tq_mod.reconstruct_block(
                        stage.apply, bp_fp, Xd, Yd, auxd, qmeta, qcfg, btcfg,
                        log=log, cache=recon_cache)
                elif method == "omniquant":
                    bp_q, qmeta = omni_mod.reconstruct_block(
                        stage.apply, bp_fp, Xd, Yd, auxd, qcfg,
                        steps=omni_steps, batch_size=btcfg.batch_size,
                        log=log, engine=btcfg.engine,
                        cache=recon_cache, mesh=btcfg.mesh)
                elif method == "signround":
                    bp_q, qmeta = sr_mod.reconstruct_block(
                        stage.apply, bp_fp, Xd, Yd, auxd, qmeta, qcfg,
                        steps=max(btcfg.par_iterations
                                  * btcfg.steps_per_iteration, 50),
                        batch_size=btcfg.batch_size,
                        log=log, engine=btcfg.engine, cache=recon_cache,
                        mesh=btcfg.mesh)
                else:
                    bp_q = bp_init
                recon_s = time.time() - tr0

                bq_b = None
                if pods is not None:
                    # master params stay pod-0-resident: the reconstructed
                    # block hops home through the pod seam, while a
                    # bmesh-local cast copy (identical values to what
                    # set_block stores) keeps this pod's stream forwards
                    # from mixing device sets
                    bq_b = jax.tree_util.tree_map(
                        lambda q, f: q.astype(f.dtype), bp_q, bp_fp)
                    if bmesh is not pods[0]:
                        bp_q = reshard_between_pods(bp_q, pods[0],
                                                    spec=PS())
                params_q = stage.set_block(params_q, i, bp_q)
                for p_, m_ in qmeta.items():
                    qmeta_all[stage.pack_target(i) + tuple(p_)] = m_
                # block-level report: recon error of the written-back block
                # (in quant mode this forward IS the stream advance — reused
                # below instead of recomputed)
                bq = bq_b if pods is not None else stage.get_block(params_q, i)
                out_q = [napply(bq, src_parts[j], _aux_part(auxs, j))
                         for j in range(len(src_parts))]
                err = float(np.mean(
                    [np.mean((np.asarray(out_q[j], np.float32)
                              - np.asarray(fp_out[j], np.float32)) ** 2)
                     for j in range(len(out_q))]))
                report["blocks"].append(
                    {"stage": stage.name, "block": i, "recon_mse": err,
                     "secs": time.time() - t0, "log": log})
                if pipeline_prof is not None:
                    pipeline_prof["blocks"].append(
                        {"stage": stage.name, "block": i,
                         "pod": (i % len(pods)) if pods else 0,
                         "recon_secs": recon_s,
                         "capture_wait_secs": wait_s,
                         "fill_secs": fill_s})
                if verbose:
                    print(f"[{stage.name} {i}] mse={err:.3e} "
                          f"({time.time()-t0:.1f}s)")

            # advance the quantized stream through the written-back block
            # (reusing the mse forward when it ran over this same stream:
            # always in quant mode, and on the first block of an fp-mode
            # stage, where X_fp still IS X)
            if pods is not None:
                # bmesh-resident block: the cast copy from above, or the
                # placed FP block (== the written-back one) on uncalibrated
                # stages, so the forward never mixes pods
                bq = bq_b if stage.calibrate else bp_fp
            else:
                bq = stage.get_block(params_q, i)
            if stage.calibrate and (input_source == "quant" or same_stream):
                X = jnp.concatenate(out_q, 0)        # the mse forward above
            else:
                xq_in = split_minibatches(X, mb, bmesh)
                X = jnp.concatenate(
                    [napply(bq, xq_in[j], _aux_part(auxs, j))
                     for j in range(len(xq_in))], 0)
            # advance the FP stream
            if input_source != "fp":
                X_fp = X
            elif stage.calibrate:
                X_fp = Y             # the targets ARE the next FP inputs
                fp_out = next_fp_out
                fp_src = next_fp_src
            elif same_stream:
                X_fp = X             # uncalibrated block: bq == bp_fp
            else:
                xs_fp = split_minibatches(X_fp, mb, bmesh)
                X_fp = jnp.concatenate(
                    [napply(bp_fp, xs_fp[j], _aux_part(auxs, j))
                     for j in range(len(xs_fp))], 0)

        if stage.save_as:
            saved[stage.save_as] = np.asarray(X)

    if pipeline_prof is not None:
        blocks_ = pipeline_prof["blocks"]
        recon_total = float(sum(b["recon_secs"] for b in blocks_))
        waits = [b["capture_wait_secs"] for b in blocks_
                 if b["capture_wait_secs"] is not None]
        wait_total = float(sum(waits))
        fill_total = float(sum(b["fill_secs"] or 0.0 for b in blocks_))
        # efficiency: fraction of the steady-state walk spent reconstructing
        # rather than stalled on the prefetched capture (1.0 == the pipeline
        # fully hides the captures); only defined once blocks were actually
        # prefetched across pods
        eff = (recon_total / (recon_total + wait_total)
               if waits and (recon_total + wait_total) > 0 else None)
        pipeline_prof.update(
            {"recon_secs": recon_total,
             "capture_wait_secs": wait_total if waits else None,
             "fill_secs": fill_total,
             "efficiency": eff})
        report["pipeline"] = pipeline_prof
    return params_q, qmeta_all, report


def pack_model(cfg: ModelConfig, params_q: Dict, qmeta_all: Dict,
               qcfg: QuantConfig) -> Dict:
    """Convert calibrated fake-quant params into stacked packed QTensors."""
    # group metas: (param_key, path) -> {layer_idx: meta}
    grouped: Dict = {}
    for key, meta in qmeta_all.items():
        pkey, idx, path = key[0], key[1], key[2:]
        grouped.setdefault((pkey, path), {})[idx] = meta

    out = params_q
    for (pkey, path), metas in grouped.items():
        idxs = sorted(metas)
        full_path = (pkey,) + path
        leaf = get_path(out, full_path)                      # (L?, ..., in, out)
        stacked_codes = np.stack(
            [np.asarray(metas[i]["codes"], np.uint8) for i in idxs])
        scale = np.stack([np.asarray(metas[i]["scale"], np.float32)
                          for i in idxs])
        zero = np.stack([np.asarray(metas[i]["zero"], np.float32)
                         for i in idxs])
        act = (np.stack([np.asarray(metas[i]["act_scale"], np.float32)
                         for i in idxs])
               if metas[idxs[0]].get("act_scale") is not None else None)
        if leaf.ndim == stacked_codes.ndim - 1:               # single block slot
            stacked_codes, scale, zero = (stacked_codes[0], scale[0], zero[0])
            act = act[0] if act is not None else None
        elif leaf.shape[0] != stacked_codes.shape[0]:
            raise ValueError(f"layer count mismatch at {full_path}")
        in_f = stacked_codes.shape[-2]
        qt = QTensor(
            packed=pack(jnp.asarray(stacked_codes), qcfg.bits, axis=-2),
            scale=jnp.asarray(scale),
            zero=jnp.asarray(zero),
            bits=qcfg.bits,
            group_size=resolve_group(in_f, qcfg.group_size),
            shape=(in_f, stacked_codes.shape[-1]),
            act_scale=jnp.asarray(act) if act is not None else None,
        )
        out = set_path(out, full_path, qt)
    return out


def quantized_memory_report(params) -> Dict:
    """Paper Table 8 'WM': weight memory of the deployment artifact."""
    total_q, total_fp = 0, 0
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, QTensor)):
        if isinstance(leaf, QTensor):
            total_q += leaf.memory_bytes()
            total_fp += int(np.prod(leaf.packed.shape[:-2])) * \
                leaf.in_features * leaf.out_features * 2
        else:
            total_q += leaf.size * 2
            total_fp += leaf.size * 2
    return {"quantized_bytes": total_q, "fp16_bytes": total_fp,
            "compression": total_fp / max(total_q, 1)}
