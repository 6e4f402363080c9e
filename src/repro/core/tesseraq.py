"""TesseraQ: Progressive Adaptive Rounding + Dequantization Scale Tuning
(the paper's contribution, Sec. 3.2/3.3, Algorithm 1).

Per block:
  * rounding variables  nu  (one per weight element), sigmoid-reparameterized,
    initialized to reproduce the FP weight exactly:
        nu0 = logit(theta/s - floor(theta/s))
  * DST variables  v  (one per quant group), dequant factor 2*sigmoid(v),
    initialized to 1 (v = 0)
  * K PAR iterations; iteration k HARDENS the still-soft variables with the
    HIGHEST hardness score  HS(nu) = |sigmoid(nu) - 0.5|  — the ones already
    closest to a binary decision, so rounding them perturbs the block least —
    (they are frozen to their binary value), then SOFTENS: T Adam steps on
    the surviving nu and all v against
    || block(theta_hat, X) - block(theta, X) ||_F^2.

Hardening is tracked with an explicit sign tensor (exactly-zero gradients for
frozen variables); the paper's memory-light alternative (set nu to +-inf) is
available via ``use_inf_freeze``.

Two interchangeable inner-loop engines (``TesseraQConfig.engine``):

  * ``"device"`` (default) — the scanned on-device engine from
    ``core/recon_engine.py``: jitted global-threshold hardening, T Adam steps
    per ``lax.scan`` dispatch with donated buffers, batches gathered on
    device from a pre-staged index plan.  At most one host sync per PAR
    iteration (the optional log line).
  * ``"reference"`` — the host-loop oracle: NumPy hardening, Python-looped
    steps with per-step host batch gather, but the (grad + Adam) step body
    fused into one jitted function — the same math the device engine scans
    over.  XLA compiles a scan body and a standalone step differently,
    which moves the continuous DST variables by an ulp, so
    ``tests/test_recon_engine.py`` pins hardened masks and codes
    bit-for-bit and folded scales to 1 ulp (the fold is taken in
    float64 and rounded once).
  * ``"legacy"`` — the original pre-engine path: jitted batch-mean
    gradient only, the Adam update dispatched EAGERLY per tree leaf.  Kept
    as the benchmark baseline (``benchmarks/recon_speed.py``); its eager
    optimizer arithmetic and non-canonical (single fused reduce) batch
    gradient differ from the engine step by ~1 ulp, so it tracks the other
    engines only up to float32 rounding (codes match, folded scales drift
    in the last bits).
  * ``"sharded"`` — the device engine's scanned step under ``shard_map`` on
    ``TesseraQConfig.mesh`` (default: a 1-D data mesh over every visible
    device): calibration streams batch-sharded over the mesh's DP axes,
    minibatch chunks computed on the device that owns their pool shard,
    and the gradient reduced hierarchically — local per-chunk ordered lane
    sums, one fused all_gather of the per-shard chunk partials, then the
    engine's rank-ordered combine (``recon_engine.grad_chunk_count``
    association).  Rounding/DST variables and Adam state stay replicated.
    The global minibatch sequence AND the chunked reduction association are
    identical to ``"device"``, so the sharded engine reproduces the device
    engine's hardened masks and packed codes bit-for-bit at the tests'
    reduced widths and pinned calibration horizons, with folded scales
    within 1 ulp (pinned by ``tests/test_recon_engine.py`` and the
    ``benchmarks/recon_speed.py`` parity gate).  At published widths it
    does not: XLA rounds the DST gradient differently inside the
    ``shard_map`` program, and a few of the millions of masks/codes per
    block come out different (ROADMAP C2).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import QuantConfig
from repro.core import quantizer as Q
from repro.core import recon_engine as RE
from repro.core.blocks import get_path, quant_leaf_paths, set_path
from repro.optim.adam import AdamW

# handcrafted soft-rate schedule from the paper's Fig. 3 (fractions of
# variables still soft after iteration k); len == K
HANDCRAFTED_SOFT_RATE = (
    0.9, 0.8, 0.7, 0.6, 0.5, 0.4, 0.3, 0.22, 0.16, 0.12,
    0.09, 0.06, 0.04, 0.025, 0.015, 0.009, 0.005, 0.002, 0.001, 0.0,
)


def exp_soft_rate(k: int, K: int, t: float) -> float:
    """Rule-based schedule 1/exp(t*x) (paper Sec. 4.3), x in (0, 1]."""
    x = (k + 1) / K
    return float(np.exp(-t * x)) if k + 1 < K else 0.0


@dataclasses.dataclass
class TesseraQConfig:
    par_iterations: int = 20              # K
    steps_per_iteration: int = 250        # T
    lr: float = 1e-3
    v_weight_decay: float = 1e-4          # on DST variables (paper Sec. 4)
    batch_size: int = 4
    soft_rate: Sequence[float] = HANDCRAFTED_SOFT_RATE
    dst: bool = True                      # dequantization scale tuning
    par: bool = True                      # progressive adaptive rounding
    use_inf_freeze: bool = False          # paper's memory-light hardening
    seed: int = 0
    engine: str = "device"     # "device" | "reference" | "legacy" | "sharded"
    # mesh for engine="sharded" (None: 1-D data mesh over all devices); the
    # pipeline also shards its capture forward passes over this mesh
    mesh: Any = None
    # keep Adam moments across PAR iterations (both engines honor this; the
    # surviving soft variables continue from warm state instead of cold
    # restarts after every harden)
    carry_opt_state: bool = True


@partial(jax.jit, static_argnames=("qcfg",))
def _leaf_state_jit(w, scale, zero, act_scale, *, qcfg: QuantConfig):
    # compiled so building per-block state stays free of eager scalar-
    # constant device_puts — the sanitizer's transfer_guard sees nothing
    wf = jnp.asarray(w, jnp.float32)
    if act_scale is not None:
        wf = wf * act_scale[..., :, None]
    g = Q.resolve_group(wf.shape[-2], qcfg.group_size)
    wg = wf.reshape(wf.shape[:-2] + (wf.shape[-2] // g, g, wf.shape[-1]))
    ratio = wg / scale[..., None, :]
    base = jnp.floor(ratio)
    frac = jnp.clip(ratio - base, 1e-4, 1 - 1e-4)
    nu = jnp.log(frac) - jnp.log1p(-frac)            # logit
    return (nu.astype(jnp.float32), jnp.zeros_like(scale),
            jnp.zeros(nu.shape, jnp.int8), base)


def _leaf_state(w, meta, qcfg: QuantConfig):
    """Per-linear PAR/DST state. Weights already in the transformed domain if
    AWQ act_scale is present (we optimize rounding of W*act_scale)."""
    scale, zero = meta["scale"], meta["zero"]
    act_scale = meta.get("act_scale")
    nu, v, hard, base = _leaf_state_jit(w, scale, zero, act_scale, qcfg=qcfg)
    return {
        "nu": nu,                                     # grouped layout
        "v": v,
        "hard": hard,                                 # 0 soft, +-1 frozen
        "base": base,
        "scale": scale,
        "zero": zero,
        "act_scale": act_scale,
    }


def soft_weight(st, qcfg: QuantConfig, dst: bool) -> jax.Array:
    """Differentiable effective weight theta_hat (Eq. 4 + Eq. 9)."""
    hard = st["hard"]
    alpha = jnp.where(hard == 0, jax.nn.sigmoid(st["nu"]),
                      (hard > 0).astype(jnp.float32))
    q = jnp.clip(st["base"] + st["zero"][..., None, :] + alpha, 0, qcfg.qmax)
    dq_scale = st["scale"][..., None, :]
    if dst:
        dq_scale = dq_scale * (2.0 * jax.nn.sigmoid(st["v"]))[..., None, :]
    w = (q - st["zero"][..., None, :]) * dq_scale
    w = w.reshape(_wshape(st["nu"]))
    if st["act_scale"] is not None:
        w = w / st["act_scale"][..., :, None]
    return w


def _wshape(nu):
    """Grouped (..., ng, g, out) -> flat (..., ng*g, out) weight shape."""
    return nu.shape[:-3] + (nu.shape[-3] * nu.shape[-2], nu.shape[-1])


def hardness_score(nu: jax.Array) -> jax.Array:
    return jnp.abs(jax.nn.sigmoid(nu) - 0.5)          # HS (Eq. 6)


# jitted alias for the reference harden: eager hardness_score embeds the 0.5
# constant as a per-call scalar device_put (transfer_guard rejects it); under
# jit the value is bit-identical, so engine parity is untouched
_hardness_score_jit = jax.jit(hardness_score)


def harden(states: Dict, target_soft_rate: float, use_inf: bool) -> Dict:
    """NumPy reference hardening: freeze the HIGHEST-HS soft variables (those
    already nearly binary — rounding them perturbs the block least) so that
    only ``target_soft_rate`` of ALL rounding variables in the block remain
    soft.  The threshold is global across the block's leaves (joint sort, as
    in Algorithm 1).  The jitted equivalent is
    ``recon_engine.harden_device``."""
    scores = []
    for st in states.values():
        s = np.asarray(_hardness_score_jit(st["nu"])).ravel()
        m = np.asarray(st["hard"]).ravel() == 0
        scores.append(s[m])
    all_scores = np.concatenate(scores) if scores else np.zeros(0)
    total = sum(int(np.asarray(st["hard"]).size) for st in states.values())
    want_soft = int(total * target_soft_rate)
    n_soft_now = all_scores.size
    n_to_freeze = max(0, n_soft_now - want_soft)
    if n_to_freeze == 0:
        return states
    # k-th largest soft score == ascending-partition index want_soft
    thresh = np.partition(all_scores, want_soft)[want_soft] \
        if n_to_freeze < n_soft_now else -np.inf

    new = {}
    for p, st in states.items():
        nu = np.asarray(st["nu"])
        hard = np.asarray(st["hard"]).copy()
        hs = np.asarray(_hardness_score_jit(st["nu"]))
        freeze = (hard == 0) & (hs >= thresh)
        sign = np.where(nu > 0, 1, -1).astype(np.int8)
        hard = np.where(freeze, sign, hard)
        st = dict(st)
        st["hard"] = jnp.asarray(hard)
        if use_inf:
            # host-side astype keeps the push zero-copy (guard-clean)
            st["nu"] = jnp.asarray(
                np.where(hard != 0, hard * 40.0, nu).astype(np.float32))
        new[p] = st
    return new


def substitute(bp, states, qcfg: QuantConfig, dst: bool):
    for p, st in states.items():
        bp = set_path(bp, p, soft_weight(st, qcfg, dst).astype(
            get_path(bp, p).dtype))
    return bp


# ---------------------------------------------------------------------------
# shared inner-loop plumbing (both engines)
# ---------------------------------------------------------------------------

def _trainables(states, dst: bool):
    t = {p: {"nu": st["nu"]} for p, st in states.items()}
    if dst:
        for p, tp in t.items():
            tp["v"] = states[p]["v"]
    return t


def _merge(states, tr, dst: bool):
    out = {}
    for p, st in states.items():
        st = dict(st)
        st["nu"] = tr[p]["nu"]
        if dst:
            st["v"] = tr[p]["v"]
        out[p] = st
    return out


def _make_loss_fn(apply: Callable, qcfg: QuantConfig, tcfg: TesseraQConfig):
    """loss(tr, frozen, xb, yb, auxb) with ``frozen = {"bp": block_params,
    "sts": states}`` — block params ride in the frozen pytree (not a trace
    closure) so ONE compiled loss serves every identically-shaped block.
    ``sts`` may be the full states or states with the trainable entries
    stripped — tr keys win on merge."""
    def loss_fn(tr, frozen, xb, yb, auxb):
        sts = {p: {**frozen["sts"][p], **tr[p]} for p in frozen["sts"]}
        bq = substitute(frozen["bp"], sts, qcfg, tcfg.dst)
        out = apply(bq, xb, auxb)
        loss = jnp.mean(jnp.square(out.astype(jnp.float32) - yb))
        if tcfg.dst and tcfg.v_weight_decay:
            loss = loss + tcfg.v_weight_decay * sum(
                jnp.sum(jnp.square(t["v"])) for t in tr.values())
        return loss
    return loss_fn


def _schedule_index(k: int, K: int, n_rates: int) -> int:
    """Stretch the soft-rate schedule over K iterations anchored at BOTH
    ends: the first harden freezes only 1-sr[0] (~10%, paper's gentle start)
    and the last always reaches the schedule's final rate (0.0 soft)."""
    return (int(round(k * (n_rates - 1) / max(K - 1, 1)))
            if K > 1 else n_rates - 1)


@jax.jit
def _log_stats(lv, hard):
    """Fused per-iteration log payload: [last loss, global soft rate] in a
    single device array so the host pulls it with ONE blocking read.  Takes
    the hardened masks alone (not the whole state tree): on a mesh run the
    trainable leaves come back sharded while the masks live on the default
    device, and mixing them as jit args would force an implicit
    device-to-device reshard the sanitizer's transfer_guard rejects."""
    soft = sum(jnp.sum((h == 0).astype(jnp.float32)) for h in hard.values())
    total = sum(int(np.prod(h.shape)) for h in hard.values())
    return jnp.stack([lv, soft / max(total, 1)])


def _soft_rate_of(states) -> float:
    """Global fraction of rounding variables still soft (element-weighted
    across leaves — the quantity the PAR schedule targets)."""
    soft = sum(int((np.asarray(st["hard"]) == 0).sum())
               for st in states.values())
    total = sum(int(np.asarray(st["hard"]).size) for st in states.values())
    return soft / max(total, 1)


# ---------------------------------------------------------------------------
# engines
# ---------------------------------------------------------------------------

def _run_reference(apply, bp, X, Y, aux, qcfg, tcfg: TesseraQConfig, states,
                   log: Optional[list], cache: Optional[dict] = None):
    """Legacy host loop: NumPy harden, per-step host batch gather, one
    dispatch per step.  The (grad + Adam) step body is a single jitted
    function — the same HLO (canonical per-sample gradient reduction
    included) the device engine scans over."""
    opt = AdamW(lr=tcfg.lr)
    N = X.shape[0]
    bs = min(tcfg.batch_size, N)
    # the canonical chunk count is baked into the compiled step, so a
    # cache shared across pool/batch shapes — or across a mutated
    # CANONICAL_LANE_CHUNKS cap — must not hand a stale association to a
    # later block (the device engine recomputes it from shapes at trace
    # time and cross-checks plan.chunks; this is the host-loop equivalent)
    cache_key = ("reference", bs, N, RE.grad_chunk_count(bs, N))
    step_fn = cache.get(cache_key) if cache is not None else None
    if step_fn is None:
        # the exact canonical chunked reduction the device engine scans over
        grad_fn = RE.make_canonical_grad(_make_loss_fn(apply, qcfg, tcfg),
                                         chunks=RE.grad_chunk_count(bs, N))

        @jax.jit
        def step_fn(tr, opt_state, frozen, xb, yb, auxb):
            lv, grads = grad_fn(tr, frozen, xb, yb, auxb)
            tr, opt_state = opt.update(grads, opt_state, tr)
            return tr, opt_state, lv

        if cache is not None:
            cache[cache_key] = step_fn
    # compiled zero-state builder, same rationale as the engine's _init
    init_fn = cache.get("reference-init") if cache is not None else None
    if init_fn is None:
        init_fn = jax.jit(opt.init)
        if cache is not None:
            cache["reference-init"] = init_fn

    K = tcfg.par_iterations if tcfg.par else 1
    T = tcfg.steps_per_iteration
    plan = RE.draw_index_plan(N, bs, K * T, tcfg.seed)
    sr = list(tcfg.soft_rate)
    opt_state = None
    for k in range(K):
        if tcfg.par:
            states = harden(states, sr[_schedule_index(k, K, len(sr))],
                            tcfg.use_inf_freeze)
        tr = _trainables(states, tcfg.dst)
        if opt_state is None or not tcfg.carry_opt_state:
            opt_state = init_fn(tr)
        lv = None
        for t in range(T):
            idx = plan[k * T + t]
            # the per-step host gather is this engine's DESIGN (host-loop
            # oracle); explicit device_put keeps it guard-clean and counted
            xb = jax.device_put(X[idx])
            yb = jax.device_put(np.asarray(Y[idx], np.float32))
            auxb = jax.device_put(aux[idx]) if aux is not None else None
            tr, opt_state, lv = step_fn(tr, opt_state,
                                        {"bp": bp, "sts": states},
                                        xb, yb, auxb)
        states = _merge(states, tr, tcfg.dst)
        if log is not None:
            log.append({"iter": k, "loss": float(RE.host_read(lv)),
                        "soft_rate": _soft_rate_of(states)})
    return states


def _run_legacy(apply, bp, X, Y, aux, qcfg, tcfg: TesseraQConfig, states,
                log: Optional[list], cache: Optional[dict] = None):
    """The original (pre-engine) loop, kept as the speed baseline: jitted
    gradient, EAGER per-leaf Adam update (one XLA dispatch per tree-map op),
    per-step host batch gather, NumPy harden."""
    opt = AdamW(lr=tcfg.lr)
    grad_fn = cache.get("legacy") if cache is not None else None
    if grad_fn is None:
        grad_fn = jax.jit(jax.value_and_grad(_make_loss_fn(apply, qcfg,
                                                           tcfg)))
        if cache is not None:
            cache["legacy"] = grad_fn

    N = X.shape[0]
    bs = min(tcfg.batch_size, N)

    K = tcfg.par_iterations if tcfg.par else 1
    T = tcfg.steps_per_iteration
    plan = RE.draw_index_plan(N, bs, K * T, tcfg.seed)
    sr = list(tcfg.soft_rate)
    opt_state = None
    for k in range(K):
        if tcfg.par:
            states = harden(states, sr[_schedule_index(k, K, len(sr))],
                            tcfg.use_inf_freeze)
        tr = _trainables(states, tcfg.dst)
        if opt_state is None or not tcfg.carry_opt_state:
            opt_state = opt.init(tr)
        lv = None
        for t in range(T):
            idx = plan[k * T + t]
            lv, grads = grad_fn(tr, {"bp": bp, "sts": states},
                                jnp.asarray(X[idx]),
                                jnp.asarray(Y[idx], jnp.float32),
                                jnp.asarray(aux[idx])
                                if aux is not None else None)
            tr, opt_state = opt.update(grads, opt_state, tr)
        states = _merge(states, tr, tcfg.dst)
        if log is not None:
            log.append({"iter": k, "loss": float(RE.host_read(lv)),
                        "soft_rate": _soft_rate_of(states)})
    return states


def _run_device(apply, bp, X, Y, aux, qcfg, tcfg: TesseraQConfig, states,
                log: Optional[list], cache: Optional[dict] = None, *,
                mesh=None):
    """On-device engine: jitted hardening, scanned soften phase, pre-staged
    batches.  The only blocking host read per PAR iteration is the optional
    log line (loss + realized soft rate fused into one transfer).

    Block params travel inside the engine's ``frozen`` argument, so with a
    per-stage ``cache`` the scanned step compiles ONCE and is reused for
    every identically-shaped block.  With ``mesh`` the scanned step is the
    shard_map variant (engine="sharded"): data-parallel over the mesh's DP
    axes, and — when the mesh has a ``model`` axis — with the rounding/DST
    variables, frozen side state, block weights and Adam moments sharded
    over it per the ``launch.sharding.ParamSpec`` placement contract."""
    K = tcfg.par_iterations if tcfg.par else 1
    T = tcfg.steps_per_iteration
    trainable_keys = ("nu", "v") if tcfg.dst else ("nu",)
    # cache per mesh object, not per engine kind: the pipelined cross-pod
    # walk hands alternating pod submeshes to the same stage cache, and a
    # shard_map traced for one mesh cannot serve another
    key = "device" if mesh is None else ("sharded", mesh)
    eng = cache.get(key) if cache is not None else None
    if eng is None:
        # lazy import: sharding.py pulls core.qtensor through the package
        # root, so a module-level import here would be circular whenever
        # launch.sharding is imported first
        from repro.launch.sharding import ParamSpec
        param_specs = None
        pspec = ParamSpec.for_mesh(mesh)
        if mesh is not None and pspec.active:
            frozen_sts = {p: {k: v for k, v in st.items()
                              if k not in trainable_keys}
                          for p, st in states.items()}
            param_specs = {
                "tr": {p: {k: pspec.state_spec(p[-1], k, states[p][k].shape)
                           for k in trainable_keys}
                       for p in states},
                "frozen": {"bp": pspec.block_specs(bp),
                           "sts": pspec.state_specs(frozen_sts)},
            }
        eng = RE.ReconstructionEngine(_make_loss_fn(apply, qcfg, tcfg),
                                      AdamW(lr=tcfg.lr), mesh=mesh,
                                      param_specs=param_specs)
        if cache is not None:
            cache[key] = eng
    plan = RE.stage_plan(X, Y, aux, batch_size=tcfg.batch_size,
                         total_steps=K * T, seed=tcfg.seed, mesh=mesh)

    # mesh runs keep the WHOLE state tree explicitly mesh-placed: harden,
    # the engine and the log jit all take (parts of) it as arguments, and
    # any leaf left behind on the default device would be resharded
    # implicitly at dispatch — a silent device-to-device broadcast the
    # sanitizer's transfer_guard rejects.  Trainables follow their TP
    # placement (ParamSpec contract), everything else the frozen-state
    # specs; pure-DP meshes replicate (prefix P()).
    states_sp = None
    if mesh is not None:
        tr_sp, _, frz_sp = eng._carry_specs
        if isinstance(tr_sp, RE.P):
            states_sp = tr_sp
        else:
            sts_sp = frz_sp["sts"]
            states_sp = {p: {k: (tr_sp[p][k] if k in trainable_keys
                                 else sts_sp[p][k])
                             for k in st}
                         for p, st in states.items()}

    sr = list(tcfg.soft_rate)
    opt_state = None
    for k in range(K):
        if mesh is not None:
            states = RE._mesh_place(mesh, states, states_sp)
        if tcfg.par:
            states = RE.harden_device(
                states, sr[_schedule_index(k, K, len(sr))],
                tcfg.use_inf_freeze, mesh=mesh)
        tr = _trainables(states, tcfg.dst)
        # strip trainable entries from the side state: tr owns those buffers
        # (and donates them), frozen carries everything else
        frozen = {p: {kk: vv for kk, vv in st.items()
                      if kk not in trainable_keys}
                  for p, st in states.items()}
        if opt_state is None or not tcfg.carry_opt_state:
            opt_state = eng.init(tr)
        tr, opt_state, lv = eng.run(tr, opt_state, {"bp": bp, "sts": frozen},
                                    plan, start=k * T, steps=T)
        states = _merge(states, tr, tcfg.dst)
        if log is not None:
            # masks only: on mesh runs they are mesh-resident alongside lv
            # (see _log_stats docstring)
            hard = {p: st["hard"] for p, st in states.items()}
            stats = RE.host_read(_log_stats(lv, hard))
            log.append({"iter": k, "loss": float(stats[0]),
                        "soft_rate": float(stats[1])})
    return states


def _run_sharded(apply, bp, X, Y, aux, qcfg, tcfg: TesseraQConfig, states,
                 log: Optional[list], cache: Optional[dict] = None):
    """Mesh data-parallel engine: the device engine's loop with the scanned
    step shard_mapped over ``tcfg.mesh`` (or a default all-device data
    mesh).  ``tcfg.batch_size`` must be a multiple of the mesh's DP degree."""
    return _run_device(apply, bp, X, Y, aux, qcfg, tcfg, states, log, cache,
                       mesh=RE.resolve_mesh(tcfg.mesh))


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def reconstruct_block(apply: Callable, bp, X: np.ndarray, Y: np.ndarray,
                      aux, qmeta: Dict, qcfg: QuantConfig,
                      tcfg: TesseraQConfig, log: Optional[list] = None,
                      cache: Optional[dict] = None):
    """Run TesseraQ on one block.

    X: (N, S, d) inputs; Y: (N, S, d) FP outputs; aux: per-sample extra
    stream or None.  Returns (bp_fq, qmeta') with DST folded into qmeta.
    The inner loop runs on the engine selected by ``tcfg.engine``.

    ``cache`` (a plain dict the caller scopes to one stage — constant
    ``apply``/shapes/qcfg/tcfg) reuses the compiled inner loop across the
    stage's blocks instead of recompiling per block.
    """
    paths = quant_leaf_paths(bp)
    states = {p: _leaf_state(get_path(bp, p), qmeta[p], qcfg) for p in paths}

    runners = {"device": _run_device, "reference": _run_reference,
               "legacy": _run_legacy, "sharded": _run_sharded}
    if tcfg.engine not in runners:
        raise ValueError(f"unknown engine {tcfg.engine!r} "
                         f"(expected one of {sorted(runners)})")
    states = runners[tcfg.engine](apply, bp, X, Y, aux, qcfg, tcfg, states,
                                  log, cache)

    # ---- post-processing: hard-round everything, fold DST into the scale ---
    new_meta = {}
    for p in paths:
        st = states[p]
        alpha = np.where(np.asarray(st["hard"]) != 0,
                         (np.asarray(st["hard"]) > 0),
                         np.asarray(st["nu"]) > 0).astype(np.float32)
        q = np.clip(np.asarray(st["base"]) + np.asarray(st["zero"])[..., None, :]
                    + alpha, 0, qcfg.qmax)
        # the fold is taken in float64 and rounded once, so engines whose
        # v differ by an ulp land within 1 ulp on the folded scale
        dst_factor = (2.0 / (1.0 + np.exp(-np.asarray(st["v"], np.float64)))
                      if tcfg.dst else None)
        scale = np.asarray(st["scale"])
        scale_eff = (scale * dst_factor).astype(scale.dtype) \
            if dst_factor is not None else scale
        w = (q - np.asarray(st["zero"])[..., None, :]) * scale_eff[..., None, :]
        w = w.reshape(_wshape(st["nu"]))
        if st["act_scale"] is not None:
            w = w / np.asarray(st["act_scale"])[..., :, None]
        orig = get_path(bp, p)
        bp = set_path(bp, p, jnp.asarray(w).astype(orig.dtype))
        new_meta[p] = {
            "scale": jnp.asarray(scale_eff),          # DST folded in
            "zero": st["zero"],
            "act_scale": st["act_scale"],
            "dst": (jnp.asarray(dst_factor.astype(np.float32))
                    if dst_factor is not None else None),
            "codes": jnp.asarray(q.astype(np.uint8)).reshape(
                _wshape(st["nu"])),
            # final hardened mask (grouped layout) — the engine-parity tests
            # pin it bit-for-bit across device/sharded
            "hard": np.asarray(st["hard"]),
        }
    return bp, new_meta


def flip_stats(qmeta_before: Dict, qmeta_after: Dict) -> Dict:
    """Paper Table 7: fraction of rounding decisions that flipped vs RTN."""
    out = {}
    for p in qmeta_after:
        if "codes" not in qmeta_after[p] or "codes" not in qmeta_before[p]:
            continue
        a = np.asarray(qmeta_before[p]["codes"], np.int32)
        b = np.asarray(qmeta_after[p]["codes"], np.int32)
        out[p] = {"flipped": int((a != b).sum()), "total": int(a.size),
                  "pct": float((a != b).mean() * 100)}
    return out
