"""On-device reconstruction engine vs the NumPy reference path.

Four contracts (jitted PAR hardening + scanned inner loop + mesh sharding):
  (a) jitted global-threshold hardening freezes EXACTLY the same variables
      as the NumPy ``harden()`` — including score ties and use_inf_freeze;
  (b) a full ``reconstruct_block`` with ``engine="device"`` reproduces
      ``engine="reference"`` hardened masks and codes bit-for-bit at fixed
      seed, with DST-folded scales within ``SCALE_MAXULP``;
  (c) the realized soft-rate trajectory tracks HANDCRAFTED_SOFT_RATE,
      anchored at both ends (gentle ~10% first freeze, 0.0 soft at the end);
  (d) ``engine="sharded"`` on a data-parallel mesh reproduces
      ``engine="device"`` under the same contract — on a degenerate
      1-device mesh always, and on the real
      multi-device mesh when the test process sees >1 device (the CI
      multi-device job runs this file under
      ``XLA_FLAGS=--xla_force_host_platform_device_count=8``);
plus the engine's host-sync guarantee (<= 1 blocking read per PAR iteration,
exactly the optional log line).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import QuantConfig
from repro.core import omniquant as OQ
from repro.core import recon_engine as RE
from repro.core import signround as SR
from repro.core import tesseraq as TQ
from repro.core.rtn import quantize_block_rtn, rtn_leaf
from repro.launch.mesh import dp_size, make_data_mesh, make_mesh

QCFG = QuantConfig(bits=2, group_size=16)


# -- fixtures ----------------------------------------------------------------

def leaf_state(seed=0, shape=(32, 8), tie_fraction=0.0):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=shape).astype(np.float32)
    if tie_fraction:
        # duplicate a slice of weights so hardness scores tie exactly and
        # the joint threshold lands ON a tied score
        flat = w.reshape(-1)
        n = int(flat.size * tie_fraction)
        flat[n:2 * n] = flat[:n]
        w = flat.reshape(shape)
    wj = jnp.asarray(w)
    _, meta = rtn_leaf(wj, QCFG)
    return TQ._leaf_state(wj, meta, QCFG)


def two_linear_block(seed=0, d=32, n_samples=8):
    rng = np.random.default_rng(seed)
    bp = {"wq": jnp.asarray(rng.normal(size=(d, d)), jnp.float32),
          "w_up": jnp.asarray(rng.normal(size=(d, 2 * d)), jnp.float32)}

    def apply(b, x, aux):
        h = jnp.tanh(x @ b["wq"])
        out = h @ b["w_up"]
        if aux is not None:
            out = out + aux
        return out

    X = rng.normal(size=(n_samples, 6, d)).astype(np.float32)
    return bp, apply, X


def states_equal(a, b):
    for p in a:
        if not np.array_equal(np.asarray(a[p]["hard"]),
                              np.asarray(b[p]["hard"])):
            return False
        if not np.array_equal(np.asarray(a[p]["nu"]), np.asarray(b[p]["nu"])):
            return False
    return True


# -- (a) hardening parity ----------------------------------------------------

@pytest.mark.parametrize("use_inf", [False, True])
@pytest.mark.parametrize("tie_fraction", [0.0, 0.25])
def test_harden_device_matches_reference(use_inf, tie_fraction):
    states_np = {("a",): leaf_state(0, (32, 8), tie_fraction),
                 ("b",): leaf_state(1, (16, 12), tie_fraction)}
    states_dev = {p: dict(st) for p, st in states_np.items()}
    # walk a whole schedule so later iterations start from frozen state
    for rate in (0.9, 0.5, 0.2, 0.05, 0.0):
        states_np = TQ.harden(states_np, rate, use_inf=use_inf)
        states_dev = RE.harden_device(states_dev, rate, use_inf=use_inf)
        assert states_equal(states_np, states_dev), \
            f"freeze sets diverged at rate {rate}"


def test_harden_device_tie_freezes_whole_tie_class():
    """When the threshold lands on a tied score, BOTH paths freeze the whole
    tie class (>= threshold), possibly overshooting the target count."""
    st = leaf_state(3, (32, 8), tie_fraction=0.3)
    total = st["nu"].size
    a = TQ.harden({("w",): dict(st)}, 0.5, use_inf=False)
    b = RE.harden_device({("w",): dict(st)}, 0.5, use_inf=False)
    na = int((np.asarray(a[("w",)]["hard"]) != 0).sum())
    nb = int((np.asarray(b[("w",)]["hard"]) != 0).sum())
    assert na == nb
    assert na >= total - int(total * 0.5)     # at least the target froze


def test_harden_device_noop_when_target_above_current():
    st = leaf_state(4)
    frozen = RE.harden_device({("w",): st}, 0.5, use_inf=False)
    again = RE.harden_device(frozen, 0.9, use_inf=False)   # nothing to do
    np.testing.assert_array_equal(np.asarray(frozen[("w",)]["hard"]),
                                  np.asarray(again[("w",)]["hard"]))


# -- canonical chunked gradient association ----------------------------------

def test_grad_chunk_count():
    """C = gcd(gcd(bs, CANONICAL_LANE_CHUNKS), pool): a pure function of
    the minibatch and pool sizes, never of the device count, capped so the
    sharded exchange stays O(C x |params|)."""
    assert RE.CANONICAL_LANE_CHUNKS == 8
    assert RE.grad_chunk_count(4, 8) == 4
    assert RE.grad_chunk_count(8, 8) == 8
    assert RE.grad_chunk_count(16, 16) == 8     # capped: 2 lanes per chunk
    assert RE.grad_chunk_count(32, 32) == 8     # capped: 4 lanes per chunk
    assert RE.grad_chunk_count(7, 8) == 1       # odd batch: single chunk
    assert RE.grad_chunk_count(12, 12) == 4
    assert RE.grad_chunk_count(8, 12) == 4      # pool limits the grid too


def test_draw_index_plan_stratified_over_chunk_shards():
    """Chunk j of every step's minibatch draws only from pool shard j
    (rows [j*N/C, (j+1)*N/C)) without replacement — the property that lets
    the sharded engine read every minibatch row from its own pool shard."""
    N, bs, steps = 16, 16, 7
    C = RE.grad_chunk_count(bs, N)
    c, Ns = bs // C, N // C
    plan = RE.draw_index_plan(N, bs, steps, seed=3)
    assert plan.shape == (steps, bs) and plan.dtype == np.int32
    for t in range(steps):
        for j in range(C):
            chunk = plan[t, j * c:(j + 1) * c]
            assert chunk.min() >= j * Ns and chunk.max() < (j + 1) * Ns
            assert len(set(chunk.tolist())) == c      # no replacement
    # pure function of (N, bs, steps, seed): identical on every call site
    np.testing.assert_array_equal(plan, RE.draw_index_plan(N, bs, steps,
                                                           seed=3))


def test_canonical_grad_matches_engine_chunking():
    """make_canonical_grad with the canonical chunk count reproduces the
    engine's two-level reduction bit-for-bit for a toy loss."""
    def loss_fn(tr, frozen, xb, yb, auxb):
        return jnp.mean(jnp.square(xb @ tr["w"] - yb))

    rng = np.random.default_rng(0)
    tr = {"w": jnp.asarray(rng.normal(size=(4, 3)), jnp.float32)}
    xb = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
    yb = jnp.asarray(rng.normal(size=(16, 3)), jnp.float32)
    C = RE.grad_chunk_count(16, 16)
    lv, g = RE.make_canonical_grad(loss_fn, chunks=C)(tr, None, xb, yb, None)
    # manual two-level association: per-chunk same-shape ordered lane sums,
    # then one ordered sum over the stacked chunk partials in chunk order
    lanes_l, lanes_g = RE.make_per_sample_grad(loss_fn)(tr, None, xb, yb,
                                                        None)
    c = 16 // C
    lp = jnp.sum(jnp.reshape(lanes_l, (C, c)), axis=1)
    gp = jnp.sum(jnp.reshape(lanes_g["w"], (C, c, 4, 3)), axis=1)
    np.testing.assert_array_equal(np.asarray(lv),
                                  np.asarray(jnp.sum(lp) / 16))
    np.testing.assert_array_equal(np.asarray(g["w"]),
                                  np.asarray(jnp.sum(gp, axis=0) / 16))


# -- (b) full-block bit-for-bit parity ---------------------------------------

@pytest.mark.parametrize("kwargs,run", [
    ({}, {}),
    ({"use_inf_freeze": True}, {}),
    ({"carry_opt_state": False}, {}),
    ({"dst": False}, {}),
    # bs=16 over a 16-sample pool puts MULTIPLE lanes in each canonical
    # chunk (C=8, 2 lanes/chunk): the two-level association must still
    # match on one device
    ({}, {"seed": 13, "bs": 16, "n_samples": 16}),
], ids=["default", "inf_freeze", "no_carry", "no_dst", "chunked"])
def test_device_engine_bit_for_bit(kwargs, run):
    if run.get("bs") == 16:
        assert RE.grad_chunk_count(16, 16) == 8
    metas = _run_both({"reference": None, "device": None}, kwargs,
                      **{"seed": 0, "bs": 4, **run})
    _assert_meta_equal(metas["reference"], metas["device"],
                       what="device vs reference")


def test_device_engine_bit_for_bit_with_aux():
    bp, apply, X = two_linear_block(seed=2)
    rng = np.random.default_rng(7)
    aux = (rng.normal(size=(8, 6, 2 * 32)) * 0.1).astype(np.float32)
    Y = np.asarray(apply(bp, jnp.asarray(X), jnp.asarray(aux)))
    _, qmeta = quantize_block_rtn(bp, QCFG)
    metas = {}
    for engine in ("reference", "device"):
        tcfg = TQ.TesseraQConfig(par_iterations=3, steps_per_iteration=10,
                                 batch_size=4, engine=engine)
        _, metas[engine] = TQ.reconstruct_block(
            apply, bp, X, Y, aux, dict(qmeta), QCFG, tcfg)
    for p in metas["reference"]:
        np.testing.assert_array_equal(
            np.asarray(metas["reference"][p]["codes"]),
            np.asarray(metas["device"][p]["codes"]))


def test_legacy_engine_codes_match_device():
    """The pre-engine eager-Adam loop drifts from the fused step by ~1 ulp
    (so folded scales are NOT bit-identical), but the discrete rounding
    decisions still agree."""
    bp, apply, X = two_linear_block(seed=9)
    Y = np.asarray(apply(bp, jnp.asarray(X), None))
    _, qmeta = quantize_block_rtn(bp, QCFG)
    metas = {}
    for engine in ("legacy", "device"):
        tcfg = TQ.TesseraQConfig(par_iterations=3, steps_per_iteration=10,
                                 batch_size=4, engine=engine)
        _, metas[engine] = TQ.reconstruct_block(
            apply, bp, X, Y, None, dict(qmeta), QCFG, tcfg)
    for p in metas["legacy"]:
        np.testing.assert_array_equal(
            np.asarray(metas["legacy"][p]["codes"]),
            np.asarray(metas["device"][p]["codes"]))
        np.testing.assert_allclose(
            np.asarray(metas["legacy"][p]["scale"]),
            np.asarray(metas["device"][p]["scale"]), rtol=1e-5)


# -- (c) soft-rate trajectory ------------------------------------------------

def test_soft_rate_trajectory_matches_schedule():
    """K == len(HANDCRAFTED_SOFT_RATE): the realized post-harden soft count
    equals int(total * schedule[k]) every iteration (no ties in random
    float32 scores), anchored at ~0.9 first and exactly 0.0 last."""
    bp, apply, X = two_linear_block(seed=5, d=16)
    Y = np.asarray(apply(bp, jnp.asarray(X), None))
    _, qmeta = quantize_block_rtn(bp, QCFG)
    sr = TQ.HANDCRAFTED_SOFT_RATE
    tcfg = TQ.TesseraQConfig(par_iterations=len(sr), steps_per_iteration=2,
                             batch_size=4, engine="device")
    log = []
    TQ.reconstruct_block(apply, bp, X, Y, None, dict(qmeta), QCFG, tcfg,
                         log=log)
    total = sum(np.asarray(bp[k]).size for k in ("wq", "w_up"))
    realized = [l["soft_rate"] for l in log]
    # both ends anchored ...
    assert realized[0] == pytest.approx(int(total * sr[0]) / total, abs=1e-6)
    assert realized[-1] == 0.0
    # ... and every intermediate iteration hits its scheduled target
    for k, r in enumerate(realized):
        n_soft = r * total
        assert n_soft == pytest.approx(int(total * sr[k]), abs=0.5), \
            f"iter {k}: {n_soft} soft vs target {int(total * sr[k])}"
    assert all(a >= b
               for a, b in zip(realized, realized[1:], strict=False))


def test_soft_rate_schedule_stretch_anchors_for_small_k():
    """K != len(schedule): the stretched schedule still starts at sr[0] and
    ends at 0.0 (paper's gentle start / complete finish)."""
    bp, apply, X = two_linear_block(seed=6, d=16)
    Y = np.asarray(apply(bp, jnp.asarray(X), None))
    _, qmeta = quantize_block_rtn(bp, QCFG)
    tcfg = TQ.TesseraQConfig(par_iterations=5, steps_per_iteration=2,
                             batch_size=4, engine="device")
    log = []
    TQ.reconstruct_block(apply, bp, X, Y, None, dict(qmeta), QCFG, tcfg,
                         log=log)
    total = sum(np.asarray(bp[k]).size for k in ("wq", "w_up"))
    assert log[0]["soft_rate"] == pytest.approx(
        int(total * TQ.HANDCRAFTED_SOFT_RATE[0]) / total, abs=1e-6)
    assert log[-1]["soft_rate"] == 0.0


# -- (d) mesh-sharded engine parity ------------------------------------------

def _multidevice_mesh():
    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device; run under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    mesh = make_data_mesh()
    if dp_size(mesh) > 8:
        pytest.skip("fixture calibration pool has 8/16 samples")
    return mesh


# DST-folded scales: the one tolerance every engine pair is held to.  XLA
# compiles the identical step differently inside different programs (a scan
# body vs a standalone jit, a scan of T steps vs T scans of one step, a
# shard_map body), which moves the continuous DST variable v by an ulp or
# so.  ``reconstruct_block`` folds DST in float64 and rounds once, so the
# folded scale lands within 1 ulp (an f32 fold, two roundings next to 1.0,
# reached 2).  Without DST the scale stays exact.
SCALE_MAXULP = 1


def _assert_meta_equal(a, b, *, what):
    """The engine-parity contract: the deployment artifacts (hardened mask
    and packed codes) bit-for-bit, DST-folded scales within
    ``SCALE_MAXULP``; without DST nothing trained touches the scale, so it
    stays exact."""
    for p in a:
        np.testing.assert_array_equal(
            np.asarray(a[p]["hard"]), np.asarray(b[p]["hard"]),
            err_msg=f"{what}: hardened mask diverged at {p}")
        np.testing.assert_array_equal(
            np.asarray(a[p]["codes"]), np.asarray(b[p]["codes"]),
            err_msg=f"{what}: codes diverged at {p}")
        sa, sb = np.asarray(a[p]["scale"]), np.asarray(b[p]["scale"])
        if a[p]["dst"] is None:
            np.testing.assert_array_equal(
                sa, sb, err_msg=f"{what}: scale diverged at {p}")
        else:
            np.testing.assert_array_max_ulp(sa, sb, maxulp=SCALE_MAXULP)


def _run_both(engines, kwargs, *, seed=11, aux_seed=None, bs, n_samples=8):
    bp, apply, X = two_linear_block(seed=seed, n_samples=n_samples)
    aux = None
    if aux_seed is not None:
        rng = np.random.default_rng(aux_seed)
        aux = (rng.normal(size=(n_samples, 6, 64)) * 0.1).astype(np.float32)
    Y = np.asarray(apply(bp, jnp.asarray(X),
                         jnp.asarray(aux) if aux is not None else None))
    _, qmeta = quantize_block_rtn(bp, QCFG)
    metas = {}
    for engine, mesh in engines.items():
        tcfg = TQ.TesseraQConfig(par_iterations=4, steps_per_iteration=12,
                                 batch_size=bs, engine=engine, mesh=mesh,
                                 **kwargs)
        _, metas[engine] = TQ.reconstruct_block(
            apply, bp, X, Y, aux, dict(qmeta), QCFG, tcfg)
    return metas


@pytest.mark.parametrize("shape,names", [
    ((1,), ("data",)),
    # a (1, 1) ("data", "model") mesh activates the ENTIRE ParamSpec
    # tensor-parallel path — sharded rounding variables and Adam state,
    # per-step gather, per-shard gradient slice — at degree 1
    ((1, 1), None),
], ids=["data1", "tp1"])
def test_sharded_engine_degenerate_mesh_bit_for_bit(shape, names):
    """Degenerate sharding (1-device data mesh, TP=1 mesh) must change
    nothing — runs in the plain tier-1 suite on a single device."""
    mesh = make_mesh(shape, names) if names else make_mesh(shape)
    metas = _run_both({"device": None, "sharded": mesh}, {}, bs=4)
    _assert_meta_equal(metas["device"], metas["sharded"],
                       what=f"sharded({dict(mesh.shape)} mesh) vs device")


def _tp_mesh():
    """A ("data", "model") mesh with real TP extent — (2, 4) on the CI
    8-device host platform."""
    n = len(jax.devices())
    if n < 4 or n % 2 or n > 16:
        pytest.skip("needs an even 4..16 device count; run under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    tp = 4 if n % 8 == 0 else 2
    return make_mesh((n // tp, tp))


@pytest.mark.parametrize("kwargs", [
    {},
    {"dst": False},
    {"carry_opt_state": False},
], ids=["default", "no_dst", "no_carry"])
def test_tp_sharded_engine_bit_for_bit_multidevice(kwargs):
    """The TP acceptance contract: with weights, rounding/DST variables and
    Adam state sharded over the model axis per ParamSpec, the engine on a
    (data=2, model=4) mesh reproduces the device engine's hardened masks,
    packed codes AND folded scales bit-for-bit — every TP peer sees the
    identical full gradient (the calibration batch is replicated over the
    model axis), and slicing before the elementwise Adam update commutes
    with updating then slicing."""
    mesh = _tp_mesh()
    metas = _run_both({"device": None, "sharded": mesh}, kwargs,
                      bs=2 * dp_size(mesh))
    _assert_meta_equal(metas["device"], metas["sharded"],
                       what=f"TP-sharded {dict(mesh.shape)} vs device")


def test_tp_sharded_engine_bit_for_bit_multidevice_with_aux():
    mesh = _tp_mesh()
    metas = _run_both({"device": None, "sharded": mesh}, {}, seed=2,
                      aux_seed=7, bs=2 * dp_size(mesh))
    _assert_meta_equal(metas["device"], metas["sharded"],
                       what="TP-sharded vs device (aux)")


def test_sharded_engine_default_mesh_resolution():
    """engine="sharded" with mesh=None resolves to a data mesh over all
    visible devices (whatever their count) and still matches device."""
    if len(jax.devices()) > 8:
        pytest.skip("fixture calibration pool has 8 samples")
    metas = _run_both({"device": None, "sharded": None}, {},
                      bs=len(jax.devices()))
    _assert_meta_equal(metas["device"], metas["sharded"],
                       what="sharded(default mesh) vs device")


@pytest.mark.parametrize("kwargs", [
    {},
    {"use_inf_freeze": True},
    {"carry_opt_state": False},
    {"dst": False},
], ids=["default", "inf_freeze", "no_carry", "no_dst"])
def test_sharded_engine_bit_for_bit_multidevice(kwargs):
    """The acceptance contract: sharded on a real multi-device mesh is
    bit-identical to the device engine (mask, codes AND folded scales at
    this calibration horizon)."""
    mesh = _multidevice_mesh()
    metas = _run_both({"device": None, "sharded": mesh}, kwargs,
                      bs=dp_size(mesh))
    _assert_meta_equal(metas["device"], metas["sharded"],
                       what="sharded vs device")


def test_sharded_engine_bit_for_bit_multidevice_with_aux():
    mesh = _multidevice_mesh()
    metas = _run_both({"device": None, "sharded": mesh}, {}, seed=2,
                      aux_seed=7, bs=dp_size(mesh))
    _assert_meta_equal(metas["device"], metas["sharded"],
                       what="sharded vs device (aux)")


def test_sharded_engine_three_way_multidevice():
    """sharded == device == reference on identical inputs."""
    mesh = _multidevice_mesh()
    metas = _run_both({"reference": None, "device": None, "sharded": mesh},
                      {}, bs=dp_size(mesh))
    _assert_meta_equal(metas["reference"], metas["device"],
                       what="device vs reference")
    _assert_meta_equal(metas["device"], metas["sharded"],
                       what="sharded vs device")


def test_chunked_association_three_way_multidevice():
    """The chunked-reduction acceptance contract at dp>1: with bs=16 over a
    16-sample pool each device reduces 2 lanes into its local chunk partial
    before the fused exchange.  With multi-lane chunks XLA may lower the
    within-chunk reduce marginally differently for the local shard than
    for the full stack — more of the cross-program noise the parity
    contract (``_assert_meta_equal``) already absorbs."""
    mesh = _multidevice_mesh()
    if RE.grad_chunk_count(16, 16) % dp_size(mesh):
        pytest.skip("DP degree must divide the canonical chunk count")
    metas = _run_both({"reference": None, "device": None, "sharded": mesh},
                      {}, seed=13, bs=16, n_samples=16)
    _assert_meta_equal(metas["reference"], metas["device"],
                       what="chunked: device vs reference")
    _assert_meta_equal(metas["device"], metas["sharded"],
                       what="chunked: sharded vs device")


def test_stage_plan_shards_streams_by_dp_degree():
    """With a mesh, staged calibration streams are batch-sharded over the
    DP axes: every device holds exactly N/D rows — per-device stream bytes
    shrink by the DP degree versus the replicated baseline."""
    mesh = _multidevice_mesh()
    D = dp_size(mesh)
    if 16 % D:
        pytest.skip("16-sample pool must divide by the DP degree")
    bp, apply, X = two_linear_block(seed=14, n_samples=16)
    Y = np.asarray(apply(bp, jnp.asarray(X), None))
    plan = RE.stage_plan(X, Y, batch_size=8, total_steps=2, mesh=mesh)
    per_device = {}
    for arr in (plan.X, plan.Y):
        for s in arr.addressable_shards:
            assert s.data.shape[0] == arr.shape[0] // D, \
                f"expected a 1/{D} batch shard, got {s.data.shape}"
            per_device[s.device] = per_device.get(s.device, 0) \
                + s.data.nbytes
    replicated = plan.X.nbytes + plan.Y.nbytes
    assert max(per_device.values()) * D == replicated
    # the index plan stays replicated (it is tiny) and the plan still runs
    eng = RE.ReconstructionEngine(
        TQ._make_loss_fn(apply, QCFG, TQ.TesseraQConfig()),
        TQ.AdamW(lr=1e-3), mesh=mesh)
    _, qmeta = quantize_block_rtn(bp, QCFG)
    states = {p: TQ._leaf_state(TQ.get_path(bp, p), qmeta[p], QCFG)
              for p in qmeta}
    tr = TQ._trainables(states, True)
    frozen = {p: {k: v for k, v in st.items() if k not in ("nu", "v")}
              for p, st in states.items()}
    tr, _, lv = eng.run(tr, eng.init(tr), {"bp": bp, "sts": frozen}, plan)
    assert np.isfinite(float(lv))


def test_sharded_engine_batch_divisibility_error():
    mesh = _multidevice_mesh()
    bp, apply, X = two_linear_block(seed=3)
    Y = np.asarray(apply(bp, jnp.asarray(X), None))
    _, qmeta = quantize_block_rtn(bp, QCFG)
    # N.B. stage_plan clamps batch_size to the pool size, so pick a bs
    # UNDER the DP degree that still doesn't divide it
    tcfg = TQ.TesseraQConfig(par_iterations=1, steps_per_iteration=2,
                             batch_size=dp_size(mesh) - 1, engine="sharded",
                             mesh=mesh)
    with pytest.raises(ValueError, match="data-parallel degree"):
        TQ.reconstruct_block(apply, bp, X, Y, None, dict(qmeta), QCFG, tcfg)


def test_sharded_engine_rejects_meshes_without_dp_axes():
    mesh = make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="no data-parallel axes"):
        RE.ReconstructionEngine(lambda tr, fr, x, y, a: 0.0, TQ.AdamW(lr=1e-3),
                                mesh=mesh)


def test_omniquant_signround_sharded_match_device():
    """The baselines share the engine: sharded == device for LWC (AdamW)
    and SignRound (SignSGD) too."""
    mesh = (make_data_mesh() if 2 <= len(jax.devices()) <= 8
            else make_mesh((1,), ("data",)))
    bs = dp_size(mesh)
    bp, apply, X = two_linear_block(seed=4)
    Y = np.asarray(apply(bp, jnp.asarray(X), None))
    _, qmeta = quantize_block_rtn(bp, QCFG)
    for name, run in (
        ("omniquant", lambda eng, m: OQ.reconstruct_block(
            apply, bp, X, Y, None, QCFG, steps=30, batch_size=bs,
            engine=eng, mesh=m)),
        ("signround", lambda eng, m: SR.reconstruct_block(
            apply, bp, X, Y, None, dict(qmeta), QCFG, steps=30,
            batch_size=bs, engine=eng, mesh=m)),
    ):
        _, md = run("device", None)
        _, ms = run("sharded", mesh)
        for p in md:
            np.testing.assert_array_equal(
                np.asarray(md[p]["codes"]), np.asarray(ms[p]["codes"]),
                err_msg=f"{name}: codes diverged at {p}")
            np.testing.assert_allclose(
                np.asarray(md[p]["scale"]), np.asarray(ms[p]["scale"]),
                rtol=1e-5, err_msg=f"{name}: scale diverged at {p}")


def test_sharded_engine_host_syncs():
    """The sharded engine keeps the device engine's host-sync contract."""
    mesh = make_mesh((1,), ("data",))
    bp, apply, X = two_linear_block(seed=8, d=16)
    Y = np.asarray(apply(bp, jnp.asarray(X), None))
    _, qmeta = quantize_block_rtn(bp, QCFG)
    K = 3
    tcfg = TQ.TesseraQConfig(par_iterations=K, steps_per_iteration=5,
                             batch_size=4, engine="sharded", mesh=mesh)
    RE.reset_sync_count()
    TQ.reconstruct_block(apply, bp, X, Y, None, dict(qmeta), QCFG, tcfg,
                         log=[])
    assert RE.sync_count() == K


def _tiny_walk(engine, *, num_layers=2, batch_size=8, K=2, T=4, mesh=None):
    from repro.configs import get_reduced_config
    from repro.core.pipeline import quantize_model
    from repro.models import get_model
    cfg = get_reduced_config("tinyllama-1.1b").replace(num_layers=num_layers)
    m = get_model(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batches = [{"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (8, 12)))}]
    tcfg = TQ.TesseraQConfig(par_iterations=K, steps_per_iteration=T,
                             batch_size=batch_size, engine=engine, mesh=mesh)
    return quantize_model(cfg, params, batches,
                          QuantConfig(bits=2, group_size=32),
                          method="tesseraq", init="rtn", tcfg=tcfg)


def test_quantize_model_sharded_end_to_end():
    """The headline path: a full quantize_model walk with engine="sharded"
    (mesh-resident streams, sharded capture forwards, prefetch pipeline)
    matches engine="device" on every block's hardened mask and codes."""
    _multidevice_mesh()
    metas = {e: _tiny_walk(e)[1] for e in ("device", "sharded")}
    assert set(metas["device"]) == set(metas["sharded"])
    for k in metas["device"]:
        np.testing.assert_array_equal(
            np.asarray(metas["device"][k]["hard"]),
            np.asarray(metas["sharded"][k]["hard"]),
            err_msg=f"walk: hardened mask diverged at {k}")
        np.testing.assert_array_equal(
            np.asarray(metas["device"][k]["codes"]),
            np.asarray(metas["sharded"][k]["codes"]),
            err_msg=f"walk: codes diverged at {k}")


def test_quantize_model_pod_pipelined_walk():
    """The multi-pod walk on a ("pod","data","model") mesh: blocks
    round-robin over the per-pod submeshes, the cross-pod prefetch feeds
    block k+1's reconstruction from block k's targets, and the report
    carries per-stage pipeline profiling.  Walk-level numerics are
    tolerance-checked, not bit-checked: placing the capture forwards
    TP-sharded makes GSPMD psum the in-split contractions, which perturbs
    the Y targets at the ulp level (the engine-level TP tests above pin
    bit-exactness on identical staged inputs)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices; run under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    mesh = make_mesh((2, 2, 2))
    _, qm_s, rep_s = _tiny_walk("sharded", num_layers=3, mesh=mesh)
    _, qm_d, rep_d = _tiny_walk("device", num_layers=3)

    pl = rep_s["pipeline"]
    assert pl["pods"] == 2 and pl["dp"] == 2 and pl["tp"] == 2
    assert [b["pod"] for b in pl["blocks"]] == [0, 1, 0]
    # blocks 1..2 were prefetched cross-pod: their residual capture wait
    # was measured, and the steady-state efficiency summarizes it
    assert [b["capture_wait_secs"] is None for b in pl["blocks"]] == \
        [True, False, False]
    assert pl["blocks"][0]["fill_secs"] > 0       # pipeline fill: block 0
    assert 0.0 < pl["efficiency"] <= 1.0

    # same artifact surface, closely tracking numerics
    assert set(qm_s) == set(qm_d)
    for k in qm_d:
        assert np.asarray(qm_s[k]["codes"]).shape == \
            np.asarray(qm_d[k]["codes"]).shape
    mse_s = [b["recon_mse"] for b in rep_s["blocks"]]
    mse_d = [b["recon_mse"] for b in rep_d["blocks"]]
    np.testing.assert_allclose(mse_s, mse_d, rtol=0.15)


def test_quantize_model_sharded_lifts_default_batch():
    """quantize_model lifts a non-divisible default batch_size to the DP
    degree instead of dying mid-walk in the engine."""
    _multidevice_mesh()
    _, qmeta, report = _tiny_walk("sharded", num_layers=1, batch_size=4,
                                  K=1, T=2)
    assert report["blocks"] and qmeta


def test_quantize_model_sharded_pool_smaller_than_mesh_fails_fast():
    """A calibration pool below the DP degree can never fill a divisible
    minibatch — quantize_model must say so up front, not mid-walk."""
    mesh = _multidevice_mesh()
    from repro.configs import get_reduced_config
    from repro.core.pipeline import quantize_model
    from repro.models import get_model
    cfg = get_reduced_config("tinyllama-1.1b").replace(num_layers=1)
    m = get_model(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batches = [{"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (dp_size(mesh) - 1, 12)))}]
    tcfg = TQ.TesseraQConfig(par_iterations=1, steps_per_iteration=2,
                             batch_size=4, engine="sharded")
    with pytest.raises(ValueError, match="calibration pool"):
        quantize_model(cfg, params, batches,
                       QuantConfig(bits=2, group_size=32),
                       method="tesseraq", init="rtn", tcfg=tcfg)


# -- host-sync guarantee -----------------------------------------------------

def test_device_engine_host_syncs():
    bp, apply, X = two_linear_block(seed=8, d=16)
    Y = np.asarray(apply(bp, jnp.asarray(X), None))
    _, qmeta = quantize_block_rtn(bp, QCFG)
    K = 4
    for log, expected in ((None, 0), ([], K)):
        tcfg = TQ.TesseraQConfig(par_iterations=K, steps_per_iteration=5,
                                 batch_size=4, engine="device")
        RE.reset_sync_count()
        TQ.reconstruct_block(apply, bp, X, Y, None, dict(qmeta), QCFG, tcfg,
                             log=log)
        assert RE.sync_count() == expected, \
            f"log={log is not None}: {RE.sync_count()} syncs, " \
            f"expected {expected} (<= 1 per PAR iteration)"
