"""Per-kernel shape/dtype sweeps, allclose against the ref.py oracles
(interpret mode executes the Pallas body on CPU)."""
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.qtensor import pack
from repro.kernels import ref
from repro.kernels.ops import int8_matmul_op, quant_matmul_op, soft_round_op


@pytest.mark.parametrize("bits", [2, 3, 4, 8])
@pytest.mark.parametrize("shape", [(16, 128, 64, 32), (8, 256, 96, 128),
                                   (33, 64, 40, 64), (1, 64, 24, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_sweep(bits, shape, dtype):
    M, K, N, g = shape
    rng = np.random.default_rng(bits * 1000 + M)
    codes = rng.integers(0, 1 << bits, (K, N)).astype(np.uint8)
    scale = (rng.random((K // g, N)).astype(np.float32) + 0.5) * 0.1
    zero = rng.integers(0, 1 << bits, (K // g, N)).astype(np.float32)
    packed = pack(jnp.asarray(codes), bits, axis=0)
    x = jnp.asarray(rng.normal(size=(M, K)), dtype)
    got = quant_matmul_op(x, packed, jnp.asarray(scale), jnp.asarray(zero),
                          bits=bits, group_size=g,
                          block_m=16, block_n=32, block_k=max(g, 64))
    want = ref.quant_matmul_ref(x, packed, jnp.asarray(scale),
                                jnp.asarray(zero), bits=bits, group_size=g)
    tol = 1e-3 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bits", [2, 3, 4])
@pytest.mark.parametrize("group,block_k", [
    (32, 64),      # bk % group_size == 0: two groups per K tile
    (64, 64),      # bk % group_size == 0: exactly one group per tile
    (128, 64),     # group_size % bk == 0: each group spans two K tiles
    (256, 64),     # group_size % bk == 0: one group covers ALL K tiles
])
def test_quant_matmul_group_tile_branches(bits, group, block_k):
    """Parity of the Pallas dequant-matmul (interpret mode) vs the ref.py
    oracle across both group/tile alignment branches."""
    M, K, N = 16, 256, 64
    rng = np.random.default_rng(bits * 100 + group)
    codes = rng.integers(0, 1 << bits, (K, N)).astype(np.uint8)
    scale = (rng.random((K // group, N)).astype(np.float32) + 0.5) * 0.1
    zero = rng.integers(0, 1 << bits, (K // group, N)).astype(np.float32)
    packed = pack(jnp.asarray(codes), bits, axis=0)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    got = quant_matmul_op(x, packed, jnp.asarray(scale), jnp.asarray(zero),
                          bits=bits, group_size=group,
                          block_m=16, block_n=32, block_k=block_k)
    want = ref.quant_matmul_ref(x, packed, jnp.asarray(scale),
                                jnp.asarray(zero), bits=bits, group_size=group)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("shape", [(32, 128, 64), (16, 256, 32), (8, 64, 8)])
def test_int8_matmul_sweep(shape):
    M, K, N = shape
    rng = np.random.default_rng(M)
    xq = jnp.asarray(rng.integers(-127, 128, (M, K)), jnp.int8)
    wq = jnp.asarray(rng.integers(-127, 128, (K, N)), jnp.int8)
    sx = jnp.asarray((rng.random((M, 1)) + .1) * .01, jnp.float32)
    sw = jnp.asarray((rng.random((1, N)) + .1) * .01, jnp.float32)
    got = int8_matmul_op(xq, wq, sx, sw)
    want = ref.int8_matmul_ref(xq, wq, sx, sw)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("qmax,dst", [(3, True), (15, False), (255, True)])
def test_soft_round_sweep(qmax, dst):
    rng = np.random.default_rng(qmax)
    ng, g, n = 4, 32, 128
    base = rng.integers(-2, qmax, (ng, g, n)).astype(np.float32)
    nu = rng.normal(size=(ng, g, n)).astype(np.float32) * 3
    hard = rng.integers(-1, 2, (ng, g, n)).astype(np.int32)
    v = rng.normal(size=(ng, n)).astype(np.float32) * 0.2
    scale = (rng.random((ng, n)).astype(np.float32) + .5) * .1
    zero = rng.integers(0, max(qmax // 2, 1), (ng, n)).astype(np.float32)
    args = tuple(jnp.asarray(a) for a in (base, nu, hard, v, scale, zero))
    got = soft_round_op(*args, qmax=qmax, dst=dst)
    want = ref.soft_round_ref(*args, qmax=qmax, dst=dst)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_w4a8_path():
    """Dynamic per-token act quant + int kernel vs fp matmul (coarse)."""
    from repro.core.quantizer import make_qtensor
    from repro.configs.base import QuantConfig
    from repro.kernels.ops import w4a8_matmul
    rng = np.random.default_rng(9)
    w = jnp.asarray(rng.normal(size=(128, 64)), jnp.float32)
    qt = make_qtensor(w, QuantConfig(bits=8, group_size=None))
    x = jnp.asarray(rng.normal(size=(16, 128)), jnp.float32)
    got = np.asarray(w4a8_matmul(x, qt), np.float32)
    want = np.asarray(x @ w, np.float32)
    rel = np.abs(got - want).mean() / np.abs(want).mean()
    assert rel < 0.05


@pytest.mark.parametrize("bits,group", [(8, 32), (4, 64)])
def test_w4a8_grouped_not_silently_wrong(bits, group):
    """Grouped QTensors used to read only scale/zero row 0, silently
    returning garbage for every group past the first; now the per-group
    epilogue makes the grouped path agree with the fp matmul."""
    from repro.core.quantizer import make_qtensor
    from repro.configs.base import QuantConfig
    from repro.kernels.ops import w4a8_matmul
    rng = np.random.default_rng(11)
    # per-group magnitudes differ wildly so a row-0-only scale CANNOT pass
    w = rng.normal(size=(128, 32)).astype(np.float32)
    w *= np.repeat(10.0 ** rng.uniform(-2, 1, 128 // group), group)[:, None]
    qt = make_qtensor(jnp.asarray(w), QuantConfig(bits=bits, group_size=group))
    assert qt.group_size == group
    x = jnp.asarray(rng.normal(size=(8, 128)), jnp.float32)
    got = np.asarray(w4a8_matmul(x, qt), np.float32)
    # oracle: exact dequantized matmul — only the 8-bit activation quant
    # separates the two, so a scale/zero row-0-only bug shows up as O(1)
    want = np.asarray(x @ qt.dequantize(jnp.float32), np.float32)
    rel = np.abs(got - want).mean() / np.abs(want).mean()
    assert rel < 0.03, f"grouped w4a8 diverged (rel={rel:.3f})"


def test_w4a8_rejects_stacked():
    from repro.core.quantizer import make_qtensor
    from repro.configs.base import QuantConfig
    from repro.kernels.ops import w4a8_matmul
    rng = np.random.default_rng(12)
    w = jnp.asarray(rng.normal(size=(2, 64, 16)), jnp.float32)
    qt = make_qtensor(w, QuantConfig(bits=8, group_size=None))
    x = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    with pytest.raises(ValueError, match="non-stacked"):
        w4a8_matmul(x, qt)


@pytest.mark.parametrize("bits", [2, 3, 4])
@pytest.mark.parametrize("K,group,block_k", [
    (48, 16, 32),     # K % snapped bk != 0: K pads 48 -> 64
    (80, 16, 32),     # K pads 80 -> 96
    (96, 32, 64),     # bk % g == 0 but K % bk != 0: K pads 96 -> 128
    (40, 40, 64),     # per-channel, K < block_k: no padding needed
    (24, 8, 16),      # tiny everything
])
def test_quant_matmul_k_padding(bits, K, group, block_k):
    """Regression: when bk snapping/padding changes the K grid, EVERY
    K-keyed operand (x cols, packed rows, scale/zero rows) must pad
    together — the wrapper used to pad only x and shape-error."""
    M, N = 8, 32
    rng = np.random.default_rng(bits * 10 + K)
    codes = rng.integers(0, 1 << bits, (K, N)).astype(np.uint8)
    scale = (rng.random((K // group, N)).astype(np.float32) + 0.5) * 0.1
    zero = rng.integers(0, 1 << bits, (K // group, N)).astype(np.float32)
    packed = pack(jnp.asarray(codes), bits, axis=0)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    got = quant_matmul_op(x, packed, jnp.asarray(scale), jnp.asarray(zero),
                          bits=bits, group_size=group,
                          block_m=8, block_n=32, block_k=block_k)
    want = ref.quant_matmul_ref(x, packed, jnp.asarray(scale),
                                jnp.asarray(zero), bits=bits,
                                group_size=group)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-3, atol=1e-3)


# -- one M block a call: each weight tile dequantized once ------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("group,block_k", [
    (32, 64),      # bk % group_size == 0: two groups per K tile
    (128, 64),     # group_size % bk == 0: each group spans two K tiles
])
@pytest.mark.parametrize("M,block_m,tiles", [
    (600, None, 1),   # past one row block: the rolled row loop + remainder
    (33, None, 1),    # M not a multiple of 16: pads to the sublane tile
    (100, None, 1),
    (48, 48, 1),      # M equal to the row cap: still one block
    (96, 48, 2),      # past the cap: equal M tiles, each dequantizing
    (100, 32, 4),
])
def test_quant_matmul_rows_per_call(M, block_m, tiles, group, block_k,
                                    dtype):
    """A call's rows are one M block up to the row cap, padded only to the
    sublane tile; past the cap, equal tiles of at most the cap.  Both
    agree with the ref.py oracle across the group/tile branches."""
    from repro.kernels.quant_matmul import qmm_tiles, row_align
    bits, K, N = 2, 256, 256
    itemsize = jnp.dtype(dtype).itemsize
    bm, bn, Mp, Np = qmm_tiles(M, K, N, ppb=4, group_size=group, bk=block_k,
                               itemsize=itemsize, block_m=block_m)
    align = row_align(itemsize)
    assert (Mp // bm, Np, bn) == (tiles, N, N)
    assert bm % align == 0 and Mp - M < tiles * align
    rng = np.random.default_rng(M * 7 + group)
    codes = rng.integers(0, 1 << bits, (K, N)).astype(np.uint8)
    scale = (rng.random((K // group, N)).astype(np.float32) + 0.5) * 0.1
    zero = rng.integers(0, 1 << bits, (K // group, N)).astype(np.float32)
    packed = pack(jnp.asarray(codes), bits, axis=0)
    x = jnp.asarray(rng.normal(size=(M, K)), dtype)
    got = quant_matmul_op(x, packed, jnp.asarray(scale), jnp.asarray(zero),
                          bits=bits, group_size=group, block_m=block_m,
                          block_k=block_k)
    want = ref.quant_matmul_ref(x, packed, jnp.asarray(scale),
                                jnp.asarray(zero), bits=bits,
                                group_size=group)
    tol = 1e-3 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def _cell_prefill_linears():
    """``(name, K, N, M)`` of every packed-GEMM call the benchmark cells'
    B=1 prefills make: Mistral-7B's block linears at each prompt length of
    its mixes (and at its 4096 sliding window), Moonlight's non-routed
    linears (latent attention, shared experts, the dense first layer) at
    each of its own."""
    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.append(root)
    from bench import weights as W
    from bench import weights_mla_moe as WM
    from bench.run import cell_spec
    mistral = [cell_spec(c) for c in ("mistral7b-w2-decode",
                                      "mistral7b-w2-prefill")]
    lens = sorted({n for c in mistral for n in c["mix"]["prompt_lens"]}
                  | {mistral[0]["config"]["sliding_window"]})
    out = [(f"mistral-{name}", K, N, M)
           for name, (K, N) in W.linear_shapes(mistral[0]["config"]).items()
           for M in lens]
    moon = cell_spec("moonlight-w2-decode")
    cfg = moon["config"]
    shapes = dict(WM.linear_shapes(cfg, True))
    shapes.update({f"shared_{k}": v for k, v in
                   WM.linear_shapes(cfg, False)["shared"].items()})
    return out + [(f"moonlight-{name}", K, N, M)
                  for name, (K, N) in shapes.items()
                  for M in moon["mix"]["prompt_lens"]]


def test_cells_prefill_calls_plan_one_m_block():
    """Every prefill call of the benchmark cells plans ONE M block within
    the VMEM budget, with no row padding (the prompts are multiples of
    16) and N padded only to whole lane tiles (Moonlight's kv_a, 576 ->
    640): each weight tile is dequantized once per call."""
    from repro.kernels.quant_matmul import (_VMEM_BUDGET, qmm_tiles,
                                            vmem_bytes)
    calls = _cell_prefill_linears()
    assert {(K, N) for _, K, N, _ in calls} >= {(4096, 14336), (14336, 4096),
                                                (2048, 576)}
    for name, K, N, M in calls:
        bm, bn, Mp, Np = qmm_tiles(M, K, N, ppb=4, group_size=128, bk=512,
                                   itemsize=2)
        need = vmem_bytes(bm, bn, 512, ppb=4, group_size=128, K=K,
                          itemsize=2)
        assert (bm, Mp) == (M, M), (name, M, bm, Mp)
        assert Np == -(-N // 128) * 128 and Np % bn == 0, (name, N, Np, bn)
        assert need <= _VMEM_BUDGET, (name, M, need)


# -- decode-shaped fused dequant-GEMV ---------------------------------------

@pytest.mark.parametrize("bits", [2, 3, 4])
@pytest.mark.parametrize("M", [1, 2, 3, 5, 8, 24, 16, 32])
def test_quant_gemv_slot_sweep(bits, M):
    """Decode batches (M = live slots, 1..slots) through the GEMV kernel
    match the oracle — grouped, at every deployed bit-width."""
    from repro.kernels.ops import quant_gemv_op
    K, N, g = 256, 96, 32
    rng = np.random.default_rng(bits * 100 + M)
    codes = rng.integers(0, 1 << bits, (K, N)).astype(np.uint8)
    scale = (rng.random((K // g, N)).astype(np.float32) + 0.5) * 0.1
    zero = rng.integers(0, 1 << bits, (K // g, N)).astype(np.float32)
    packed = pack(jnp.asarray(codes), bits, axis=0)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    got = quant_gemv_op(x, packed, jnp.asarray(scale), jnp.asarray(zero),
                        bits=bits, group_size=g)
    want = ref.quant_matmul_ref(x, packed, jnp.asarray(scale),
                                jnp.asarray(zero), bits=bits, group_size=g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("bits", [2, 3, 4])
@pytest.mark.parametrize("K,group,N,M", [
    # per-channel: one group, one unit of 128 rows
    pytest.param(128, 128, 40, 3, id="128-128"),
    # groups smaller than a lane tile: unaligned x columns
    pytest.param(48, 16, 40, 3, id="48-16"),
    pytest.param(256, 64, 40, 3, id="256-64"),
    # several grid steps along N (5 of 128 columns), 8 groups along K
    pytest.param(1024, 128, 640, 1, id="1024-128-640-m1"),
    pytest.param(1024, 128, 640, 16, id="1024-128-640-m16"),
    pytest.param(1024, 128, 640, 32, id="1024-128-640-m32"),
    # 10 groups: one rolled trip of the group loop and two after it
    pytest.param(1280, 128, 384, 16, id="1280-128-384-m16"),
    # per-channel over 4 units of 256 rows, scaled once
    pytest.param(1024, 1024, 256, 8, id="perchannel-1024-m8"),
    # groups larger than a unit: 2 units of 256 rows each
    pytest.param(2048, 512, 128, 4, id="2048-512-m4"),
    # per-channel in units of 200 rows; N pads 200 -> 256
    pytest.param(1000, 1000, 200, 5, id="perchannel-1000-n200-m5"),
])
def test_quant_gemv_grouping_and_padding(bits, K, group, N, M):
    from repro.kernels.ops import quant_gemv_op
    rng = np.random.default_rng(bits * 10 + K + M)
    codes = rng.integers(0, 1 << bits, (K, N)).astype(np.uint8)
    scale = (rng.random((K // group, N)).astype(np.float32) + 0.5) * 0.1
    zero = rng.integers(0, 1 << bits, (K // group, N)).astype(np.float32)
    packed = pack(jnp.asarray(codes), bits, axis=0)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    got = quant_gemv_op(x, packed, jnp.asarray(scale), jnp.asarray(zero),
                        bits=bits, group_size=group)
    want = ref.quant_matmul_ref(x, packed, jnp.asarray(scale),
                                jnp.asarray(zero), bits=bits,
                                group_size=group)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("bits", [2, 3, 4])
def test_quant_gemv_bf16_within_the_xla_paths_weight_rounding(bits):
    """bf16 activations.  The XLA path (``ref.quant_matmul_ref``) rounds
    each weight ``(q - z) s`` to bf16 before its product; the GEMV applies
    scale and zero to each group's f32 partial instead.  So the two differ
    by at most that rounding of each weight, 2**-9 of it, summed over K,
    plus each one's rounding of its bf16 output, 2**-9 of the result.  The
    GEMV is the nearer of the two to the float32 product."""
    from repro.core.qtensor import QTensor
    from repro.kernels.ops import quant_gemv_op
    M, K, N, g = 16, 1024, 256, 128
    rng = np.random.default_rng(bits)
    codes = rng.integers(0, 1 << bits, (K, N)).astype(np.uint8)
    scale = jnp.asarray((rng.random((K // g, N)) + 0.5) * 0.1, jnp.float32)
    zero = jnp.asarray(rng.integers(0, 1 << bits, (K // g, N)), jnp.float32)
    packed = pack(jnp.asarray(codes), bits, axis=0)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.bfloat16)
    got = np.asarray(quant_gemv_op(x, packed, scale, zero, bits=bits,
                                   group_size=g), np.float64)
    want = np.asarray(ref.quant_matmul_ref(x, packed, scale, zero, bits=bits,
                                           group_size=g), np.float64)
    # scale and zero rounded to bf16 as both paths round them; the weight
    # itself exact (a small integer times a bf16 value)
    w = np.asarray(QTensor(packed, scale.astype(jnp.bfloat16).astype(
        jnp.float32), zero.astype(jnp.bfloat16).astype(jnp.float32), bits,
        g, (K, N)).dequantize(jnp.float32), np.float64)
    xf = np.asarray(x, np.float64)
    exact = xf @ w
    bound = 2.0 ** -9 * (np.abs(xf) @ np.abs(w)) \
        + 2.0 ** -9 * (np.abs(got) + np.abs(want))
    assert np.all(np.abs(got - want) <= bound)
    assert np.linalg.norm(got - exact) < np.linalg.norm(want - exact)


@pytest.mark.parametrize("bits", [2, 3, 4])
@pytest.mark.parametrize("group", [32, None])       # grouped and per-channel
@pytest.mark.parametrize("M", [1, 2, 4, 6, 8])
def test_qtensor_matmul_backend_parity_decode_rows(bits, group, M):
    """xla-vs-pallas parity at M = 1..slots on a real QTensor — the decode
    dispatch (GEMV route) must agree with the XLA unpack path at every
    deployed bit-width, grouped and per-channel."""
    from repro.core.quantizer import make_qtensor
    from repro.configs.base import QuantConfig
    from repro.core.qtensor import qmatmul
    from repro.kernels.ops import qtensor_matmul
    K = 128
    rng = np.random.default_rng(bits * 1000 + M + (group or 0))
    w = jnp.asarray(rng.normal(size=(K, 64)), jnp.float32)
    qt = make_qtensor(w, QuantConfig(bits=bits, group_size=group))
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    got = qtensor_matmul(x, qt)
    want = qmatmul(x, qt)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-3, atol=2e-3)


def test_qtensor_matmul_dispatch_boundary():
    """Rows <= DECODE_GEMV_MAX_ROWS take the GEMV, above take the tiled
    matmul — and the two agree where they meet."""
    from repro.core.quantizer import make_qtensor
    from repro.configs.base import QuantConfig
    from repro.kernels.ops import (DECODE_GEMV_MAX_ROWS, qtensor_matmul,
                                   quant_gemv_op, quant_matmul_op)
    K = 64
    rng = np.random.default_rng(21)
    w = jnp.asarray(rng.normal(size=(K, 32)), jnp.float32)
    qt = make_qtensor(w, QuantConfig(bits=4, group_size=32))
    s, z = qt.scale.astype(jnp.float32), qt.zero.astype(jnp.float32)
    at = jnp.asarray(rng.normal(size=(DECODE_GEMV_MAX_ROWS, K)), jnp.float32)
    above = jnp.asarray(rng.normal(size=(DECODE_GEMV_MAX_ROWS + 1, K)),
                        jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(qtensor_matmul(at, qt)),
        np.asarray(quant_gemv_op(at, qt.packed, s, z, bits=4, group_size=32)))
    np.testing.assert_array_equal(
        np.asarray(qtensor_matmul(above, qt)),
        np.asarray(quant_matmul_op(above, qt.packed, s, z,
                                   bits=4, group_size=32)))


# -- expert-folded grid ------------------------------------------------------

@pytest.mark.parametrize("bits", [2, 3, 4])
def test_expert_matmul_fused_grid_bit_parity(bits):
    """One pallas_call with the expert dim folded into the grid must be
    BIT-identical to the unrolled one-launch-per-expert version."""
    from repro.core.quantizer import make_qtensor
    from repro.configs.base import QuantConfig
    from repro.kernels.ops import (qtensor_expert_matmul,
                                   qtensor_expert_matmul_unrolled)
    E, C, K, N = 4, 16, 96, 48
    rng = np.random.default_rng(bits * 31)
    w = jnp.asarray(rng.normal(size=(E, K, N)), jnp.float32)
    qt = make_qtensor(w, QuantConfig(bits=bits, group_size=32))
    a = jnp.asarray(rng.normal(size=(E, C, K)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(qtensor_expert_matmul(a, qt)),
        np.asarray(qtensor_expert_matmul_unrolled(a, qt)))


def test_expert_matmul_rejects_non_stacked():
    from repro.core.quantizer import make_qtensor
    from repro.configs.base import QuantConfig
    from repro.kernels.ops import qtensor_expert_matmul
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    qt = make_qtensor(w, QuantConfig(bits=4, group_size=32))
    a = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    with pytest.raises(ValueError, match="expert-stacked"):
        qtensor_expert_matmul(a, qt)
