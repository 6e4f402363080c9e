"""AWQ: activation-aware weight quantization (Lin et al., 2023), with the
asymmetric-clipping variant (Gong et al., 2024) the paper initializes from.

Per linear: grid-search (1) the equivalent-transformation exponent alpha for
the per-input-channel scale  s_ch = mean|X|^alpha / norm , and (2) a clipping
shrink factor on the group min/max — both against the layer reconstruction
objective  || (X/s_ch) Q(W*s_ch) - X W ||_F^2  on a captured token subsample.

The candidates are scored on the device, all of a linear's in one program
(one host read per linear): each is a full fake-quantize and a
(rows x in x out) product, so scoring on the host would move every
candidate weight off the device and multiply it there.
"""
from __future__ import annotations

import functools
import warnings

import jax
import numpy as np
import jax.numpy as jnp

from repro.configs.base import QuantConfig
from repro.core import quantizer as Q
from repro.core.blocks import get_path, quant_leaf_paths, set_path

ALPHA_GRID = (0.0, 0.15, 0.3, 0.45, 0.6, 0.75, 0.9)
CLIP_GRID = (1.0, 0.95, 0.9, 0.85)


def _act_scale(mean_abs: np.ndarray, alpha: float) -> np.ndarray:
    s = np.power(np.maximum(mean_abs, 1e-5), alpha)
    s = s / np.exp(np.mean(np.log(s)))          # geo-mean normalize
    return np.clip(s, 1e-4, 1e4).astype(np.float32)


def _layer_out(X, w):
    """(n, in) x (..., in, out) in full f32 precision."""
    hi = jax.lax.Precision.HIGHEST
    if w.ndim == 2:
        return jnp.matmul(X, w, precision=hi)
    return jnp.einsum("ni,eio->eno", X, w, precision=hi)



def _candidate_err(X, wf, y_ref, s_ch, clip, qcfg: QuantConfig):
    """Layer reconstruction error of one (alpha, clip) candidate."""
    fq = Q.fake_quantize(wf * s_ch[..., :, None], qcfg, gamma=clip,
                         beta=clip)
    y = _layer_out(X, fq / s_ch[..., :, None])
    return jnp.mean(jnp.square(y - y_ref))


@functools.partial(jax.jit, static_argnames=("qcfg",))
def _candidate_errs(X, wf, s_all, clips, *, qcfg: QuantConfig):
    """Errors of every candidate, one after another (``lax.map``, so only
    one candidate's fake-quantized weight is live at a time)."""
    y_ref = _layer_out(X, wf)
    return jax.lax.map(
        lambda sc: _candidate_err(X, wf, y_ref, sc[0], sc[1], qcfg),
        (s_all, clips))


def awq_leaf(w, stats, qcfg: QuantConfig):
    """Returns (fake-quant effective weight, qmeta).  w: (..., in, out)."""
    wf = np.asarray(w, np.float32)
    X = stats.sample                                     # (n, in)
    if X.shape[0] == 0 or X.shape[1] != wf.shape[-2]:
        # no activations seen (shouldn't happen) -> fall back to RTN
        from repro.core.rtn import rtn_leaf
        return rtn_leaf(w, qcfg)
    grid = [(a, c) for a in ALPHA_GRID for c in CLIP_GRID]
    s_all = np.stack([_act_scale(stats.mean_abs, a) for a, _ in grid])
    clips = np.asarray([c for _, c in grid], np.float32)
    # reprolint: ok[host-sync] — one read of the candidate errors per linear
    errs = np.asarray(jax.device_get(_candidate_errs(
        jax.device_put(X), jax.device_put(wf), jax.device_put(s_all),
        jax.device_put(clips), qcfg=qcfg)))
    # the first finite minimum in grid order; non-finite candidates never win
    errs = np.where(np.isnan(errs), np.inf, errs)
    i = int(np.argmin(errs))
    alpha, clip = grid[i] if np.isfinite(errs[i]) else (None, None)
    if alpha is None:
        # every (alpha, clip) candidate scored non-finite (degenerate
        # capture stats — NaN/inf activations); fall back to the identity
        # transform instead of crashing in _act_scale(mean_abs, None)
        warnings.warn("awq_leaf: grid search found no finite candidate "
                      "(degenerate capture stats); falling back to "
                      "alpha=0.0, clip=1.0", stacklevel=2)
        alpha, clip = 0.0, 1.0
    s_ch = _act_scale(stats.mean_abs, alpha)
    wt = jnp.asarray(wf * s_ch[..., :, None])
    scale, zero = Q.compute_scale_zero(wt, qcfg, gamma=clip, beta=clip)
    codes = Q.quantize_codes(wt, scale, zero, qcfg)
    fq = Q.dequantize_codes(codes, scale, zero, qcfg) / s_ch[..., :, None]
    meta = {"scale": scale, "zero": zero,
            "act_scale": jnp.asarray(s_ch), "dst": None,
            "alpha": alpha, "clip": clip, "codes": codes.astype(jnp.uint8)}
    return fq.astype(w.dtype), meta


def quantize_block_awq(bp, captures, qcfg: QuantConfig):
    qmeta = {}
    for p in quant_leaf_paths(bp):
        w = get_path(bp, p)
        fq, meta = awq_leaf(w, captures[p], qcfg)
        bp = set_path(bp, p, fq)
        qmeta[p] = meta
    return bp, qmeta
