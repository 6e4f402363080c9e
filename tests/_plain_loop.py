"""The tests' serving oracle: a plain greedy loop over ``make_serve_steps``.

One B-row prefill over the whole prompts, then lock-step decode steps, each
feeding back the argmax — no slots, no admission, no completion masking.
The scheduler (``launch.scheduler.serve_scheduled``) is pinned against it:
a request served there must emit the tokens this loop emits for it alone
at the same cache width.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.steps import make_serve_steps


@functools.lru_cache(maxsize=None)
def _steps(cfg, kernel_backend, mesh, tp_shard):
    model, prefill, decode = make_serve_steps(
        cfg, mesh, kernel_backend=kernel_backend, tp_shard=tp_shard)
    return model, jax.jit(prefill), jax.jit(decode)


def plain_serve(cfg, params, prompts, *, gen, max_seq=None, extras=None,
                kernel_backend=None, mesh=None, tp_shard=False,
                logits=False):
    """Greedy-serve the (B, S) ``prompts`` for ``gen`` tokens each.

    ``extras``: batched prefill inputs beside the tokens (``frames`` for
    encdec, ``patches`` for vlm).  ``max_seq`` is the cache width (default:
    exactly the prefill plus ``gen``).  Returns the (B, gen) tokens, and
    with ``logits=True`` also the (B, gen, V) float32 logits of the prefill
    and of each decode step."""
    model, pstep, dstep = _steps(cfg, kernel_backend, mesh, tp_shard)
    params = model.serve_params(params)
    B, S = prompts.shape
    start = S + (cfg.num_patches if cfg.family == "vlm" else 0)
    cache = model.init_cache(B, max_seq or start + gen)
    batch = {"tokens": jnp.asarray(prompts),
             **{k: jnp.asarray(v) for k, v in (extras or {}).items()}}
    lg, cache = pstep(params, batch, cache)
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    pos = jnp.full((B,), start, jnp.int32)
    toks, lgs = [tok], [lg]
    for _ in range(gen - 1):
        lg, cache = dstep(params, cache, tok, pos)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        pos = pos + 1
        toks.append(tok)
        lgs.append(lg)
    toks = np.stack([np.asarray(t) for t in toks], 1)
    if not logits:
        return toks
    return toks, np.stack([np.asarray(g, np.float32) for g in lgs], 1)
