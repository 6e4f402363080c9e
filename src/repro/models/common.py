"""Shared forward-context and cache plumbing for the model zoo.

Cache contract (see README "Cache contract"): every family's decode cache
is a dict of leaves stacked ``(layers_or_sites, slots, ...)``, and each
family declares a :class:`CacheSpec` (in ``models/registry.py``) naming its
leaves and their kind — ``token`` leaves carry a per-token extent on
``token_axis`` and can be paged; ``state``/``fixed`` leaves are O(1) or
fixed-extent per slot and always stay slot-major.  The old convention
("slot dim == axis 1 on every leaf") survives as ``CacheSpec.slot_axis``,
but consumers must go through the spec instead of assuming it.

Two :class:`CacheStore` implementations serve that contract behind the same
``init_cache`` / ``write_slot`` / ``read_slot`` verbs:

  * :class:`DenseCacheStore` — one contiguous ``max_seq`` lane per slot
    (the historical layout, and the bit-identity parity anchor);
  * :class:`PagedCacheStore` — token leaves live in a fixed pool of
    ``page_size``-token pages; a per-slot page table maps logical pages to
    pool pages, admission allocates pages instead of copying lanes, and
    full prompt-prefix pages are shared copy-on-write across requests.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _identity_shard(x, names):
    return x


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def zeros_jit(shape, dtype):
    """Compiled zeros for cache allocation: eager ``jnp.zeros`` device_puts
    its scalar fill constant on every call, which the serving sanitizer's
    ``transfer_guard("disallow")`` rejects."""
    return jnp.zeros(shape, dtype)


@dataclasses.dataclass(frozen=True)
class Ctx:
    """Per-call forward context.

    ``shard(x, logical_names)`` applies a sharding constraint (identity when
    running un-meshed); ``ep_axis`` names the mesh axis experts are sharded
    over (None = single-device local MoE); ``act_bits`` turns on per-token
    activation fake-quant in inference paths (W4A4 etc.).

    ``kernel_backend`` is the per-call QTensor matmul dispatch: "xla"
    (unpack + dense matmul) or "pallas" (fused dequant-matmul kernel).
    ``None`` falls back to the ``REPRO_KERNEL_BACKEND`` env var (read fresh
    at trace time, never cached) and then to "xla" — explicit plumbing via
    ``QuantConfig.kernel_backend`` is the supported path.
    """
    shard: Callable = _identity_shard
    mesh: Any = None
    ep_axis: Optional[str] = None
    dp_axes: tuple = ()            # mesh axes the batch/token dim is sharded over
    act_bits: Optional[int] = None
    kernel_backend: Optional[str] = None   # "xla" | "pallas" | None (env/default)
    # int8 KV cache (beyond-paper, §Perf A4): static-scale symmetric
    # quantization of cache entries; scale calibrated offline (default is a
    # conservative bound for post-RoPE keys/values at unit-variance init)
    kv_bits: Optional[int] = None
    kv_scale: float = 0.05
    # serve-time inner expert parallelism: the axis name of an ENCLOSING
    # shard_map over which expert weights arrive pre-sliced (TP serving via
    # launch.sharding.ServeSpec).  Mutually exclusive with ``ep_axis``,
    # which builds its own shard_map from globally-replicated weights.
    ep_inner: Optional[str] = None
    attn_chunk: int = 512
    remat: bool = False
    decode: bool = False
    # paged KV cache: page size in tokens (0 = dense slot lanes).  When > 0
    # the per-token cache leaves handed to the family are PAGE POOLS
    # (lead, num_pages, page_size, ...) and the step passes a page table.
    page_size: int = 0


DEFAULT_CTX = Ctx()

_CTX_FIELDS = {f.name for f in dataclasses.fields(Ctx)}


def make_ctx(cfg, qcfg=None, *, mesh=None, decode: bool = False,
             shard_overrides=None, **overrides) -> Ctx:
    """THE blessed :class:`Ctx` constructor for every serving/eval call site.

    ``qcfg`` (a ``QuantConfig`` or None for FP serving) supplies
    ``kernel_backend`` and ``act_bits``; keyword ``overrides`` may override
    any :class:`Ctx` field (e.g. ``attn_chunk``, ``kv_bits``, ``remat``,
    ``page_size``) and unknown names raise instead of being silently
    dropped — the failure mode that let hand-built Ctx calls drift apart.
    ``remat`` defaults to ``cfg.remat``; mesh-aware fields (shard fn,
    ``ep_axis``, ``dp_axes``) are derived from ``mesh`` when given.
    """
    unknown = set(overrides) - (_CTX_FIELDS - {"shard", "mesh", "ep_axis",
                                               "dp_axes", "decode"})
    if unknown:
        raise TypeError(f"make_ctx: unknown Ctx field(s) {sorted(unknown)}; "
                        f"valid overrides: {sorted(_CTX_FIELDS)}")
    kw: Dict[str, Any] = dict(overrides)
    if qcfg is not None:
        kw.setdefault("kernel_backend", qcfg.kernel_backend)
        kw.setdefault("act_bits", qcfg.act_bits)
    kw.setdefault("remat", cfg.remat)
    if kw["remat"] is None:
        kw["remat"] = cfg.remat
    backend = kw.get("kernel_backend")
    if backend is not None and backend not in ("xla", "pallas"):
        raise ValueError(f"make_ctx: unknown kernel_backend {backend!r} "
                         f"(expected 'xla', 'pallas' or None)")
    kv_bits = kw.get("kv_bits")
    if kv_bits not in (None, 8):
        raise ValueError(f"make_ctx: unsupported kv_bits {kv_bits!r} "
                         f"(the int8 KV cache supports None or 8)")
    page_size = kw.get("page_size", 0)
    if page_size < 0:
        raise ValueError(f"make_ctx: page_size must be >= 0, got {page_size}")
    chunk = kw.get("attn_chunk", 512)
    if chunk < 1:
        raise ValueError(f"make_ctx: attn_chunk must be >= 1, got {chunk}")
    if page_size and chunk % page_size:
        # page-aligned attention chunking is what keeps the pallas paged
        # kernel's chunk grid identical to the dense kernel's (the
        # dense-vs-paged bit-identity contract)
        raise ValueError(f"make_ctx: attn_chunk ({chunk}) must be a "
                         f"multiple of page_size ({page_size})")
    if mesh is not None:
        # lazy import: common.py sits below launch/ in the layering
        from repro.launch.mesh import dp_axes, tp_axis, tp_size
        from repro.launch.sharding import make_sharder
        # EP rides the model axis only when it has real extent — a
        # degenerate ("data",)-style mesh must not hand the moe kernels a
        # dead axis name
        kw.setdefault("ep_axis",
                      tp_axis(mesh)
                      if cfg.family == "moe" and tp_size(mesh) > 1
                      else None)
        kw.update(shard=make_sharder(mesh, shard_overrides), mesh=mesh,
                  dp_axes=dp_axes(mesh))
    return Ctx(decode=decode, **kw)


def maybe_remat(fn, ctx: Ctx):
    return jax.checkpoint(fn) if ctx.remat else fn


def take_layer(params, i):
    """Slice layer ``i`` out of stacked (L, ...) block params."""
    return jax.tree_util.tree_map(lambda a: a[i], params)


def layer_loop(step, carry, xs, unroll: bool):
    """lax.scan over stacked layers, or an unrolled python loop when
    ``unroll`` (used by the dry-run's depth-differencing cost accounting —
    cost_analysis counts a scan body once regardless of trip count)."""
    if not unroll:
        return jax.lax.scan(step, carry, xs)
    L = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(L):
        carry, y = step(carry, take_layer(xs, i))
        ys.append(y)
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)
    return carry, stacked


def cache_layer_loop(step, carry, cache, xs, unroll: bool):
    """:func:`layer_loop` with the stacked cache in the carry, not in
    ``xs``/``ys``: ``step(carry, cache, layer, x) -> (carry, cache, y)``
    gets the whole stacked cache and the layer's index (int32), writes
    its rows in place (:func:`write_rows`) and reads its layer by index.
    Returns (carry, cache, ys).  The one-token decode over a dense store
    takes this loop: a cache in ``xs``/``ys`` is sliced out, rewritten and
    restacked every layer, copies of the whole cache a step."""
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]

    def body(c, x):
        h, kv = c
        h, kv, y = step(h, kv, x[1], x[0])
        return (h, kv), y

    (carry, cache), ys = layer_loop(
        body, (carry, cache), (xs, jnp.arange(n, dtype=jnp.int32)), unroll)
    return carry, cache, ys


# --------------------------------------------------------------------------
# cache layout contract (CacheSpec) + slot plumbing
# --------------------------------------------------------------------------
#
# Families stack cache leaves (layers/sites, slots, ...); the slot axis and
# each leaf's kind are DECLARED per family via CacheSpec (models/registry.py)
# rather than assumed.  ``write_slot``/``read_slot`` below implement the
# dense store's verbs; the paged store's verbs live in PagedCacheStore.

CACHE_SLOT_AXIS = 1      # default slot axis every in-tree family uses

LEAF_TOKEN = "token"     # per-token extent on token_axis; pageable
LEAF_STATE = "state"     # O(1)-in-seq recurrent state; always slot-major
LEAF_FIXED = "fixed"     # fixed extent (e.g. encdec cross-attn); slot-major


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """Layout of one cache leaf within the stacked cache tree."""
    kind: str                       # LEAF_TOKEN | LEAF_STATE | LEAF_FIXED
    token_axis: int = 2             # per-token axis (token leaves only)


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """A model family's declared cache layout (the explicit replacement for
    the implicit "slot dim == axis 1" folklore).

    ``leaves`` maps a leaf path ("k", "mamba/conv", ...) to its
    :class:`LeafSpec`.  ``chunkable`` marks families whose prefill can
    resume mid-sequence (required for chunked prefill; False for recurrent
    state, positional-coupled, and capacity-routed families — MoE capacity
    dispatch couples sequence positions, so chunked prefill would change
    its outputs).  ``shareable`` marks families whose full prompt-prefix
    pages may be shared copy-on-write across requests (requires
    ``chunkable`` plus a prompt that is fully described by its token ids).
    ``pageable`` marks families whose token leaves the paged store may pool
    (their steps walk a page table).
    """
    family: str
    leaves: Tuple[Tuple[str, LeafSpec], ...]
    slot_axis: int = CACHE_SLOT_AXIS
    chunkable: bool = False
    shareable: bool = False
    pageable: bool = True

    def leaf(self, path: str) -> LeafSpec:
        for p, ls in self.leaves:
            if p == path:
                return ls
        raise KeyError(f"cache leaf {path!r} not declared for family "
                       f"{self.family!r}")

    @property
    def token_paths(self) -> Tuple[str, ...]:
        return tuple(p for p, ls in self.leaves if ls.kind == LEAF_TOKEN)

    def validate(self, cache) -> None:
        """Check a cache pytree structurally matches this spec."""
        got = set(_leaf_paths(cache))
        want = {p for p, _ in self.leaves}
        if got != want:
            raise ValueError(
                f"cache leaves {sorted(got)} do not match CacheSpec for "
                f"family {self.family!r} (declared {sorted(want)})")


def _leaf_paths(tree, prefix=()) -> List[str]:
    if isinstance(tree, dict):
        out: List[str] = []
        for k, v in sorted(tree.items()):
            out += _leaf_paths(v, prefix + (k,))
        return out
    return ["/".join(prefix)]


def _get_leaf(tree, path: str):
    node = tree
    for k in path.split("/"):
        node = node[k]
    return node


def _set_leaf(tree, path: str, value):
    """Functional leaf replacement (trees are plain nested dicts)."""
    keys = path.split("/")
    if len(keys) == 1:
        return {**tree, keys[0]: value}
    return {**tree, keys[0]: _set_leaf(tree[keys[0]], "/".join(keys[1:]),
                                       value)}


def write_slot(cache, slot_cache, slot):
    """Insert a single-request cache (size 1 along axis 1) into ``slot`` of a
    batched cache.  ``slot`` may be a traced int32 — shapes are static, so one
    jit compilation covers every slot index and occupancy."""
    def one(dst, src):
        return jax.lax.dynamic_update_slice_in_dim(
            dst, src.astype(dst.dtype), slot, axis=CACHE_SLOT_AXIS)
    return jax.tree_util.tree_map(one, cache, slot_cache)


def read_slot(cache, slot):
    """Extract slot ``slot`` as a batch-of-1 cache (inverse of write_slot)."""
    def one(leaf):
        return jax.lax.dynamic_slice_in_dim(leaf, slot, 1,
                                            axis=CACHE_SLOT_AXIS)
    return jax.tree_util.tree_map(one, cache)


def update_cache(cache_k, cache_v, k, v, pos):
    """Insert k,v (B, S_new, H, D) into one layer's caches (B, S_max, H, D)
    at ``pos``.

    ``pos`` is (B,) per-request write offsets (ragged batches supported).
    A one-token write (S_new == 1) is a broadcast-compare masked select
    over the whole lane: a position past the lane (``pos == S_max``, as
    inactive slots get) writes nothing.  Families whose decode scans the
    cache through ``xs`` (encdec, hybrid) use it; the dense store's
    one-token decode of the transformer family writes in place instead
    (:func:`cache_layer_loop`, :func:`write_rows`).
    """
    B, S_new = k.shape[0], k.shape[1]
    if S_new == 1:
        S = cache_k.shape[1]
        m = (jnp.arange(S)[None, :] == pos[:, None])[:, :, None, None]
        cache_k = jnp.where(m, k.astype(cache_k.dtype), cache_k)
        cache_v = jnp.where(m, v.astype(cache_v.dtype), cache_v)
        return cache_k, cache_v
    idx = pos[:, None] + jnp.arange(S_new)[None, :]            # (B, S_new)
    b = jnp.arange(B)[:, None]
    cache_k = cache_k.at[b, idx].set(k.astype(cache_k.dtype))
    cache_v = cache_v.at[b, idx].set(v.astype(cache_v.dtype))
    return cache_k, cache_v


def write_rows(leaf, layer, rows, pos, backend=None):
    """Write each slot's one new row into a stacked token leaf, in place.

    leaf (L, B, S, *tail); layer int32 scalar; rows (B, *tail); pos (B,).
    Slot ``b``'s row lands at ``(layer, b, pos[b])`` and nothing else of
    the leaf changes; a row whose position lies past the lane (``pos ==
    S``, as the scheduler gives inactive slots) is dropped, never clamped
    onto the last row.  Under a donated cache carried through the layer
    loop the buffer is updated in place.

    ``backend`` "pallas" writes through one aliased kernel call
    (``kernels/cache_write.py``): the chip runs XLA's scatter as a loop of
    a few ops a slot.  Otherwise one scatter, batched over the slot axis
    (each slot writes its own row, so GSPMD partitions it along a
    batch-sharded cache)."""
    from repro.models.layers import resolve_backend
    if resolve_backend(backend) == "pallas":
        from repro.kernels.ops import cache_write_op
        return cache_write_op(leaf, rows, layer, pos)
    B = rows.shape[0]
    idx = jnp.stack([jnp.broadcast_to(layer, (B,)), pos],
                    axis=-1).astype(jnp.int32)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=tuple(range(1, rows.ndim)),
        inserted_window_dims=(0, 2), scatter_dims_to_operand_dims=(0, 2),
        operand_batching_dims=(1,), scatter_indices_batching_dims=(0,))
    return jax.lax.scatter(leaf, idx, rows.astype(leaf.dtype), dnums,
                           unique_indices=True,
                           mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


# --------------------------------------------------------------------------
# paged token leaves: device verbs
# --------------------------------------------------------------------------
#
# A paged token leaf is a POOL ``(num_pages, page_size, *tail)`` (after the
# layer scan strips the leading layers/sites axis) shared by every slot;
# a page table ``ptab`` (slots, W) int32 maps each slot's logical page
# ``j`` (tokens [j*psz, (j+1)*psz)) to a pool page.  ``W = max_seq //
# page_size`` spans the FULL logical width, so the gathered virtual cache
# has exactly the dense slot-lane shape — unallocated entries point at
# page 0, whose junk is finite (pages only ever hold zeros or real
# values) and sits strictly beyond ``kv_len``, where the attention masks
# replace scores with exactly -1e30 in dense and paged alike.  That makes
# every dense-vs-paged comparison an elementwise-identical reduction:
# per-request outputs are BIT-identical, not just close.


def gather_pages(pool, ptab):
    """Materialize a slot-major virtual cache from a page pool.

    pool (P, psz, *tail), ptab (B, W) int32 -> (B, W*psz, *tail)."""
    psz = pool.shape[1]
    g = pool[ptab]                                   # (B, W, psz, *tail)
    return g.reshape(ptab.shape[0], ptab.shape[1] * psz, *pool.shape[2:])


def page_write_tokens(pool, vals, ptab, pos, page_size: int):
    """Scatter per-token values into pool pages.

    pool (P, psz, *tail); vals (B, S, *tail); ptab (B, W); pos (B,) start
    positions.  Rows whose position lands beyond the table (the
    scheduler's ``pos = max_seq`` freeze for inactive slots) get the
    sentinel page index P, out of range, and ``mode="drop"`` discards
    them, as :func:`write_rows` drops them from a dense lane."""
    P = pool.shape[0]
    W = ptab.shape[1]
    B, S = vals.shape[:2]
    tpos = pos[:, None] + jnp.arange(S)[None, :]               # (B, S)
    page_log = tpos // page_size
    off = tpos % page_size
    pidx = jnp.take_along_axis(ptab, jnp.clip(page_log, 0, W - 1), axis=1)
    pidx = jnp.where(page_log < W, pidx, P)                    # sentinel
    return pool.at[pidx.reshape(-1), off.reshape(-1)].set(
        vals.reshape(B * S, *vals.shape[2:]).astype(pool.dtype),
        mode="drop")


def page_update_cache(cache_k, cache_v, k, v, pos, ptab, page_size: int):
    """Paged counterpart of :func:`update_cache` (same call shape)."""
    return (page_write_tokens(cache_k, k, ptab, pos, page_size),
            page_write_tokens(cache_v, v, ptab, pos, page_size))


# --------------------------------------------------------------------------
# CacheStore: dense + paged cache layout/allocator behind one verb set
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AdmitPlan:
    """Outcome of a successful admission: where the request's tokens live.

    ``shared_tokens`` > 0 means the first ``shared_tokens`` prompt
    positions are served by copy-on-write shared pages (already filled by
    an earlier request with the same prefix) — prefill starts there."""
    slot: int
    pages: Tuple[int, ...] = ()
    shared_tokens: int = 0


class DenseCacheStore:
    """One contiguous ``max_seq`` lane per slot (the historical layout).

    Admission always succeeds (a free slot IS the capacity unit); the
    class exists so the scheduler speaks one store API and so paged runs
    have an explicit bit-identity/memory anchor to compare against."""

    kind = "dense"

    def __init__(self, model, *, slots: int, max_seq: int,
                 dtype=jnp.bfloat16):
        self.spec = model.cache_spec
        self.slots, self.max_seq = slots, max_seq
        self.cache = model.init_cache(slots, max_seq, dtype)
        self.spec.validate(self.cache)
        self.ptab_h = None                  # no page table: dense lanes

    def try_admit(self, slot: int, total_len: int,
                  prompt: Optional[np.ndarray] = None,
                  share: bool = False) -> Optional[AdmitPlan]:
        if total_len > self.max_seq:
            raise ValueError(f"request needs {total_len} positions; "
                             f"max_seq is {self.max_seq}")
        return AdmitPlan(slot=slot)

    def register_prefix(self, slot: int, prompt: np.ndarray) -> None:
        pass

    def release(self, slot: int) -> None:
        pass

    def cache_bytes(self) -> int:
        return sum(l.nbytes for l in jax.tree_util.tree_leaves(self.cache))

    def stats(self) -> Dict[str, Any]:
        return {"store": self.kind, "cache_bytes": self.cache_bytes(),
                "slots": self.slots, "max_seq": self.max_seq}


class PagedCacheStore:
    """Fixed pool of ``page_size``-token pages + per-slot page tables.

    Token leaves of the family cache become pools ``(lead, num_pages,
    page_size, *tail)``; state/fixed leaves keep their dense slot-major
    layout.  The host side owns the allocator: a free list, per-page
    refcounts, and a prompt-prefix map for copy-on-write sharing of FULL
    prompt-prefix pages (keyed by the exact token bytes up to the page
    end, so two requests share a page only when every token influencing
    its KV values is identical).  Shared pages are never written again:
    a sharer's prefill starts after the shared region and decode writes
    land beyond the prompt, so "copy-on-write" needs no copies.
    """

    kind = "paged"

    def __init__(self, model, *, slots: int, max_seq: int, page_size: int,
                 num_pages: int, dtype=jnp.bfloat16):
        if page_size < 1 or max_seq % page_size:
            raise ValueError(f"max_seq ({max_seq}) must be a positive "
                             f"multiple of page_size ({page_size})")
        if num_pages < 1:
            raise ValueError(f"need at least one page, got {num_pages}")
        self.spec = model.cache_spec
        if not self.spec.pageable:
            raise NotImplementedError(
                f"family {self.spec.family!r} has no paged serving: its "
                f"CacheSpec is not pageable; use the dense store")
        self.slots, self.max_seq = slots, max_seq
        self.page_size, self.num_pages = page_size, num_pages
        self.W = max_seq // page_size
        struct = jax.eval_shape(
            lambda: model.init_cache(slots, max_seq, dtype))

        def build(tree, prefix=()):
            if isinstance(tree, dict):
                return {k: build(v, prefix + (k,)) for k, v in tree.items()}
            path = "/".join(prefix)
            ls = self.spec.leaf(path)
            if ls.kind != LEAF_TOKEN:
                return zeros_jit(tree.shape, tree.dtype)
            if (self.spec.slot_axis, ls.token_axis) != (1, 2):
                raise NotImplementedError(
                    f"paged leaf {path!r}: pool layout assumes slot axis 1 "
                    f"/ token axis 2")
            shape = (tree.shape[0], num_pages, page_size) + tree.shape[3:]
            return zeros_jit(shape, tree.dtype)

        self.cache = build(struct)
        self.ptab_h = np.zeros((slots, self.W), np.int32)
        self._free: List[int] = list(range(num_pages - 1, -1, -1))
        self._ref = np.zeros((num_pages,), np.int64)
        self._slot_pages: Dict[int, Tuple[int, ...]] = {}
        self._prefix_map: Dict[bytes, int] = {}     # token-bytes -> page
        self._page_key: Dict[int, bytes] = {}
        self.peak_pages_in_use = 0
        self.refused_admissions = 0
        self.shared_page_hits = 0

    # ---- allocator -------------------------------------------------------

    def pages_needed(self, total_len: int) -> int:
        return -(-total_len // self.page_size)

    def _prefix_chain(self, prompt: np.ndarray) -> List[int]:
        """Longest run of already-resident full prompt-prefix pages.

        Sharing stops before the LAST prompt token: its logits seed the
        generation, so at least one position must run through prefill."""
        psz = self.page_size
        pages = []
        for j in range((len(prompt) - 1) // psz):
            page = self._prefix_map.get(prompt[:(j + 1) * psz].tobytes())
            if page is None:
                break
            pages.append(page)
        return pages

    def try_admit(self, slot: int, total_len: int,
                  prompt: Optional[np.ndarray] = None,
                  share: bool = False) -> Optional[AdmitPlan]:
        """Allocate a lifetime's worth of pages, or return None (request
        waits in queue) when the pool can't cover it right now."""
        need = self.pages_needed(total_len)
        if need > self.W:
            raise ValueError(f"request needs {need} pages; max_seq allows "
                             f"{self.W}")
        if need > self.num_pages:
            raise ValueError(
                f"request needs {need} pages but the pool only has "
                f"{self.num_pages} — it can never be admitted; raise "
                f"num_pages or lower the request's length")
        shared = self._prefix_chain(prompt) if (share and prompt is not None
                                               ) else []
        fresh = need - len(shared)
        if fresh > len(self._free):
            self.refused_admissions += 1
            return None
        pages = tuple(shared) + tuple(self._free.pop() for _ in range(fresh))
        for p in pages:
            self._ref[p] += 1
        self.shared_page_hits += len(shared)
        self._slot_pages[slot] = pages
        self.ptab_h[slot] = 0
        self.ptab_h[slot, :need] = pages
        in_use = self.num_pages - len(self._free)
        self.peak_pages_in_use = max(self.peak_pages_in_use, in_use)
        return AdmitPlan(slot=slot, pages=pages,
                         shared_tokens=len(shared) * self.page_size)

    def register_prefix(self, slot: int, prompt: np.ndarray) -> None:
        """Publish this request's full prompt-prefix pages for sharing —
        call AFTER its prefill has filled them."""
        psz = self.page_size
        pages = self._slot_pages.get(slot, ())
        for j in range(len(prompt) // psz):
            key = prompt[:(j + 1) * psz].tobytes()
            if key not in self._prefix_map:
                self._prefix_map[key] = pages[j]
                self._page_key[pages[j]] = key
            elif self._prefix_map[key] != pages[j]:
                # an identical prefix resident twice (admitted before this
                # one published); keep the first registration
                pass

    def release(self, slot: int) -> None:
        for p in self._slot_pages.pop(slot, ()):
            self._ref[p] -= 1
            if self._ref[p] == 0:
                key = self._page_key.pop(p, None)
                if key is not None:
                    del self._prefix_map[key]
                self._free.append(p)
        self.ptab_h[slot] = 0

    # ---- accounting ------------------------------------------------------

    def cache_bytes(self) -> int:
        n = sum(l.nbytes for l in jax.tree_util.tree_leaves(self.cache))
        return n + self.ptab_h.nbytes

    def stats(self) -> Dict[str, Any]:
        return {
            "store": self.kind, "cache_bytes": self.cache_bytes(),
            "slots": self.slots, "max_seq": self.max_seq,
            "page_size": self.page_size, "num_pages": self.num_pages,
            "pages_in_use": self.num_pages - len(self._free),
            "peak_pages_in_use": self.peak_pages_in_use,
            "refused_admissions": self.refused_admissions,
            "shared_page_hits": self.shared_page_hits,
        }
