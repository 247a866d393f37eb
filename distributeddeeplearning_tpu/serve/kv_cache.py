"""Preallocated, slot-indexed KV cache for autoregressive decoding.

Decode reads the *entire* history every step, so the cache — not the
parameters — is the serving memory budget: ``2 · slots · L · S · h · hd``
elements, preallocated once and updated in place (the engine jits every
touch with the cache donated, so steady-state HBM holds exactly one copy).

Layout: ``k, v: [batch_slots, n_layers, max_seq, n_heads, head_dim]``.
Slot-major so a slot is one contiguous leading-dim slice — admission is a
single ``dynamic_update_slice`` and the slot axis shards over the training
mesh's data axes (``parallel.mesh.DATA_AXES``) exactly like a training
batch; heads shard over ``tensor``.  Layer-major views for the
scan-over-layers decode are taken with ``moveaxis`` inside the jitted step
(``models.pipelined_transformer.forward_decode``).

Sequence *lengths* are deliberately not device state: the continuous-
batching scheduler owns per-slot positions host-side and passes them into
each decode step as a ``[slots]`` vector, so slot admission/release never
mutates device buffers beyond the K/V writes themselves.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributeddeeplearning_tpu.parallel import sharding as _layout
from distributeddeeplearning_tpu.quant.qtensor import (
    quantize_kv,
    quantized_cache,
)

Cache = Dict[str, jax.Array]


def _is_int8(dtype) -> bool:
    return np.dtype(dtype) == np.int8

#: Page id 0 is a reserved scratch page: released/inactive decode slots and
#: out-of-range block-table entries point at it, so their (masked, ignored)
#: K/V writes can never corrupt a live sequence's pages.
SCRATCH_PAGE = 0


def init_cache(
    *,
    batch_slots: int,
    num_layers: int,
    max_seq: int,
    num_heads: int,
    head_dim: int,
    dtype: Any = jnp.float32,
) -> Cache:
    """Zero-filled cache pytree ``{"k", "v"}``, each [slots, L, S, h, hd].

    Zeros are never *read*: the decode position mask hides every position
    above a slot's current length, and admission overwrites from 0.

    ``dtype=jnp.int8`` selects the quantized layout: values int8 plus f32
    per-position-per-head scale leaves ``{"k_scale", "v_scale"}``, each
    [slots, L, S, h] — ~(1 + 4/hd)/4 of the f32 footprint.
    """
    shape = (batch_slots, num_layers, max_seq, num_heads, head_dim)
    cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if _is_int8(dtype):
        cache["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
        cache["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
    return cache


def cache_sharding(
    mesh, *, quantized: bool = False, layout: str = "dense"
) -> Cache:
    """NamedShardings for a cache pytree, resolved through the partition-
    rule layout table (``parallel.sharding.LAYOUT_RULES``).

    Dense: slots over the data axes, heads over ``tensor`` — the serving
    analogue of the training batch/TP layout, so an engine built on the
    training mesh reuses its geometry unchanged.  Paged: the page-pool
    axis stays chip-local (the block-table gather must not cross chips)
    and only heads shard over ``tensor``.  The int8 layouts' scale leaves
    shard identically (same slot/page/head dims, just no head_dim).
    """
    if layout not in ("dense", "paged"):
        raise ValueError(f"unknown cache layout {layout!r}")
    names = {"k": None, "v": None}
    if quantized:
        names["k_scale"] = None
        names["v_scale"] = None
    return _layout.resolve_shardings(mesh, names, prefix=f"kv_{layout}")


def insert_sequence(cache: Cache, k: jax.Array, v: jax.Array, slot) -> Cache:
    """Write one prefilled prompt's K/V into ``slot``, positions [0, P).

    ``k``/``v``: [1, L, P, h, hd] (or [L, P, h, hd]) from
    ``forward_prefill``; P may be the padded prompt bucket — padding K/V
    land above the slot's length and stay masked until overwritten by
    decode steps.  ``slot`` may be a traced index (one compiled insert
    serves every slot).

    Int8 caches quantize here (per-position-per-head scales written
    alongside the values) — the prefill pass itself stays f32; only the
    stored history is 8-bit.
    """
    if k.ndim == 4:
        k, v = k[None], v[None]
    start = (slot, 0, 0, 0, 0)
    if quantized_cache(cache):
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        return {
            "k": jax.lax.dynamic_update_slice(cache["k"], kq, start),
            "v": jax.lax.dynamic_update_slice(cache["v"], vq, start),
            "k_scale": jax.lax.dynamic_update_slice(
                cache["k_scale"], ks, start[:-1]
            ),
            "v_scale": jax.lax.dynamic_update_slice(
                cache["v_scale"], vs, start[:-1]
            ),
        }
    return {
        "k": jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), start
        ),
        "v": jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), start
        ),
    }


def cache_bytes(cache: Cache) -> int:
    """Total cache footprint in bytes (the serving HBM budget line) —
    summed over EVERY leaf of the pytree (k, v, and the int8 layout's
    scale tensors), so the accounting stays honest whatever the layout."""
    return sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(cache)
    )


# --------------------------------------------------------------------------
# Paged layout: a global pool of fixed-size pages + per-slot block tables.
#
# The dense layout above reserves ``max_seq`` positions per slot whether or
# not the sequence ever grows that long; the paged layout allocates HBM by
# ACTUAL tokens: ``k, v: [num_pages, L, page_size, h * hd]`` (the heads
# folded into the minor axis, head ``i`` at lanes ``[i * hd, (i + 1) * hd)``)
# and each slot owns a host-side list of page ids (its block table).  Logical
# position ``j`` of a slot lives at ``(table[j // page_size], j % page_size)``.
# Admissible concurrency is then bounded by free pages, not by ``slots ×
# max_seq`` reservations, and identical prompt prefixes can SHARE physical
# pages (refcounted — a full page whose token ids match an already-cached
# chunk is mapped, not recomputed).
# --------------------------------------------------------------------------


def init_paged_cache(
    *,
    num_pages: int,
    num_layers: int,
    page_size: int,
    num_heads: int,
    head_dim: int,
    dtype: Any = jnp.float32,
) -> Cache:
    """Zero-filled page pool ``{"k", "v"}``, each [pages + 1, L, page_size,
    h * hd]: a position's heads lie side by side in the minor axis, head
    ``i`` at lanes ``[i * hd, (i + 1) * hd)``.

    ``num_pages`` counts USABLE pages; one extra scratch page (id 0,
    :data:`SCRATCH_PAGE`) is prepended so inactive decode lanes have a safe
    write target.  Page-major so one page is a contiguous leading-dim slice:
    the paged forwards (``models.pipelined_transformer._scan_pool``) view a
    leaf as rows ``[(pages+1) * L, page_size, h * hd]``, layer ``l`` of page
    ``p`` at row ``p * L + l``, write new positions into the donated pool
    in place and gather a block table's rows with one leading-axis take.
    The heads are folded because a TPU lays an array out by its shape
    alone: a trailing ``(h, 64)`` pads its 64 lanes to 128 and makes the
    PAGE axis the minor one, so every program that addressed pages
    transposed the pool in and out; a minor axis of ``h * hd`` (128 or
    more) lies row-major by default, the row view is a bitcast, and a
    position holds the bytes it counts (``PERF.md`` section 6, PR 35).

    ``dtype=jnp.int8`` adds f32 scale pools ``{"k_scale", "v_scale"}``,
    each [pages + 1, L, page_size, h] — one scale per stored K/V vector, so
    incremental token writes never force a page-wide requantize.
    """
    if num_pages < 1:
        raise ValueError(f"num_pages must be >= 1, got {num_pages}")
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    rows = (num_pages + 1, num_layers, page_size)
    shape = rows + (num_heads * head_dim,)
    cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if _is_int8(dtype):
        cache["k_scale"] = jnp.zeros(rows + (num_heads,), jnp.float32)
        cache["v_scale"] = jnp.zeros(rows + (num_heads,), jnp.float32)
    return cache


def page_bytes(cache: Cache) -> int:
    """Bytes of ONE page across k+v and all layers — the HBM granule the
    allocator hands out (``cache_bytes == (num_pages+1) * page_bytes``).
    Sums EVERY pool leaf, so the int8 layout's per-page scale bytes are
    charged to the page they belong to.  In a cache of several kinds
    (:func:`init_hybrid_cache`) only the full layers' leaves are paged;
    the window layers' rings and the convolution layers' states are
    :func:`slot_state_bytes`."""
    return sum(
        leaf.size // leaf.shape[0] * leaf.dtype.itemsize
        for leaf in paged_leaves(cache)
    )


def paged_leaves(cache: Cache) -> List[jax.Array]:
    """The leaves laid out ``[pages, ...]``, which the allocator's pages
    index (every leaf, but for the per-slot leaves of
    :data:`RING_LEAVES`)."""
    return jax.tree_util.tree_leaves(
        {k: v for k, v in cache.items() if k not in RING_LEAVES}
    )


def slot_state_bytes(cache: Cache) -> int:
    """Bytes held per SLOT rather than per page, summed over the slots:
    the window layers' rings and the convolution layers' states of a
    cache of several kinds (0 for every other layout).  They are committed
    whole from the start and never grow."""
    return cache_bytes({k: v for k, v in cache.items() if k in RING_LEAVES})


# --------------------------------------------------------------------------
# A cache of two kinds under one pytree, for models that mix full and
# window attention layers (``models.hybrid_moe_transformer``).  Full layers
# keep every position: each owns a paged pool, addressed through the same
# block tables and the same PageAllocator as above.  Window layers need the
# last ``window`` positions only: each owns a RING of ``window`` positions a
# slot, written at ``pos mod window`` and masked by absolute position, so
# its bytes do not grow with the sequence.  KV heads are folded into the
# minor axis (``[.., kv_heads * width]``): a bfloat16 page or ring tiles
# (16, 128) without padding whatever the head count, where a trailing
# ``(4, 192)`` would pad to ``(16, 256)``.  K and V differ in width, so each
# has a leaf of its own per layer (a tuple of per-layer arrays: every layer's
# buffer is updated in place on its own).  A gated short-convolution layer
# has no K/V at all: what it carries of a sequence is the last few inputs of
# its convolution, ``conv_positions`` rows of the model's width a slot,
# folded into the minor axis like the heads (oldest first).  A state-space
# (Mamba) layer carries the same of its own convolution (``d_conv - 1``
# inputs of ``d_inner``) and its scan's state, ``d_state x d_inner`` values
# in float32 whatever the cache's dtype (a decode step would otherwise round
# a state carried over tens of thousands of positions), folded state-major.
# --------------------------------------------------------------------------

#: the per-slot leaves of such a cache (every other leaf is paged)
RING_LEAVES = ("k_win", "v_win", "conv_state", "mamba_conv_state",
               "ssm_state")


def init_hybrid_cache(
    *,
    num_pages: int,
    page_size: int,
    batch_slots: int,
    window: int,
    full_layers: int,
    window_layers: int,
    kv_heads_full: int,
    kv_heads_window: int,
    k_dim: int,
    v_dim: int,
    conv_layers: int = 0,
    conv_positions: int = 0,
    d_model: int = 0,
    ssm_layers: int = 0,
    ssm_conv_positions: int = 0,
    d_inner: int = 0,
    d_state: int = 0,
    dtype: Any = jnp.bfloat16,
) -> Cache:
    """``{"k_full", "v_full"}``: per full layer ``[pages + 1, page_size,
    kv_heads_full * width]`` (page 0 the scratch page); ``{"k_win",
    "v_win"}``: per window layer ``[batch_slots, window, kv_heads_window *
    width]``; ``{"conv_state"}``: per convolution layer ``[batch_slots,
    conv_positions * d_model]``.  A kind with no layers has an empty
    tuple.  Per state-space layer, ``{"mamba_conv_state"}`` ``[batch_slots,
    ssm_conv_positions * d_inner]`` and ``{"ssm_state"}`` ``[batch_slots,
    d_state * d_inner]``, the latter float32."""
    if num_pages < 1:
        raise ValueError(f"num_pages must be >= 1, got {num_pages}")
    if _is_int8(dtype):
        raise ValueError("a cache of two kinds has no int8 layout")

    def leaves(n, lead, heads, width):
        return tuple(
            jnp.zeros((lead[0], lead[1], heads * width), dtype)
            for _ in range(n)
        )

    pool = (num_pages + 1, page_size)
    ring = (batch_slots, window)
    return {
        "k_full": leaves(full_layers, pool, kv_heads_full, k_dim),
        "v_full": leaves(full_layers, pool, kv_heads_full, v_dim),
        "k_win": leaves(window_layers, ring, kv_heads_window, k_dim),
        "v_win": leaves(window_layers, ring, kv_heads_window, v_dim),
        "conv_state": tuple(
            jnp.zeros((batch_slots, conv_positions * d_model), dtype)
            for _ in range(conv_layers)
        ),
        "mamba_conv_state": tuple(
            jnp.zeros((batch_slots, ssm_conv_positions * d_inner), dtype)
            for _ in range(ssm_layers)
        ),
        "ssm_state": tuple(
            jnp.zeros((batch_slots, d_state * d_inner), jnp.float32)
            for _ in range(ssm_layers)
        ),
    }


def pages_for(tokens: int, page_size: int) -> int:
    """Pages covering ``tokens`` positions (ceil division)."""
    return -(-tokens // page_size)


class OutOfPages(RuntimeError):
    """Page pool exhausted — the admission-backpressure signal.

    The scheduler treats this as "wait for completions to free pages", not
    as a request failure, unless the request can never fit the pool."""


class PageAllocator:
    """Host-side bookkeeping for the page pool: free list, refcounts, and
    a prefix table of reusable immutable pages.

    Pages move through three states:

    - **free** — on the free list, contents meaningless;
    - **live** — refcount >= 1, owned by one or more block tables (a page
      shared via the prefix table is live in several tables at once);
    - **reclaimable** — refcount == 0 but still registered in the prefix
      table (its token contents remain valid), kept in LRU order.  A
      prefix lookup resurrects it (incref); allocation pressure evicts it
      (drops the prefix entry, hands the page out fresh).

    The prefix table maps ``key -> page`` where ``key`` identifies the
    FULL token history through the end of that page (the engine uses
    ``tuple(prompt[: (i+1) * page_size])``), so a hit guarantees the
    page's K/V are bit-identical to what prefill would recompute.

    With a host tier attached (:mod:`serve.kv_tier`) a prefix key has a
    third place to live beyond resident-in-HBM and gone: **host** — the
    chunk's K/V bytes sit in the pinned host pool and its HBM page id
    has been returned to the free list.  The allocator tracks host-tier
    keys so the prefix table answers hits in either tier
    (:meth:`tier_state`); the byte copies themselves belong to the tier
    object — the allocator only moves bookkeeping, and the ordering
    contract is copy-then-:meth:`spill_prefix` /
    alloc-copy-then-:meth:`restore_prefix` so contents are always valid
    in at least one tier.
    """

    def __init__(self, num_pages: int):
        if num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {num_pages}")
        self.num_pages = num_pages
        # page ids 1..num_pages (0 is the scratch page, never allocated)
        self._free: List[int] = list(range(num_pages, 0, -1))
        self._rc: Dict[int, int] = {}
        self._prefix: Dict[Any, int] = {}
        self._page_key: Dict[int, Any] = {}
        self._reclaim: "OrderedDict[int, None]" = OrderedDict()
        # prefix keys whose contents live ONLY in the host tier (no HBM
        # page); insertion-ordered so the host pool can evict LRU
        self._host: "OrderedDict[Any, None]" = OrderedDict()
        # alloc-pressure demotion hook (serve/kv_tier.py): called as
        # hook(key, page) BEFORE an evicted reclaimable page is handed
        # out — contents are still valid at that point, so the tier can
        # copy them host-side; returning True keeps the key answerable
        # from the host tier instead of forgotten
        self._evict_hook = None

    # -- capacity ----------------------------------------------------------
    @property
    def available(self) -> int:
        """Pages an ``alloc`` could hand out right now (free + evictable)."""
        return len(self._free) + len(self._reclaim)

    @property
    def pages_in_use(self) -> int:
        """Live pages (refcount >= 1)."""
        return self.num_pages - self.available

    @property
    def free_pages(self) -> int:
        """Pages on the free list proper (contents meaningless) — the
        spill pump's cushion signal: when this runs low, the next alloc
        starts evicting reclaimable prefix pages synchronously."""
        return len(self._free)

    @property
    def reclaimable_pages(self) -> int:
        """Refcount-0 pages still answering prefix hits — the spill
        pump's candidate pool."""
        return len(self._reclaim)

    # -- alloc / refcount --------------------------------------------------
    def alloc(self, n: int) -> List[int]:
        """Hand out ``n`` pages at refcount 1, evicting LRU reclaimable
        prefix pages as needed.  Raises :class:`OutOfPages` (allocating
        nothing) when fewer than ``n`` are available."""
        if n < 0:
            raise ValueError(f"cannot alloc {n} pages")
        if n > self.available:
            raise OutOfPages(
                f"need {n} pages, {self.available} available "
                f"({self.pages_in_use}/{self.num_pages} live)"
            )
        out: List[int] = []
        for _ in range(n):
            if self._free:
                page = self._free.pop()
            else:  # evict the least-recently-used cached prefix page
                page, _ = self._reclaim.popitem(last=False)
                key = self._page_key.pop(page)
                del self._prefix[key]
                # demote instead of forget when a host tier is attached:
                # the hook copies the page's bytes out NOW (they stay
                # valid until the new owner's first write) and the key
                # keeps answering prefix hits from the host tier
                if self._evict_hook is not None and self._evict_hook(
                    key, page
                ):
                    self._host[key] = None
            self._rc[page] = 1
            out.append(page)
        return out

    def set_evict_hook(self, hook) -> None:
        """Install the alloc-pressure demotion hook (see ``__init__``);
        None detaches it (evictions forget contents again)."""
        self._evict_hook = hook

    def incref(self, page: int) -> None:
        rc = self._rc.get(page, 0)
        if rc == 0:
            if page not in self._reclaim:
                raise ValueError(f"incref on non-live page {page}")
            del self._reclaim[page]  # resurrected from the prefix table
        self._rc[page] = rc + 1

    def decref(self, page: int) -> None:
        rc = self._rc.get(page, 0)
        if rc < 1:
            raise ValueError(f"decref on non-live page {page}")
        if rc > 1:
            self._rc[page] = rc - 1
            return
        del self._rc[page]
        if page in self._page_key:
            # still named by the prefix table: keep its contents around
            # for future hits until allocation pressure evicts it
            self._reclaim[page] = None
        else:
            self._free.append(page)

    def refcount(self, page: int) -> int:
        return self._rc.get(page, 0)

    def is_shared(self, page: int) -> bool:
        """True when writing this page could corrupt state beyond one
        slot: it is mapped by more than one block table (refcount > 1)
        or published in the prefix table (future hits would resurrect
        its contents).  The scrub/rollback paths refuse to touch such
        pages — shared pages are immutable by contract."""
        return self._rc.get(page, 0) > 1 or page in self._page_key

    # -- prefix table ------------------------------------------------------
    def lookup_prefix(self, key) -> Optional[int]:
        """Page holding ``key``'s chunk, or None.  Does NOT incref — the
        caller takes the reference explicitly (and marks recency)."""
        page = self._prefix.get(key)
        if page is not None and page in self._reclaim:
            self._reclaim.move_to_end(page)  # LRU touch
        return page

    def register_prefix(self, key, page: int) -> None:
        """Publish a live, fully-written, immutable page for reuse.  A key
        already registered keeps its existing page (first writer wins —
        both copies hold identical K/V, so dropping the duplicate
        registration is purely an HBM-dedup decision)."""
        if self._rc.get(page, 0) < 1:
            raise ValueError(f"cannot register non-live page {page}")
        if key in self._prefix or page in self._page_key:
            return
        self._prefix[key] = page
        self._page_key[page] = key

    def clear_prefix(self) -> None:
        """Drop every prefix entry; reclaimable pages return to the free
        list (benchmark hygiene: warmup must not seed the timed run).
        Host-tier keys are forgotten too — the caller owns releasing the
        matching host-pool slots (:meth:`HostPageTier.clear`)."""
        for page in list(self._reclaim):
            del self._prefix[self._page_key.pop(page)]
            self._free.append(page)
        self._reclaim.clear()
        for page in list(self._page_key):  # live pages: unregister only
            del self._prefix[self._page_key.pop(page)]
        self._host.clear()

    @property
    def prefix_entries(self) -> int:
        return len(self._prefix)

    # -- host tier ---------------------------------------------------------
    def tier_state(self, key) -> Optional[str]:
        """Where ``key``'s chunk currently lives: ``"resident"`` (an HBM
        page, live or reclaimable), ``"host"`` (host pool only), or None
        (not cached anywhere — prefill must recompute it)."""
        if key in self._prefix:
            return "resident"
        if key in self._host:
            return "host"
        return None

    def spill_prefix(self, key) -> int:
        """Demote a RECLAIMABLE prefix page to the host tier: its HBM
        page returns to the free list and the key is answered from host
        from now on.  Returns the freed page id.  The caller must have
        already copied the page's leaves device→host — after this call
        the page id may be reallocated and overwritten at any time.

        Only refcount-0 pages spill: a live page is mapped by a block
        table some decode step may read this iteration, so spilling it
        would corrupt an active stream (the never-spill-a-decode-active
        -page rule)."""
        page = self._prefix.get(key)
        if page is None:
            raise ValueError(f"spill of unregistered prefix key {key!r}")
        if page not in self._reclaim:
            raise ValueError(
                f"page {page} is live (rc={self._rc.get(page, 0)}); "
                "only reclaimable pages may spill"
            )
        del self._reclaim[page]
        del self._prefix[key]
        del self._page_key[page]
        self._free.append(page)
        self._host[key] = None
        return page

    def host_prefix(self, key) -> None:
        """Record ``key`` as host-resident WITHOUT it ever having been in
        the prefix table — the preemption path uses this to spill a
        victim's private full pages (copied device→host by the caller)
        so the retry's prefix walk restores them instead of
        re-prefilling."""
        if key in self._prefix:
            raise ValueError(f"key {key!r} already resident")
        self._host[key] = None

    def restore_prefix(self, key, page: int) -> None:
        """Promote a host-tier key back to resident: ``page`` is a
        freshly allocated (live) page the caller has already filled with
        the key's host-pool bytes.  The key leaves the host set and the
        prefix table answers it as resident again."""
        if key not in self._host:
            raise ValueError(f"restore of non-host key {key!r}")
        if self._rc.get(page, 0) < 1:
            raise ValueError(f"cannot restore into non-live page {page}")
        del self._host[key]
        self.register_prefix(key, page)

    def drop_host(self, key) -> None:
        """Forget a host-tier key (host-pool LRU eviction dropped its
        bytes) — the next miss on it re-prefills from scratch."""
        del self._host[key]

    def coldest_reclaimable(self, n: int) -> List[tuple]:
        """Up to ``n`` LRU-first ``(key, page)`` spill candidates: pages
        with refcount 0 still named by the prefix table — exactly the
        set whose bytes are stable (no decode lane can write them) and
        whose HBM a hotter sequence could use.  The spill pump walks
        this list; live pages never appear in it."""
        out: List[tuple] = []
        for page in self._reclaim:
            if len(out) >= n:
                break
            out.append((self._page_key[page], page))
        return out

    @property
    def host_entries(self) -> int:
        return len(self._host)

    # -- invariants (test hook) -------------------------------------------
    def check(self) -> None:
        """Assert the allocator's internal invariants (tests call this
        after every mutation pattern)."""
        live = set(self._rc)
        free = set(self._free)
        reclaim = set(self._reclaim)
        assert not (live & free), "page both live and free"
        assert not (live & reclaim), "page both live and reclaimable"
        assert not (free & reclaim), "page both free and reclaimable"
        assert len(free) == len(self._free), "duplicate free-list entry"
        assert live | free | reclaim == set(range(1, self.num_pages + 1)), \
            "page leaked (not live, free, or reclaimable)"
        assert all(rc >= 1 for rc in self._rc.values())
        assert reclaim <= set(self._page_key), "reclaimable page unnamed"
        for key, page in self._prefix.items():
            assert self._page_key.get(page) == key, "prefix maps diverged"
        # a prefix entry must name a page that still HOLDS its bytes: a
        # freed page may be reallocated and overwritten at any moment,
        # so a table entry pointing at one is a stale-read time bomb
        # (this is exactly the corruption a buggy spill path produces —
        # freeing the page without unregistering the key)
        prefix_pages = set(self._page_key)
        assert not (prefix_pages & free), \
            "prefix entry names a freed page"
        assert prefix_pages <= live | reclaim, \
            "prefix entry names an untracked page"
        # host-tier keys are keys WITHOUT an HBM page: a key answered in
        # both tiers would let restore and resident reads race
        host_keys = set(self._host)
        assert not (host_keys & set(self._prefix)), \
            "prefix key both resident and host"


def insert_pages(
    cache: Cache,
    k: jax.Array,
    v: jax.Array,
    page_ids: jax.Array,
    *,
    page_size: int,
) -> Cache:
    """Scatter a prefilled prompt's K/V ([L, P, h, hd], P a multiple of
    ``page_size``) into the pool pages listed in ``page_ids`` — the paged
    analogue of :func:`insert_sequence`, used by tests and one-shot
    (non-chunked) inserts; the engine's chunked prefill writes pages inside
    the compiled chunk program instead.  The heads fold into the pool's
    minor axis on the way in; int8 pools quantize per head first
    (per-position-per-head scales scattered alongside the values)."""
    if k.ndim == 5:
        k, v = k[0], v[0]
    L, P, h, hd = k.shape
    n = P // page_size
    paged_k = k.reshape(L, n, page_size, h, hd).swapaxes(0, 1)
    paged_v = v.reshape(L, n, page_size, h, hd).swapaxes(0, 1)
    fold = (n, L, page_size, h * hd)
    if quantized_cache(cache):
        kq, ks = quantize_kv(paged_k)
        vq, vs = quantize_kv(paged_v)
        return {
            "k": cache["k"].at[page_ids].set(kq.reshape(fold)),
            "v": cache["v"].at[page_ids].set(vq.reshape(fold)),
            "k_scale": cache["k_scale"].at[page_ids].set(ks),
            "v_scale": cache["v_scale"].at[page_ids].set(vs),
        }
    dtype = cache["k"].dtype
    return {
        "k": cache["k"].at[page_ids].set(paged_k.reshape(fold).astype(dtype)),
        "v": cache["v"].at[page_ids].set(paged_v.reshape(fold).astype(dtype)),
    }
