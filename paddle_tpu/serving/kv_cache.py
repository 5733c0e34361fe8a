"""Paged KV cache: fixed-size pages over one preallocated per-layer pool.

The static-cache generator (models/generation.py) gives every request a
private (b, max_len, kv_heads, head_dim) buffer — memory scales with the
WORST-CASE length of every live request, which is what kills concurrent
serving. Here the cache is one flat pool of `num_pages` pages of
`page_size` tokens per layer (Ragged Paged Attention's layout, arxiv
2604.15464); a sequence owns a list of page ids (its page table) and pages
return to a free list the moment the request finishes, so memory scales
with TOKENS ACTUALLY RESIDENT.

Page 0 is reserved as the null page: fixed-shape jitted steps pad the
batch with inactive rows, and those rows need somewhere harmless to write
their K/V. Nothing ever reads page 0 through a real page table.

Host/device split: the allocator and per-request page lists live on the
host (tiny, O(pages) ints); the pools are jax arrays threaded through the
jitted step (donated, so XLA updates them in place); the (B, max_pages)
page-table array handed to each step is rebuilt from the host lists —
copy-on-extend, a few hundred bytes per step.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

__all__ = ["BlockAllocator", "SlotAllocator", "PagedKVCache",
           "PagedLayerCache", "LatentLayerCache", "StateLayerCache",
           "StateSpec", "LayerPool", "NULL_PAGE", "NULL_SLOT", "pages_for",
           "overflow_position", "views_from_pools", "pools_from_views"]

NULL_PAGE = 0
# the state slot that padding rows and rows parked inside a decode block
# read and write, as the null page is for K/V: never handed out
NULL_SLOT = 0

# unquantized pool dtypes resolvable WITHOUT importing serving.quant —
# kv_dtype="fp32"/"bf16" must keep the quantization module entirely
# un-imported (poisoned-module guarantee)
_PLAIN_KV_DTYPES = {
    "fp32": jnp.float32, "float32": jnp.float32,
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
}


def pages_for(num_tokens: int, page_size: int) -> int:
    """Pages needed to hold `num_tokens` tokens."""
    return -(-num_tokens // page_size)


def overflow_position(max_pages: int, page_size: int) -> int:
    """First position past a (max_pages,)-table's capacity. `paged_attend`
    routes K/V writes at or beyond it to the reserved null page, so this
    doubles as the parking slot for rows that must stop writing real
    pages: padding rows of a fixed-shape batch, and decode-horizon rows
    that hit EOS or their token budget mid-block."""
    return max_pages * page_size


class BlockAllocator:
    """Refcounted free-list page allocator. Page ids are ints in
    [1, num_pages); page 0 is the reserved null page and is never handed
    out.

    A freshly alloc'd page carries ONE reference (its allocator). The
    prefix cache `acquire`s extra references when a page enters the radix
    tree or another sequence's page table, so one physical page can sit in
    many page tables at once; `free` drops one reference and the page only
    returns to the free list when the count hits zero. Without a prefix
    cache every page stays at refcount 1 and alloc/free behave exactly as
    the plain free list did."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        self.num_pages = num_pages
        # LIFO keeps recently-freed (cache-warm) pages in rotation
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._refs: dict[int, int] = {}
        # observability counters (bind_metrics); unbound allocators pay a
        # single None check per page event
        self._m_alloc = None
        self._m_recycle = None
        self._m_share = None
        # fault injection (bind_faults): same None-check discipline —
        # an uninjected allocator executes zero resilience code
        self._faults = None

    def bind_metrics(self, registry) -> None:
        """Attach page-lifecycle counters from an observability
        MetricsRegistry (the engine binds its own registry here, so
        alloc/recycle/share rates land next to the serving metrics).
        Handles are resolved once — no registry lookups on page ops."""
        self._m_alloc = registry.counter(
            "serving_kv_page_allocs_total", "pages handed out")
        self._m_recycle = registry.counter(
            "serving_kv_page_recycles_total",
            "pages returned to the free list (last reference dropped)")
        self._m_share = registry.counter(
            "serving_kv_page_shares_total",
            "extra references acquired on shared pages")

    def bind_faults(self, injector) -> None:
        """Attach a resilience.FaultInjector; every alloc/alloc_n entry
        then consults its `alloc` site (one check per ENTRY, not per
        page, so "alloc fails on call 7" schedules stay readable)."""
        self._faults = injector

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_allocatable(self) -> int:
        """Pages the allocator can ever hand out: `num_pages` minus the
        reserved null page. Every capacity check and error message counts
        against THIS, never the raw pool size — the scheduler's
        too-large-for-pool paths used to disagree by one (num_pages vs
        num_pages - 1) depending on which raised."""
        return self.num_pages - 1

    @property
    def num_used(self) -> int:
        return len(self._refs)

    def ref_count(self, page: int) -> int:
        """Live references on `page` (0 = free)."""
        return self._refs.get(page, 0)

    def live_pages(self) -> List[int]:
        """Sorted page ids holding at least one live reference — the
        restore-side audit compares this against the pages the rebuilt
        requests and prefix cache actually account for."""
        return sorted(self._refs)

    def _alloc_unchecked(self) -> Optional[int]:
        if not self._free:
            return None
        page = self._free.pop()
        self._refs[page] = 1
        if self._m_alloc is not None:
            self._m_alloc.inc()
        return page

    def alloc(self) -> Optional[int]:
        """One free page id (refcount 1), or None when the pool is
        exhausted. May raise InjectedFault under a bound FaultInjector
        (callers in the scheduler degrade it to the exhausted path)."""
        if self._faults is not None:
            self._faults.check("alloc")
        return self._alloc_unchecked()

    def alloc_n(self, n: int) -> Optional[List[int]]:
        """All-or-nothing batch alloc (request admission)."""
        if self._faults is not None:
            self._faults.check("alloc")
        if len(self._free) < n:
            return None
        return [self._alloc_unchecked() for _ in range(n)]

    def acquire(self, page: int) -> None:
        """Add one reference to an allocated page (prefix-cache sharing:
        the page is entering another page table or the radix tree)."""
        if page == NULL_PAGE:
            raise ValueError("page 0 is the reserved null page")
        if page not in self._refs:
            raise ValueError(f"acquire of free/unknown page {page}")
        self._refs[page] += 1
        if self._m_share is not None:
            self._m_share.inc()

    def free(self, page: int) -> None:
        """Drop one reference; the page returns to the free list only when
        no references remain."""
        if page == NULL_PAGE:
            raise ValueError("page 0 is the reserved null page")
        if page not in self._refs:
            raise ValueError(f"double free or unknown page {page}")
        self._refs[page] -= 1
        if self._refs[page] == 0:
            del self._refs[page]
            self._free.append(page)
            if self._m_recycle is not None:
                self._m_recycle.inc()

    def free_all(self, pages: Sequence[int]) -> None:
        for p in pages:
            self.free(p)

    def check_consistency(self) -> bool:
        """Full invariant audit of the pool, run after every
        failure-isolation event (and per step in chaos tests): the free
        list and the refcount table must exactly partition the
        allocatable ids [1, num_pages), with no duplicates, no null-page
        entries, and every live refcount >= 1. Raises RuntimeError on
        the first violation; returns True when the pool is sound."""
        free = self._free
        if len(set(free)) != len(free):
            raise RuntimeError("allocator corrupt: duplicate free pages")
        if NULL_PAGE in self._refs or NULL_PAGE in free:
            raise RuntimeError(
                "allocator corrupt: null page entered circulation")
        both = set(free) & self._refs.keys()
        if both:
            raise RuntimeError(
                f"allocator corrupt: pages {sorted(both)} are both free "
                "and referenced")
        for page, refs in self._refs.items():
            if not 1 <= page < self.num_pages:
                raise RuntimeError(
                    f"allocator corrupt: page id {page} out of range")
            if refs < 1:
                raise RuntimeError(
                    f"allocator corrupt: page {page} held at refcount "
                    f"{refs}")
        if any(not 1 <= p < self.num_pages for p in free):
            raise RuntimeError(
                "allocator corrupt: free-list id out of range")
        if len(free) + len(self._refs) != self.num_pages - 1:
            raise RuntimeError(
                f"allocator corrupt: {len(free)} free + "
                f"{len(self._refs)} live != {self.num_pages - 1} "
                "allocatable pages (leak or double-account)")
        return True


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedLayerCache:
    """One layer's view of the pool, handed to the model's attention in
    place of the static (k_cache, v_cache) pair. `attend_with_cache`
    dispatches on this type (duck-typed by `page_table`), so LLaMA/GPT/T5
    attention modules ride the paged path unmodified.

    k_pool/v_pool: (kv_heads, num_pages, page_size, head_dim), or, where
                   `PagedKVCache.head_pack` = g > 1, (kv_heads / g,
                   num_pages, page_size, g * head_dim) with head h in
                   columns [(h % g) * head_dim, ...) of row block h // g:
                   a reshape of a token's (kv_heads, head_dim) — kv-head
                   major so the Pallas kernels reach a page's (page_size,
                   head_dim) tile, for one head or a block of heads, by
                   one copy straight from the pool, without a per-step
                   pool transpose. Written only by `attention._write_pages`,
                   and only through the (kv_heads * num_pages, page_size,
                   head_dim) view: scattered as `pool.at[:, entries,
                   slots]` the TPU compiler copies the whole pool into
                   another layout and back at every write, where through
                   the view it updates the donated buffer in place (the
                   compiler's text is quoted there)
    page_table:    (B, max_pages) int32 — logical page j of row i lives in
                   physical page page_table[i, j] (0 = null page padding)
    row_ids:       optional (T,) int32 — ragged flat-batch mode: the step
                   carries all rows' tokens in ONE (1, T) sequence axis and
                   row_ids[t] names the page-table row token t belongs to.
                   None (the default) keeps the classic one-row-per-batch-
                   entry layout.
    k_scale/v_scale: optional (kv_heads, num_pages, page_size, 1) fp32 —
                   quantized pools only (kv_dtype="int8"/"fp8"): one
                   dequantization scale per (head, page, slot), scattered
                   by the exact same page/slot arithmetic as the data, so
                   a logical page is a data slab + a scale slab and the
                   allocator/page-table accounting never changes.
    """

    k_pool: jnp.ndarray
    v_pool: jnp.ndarray
    page_table: jnp.ndarray
    row_ids: Optional[jnp.ndarray] = None
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None
    # kv heads side by side in one pool row (`PagedKVCache.head_pack`):
    # static, and the one place the kernels read it from
    head_pack: int = 1

    @property
    def page_size(self) -> int:
        return self.k_pool.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def tree_flatten(self):
        # the children are what they were: row_ids and the scales appear
        # only where they are set, so a plain view keeps three; which of
        # them are there, and the head packing, are static
        children = (self.k_pool, self.v_pool, self.page_table)
        if self.quantized:
            children += (self.k_scale, self.v_scale)
        if self.row_ids is not None:
            children += (self.row_ids,)
        return children, (self.quantized, self.row_ids is not None,
                          self.head_pack)

    @classmethod
    def tree_unflatten(cls, aux, children):
        quantized, rows, head_pack = aux
        ks, vs = children[3:5] if quantized else (None, None)
        return cls(*children[:3], children[-1] if rows else None,
                   k_scale=ks, v_scale=vs, head_pack=head_pack)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LatentLayerCache:
    """One layer's view of a latent pool (the pool kind of a model with
    latent attention, MLA): one row a token, the normed compressed
    latent followed by the one rotated rope key all heads share, and no
    V; for a model whose attention is sparse (a lightning indexer beside
    MLA) also one index key a token, in a second array under the same
    page table: a page is handed out, written and freed for both at
    once. `serving.attention.latent_write` writes them,
    `latent_decode_attention` reads the rows, `dsa_index_scores` the
    keys and `sparse_latent_decode_attention` the rows it is told; the
    allocator, the page tables and the null page are the K/V kind's.

    pool:       (num_pages, page_size, width) — a page is one contiguous
                (page_size, width) slab, which the decode kernel brings
                by one copy. `width` is the row's own width rounded up
                to whole 128-lane tiles, as the chip's memory holds it
                anyway (a kernel cannot copy part of a tile out of HBM);
                the columns past the row stay zero
    page_table: (B, max_pages) int32, as `PagedLayerCache`'s
    index_pool: (num_pages, page_size, index width) or None
    """

    pool: jnp.ndarray
    page_table: jnp.ndarray
    index_pool: Optional[jnp.ndarray] = None

    @property
    def page_size(self) -> int:
        return self.pool.shape[1]

    def tree_flatten(self):
        if self.index_pool is None:
            return (self.pool, self.page_table), False
        return (self.pool, self.page_table, self.index_pool), True

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _lane_pack(width: int, heads: int) -> int:
    """How many heads of `width` columns lie side by side in one
    128-lane row: `128 // width` where that is whole, more than one and
    divides `heads`, else 1 (a head a row)."""
    g = 128 // width if 0 < width < 128 and 128 % width == 0 else 1
    return g if heads % g == 0 else 1


class SlotAllocator:
    """Free list of the fixed-size state slots of a model with recurrent
    layers: one slot a live request, ids in [1, num_slots]; slot 0
    (`NULL_SLOT`) is never handed out. A slot has one owner, so there is
    no reference count."""

    def __init__(self, num_slots: int):
        if num_slots < 1:
            raise ValueError("need at least 1 state slot")
        self.num_slots = num_slots
        self._free: List[int] = list(range(num_slots, 0, -1))
        self._used: set = set()

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return len(self._used)

    def alloc(self) -> Optional[int]:
        if not self._free:
            return None
        slot = self._free.pop()
        self._used.add(slot)
        return slot

    def free(self, slot: int) -> None:
        if slot not in self._used:
            raise ValueError(f"double free or unknown state slot {slot}")
        self._used.remove(slot)
        self._free.append(slot)

    def check_consistency(self) -> bool:
        ids = sorted(self._free + list(self._used))
        if ids != list(range(1, self.num_slots + 1)):
            raise RuntimeError(
                "slot allocator corrupt: free and used slots do not "
                f"partition [1, {self.num_slots}]")
        return True


@dataclasses.dataclass(frozen=True)
class StateSpec:
    """What a model with recurrent (state-space) layers tells the cache
    manager through its config's `state_cache_spec`: which layers carry
    a state instead of K/V pages, and the state's sizes."""

    state_layers: tuple       # a bool a decoder layer: True = state
    heads: int                # H
    head_dim: int             # P
    state_dim: int            # N
    conv_dim: int             # channels of the causal conv (x, B and C)
    conv_width: int           # taps; the tail holds conv_width - 1 rows

    @property
    def head_pack(self) -> int:
        """Heads that share one 128-lane row of the stored state."""
        return _lane_pack(self.head_dim, self.heads)

    @property
    def ssm_shape(self) -> tuple:
        """One slot's state as the pool holds it: (H / g, N, g * P), the
        state of head h as its transpose (N, P) in lanes [(h % g) * P,
        (h % g + 1) * P) of row block h // g. Whole 128-lane rows for a
        head of 64 (g = 2); the decode kernel (`serving/ssm.py`) takes
        the token's x as rows and its B and C as columns in this form,
        and neither needs a transpose on the chip."""
        g = self.head_pack
        return (self.heads // g, self.state_dim, g * self.head_dim)

    @property
    def conv_elems(self) -> int:
        return (self.conv_width - 1) * self.conv_dim


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StateLayerCache:
    """One recurrent layer's view of the state pools, handed to the
    model's mixer in place of a K/V page view.

    ssm_pool:  (slots + 1, *StateSpec.ssm_shape) float32: the SSM state
               of every slot; `serving.ssm` reads and writes it, the
               decode kernel in place
    conv_pool: (slots + 1, (conv_width - 1) * conv_dim): the last
               conv_width - 1 rows of the conv's input, oldest first, one
               flat row a slot (the indexed dimension leads and the
               window is the minor one, so a scatter into the donated
               pool stays in place: `attention._write_pages`)
    slots:     (B,) int32: the slot of each row of the step
               (`NULL_SLOT` for padding)
    """

    ssm_pool: jnp.ndarray
    conv_pool: jnp.ndarray
    slots: jnp.ndarray

    def tree_flatten(self):
        return (self.ssm_pool, self.conv_pool, self.slots), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
class LayerPool(tuple):
    """One layer's pool arrays, tagged with what they hold: "kv" (k, v),
    "kv_quant" (k, v, k_scale, v_scale), "latent" (rows,) or (rows,
    index keys), or "state"
    (ssm, conv), and for a "kv" pool with how many kv heads lie side by
    side in a row (`head_pack`). Both are static under `jax.jit` (the
    tree's auxiliary data); the leaves and their order are the bare
    tuple's, so a program over tagged pools is the program over bare
    ones."""

    KINDS = ("kv", "kv_quant", "latent", "state")

    def __new__(cls, kind: str, arrays, head_pack: int = 1):
        if kind not in cls.KINDS:
            raise ValueError(f"unknown pool kind {kind!r}")
        if head_pack != 1 and kind != "kv":
            raise ValueError(f"a {kind!r} pool does not pack heads")
        self = super().__new__(cls, arrays)
        self.kind = kind
        self.head_pack = head_pack
        return self

    def tree_flatten(self):
        return tuple(self), (self.kind, self.head_pack)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux[0], children, aux[1])

    def __repr__(self):
        return (f"LayerPool({self.kind!r}, {tuple(self)!r}, "
                f"head_pack={self.head_pack})")


def views_from_pools(pools, page_table, row_ids=None, slots=None):
    """Per-layer cache views from the engine's tagged pools
    (`LayerPool`), each of its own kind: a model with recurrent layers
    has "kv" and "state" pools side by side. `slots` is the (B,) state
    slot of each row, needed where a "state" pool is. Runs at trace time
    inside every jitted step."""
    views = []
    for p in pools:
        if p.kind == "latent":
            if row_ids is not None:
                raise NotImplementedError(
                    "a latent pool has no flat ragged step (row_ids)")
            views.append(LatentLayerCache(p[0], page_table, *p[1:]))
        elif p.kind == "state":
            if row_ids is not None or slots is None:
                raise NotImplementedError(
                    "a state pool is read by one slot a row: it has no "
                    "flat ragged step, and the step must name the slots")
            views.append(StateLayerCache(p[0], p[1], slots))
        else:
            quant = p.kind == "kv_quant"
            views.append(PagedLayerCache(
                p[0], p[1], page_table, row_ids,
                k_scale=p[2] if quant else None,
                v_scale=p[3] if quant else None, head_pack=p.head_pack))
    return views


def pools_from_views(views):
    """Inverse of `views_from_pools`: tagged pools from the new caches a
    step returned."""
    return [LayerPool("latent", (v.pool,) if v.index_pool is None
                      else (v.pool, v.index_pool))
            if isinstance(v, LatentLayerCache)
            else LayerPool("state", (v.ssm_pool, v.conv_pool))
            if isinstance(v, StateLayerCache)
            else LayerPool("kv", (v.k_pool, v.v_pool), v.head_pack)
            if v.k_scale is None
            else LayerPool("kv_quant",
                           (v.k_pool, v.v_pool, v.k_scale, v.v_scale))
            for v in views]


# pages an indexed latent pool may hold: `attention._page_lookup` carries
# a page id as two bytes, each exact in bf16
INDEXED_POOL_MAX_PAGES = 1 << 16


class PagedKVCache:
    """The per-layer pools plus the allocator. Pools are plain jax arrays
    so the engine can thread (and donate) them through jitted steps."""

    def __init__(self, num_layers: int, num_pages: int, page_size: int,
                 num_kv_heads: int, head_dim: int, dtype=jnp.float32,
                 kv_dtype: Optional[str] = None,
                 latent_dim: Optional[int] = None,
                 index_dim: Optional[int] = None,
                 head_pack: int = 1,
                 state_spec: Optional[StateSpec] = None,
                 state_slots: int = 0):
        """`latent_dim` selects the pool kind: None for K and V pools of
        `num_kv_heads` heads of `head_dim`; a row's width for the latent
        kind, one (num_pages, page_size, latent_dim rounded up to whole
        128-lane tiles) pool a layer (`num_kv_heads` and `head_dim` are
        then not read), and with `index_dim` (a model whose attention
        chooses its keys by an indexer) a second array a layer beside
        it, (num_pages, page_size, index_dim), for the index keys.

        `head_pack` > 1 (plain K/V pools only) holds that many kv heads
        side by side in one row: pools of (num_kv_heads / head_pack,
        num_pages, page_size, head_pack * head_dim). A row of 64 is half
        a 128-lane tile, which the chip's memory pads and the decode
        kernel would have to pad again at every call; two heads of 64
        fill the row (`attention._paged_decode_pallas` has the
        arithmetic).

        `state_spec` (a model with recurrent layers) gives the layers it
        marks a state pool of `state_slots` + 1 slots instead of K/V
        pages, and the cache a `slot_allocator`; the other layers get
        the K/V pools described above."""
        self.num_layers = num_layers
        self.num_pages = num_pages
        self.page_size = page_size
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.latent_dim = latent_dim
        self.index_dim = index_dim if latent_dim is not None else None
        if index_dim is not None and latent_dim is None:
            raise ValueError("index keys are cached beside latent rows: "
                             "index_dim needs latent_dim")
        if index_dim is not None and num_pages > INDEXED_POOL_MAX_PAGES:
            raise ValueError(
                f"num_pages={num_pages}: the sparse decode step looks a "
                "chosen token's page up as two bytes "
                "(`attention._page_lookup`), so an indexed latent pool "
                f"holds at most {INDEXED_POOL_MAX_PAGES} pages")
        self.state_spec = state_spec
        self.state_slots = int(state_slots) if state_spec is not None else 0
        if kv_dtype is not None and kv_dtype in _PLAIN_KV_DTYPES:
            dtype = _PLAIN_KV_DTYPES[kv_dtype]
            kv_dtype = None
        self.quant_spec = None
        self.head_pack = int(head_pack)
        if self.head_pack > 1 and (latent_dim is not None
                                   or kv_dtype is not None
                                   or num_kv_heads % self.head_pack):
            raise ValueError(
                f"head_pack={head_pack}: only plain K/V pools pack heads, "
                "and the kv heads must divide into whole packs")
        if state_spec is not None:
            if latent_dim is not None or kv_dtype is not None:
                raise ValueError(
                    "state slots stand beside fp32 or bf16 K/V pages: "
                    "latent or quantized pages beside them are not "
                    "written yet")
            if len(state_spec.state_layers) != num_layers:
                raise ValueError(
                    f"state_spec marks {len(state_spec.state_layers)} "
                    f"layers, the model has {num_layers}")
            if self.state_slots < 1:
                raise ValueError("a model with recurrent layers needs "
                                 "state_slots >= 1")
        is_state = (tuple(state_spec.state_layers) if state_spec is not None
                    else (False,) * num_layers)
        self.num_kv_layers = num_layers - sum(is_state)
        if latent_dim is not None:
            if kv_dtype is not None:
                raise ValueError(
                    f"kv_dtype={kv_dtype!r}: a latent pool holds fp32 or "
                    "bf16 rows; quantized latent pages are not written yet")

            def paged():
                shape = (num_pages, page_size)
                arrays = (jnp.zeros(shape + (self._latent_width,), dtype),)
                if index_dim is not None:
                    arrays += (jnp.zeros(shape + (index_dim,), dtype),)
                return LayerPool("latent", arrays)
        elif kv_dtype is not None:
            # quantized pools ONLY: the fp32/bf16 constructor path above
            # must never import serving.quant
            from .quant import SCALE_DTYPE, resolve_kv_dtype
            self.quant_spec = resolve_kv_dtype(kv_dtype,
                                               compute_dtype=dtype)
            store = self.quant_spec.storage_dtype
            shape = (num_kv_heads, num_pages, page_size, head_dim)
            sshape = (num_kv_heads, num_pages, page_size, 1)

            def paged():
                return LayerPool("kv_quant", (
                    jnp.zeros(shape, store), jnp.zeros(shape, store),
                    jnp.ones(sshape, SCALE_DTYPE),
                    jnp.ones(sshape, SCALE_DTYPE)))
        else:
            shape = (num_kv_heads // self.head_pack, num_pages, page_size,
                     head_dim * self.head_pack)

            def paged():
                return LayerPool("kv", (jnp.zeros(shape, dtype),
                                        jnp.zeros(shape, dtype)),
                                 self.head_pack)

        def state():
            n = self.state_slots + 1
            return LayerPool("state", (
                jnp.zeros((n,) + state_spec.ssm_shape, jnp.float32),
                jnp.zeros((n, state_spec.conv_elems), dtype)))

        self.pools = [state() if s else paged() for s in is_state]
        self.dtype = dtype
        self.allocator = BlockAllocator(num_pages)
        self.slot_allocator = (SlotAllocator(self.state_slots)
                               if state_spec is not None else None)

    @property
    def kv_dtype(self) -> str:
        """Canonical name of the pool storage format."""
        if self.quant_spec is not None:
            return self.quant_spec.name
        return {"float32": "fp32",
                "bfloat16": "bf16"}.get(jnp.dtype(self.dtype).name,
                                        jnp.dtype(self.dtype).name)

    @property
    def quantized(self) -> bool:
        return self.quant_spec is not None

    @property
    def kind(self) -> str:
        """What the sequence state is held in: "kv" or "latent" pages,
        or "kv+state": K/V pages for the attention layers and one state
        slot a request for the recurrent ones."""
        if self.state_spec is not None:
            return "kv+state"
        return "kv" if self.latent_dim is None else "latent"

    @property
    def _latent_width(self) -> int:
        """The latent row in whole 128-lane tiles."""
        return -(-self.latent_dim // 128) * 128

    @property
    def slot_elems(self) -> int:
        """Stored elements of one token in one paged layer: the latent
        row in whole 128-lane tiles and, where the layer holds one, the
        index key; or K and V of every kv head."""
        if self.latent_dim is not None:
            return self._latent_width + (self.index_dim or 0)
        return 2 * self.num_kv_heads * self.head_dim

    @property
    def page_bytes(self) -> int:
        """Bytes one logical page occupies across the paged layers: the
        data slabs of the pool's kind plus (quantized pools) the parallel
        scale slabs. This is the capacity unit of the paged state —
        resident sequences per pool byte budget is
        `budget // (pages_for(seq_len) * page_bytes)`."""
        itemsize = (self.quant_spec.storage_itemsize
                    if self.quant_spec is not None
                    else jnp.dtype(self.dtype).itemsize)
        per_slot = self.slot_elems * itemsize + (
            2 * self.num_kv_heads * 4 if self.quantized else 0)
        return self.num_kv_layers * self.page_size * per_slot

    @property
    def state_slot_bytes(self) -> int:
        """Bytes of one request's state slot across the recurrent
        layers (0 for a model without them): the float32 SSM state and
        the conv tail in the cache's type."""
        spec = self.state_spec
        if spec is None:
            return 0
        ssm = 4 * spec.heads * spec.head_dim * spec.state_dim
        conv = spec.conv_elems * jnp.dtype(self.dtype).itemsize
        return (self.num_layers - self.num_kv_layers) * (ssm + conv)

    @property
    def pool_bytes(self) -> int:
        """Total bytes of all pool leaves: pages (data + scale slabs)
        and state slots, the null page and the null slot among them."""
        return (self.num_pages * self.page_bytes
                + (self.state_slots + 1) * self.state_slot_bytes)

    @classmethod
    def for_model(cls, model, num_pages: int, page_size: int,
                  dtype=jnp.float32,
                  kv_dtype: Optional[str] = None,
                  pack_heads: bool = True,
                  state_slots: int = 0) -> "PagedKVCache":
        from ..models.generation import _config_of

        cfg = _config_of(model)
        kv_heads = getattr(cfg, "num_key_value_heads",
                           cfg.num_attention_heads)
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        # a model with latent attention says how wide its cached row is,
        # one with recurrent layers which they are and how large a state
        latent_dim = getattr(cfg, "latent_cache_dim", None)
        index_dim = getattr(cfg, "index_cache_dim", None)
        state_spec = getattr(cfg, "state_cache_spec", None)
        # validate the model's compute dtype against the requested pool
        # format up front — the old code silently assumed fp32 pools and
        # a mismatch surfaced as a cryptic XLA dtype error mid-step
        try:
            compute = next(iter(model.parameters()))._data.dtype
        except (StopIteration, AttributeError):
            compute = jnp.float32
        if jnp.dtype(compute) not in (jnp.dtype(jnp.float32),
                                      jnp.dtype(jnp.bfloat16)):
            raise ValueError(
                f"paged serving needs a float32/bfloat16 model, got "
                f"parameters of dtype {jnp.dtype(compute).name}")
        if kv_dtype is not None and kv_dtype not in _PLAIN_KV_DTYPES \
                and kv_dtype not in ("int8", "fp8"):
            raise ValueError(
                f"unknown kv_dtype {kv_dtype!r}: expected one of "
                "'fp32', 'bf16', 'int8', 'fp8'")
        # plain pools of heads that divide a 128-lane row hold a row's
        # worth of heads side by side (see `__init__`); sharded pools
        # (`pack_heads=False` from a tensor-parallel engine) keep one
        # head a row, since their head axis is what is sharded
        plain = (latent_dim is None
                 and (kv_dtype is None or kv_dtype in _PLAIN_KV_DTYPES))
        pack = _lane_pack(head_dim, kv_heads) if pack_heads and plain else 1
        return cls(cfg.num_hidden_layers, num_pages, page_size, kv_heads,
                   head_dim, dtype, kv_dtype=kv_dtype,
                   latent_dim=latent_dim, index_dim=index_dim,
                   head_pack=pack,
                   state_spec=state_spec, state_slots=state_slots)

    def shard_pools(self, mesh, spec) -> None:
        """Place every layer's pool tuple onto `mesh` under `spec` —
        tensor-parallel serving shards the kv-head axis (`P("tp", ...)`)
        so each device owns a (kv_heads/tp, num_pages, page_size,
        head_dim) slab. Scale slabs are rank-4 with the same leading
        kv-head axis, so the one spec covers every leaf. The pools'
        LOGICAL shape, the allocator, page ids and the null page are
        untouched: one logical page is tp physical slabs, so all
        host-side accounting stays byte-identical to the single-device
        layout."""
        from jax.sharding import NamedSharding

        if self.latent_dim is not None or self.state_spec is not None \
                or self.head_pack > 1:
            raise ValueError(
                "a latent pool, a state pool and a pool of packed heads "
                "have no kv-head axis to shard: tensor parallelism over "
                "them is not written yet")
        sh = NamedSharding(mesh, spec)
        self.pools = [LayerPool(layer.kind,
                                tuple(jax.device_put(x, sh) for x in layer))
                      for layer in self.pools]

    def page_table_array(self, page_lists: Sequence[Sequence[int]],
                         max_pages: int) -> jnp.ndarray:
        """(B, max_pages) int32 device page table from host page lists,
        padded with the null page."""
        import numpy as np

        out = np.zeros((len(page_lists), max_pages), np.int32)
        for i, pages in enumerate(page_lists):
            if len(pages) > max_pages:
                raise ValueError(f"sequence holds {len(pages)} pages > "
                                 f"max_pages {max_pages}")
            out[i, :len(pages)] = pages
        return jnp.asarray(out)

    def layer_views(self, page_table: jnp.ndarray, slots=None) -> list:
        """Per-layer views, each of its pool's kind (`PagedLayerCache`,
        `LatentLayerCache`, `StateLayerCache`), in the shape the models
        expect for their `caches` argument; `slots` as
        `views_from_pools` takes it."""
        return views_from_pools(self.pools, page_table, slots=slots)

    def slot_array(self, slots: Sequence[Optional[int]]) -> jnp.ndarray:
        """(B,) int32 device slot table from host slot ids, `None`
        (padding) as the null slot."""
        import numpy as np

        return jnp.asarray(np.asarray(
            [NULL_SLOT if s is None else s for s in slots], np.int32))

    def update(self, new_views: Sequence) -> None:
        """Adopt the pools a jitted step returned (the step's new_caches)."""
        self.pools = pools_from_views(new_views)
