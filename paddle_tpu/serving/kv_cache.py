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

__all__ = ["BlockAllocator", "PagedKVCache", "PagedLayerCache",
           "LatentLayerCache", "NULL_PAGE", "pages_for",
           "overflow_position", "views_from_pools", "pools_from_views"]

NULL_PAGE = 0

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

    k_pool/v_pool: (kv_heads, num_pages, page_size, head_dim) — kv-head
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

    @property
    def page_size(self) -> int:
        return self.k_pool.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def tree_flatten(self):
        # keep the 3-child structure (and treedef equality) of every
        # existing executable when row_ids is absent; quantized views get
        # their own aux tags so fp32/bf16 treedefs stay byte-identical
        if self.k_scale is None:
            if self.row_ids is None:
                return (self.k_pool, self.v_pool, self.page_table), None
            return (self.k_pool, self.v_pool, self.page_table,
                    self.row_ids), True
        if self.row_ids is None:
            return (self.k_pool, self.v_pool, self.page_table,
                    self.k_scale, self.v_scale), "quant"
        return (self.k_pool, self.v_pool, self.page_table,
                self.k_scale, self.v_scale, self.row_ids), "quant+rows"

    @classmethod
    def tree_unflatten(cls, aux, children):
        if aux in (None, True):
            return cls(*children)
        kp, vp, pt, ks, vs = children[:5]
        rid = children[5] if aux == "quant+rows" else None
        return cls(kp, vp, pt, rid, k_scale=ks, v_scale=vs)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LatentLayerCache:
    """One layer's view of a latent pool (the pool kind of a model with
    latent attention, MLA): one row a token, the normed compressed
    latent followed by the one rotated rope key all heads share, and no
    V. `serving.attention.latent_write` writes it and
    `latent_decode_attention` reads it; the allocator, the page tables and the null page are the K/V kind's.

    pool:       (num_pages, page_size, width) — a page is one contiguous
                (page_size, width) slab, which the decode kernel brings
                by one copy. `width` is the row's own width rounded up
                to whole 128-lane tiles, as the chip's memory holds it
                anyway (a kernel cannot copy part of a tile out of HBM);
                the columns past the row stay zero
    page_table: (B, max_pages) int32, as `PagedLayerCache`'s
    """

    pool: jnp.ndarray
    page_table: jnp.ndarray

    @property
    def page_size(self) -> int:
        return self.pool.shape[1]

    def tree_flatten(self):
        return (self.pool, self.page_table), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def views_from_pools(pools, page_table, row_ids=None):
    """Per-layer cache views from engine pool tuples — 1-tuples (pool,)
    for the latent kind, 2-tuples (k, v) for plain K/V pools, 4-tuples
    (k, v, k_scale, v_scale) for quantized ones. Runs at trace time
    inside every jitted step."""
    if pools and len(pools[0]) == 1:
        if row_ids is not None:
            raise NotImplementedError(
                "a latent pool has no flat ragged step (row_ids)")
        return [LatentLayerCache(p[0], page_table) for p in pools]
    return [PagedLayerCache(p[0], p[1], page_table, row_ids,
                            k_scale=p[2] if len(p) == 4 else None,
                            v_scale=p[3] if len(p) == 4 else None)
            for p in pools]


def pools_from_views(views):
    """Inverse of `views_from_pools`: pool tuples from the new caches a
    step returned, preserving the tuples' arity."""
    return [(v.pool,) if isinstance(v, LatentLayerCache)
            else (v.k_pool, v.v_pool) if v.k_scale is None
            else (v.k_pool, v.v_pool, v.k_scale, v.v_scale)
            for v in views]


class PagedKVCache:
    """The per-layer pools plus the allocator. Pools are plain jax arrays
    so the engine can thread (and donate) them through jitted steps."""

    def __init__(self, num_layers: int, num_pages: int, page_size: int,
                 num_kv_heads: int, head_dim: int, dtype=jnp.float32,
                 kv_dtype: Optional[str] = None,
                 latent_dim: Optional[int] = None):
        """`latent_dim` selects the pool kind: None for K and V pools of
        `num_kv_heads` heads of `head_dim`; a row's width for the latent
        kind, one (num_pages, page_size, latent_dim rounded up to whole
        128-lane tiles) pool a layer (`num_kv_heads` and `head_dim` are
        then not read)."""
        self.num_layers = num_layers
        self.num_pages = num_pages
        self.page_size = page_size
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.latent_dim = latent_dim
        if kv_dtype is not None and kv_dtype in _PLAIN_KV_DTYPES:
            dtype = _PLAIN_KV_DTYPES[kv_dtype]
            kv_dtype = None
        self.quant_spec = None
        if latent_dim is not None:
            if kv_dtype is not None:
                raise ValueError(
                    f"kv_dtype={kv_dtype!r}: a latent pool holds fp32 or "
                    "bf16 rows; quantized latent pages are not written yet")
            self.pools = [(jnp.zeros((num_pages, page_size,
                                      self.slot_elems), dtype),)
                          for _ in range(num_layers)]
        elif kv_dtype is not None:
            # quantized pools ONLY: the fp32/bf16 constructor path above
            # must never import serving.quant
            from .quant import SCALE_DTYPE, resolve_kv_dtype
            self.quant_spec = resolve_kv_dtype(kv_dtype,
                                               compute_dtype=dtype)
            store = self.quant_spec.storage_dtype
            shape = (num_kv_heads, num_pages, page_size, head_dim)
            sshape = (num_kv_heads, num_pages, page_size, 1)
            self.pools = [
                (jnp.zeros(shape, store), jnp.zeros(shape, store),
                 jnp.ones(sshape, SCALE_DTYPE),
                 jnp.ones(sshape, SCALE_DTYPE))
                for _ in range(num_layers)]
        else:
            shape = (num_kv_heads, num_pages, page_size, head_dim)
            self.pools = [(jnp.zeros(shape, dtype),
                           jnp.zeros(shape, dtype))
                          for _ in range(num_layers)]
        self.dtype = dtype
        self.allocator = BlockAllocator(num_pages)

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
        """"latent" or "kv": what a page holds."""
        return "kv" if self.latent_dim is None else "latent"

    @property
    def slot_elems(self) -> int:
        """Stored elements of one token in one layer: the latent row in
        whole 128-lane tiles, or K and V of every kv head."""
        if self.latent_dim is not None:
            return -(-self.latent_dim // 128) * 128
        return 2 * self.num_kv_heads * self.head_dim

    @property
    def page_bytes(self) -> int:
        """Bytes one logical page occupies across all layers: the data
        slabs of the pool's kind plus (quantized pools) the parallel
        scale slabs. This is the capacity unit — resident sequences per
        pool byte budget is
        `budget // (pages_for(seq_len) * page_bytes)`."""
        itemsize = (self.quant_spec.storage_itemsize
                    if self.quant_spec is not None
                    else jnp.dtype(self.dtype).itemsize)
        per_slot = self.slot_elems * itemsize + (
            2 * self.num_kv_heads * 4 if self.quantized else 0)
        return self.num_layers * self.page_size * per_slot

    @property
    def pool_bytes(self) -> int:
        """Total bytes of all pool leaves (data + scale slabs)."""
        return self.num_pages * self.page_bytes

    @classmethod
    def for_model(cls, model, num_pages: int, page_size: int,
                  dtype=jnp.float32,
                  kv_dtype: Optional[str] = None) -> "PagedKVCache":
        from ..models.generation import _config_of

        cfg = _config_of(model)
        kv_heads = getattr(cfg, "num_key_value_heads",
                           cfg.num_attention_heads)
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        # a model with latent attention says how wide its cached row is
        latent_dim = getattr(cfg, "latent_cache_dim", None)
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
        return cls(cfg.num_hidden_layers, num_pages, page_size, kv_heads,
                   head_dim, dtype, kv_dtype=kv_dtype,
                   latent_dim=latent_dim)

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

        if self.latent_dim is not None:
            raise ValueError("a latent pool has no kv-head axis to shard: "
                             "tensor parallelism over it is not written yet")
        sh = NamedSharding(mesh, spec)
        self.pools = [tuple(jax.device_put(x, sh) for x in layer)
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

    def layer_views(self, page_table: jnp.ndarray) -> list:
        """Per-layer views of the pool's kind (`PagedLayerCache` or
        `LatentLayerCache`) in the shape the models expect for their
        `caches` argument."""
        return views_from_pools(self.pools, page_table)

    def update(self, new_views: Sequence) -> None:
        """Adopt the pools a jitted step returned (the step's new_caches)."""
        self.pools = pools_from_views(new_views)
