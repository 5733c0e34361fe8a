"""paddle_tpu.serving: block-allocator invariants (incl. refcounted page
sharing), paged-attention parity vs the static-cache `attend_with_cache`,
continuous batching with staggered arrivals token-identical to sequential
`generate`, the multi-token decode horizon (fused decode+sample blocks at
horizon 1/4/8 token-identical to each other, to horizon 1, and to
`generate`; host syncs ~1/horizon; block page reservation; preemption
with blocks in flight), admission backpressure / preemption, automatic
prefix caching (radix-tree hits token-identical to cold runs, LRU
eviction, shared-page preemption safety), and BOUNDED compilation counts
(asserted via the jit caches' miss counts — each `_cache_size` entry is
one cache miss -> one compiled executable; the prefix cache may add at
most one offset-aware prefill executable per bucket, and each decode
horizon gets exactly one fused decode+sample executable).

Fast-lane tests compile only the prefill-bucket + decode + sampler set (a
single tiny model reused module-wide); anything beyond that — the second
model family, the multi-bucket sweep — is `slow`.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models import (
    GPTConfig, GPTForCausalLM, LlamaConfig, LlamaForCausalLM,
)
from paddle_tpu.models.generation import attend_with_cache
from paddle_tpu.serving import (
    BlockAllocator, NULL_PAGE, PagedKVCache, PagedLayerCache, PrefixCache,
    Request, SamplingParams, Scheduler, ServingEngine, pages_for,
)
from paddle_tpu.serving import attention as satt


@functools.lru_cache(maxsize=None)
def _llama():
    paddle.seed(1234)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    return m


@functools.lru_cache(maxsize=None)
def _gpt():
    paddle.seed(1234)
    m = GPTForCausalLM(GPTConfig.tiny())
    m.eval()
    return m


def _sequential_reference(model, prompts, max_new_tokens):
    """Per-request greedy `generate`, the engine's parity oracle."""
    return [list(model.generate(paddle.to_tensor(np.asarray(p)[None]),
                                max_new_tokens=max_new_tokens,
                                temperature=0.0).numpy()[0])
            for p in prompts]


# ---------------------------------------------------------------- allocator

class TestBlockAllocator:
    def test_alloc_free_roundtrip(self):
        a = BlockAllocator(8)
        assert a.num_free == 7           # page 0 reserved
        pages = [a.alloc() for _ in range(7)]
        assert sorted(pages) == list(range(1, 8))
        assert a.alloc() is None         # exhausted
        for p in pages:
            a.free(p)
        assert a.num_free == 7 and a.num_used == 0

    def test_double_free_raises(self):
        a = BlockAllocator(4)
        p = a.alloc()
        a.free(p)
        with pytest.raises(ValueError, match="double free"):
            a.free(p)

    def test_null_page_is_never_handed_out_and_unfreeable(self):
        a = BlockAllocator(4)
        assert NULL_PAGE not in [a.alloc() for _ in range(3)]
        with pytest.raises(ValueError, match="null page"):
            a.free(NULL_PAGE)

    def test_alloc_n_all_or_nothing(self):
        a = BlockAllocator(4)
        assert a.alloc_n(4) is None      # only 3 allocatable
        assert a.num_free == 3           # failed batch leaks nothing
        got = a.alloc_n(3)
        assert len(got) == 3 and a.num_free == 0

    def test_pages_for(self):
        assert pages_for(1, 8) == 1
        assert pages_for(8, 8) == 1
        assert pages_for(9, 8) == 2
        assert pages_for(17, 8) == 3


# -------------------------------------------------- refcounted allocator

class TestBlockAllocatorRefcounts:
    def test_acquire_defers_free_until_last_release(self):
        a = BlockAllocator(4)
        p = a.alloc()
        assert a.ref_count(p) == 1
        a.acquire(p)
        a.acquire(p)
        assert a.ref_count(p) == 3
        a.free(p)
        a.free(p)
        assert a.ref_count(p) == 1 and a.num_used == 1
        free_before = a.num_free
        a.free(p)                        # last holder: page really frees
        assert a.ref_count(p) == 0
        assert a.num_free == free_before + 1 and a.num_used == 0

    def test_release_past_zero_raises(self):
        a = BlockAllocator(4)
        p = a.alloc()
        a.acquire(p)
        a.free(p)
        a.free(p)
        with pytest.raises(ValueError, match="double free"):
            a.free(p)

    def test_acquire_free_or_null_page_raises(self):
        a = BlockAllocator(4)
        with pytest.raises(ValueError, match="null page"):
            a.acquire(NULL_PAGE)
        with pytest.raises(ValueError, match="free/unknown"):
            a.acquire(2)                 # never alloc'd

    def test_shared_page_survives_one_owner(self):
        """Two 'sequences' hold the same page; freeing one table leaves
        the page resident for the other."""
        a = BlockAllocator(8)
        shared = a.alloc()
        a.acquire(shared)                # second sequence's table
        own = a.alloc()
        a.free_all([shared, own])        # first sequence finishes
        assert a.ref_count(shared) == 1  # survivor still holds it
        assert a.ref_count(own) == 0


# ------------------------------------------------------- prefix cache

class TestPrefixCache:
    """Host-side radix-tree invariants (no model, no jit)."""

    def _cache(self, num_pages=16, ps=4):
        a = BlockAllocator(num_pages)
        return a, PrefixCache(a, ps)

    def test_match_miss_then_insert_then_hit(self):
        a, pc = self._cache()
        toks = list(range(11))           # 2 full pages + 3 spare @ ps=4
        assert pc.match(toks) == []
        pages = a.alloc_n(3)
        pc.insert(toks, pages)           # registers pages[0:2] only
        assert pc.cached_pages == 2
        got = pc.match(toks)
        assert got == pages[:2]
        # match acquired one ref per page on top of owner + tree
        assert a.ref_count(pages[0]) == 3
        assert a.ref_count(pages[2]) == 1   # partial page never cached

    def test_match_caps_below_full_prompt(self):
        """A fully-cached page-aligned prompt still leaves its last token
        uncached — the engine needs that token's logits to sample."""
        a, pc = self._cache(ps=4)
        toks = list(range(8))            # exactly 2 pages
        pages = a.alloc_n(2)
        pc.insert(toks, pages)
        assert pc.cached_pages == 2
        assert pc.match(toks) == pages[:1]   # cap: (8-1)//4 = 1 chunk

    def test_eviction_frees_only_unreferenced_lru_leaves(self):
        a, pc = self._cache(ps=4)
        hot = list(range(8))
        cold = [90, 91, 92, 93, 94]
        hot_pages, cold_pages = a.alloc_n(2), a.alloc_n(2)
        pc.insert(hot, hot_pages)
        pc.insert(cold, cold_pages)          # registers cold_pages[0] only
        held = pc.match(hot)                 # live sequence pins hot[0]
        assert held == hot_pages[:1]
        a.free_all(hot_pages + cold_pages)   # original owners finish
        assert pc.evict(10) == 2             # hot leaf + cold leaf only
        assert a.ref_count(cold_pages[0]) == 0   # tree-only ref: freed
        assert a.ref_count(hot_pages[1]) == 0
        assert a.ref_count(hot_pages[0]) == 2    # pinned by match: kept
        assert pc.cached_pages == 1
        a.free_all(held)
        assert pc.flush() == 1               # now evictable
        assert pc.cached_pages == 0 and a.num_used == 0

    def test_lru_order(self):
        a, pc = self._cache(ps=2)
        p1, p2 = [a.alloc()], [a.alloc()]
        pc.insert([1, 2], p1)
        pc.insert([3, 4], p2)
        a.free(p1[0])
        a.free(p2[0])                    # owners gone, tree-only refs
        a.free_all(pc.match([1, 2, 99]))  # touch the first prefix
        assert pc.evict(1) == 1
        assert a.ref_count(p2[0]) == 0   # LRU victim was the untouched one
        assert a.ref_count(p1[0]) == 1

    def test_duplicate_insert_keeps_incumbent(self):
        a, pc = self._cache(ps=4)
        toks = list(range(5))
        first, second = a.alloc_n(2), a.alloc_n(2)
        assert pc.insert(toks, first) == 1
        assert pc.insert(toks, second) == 0      # chunk already cached
        assert pc.match(toks) == first[:1]
        assert a.ref_count(second[0]) == 1       # duplicate stays private

    def test_stats_counters(self):
        a, pc = self._cache(ps=4)
        toks = list(range(9))
        pc.insert(toks, a.alloc_n(3))
        pc.record(9, 0)
        pc.record(9, 8)
        s = pc.stats()
        assert s["hit_tokens"] == 8 and s["miss_tokens"] == 10
        assert s["lookups"] == 2 and s["cached_pages"] == 2
        assert abs(s["hit_rate"] - 8 / 18) < 1e-9


# ------------------------------------------- admission page accounting

class TestAdmissionPageAccounting:
    """ISSUE 2 satellite audit: `_admission_pages` (prompt + 1 token) must
    equal what the first post-prefill `_ensure_decode_pages` demands
    (pages_for(num_tokens) with num_tokens = prompt + 1). The audit found
    the two CONSISTENT — including the exact-fill case where the +1 rolls
    into a fresh page and the null-page convention (page 0 lives outside
    the allocator, so free counts need no adjustment). These tests pin
    that equivalence so a refactor can't silently reintroduce the
    off-by-one."""

    @pytest.mark.parametrize("prompt_len", [7, 8, 9, 15, 16, 17])
    def test_admission_matches_first_decode_demand(self, prompt_len):
        sched = Scheduler(BlockAllocator(64), page_size=8,
                          max_batch_size=2, max_pages_per_seq=8)
        req = Request(prompt=[1] * prompt_len, max_new_tokens=4,
                      sampling=SamplingParams())
        sched.add(req)
        assert sched.schedule().kind == "prefill"
        admitted = len(req.pages)
        assert admitted == sched._admission_pages(req)
        req.generated.append(0)          # the token prefill emitted
        free_before = sched.allocator.num_free
        sched._ensure_decode_pages()     # first decode's page demand
        assert sched.allocator.num_free == free_before, \
            "admission under-charged: first decode had to allocate"
        assert len(req.pages) == pages_for(prompt_len + 1, 8)

    @pytest.mark.slow            # compiles a fresh pool-shape executable set
    def test_exact_fill_prompt_end_to_end(self):
        """Prompt exactly fills its last page: prefill + first decode must
        not wedge or leak, and tokens match sequential generate."""
        model = _llama()
        rng = np.random.RandomState(11)
        vocab = LlamaConfig.tiny().vocab_size
        prompt = rng.randint(0, vocab, (16,))    # 2 pages @ page_size 8
        ref = _sequential_reference(model, [prompt], 4)[0]
        eng = ServingEngine(model, page_size=8, max_batch_size=2,
                            max_seq_len=32, prefill_buckets=(16, 32))
        rid = eng.add_request(prompt, max_new_tokens=4, temperature=0.0)
        assert eng.run()[rid] == ref
        assert eng.cache.allocator.num_used == 0


# ------------------------------------------- allocatable-page accounting

class TestAllocatablePageAccounting:
    """ISSUE 7 satellite: every too-large-for-pool error path must count
    ALLOCATABLE pages (num_pages minus the reserved null page). Before
    the fix, `_ensure_decode_pages` reported `num_pages` "pages total"
    while `schedule()` reported `num_pages - 1` "allocatable" — the same
    pool described with two different capacities depending on which path
    raised. Pinned here across all three raise sites."""

    def test_num_allocatable_property(self):
        a = BlockAllocator(4)
        assert a.num_allocatable == 3
        assert a.alloc_n(a.num_allocatable) is not None   # exactly fits
        assert a.alloc() is None                          # and no more

    def test_idle_too_large_check_counts_allocatable(self):
        sched = Scheduler(BlockAllocator(4), page_size=8,
                          max_batch_size=2, max_pages_per_seq=8)
        req = Request(prompt=[1] * 25, max_new_tokens=2,
                      sampling=SamplingParams())
        sched.add(req)                    # needs 4 pages, 3 allocatable
        with pytest.raises(RuntimeError, match="3 allocatable in total"):
            sched.schedule()

    def test_decode_too_large_check_counts_allocatable(self):
        sched = Scheduler(BlockAllocator(4), page_size=8,
                          max_batch_size=2, max_pages_per_seq=8)
        req = Request(prompt=[1] * 24, max_new_tokens=4,
                      sampling=SamplingParams())
        req.status = "running"
        req.pages = sched.allocator.alloc_n(3)
        sched.running.append(req)
        req.generated.append(0)           # next block needs a 4th page
        with pytest.raises(RuntimeError,
                           match="3 allocatable pages in total"):
            sched._ensure_decode_pages()

    def test_chunked_too_large_check_counts_allocatable(self):
        sched = Scheduler(BlockAllocator(4), page_size=8,
                          max_batch_size=2, max_pages_per_seq=8,
                          prefill_chunk_tokens=8,
                          max_num_batched_tokens=16)
        req = Request(prompt=[1] * 30, max_new_tokens=4,
                      sampling=SamplingParams())
        req.status = "running"
        req.pages = sched.allocator.alloc_n(3)
        req.num_computed_tokens = 24      # final chunk needs a 4th page
        sched.running.append(req)
        with pytest.raises(RuntimeError,
                           match="3 allocatable pages in total"):
            sched.schedule()


# ------------------------------------------------- prefix caching engine

def _shared_prefix_prompts(rng, vocab, prefix_pages, page_size, tails):
    shared = rng.randint(0, vocab, (prefix_pages * page_size,)).tolist()
    return [shared + rng.randint(0, vocab, (t,)).tolist() for t in tails]


class TestPrefixCaching:
    def test_shared_prefix_hits_and_stays_token_identical(self):
        """THE acceptance gate: two requests share a 2-page prefix; the
        second's prefill touches only its suffix (hit tokens == both
        shared pages), outputs are token-identical to the cache-off
        engine, and the pool drains to zero after an eviction flush.
        Also the CI guard: enabling the cache adds at most ONE new
        prefill executable per touched bucket."""
        model = _llama()
        rng = np.random.RandomState(21)
        vocab = LlamaConfig.tiny().vocab_size
        prompts = _shared_prefix_prompts(rng, vocab, prefix_pages=2,
                                         page_size=8, tails=[4, 6])

        def run(flag):
            eng = ServingEngine(model, page_size=8, max_batch_size=4,
                                max_seq_len=32, prefill_buckets=(16, 32),
                                enable_prefix_caching=flag)
            rids = [eng.add_request(p, max_new_tokens=5, temperature=0.0)
                    for p in prompts]
            outs = eng.run()
            return eng, [outs[r] for r in rids]

        eng_off, outs_off = run(False)
        eng_on, outs_on = run(True)
        assert outs_on == outs_off       # token-identical with cache on

        pcs = eng_on.stats()["prefix_cache"]
        assert pcs["hit_tokens"] >= 8 * 2        # both shared pages reused
        assert pcs["miss_tokens"] < sum(len(p) for p in prompts)
        assert 0.0 < pcs["hit_rate"] < 1.0
        assert pcs["cached_pages"] > 0

        # CI satellite: at most one NEW prefill executable per bucket
        on, off = eng_on.compile_counts(), eng_off.compile_counts()
        assert on["prefill_offset"] <= len({16, 32})
        assert on["prefill"] <= off["prefill"]
        assert on["decode"] == 1

        # zero leaked pages once the cache lets go
        assert eng_on.prefix_cache.flush() == pcs["cached_pages"]
        assert eng_on.cache.allocator.num_used == 0
        assert eng_on.cache.allocator.num_free == eng_on.cache.num_pages - 1

    @pytest.mark.slow            # extra offset-bucket compile on this pool
    def test_cache_hit_byte_identical_to_cold(self):
        """Same prompt twice on one engine: the second run is a cache hit
        (suffix-only prefill) yet emits byte-identical tokens."""
        model = _llama()
        rng = np.random.RandomState(22)
        vocab = LlamaConfig.tiny().vocab_size
        prompt = rng.randint(0, vocab, (19,)).tolist()
        eng = ServingEngine(model, page_size=8, max_batch_size=2,
                            max_seq_len=32, prefill_buckets=(16, 32),
                            enable_prefix_caching=True)
        r_cold = eng.add_request(prompt, max_new_tokens=6, temperature=0.0)
        eng.run()
        r_hit = eng.add_request(prompt, max_new_tokens=6, temperature=0.0)
        outs = eng.run()
        assert outs[r_hit] == outs[r_cold]
        st = eng.stats()["prefix_cache"]
        assert st["hit_tokens"] == 16    # 2 full pages of the 19 tokens
        ref = _sequential_reference(model, [prompt], 6)[0]
        assert outs[r_hit] == ref

    @pytest.mark.slow            # small-pool shapes compile beyond fast set
    def test_preemption_while_shared_keeps_survivor_intact(self):
        """Pool pressure preempts the youngest of two prefix-sharing
        requests: the victim's release must only drop ITS references —
        the survivor keeps decoding on the shared pages and both end
        token-identical to sequential generate."""
        model = _llama()
        rng = np.random.RandomState(23)
        vocab = LlamaConfig.tiny().vocab_size
        prompts = _shared_prefix_prompts(rng, vocab, prefix_pages=2,
                                         page_size=8, tails=[2, 3, 5])
        refs = _sequential_reference(model, prompts, max_new_tokens=8)
        # 7 usable pages: the 2 shared + one private page per request fit,
        # but copy-on-extend during decode runs the pool dry — the
        # youngest sharer must be preempted (shared pages are pinned by
        # the tree + survivors, so eviction cannot save it).
        # decode_horizon=1 pins the CLASSIC per-token reservation path:
        # at the default horizon, admission reserves the whole block and
        # this pool simply defers the youngest instead of preempting
        # (TestDecodeHorizon covers preemption while a block is in flight)
        eng = ServingEngine(model, page_size=8, max_batch_size=3,
                            max_seq_len=32, prefill_buckets=(16, 32),
                            num_pages=8, enable_prefix_caching=True,
                            decode_horizon=1)
        rids = [eng.add_request(p, max_new_tokens=8, temperature=0.0)
                for p in prompts]
        outs = eng.run()
        assert eng.stats()["preemptions"] >= 1
        for rid, ref in zip(rids, refs):
            assert outs[rid] == ref
        eng.prefix_cache.flush()
        assert eng.cache.allocator.num_used == 0

    def test_stats_section_shape(self):
        model = _llama()
        eng = ServingEngine(model, page_size=8, max_batch_size=2,
                            max_seq_len=32, prefill_buckets=(16, 32),
                            enable_prefix_caching=True)
        eng.add_request([1, 2, 3], max_new_tokens=2, temperature=0.0)
        eng.run()
        st = eng.stats()
        assert set(st["prefix_cache"]) >= {
            "hit_tokens", "miss_tokens", "hit_rate", "cached_pages",
            "evictions", "lookups"}
        # cache off: no section (semantics unchanged from PR 1)
        eng_off = ServingEngine(model, page_size=8, max_batch_size=2,
                                max_seq_len=32, prefill_buckets=(16, 32))
        assert "prefix_cache" not in eng_off.stats()


# ------------------------------------------------- paged-attention parity

def _static_vs_paged(rng, *, heads, kv_heads, hd, prompt_len, decode_steps,
                     page_size, bias=None):
    """Drive attend_with_cache down BOTH cache layouts on the same data:
    a static (1, max_len, kvh, hd) cache per request vs one ragged paged
    batch, and return (static ctx rows, paged ctx) per step."""
    b = len(prompt_len)
    max_pages = max(pages_for(n + decode_steps, page_size)
                    for n in prompt_len)
    max_len = max_pages * page_size
    rep = heads // kv_heads

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    # one paged pool shared by all rows; page tables disjoint per row
    pool = PagedKVCache(1, b * max_pages + 1, page_size, kv_heads, hd)
    alloc = pool.allocator
    tables = [[alloc.alloc() for _ in range(max_pages)] for _ in range(b)]
    pt = pool.page_table_array(tables, max_pages)

    statics = [(jnp.zeros((1, max_len, kv_heads, hd)),
                jnp.zeros((1, max_len, kv_heads, hd))) for _ in range(b)]
    outs = []

    # prefill: each request alone on the static path (its true ragged
    # length), all together on the paged path padded to the max bucket
    s = max(prompt_len)
    q, k, v = rand(b, s, heads, hd), rand(b, s, kv_heads, hd), \
        rand(b, s, kv_heads, hd)
    paged_view = pool.layer_views(pt)[0]
    static_rows = []
    for i in range(b):
        n = prompt_len[i]
        ctx, statics[i] = attend_with_cache(
            Tensor(q[i:i + 1, :n]), Tensor(k[i:i + 1, :n]),
            Tensor(v[i:i + 1, :n]), statics[i], 0, rep, bias=bias)
        static_rows.append(ctx.numpy()[0])
    ctx_p, paged_view = attend_with_cache(
        Tensor(q), Tensor(k), Tensor(v), paged_view, 0, rep, bias=bias)
    outs.append((static_rows, [ctx_p.numpy()[i, :prompt_len[i]]
                               for i in range(b)]))

    # ragged decode: every row at its OWN position in one paged call
    pos = np.asarray(prompt_len, np.int32)
    for _ in range(decode_steps):
        q1, k1, v1 = rand(b, 1, heads, hd), rand(b, 1, kv_heads, hd), \
            rand(b, 1, kv_heads, hd)
        static_rows = []
        for i in range(b):
            ctx, statics[i] = attend_with_cache(
                Tensor(q1[i:i + 1]), Tensor(k1[i:i + 1]),
                Tensor(v1[i:i + 1]), statics[i], int(pos[i]), rep,
                bias=bias)
            static_rows.append(ctx.numpy()[0])
        ctx_p, paged_view = attend_with_cache(
            Tensor(q1), Tensor(k1), Tensor(v1), paged_view,
            jnp.asarray(pos), rep, bias=bias)
        outs.append((static_rows, [ctx_p.numpy()[i] for i in range(b)]))
        pos = pos + 1
    return outs


class TestPagedAttentionParity:
    def test_ragged_batch_matches_static_per_request(self, rng):
        """Mixed prompt lengths: one ragged paged batch computes exactly
        what b independent static-cache requests compute."""
        steps = _static_vs_paged(rng, heads=4, kv_heads=4, hd=8,
                                 prompt_len=[5, 9, 3], decode_steps=3,
                                 page_size=4)
        for static_rows, paged_rows in steps:
            for srow, prow in zip(static_rows, paged_rows):
                np.testing.assert_allclose(prow, srow, atol=1e-5)

    def test_gqa_parity(self, rng):
        steps = _static_vs_paged(rng, heads=4, kv_heads=2, hd=8,
                                 prompt_len=[6, 4], decode_steps=2,
                                 page_size=4)
        for static_rows, paged_rows in steps:
            for srow, prow in zip(static_rows, paged_rows):
                np.testing.assert_allclose(prow, srow, atol=1e-5)

    def test_additive_bias_parity(self, rng):
        """T5's relative-position bias rides the mask on both paths; the
        paged path crops/pads it to its own key extent."""
        ps, n, steps = 4, 6, 2
        max_len = pages_for(n + steps, ps) * ps
        bias = Tensor(jnp.asarray(
            rng.standard_normal((1, 4, 1, max_len)) * 0.1, jnp.float32))
        out = _static_vs_paged(rng, heads=4, kv_heads=4, hd=8,
                               prompt_len=[n], decode_steps=steps,
                               page_size=ps, bias=bias)
        # bias shape (1, h, 1, L) only broadcasts over single-token steps
        for static_rows, paged_rows in out[1:]:
            np.testing.assert_allclose(paged_rows[0], static_rows[0],
                                       atol=1e-5)

    def test_pallas_kernel_interpret_matches_reference(self, rng):
        """The Pallas decode kernel (interpret mode, hermetic on CPU) is
        numerically the jnp reference gather."""
        kvh, hd, ps, P, maxp, b, heads = 2, 32, 8, 10, 3, 4, 4
        kp = jnp.asarray(rng.standard_normal((kvh, P, ps, hd)), jnp.float32)
        vp = jnp.asarray(rng.standard_normal((kvh, P, ps, hd)), jnp.float32)
        pt = jnp.asarray(rng.integers(1, P, (b, maxp)), jnp.int32)
        pos = jnp.asarray([3, 7, 14, 21], jnp.int32)
        q = Tensor(jnp.asarray(rng.standard_normal((b, 1, heads, hd)),
                               jnp.float32))
        cache = PagedLayerCache(kp, vp, pt)
        ref = satt._paged_decode_reference(q, cache, pos, heads // kvh)
        out = satt._paged_decode_pallas(q._data, kp, vp, pt, pos,
                                        interpret=True)
        np.testing.assert_allclose(np.asarray(out), ref.numpy(), atol=1e-5)

    @staticmethod
    def _decode_case(rng, *, rep, hd, ps, dtype, max_pages, pos, kvh=2,
                     int8=False):
        """Pools, a page table with repeated and out-of-order pages (12
        real pages for a longer table), q and the jnp reference's output
        for a decode step at positions `pos`."""
        heads, P, b = kvh * rep, 13, len(pos)
        shape = (kvh, P, ps, hd)
        scales = {}
        if int8:
            kp, vp = (jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                      for _ in range(2))
            scales = {n: jnp.asarray(rng.uniform(0.005, 0.02, shape[:3]
                                                 + (1,)), jnp.float32)
                      for n in ("k_scale", "v_scale")}
        else:
            kp, vp = (jnp.asarray(rng.standard_normal(shape), dtype)
                      for _ in range(2))
        pt = jnp.asarray(rng.integers(1, P, (b, max_pages)), jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        q = jnp.asarray(rng.standard_normal((b, 1, heads, hd)), dtype)
        cache = PagedLayerCache(kp, vp, pt, **scales)
        ref = satt._paged_decode_reference(Tensor(q), cache, pos, rep)
        return q, cache, pos, np.asarray(ref._data.astype(jnp.float32))

    @staticmethod
    def _decode_kernel(q, cache, pos):
        out = satt._paged_decode_pallas(
            q, cache.k_pool, cache.v_pool, cache.page_table, pos,
            k_scale=cache.k_scale, v_scale=cache.v_scale, interpret=True)
        return np.asarray(out.astype(jnp.float32))

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("ps", [8, 16])
    @pytest.mark.parametrize("hd", [32, 64, 128])
    @pytest.mark.parametrize("rep", [1, 4])
    def test_decode_kernel_walks_blocks_like_the_reference(self, rng, rep,
                                                           hd, ps, dtype):
        """The block-walking decode kernel against the jnp gather: a
        table of 20 pages that no block of 128 tokens divides, rows at
        position 0 (one page), one under and on a page edge, one under
        and on a block edge, a page past it, and in the table's last slot
        (the full table), pages repeated and out of order."""
        max_pages, bk = 20, 128
        pos = [0, ps - 1, ps, bk - 1, bk, bk + ps, max_pages * ps - 1]
        assert satt._decode_tiling(2, ps, 128, max_pages, 4, False)[1] \
            * ps == bk and (max_pages * ps) % bk
        q, cache, pos, ref = self._decode_case(
            rng, rep=rep, hd=hd, ps=ps, dtype=jnp.dtype(dtype),
            max_pages=max_pages, pos=pos)
        np.testing.assert_allclose(
            self._decode_kernel(q, cache, pos), ref,
            atol=1e-5 if dtype == "float32" else 2e-2)

    @pytest.mark.parametrize("rep", [1, 4])
    def test_decode_kernel_dequantizes_int8_pages(self, rng, rep):
        """int8 pages of 32 tokens with their fp32 scale slabs."""
        ps, max_pages = 32, 6
        pos = [0, ps - 1, ps, 4 * ps - 1, 4 * ps, max_pages * ps - 1]
        q, cache, pos, ref = self._decode_case(
            rng, rep=rep, hd=64, ps=ps, dtype=jnp.float32,
            max_pages=max_pages, pos=pos, int8=True)
        np.testing.assert_allclose(self._decode_kernel(q, cache, pos), ref,
                                   atol=1e-4)

    def test_decode_kernel_parked_rows_and_stale_pages(self, rng):
        """Rows parked at the table's capacity walk nothing and yield
        finite values; whatever sits behind a live row's position (NaN
        in the null page, where parked rows write, and in its last
        page's stale slots) stays out of its output."""
        ps, max_pages = 16, 12
        park = max_pages * ps
        pos = np.asarray([park, 5, park, 130, ps * 3 - 1, park])
        q, cache, pos_d, ref = self._decode_case(
            rng, rep=1, hd=32, ps=ps, dtype=jnp.float32,
            max_pages=max_pages, pos=pos)
        pt = np.asarray(cache.page_table).copy()
        kp, vp = (np.asarray(x).copy() for x in (cache.k_pool, cache.v_pool))
        for row in np.flatnonzero(pos < park):
            # the row's own pages, in order, then null pages; NaN in
            # every slot past its position
            n = pos[row] // ps + 1
            pt[row, :n] = 1 + (np.arange(n) + 3 * row) % 12
            pt[row, n:] = NULL_PAGE
        for pool in (kp, vp):
            pool[:, NULL_PAGE] = np.nan
        dirty = {"k": kp.copy(), "v": vp.copy()}
        for row in np.flatnonzero(pos < park):
            last = pt[row, pos[row] // ps]
            if (pt[pos < park] == last).sum() == 1:     # nobody else's
                for pool in dirty.values():
                    pool[:, last, pos[row] % ps + 1:] = np.nan
        clean = PagedLayerCache(jnp.asarray(np.nan_to_num(kp)),
                                jnp.asarray(np.nan_to_num(vp)),
                                jnp.asarray(pt))
        ref = satt._paged_decode_reference(Tensor(q), clean, pos_d, 1)
        out = self._decode_kernel(
            q, PagedLayerCache(jnp.asarray(dirty["k"]),
                               jnp.asarray(dirty["v"]), jnp.asarray(pt)),
            pos_d)
        assert np.isfinite(out).all()
        live = pos < park
        np.testing.assert_allclose(out[live], ref.numpy()[live], atol=1e-5)

    def test_kernel_shape_gates(self):
        assert satt.paged_decode_available(16, 128)
        assert not satt.paged_decode_available(7, 128)   # ragged sublanes
        assert not satt.paged_decode_available(16, 4)    # hd too small

    def test_overflow_positions_write_null_page_not_last_page(self, rng):
        """Null-page convention regression (found by the prefix-cache
        stress test): a suffix prefill's padding positions can exceed
        max_pages * page_size; those writes must land in the reserved
        null page. Clipping the PAGE INDEX instead aliases them onto the
        sequence's real last page and corrupts resident K/V."""
        ps, max_pages, hd = 4, 2, 8
        pool = PagedKVCache(1, 4, ps, 1, hd)
        pages = [pool.allocator.alloc() for _ in range(max_pages)]
        pt = pool.page_table_array([pages], max_pages)
        view = pool.layer_views(pt)[0]

        def rand(*shape):
            return Tensor(jnp.asarray(rng.standard_normal(shape),
                                      jnp.float32))

        # offset 4, block of 8: positions 4..11, but capacity is 8 —
        # positions 8..11 are table overflow (padding rows)
        q, k, v = rand(1, 8, 1, hd), rand(1, 8, 1, hd), rand(1, 8, 1, hd)
        _, new_view = satt.paged_attend(q, k, v, view, jnp.int32(4), 1)
        got = np.asarray(new_view.k_pool[0, pages[1]])   # positions 4..7
        np.testing.assert_array_equal(got, np.asarray(k._data[0, :4, 0]))
        # and the overflow really went to page 0, not nowhere
        assert np.any(np.asarray(new_view.k_pool[0, NULL_PAGE]) != 0)


# ------------------------------------------------- the K/V write in place

def _write_case(name, rng):
    """(page_table, start_pos, b, s, row_ids, page_size) of one caller of
    `_write_pages`, at CPU size; pools have 40 pages, page 0 the null
    page."""
    if name == "decode16":
        # a full decode block's step: 16 rows, one token each, every row
        # at its own position in its own pages
        pt = 1 + np.arange(16 * 2).reshape(16, 2)
        return pt, rng.integers(0, 16, 16), 16, 1, None, 8
    if name in ("prefill64", "prefill64_whole_pages"):
        return 3 + np.arange(8)[None], 0, 1, 64, None, 8
    if name == "prefill_whole_pages_overflow":
        # two rows of 32 tokens over tables of 3 pages of 8: each row's
        # fourth page is past its table
        return np.array([[9, 4, 30], [2, 17, 5]]), 0, 2, 32, None, 8
    if name == "offset_prefill_overflow":
        # offset 20 + 16 tokens over a table of 4 pages of 8: positions
        # 32..35 are past the table and go to the null page
        return np.array([[9, 4, 30, 17]]), jnp.int32(20), 1, 16, None, 8
    if name == "ragged_row_ids":
        # the flat batch: 3 rows' tokens on one sequence axis, decode
        # rows of one token beside a 6-token prefill chunk
        pt = np.array([[5, 6, 7], [11, 3, 2], [20, 21, 22]])
        row_ids = np.array([0, 1, 1, 1, 1, 1, 1, 2, 2])
        pos = np.array([[11, 2, 3, 4, 5, 6, 7, 8, 9]])
        return pt, pos, 1, 9, row_ids, 4
    assert name == "parked_rows"
    # rows 1, 2 and 5 of 6 are parked at the table-overflow position
    pt = 1 + np.arange(6 * 3).reshape(6, 3)
    park = 3 * 8
    return pt, np.array([4, park, park, 23, 0, park]), 6, 1, None, 8


class TestWritePages:
    """`_write_pages` against a plain numpy loop over (entries, slots):
    the same rows at the same (page, slot) of the same pool, every other
    byte as it was."""

    @pytest.mark.parametrize("kvh", [1, 4])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
    @pytest.mark.parametrize("case", [
        "decode16", "prefill64", "prefill64_whole_pages",
        "prefill_whole_pages_overflow", "offset_prefill_overflow",
        "ragged_row_ids", "parked_rows"])
    def test_matches_a_numpy_loop_bit_for_bit(self, rng, case, dtype, kvh):
        pt, start, b, s, row_ids, ps = _write_case(case, rng)
        P, hd, n = 40, 8, b * s
        pos = satt._positions(jnp.asarray(start, jnp.int32), b, s)
        entries, slots = satt._write_targets(
            jnp.asarray(pt, jnp.int32), pos, ps,
            None if row_ids is None else jnp.asarray(row_ids, jnp.int32))
        entries, slots = entries.reshape(-1), slots.reshape(-1)
        # a prefill from position 0 over whole pages hands pages, not
        # rows: (n / ps, ps, kvh, width) at the page of each first token
        whole = "whole_pages" in case

        def draw(shape, dt):
            if dt == "int8":
                return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
            return jnp.asarray(rng.standard_normal(shape), dt)

        # an int8 pool is a data slab and an fp32 (kvh, P, ps, 1) scale
        # slab, written by the same function at the same targets
        slabs = [(dtype, hd)] + ([("float32", 1)] if dtype == "int8" else [])
        for dt, width in slabs:
            pool = draw((kvh, P, ps, width), dt)
            vals = draw((n, kvh, width), dt)
            if whole:
                got = satt._write_pages(
                    pool, vals.reshape(n // ps, ps, kvh, width),
                    entries[::ps])
            else:
                got = satt._write_pages(pool, vals, entries, slots)
            got = np.asarray(got.astype(jnp.float32))
            assert got.shape == (kvh, P, ps, width)
            want = np.asarray(pool.astype(jnp.float32)).copy()
            rows = np.asarray(vals.astype(jnp.float32))
            e, sl = np.asarray(entries), np.asarray(slots)
            for t in range(n):
                want[:, e[t], sl[t]] = rows[t]
            # real pages: bit for bit, written and untouched alike
            np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
            # the null page takes the collisions: a slot holds one of the
            # rows sent there, or what it held
            for slot in range(ps):
                sent = [rows[t] for t in range(n)
                        if e[t] == NULL_PAGE and sl[t] == slot]
                sent = sent or [want[:, NULL_PAGE, slot]]
                assert any(np.array_equal(got[:, NULL_PAGE, slot], r)
                           for r in sent)
        if case in ("offset_prefill_overflow", "parked_rows",
                    "prefill_whole_pages_overflow"):
            assert np.any(np.asarray(entries) == NULL_PAGE)
        else:
            assert not np.any(np.asarray(entries) == NULL_PAGE)

    @pytest.mark.parametrize("s,whole", [(16, True), (10, False)])
    def test_paged_attend_chooses_the_form_from_its_shapes(
            self, rng, monkeypatch, s, whole):
        """From position 0 over whole pages `paged_attend` writes pages,
        otherwise rows; either way the pools are the numpy loop's."""
        kvh, P, ps, hd, b = 2, 12, 4, 8, 2
        pt = np.array([[3, 7, 1, 9], [2, 8, 5, 11]])
        pools = [jnp.asarray(rng.standard_normal((kvh, P, ps, hd)),
                             jnp.float32) for _ in range(2)]
        view = PagedLayerCache(*pools, jnp.asarray(pt, jnp.int32))
        q, k, v = (Tensor(jnp.asarray(
            rng.standard_normal((b, s, kvh, hd)), jnp.float32))
            for _ in range(3))
        forms, real = [], satt._write_pages

        def spy(pool, vals, entries, slots=None):
            forms.append(slots is None)
            return real(pool, vals, entries, slots)

        monkeypatch.setattr(satt, "_write_pages", spy)
        _, new_view = satt.paged_attend(q, k, v, view, 0, 1)
        assert forms == [whole, whole]
        for pool, vals, got in ((pools[0], k, new_view.k_pool),
                                (pools[1], v, new_view.v_pool)):
            want = np.asarray(pool).copy()
            for i in range(b):
                for j in range(s):
                    want[:, pt[i, j // ps], j % ps] = np.asarray(
                        vals._data[i, j])
            np.testing.assert_array_equal(np.asarray(got), want)


# ------------------------------------------- the exact prefill says causal

class TestExactPrefillIsCausal:
    # (heads, kv heads, head width) of GPTConfig.tiny() and of
    # HybridSsmConfig.tiny()'s attention layers; 1,040 tokens are three
    # 512-blocks a side with a ragged last one
    SHAPES = {"gpt": (4, 4, 32), "hybrid": (4, 2, 32)}

    def _case(self, rng, family, s=1040, ps=8):
        heads, kvh, hd = self.SHAPES[family]
        pool = PagedKVCache(1, s // ps + 2, ps, kvh, hd)
        pages = [pool.allocator.alloc() for _ in range(s // ps)]
        view = pool.layer_views(pool.page_table_array([pages], s // ps))[0]
        q, k, v = (Tensor(jnp.asarray(rng.standard_normal((1, s, n, hd)),
                                      jnp.float32))
                   for n in (heads, kvh, kvh))
        return q, k, v, view, heads // kvh

    @staticmethod
    def _dense_reference(q, k, v, rep, bias=None):
        qd, kd, vd = (np.asarray(x._data, np.float64) for x in (q, k, v))
        kd, vd = np.repeat(kd, rep, axis=2), np.repeat(vd, rep, axis=2)
        s = qd.shape[1]
        scores = np.einsum("bqhd,bkhd->bhqk", qd, kd) / np.sqrt(qd.shape[-1])
        if bias is not None:
            scores = scores + np.asarray(bias, np.float64)[..., :s]
        scores = np.where(np.tril(np.ones((s, s), bool)), scores, -np.inf)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        return np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), vd)

    @pytest.mark.parametrize("family", ["gpt", "hybrid"])
    def test_no_bias_takes_the_flag_and_matches_the_dense_mask(
            self, rng, flash_interpreted, attention_dispatches,
            family):
        q, k, v, view, rep = self._case(rng, family)
        ctx, _ = satt.paged_attend(q, k, v, view, 0, rep)
        assert flash_interpreted == [(True, False)]     # causal, no mask
        assert attention_dispatches() == {"prefill": 1}
        np.testing.assert_allclose(
            ctx.numpy(), self._dense_reference(q, k, v, rep), atol=1e-5)

    @pytest.mark.parametrize("family", ["gpt", "hybrid"])
    def test_a_bias_still_takes_the_mask(self, rng, flash_interpreted,
                                         attention_dispatches, family):
        q, k, v, view, rep = self._case(rng, family)
        s, heads = q.shape[1], q.shape[2]
        # the bias builder's key axis is its own max_len: cropped to s
        bias = jnp.asarray(rng.standard_normal((1, heads, s, s + 24)),
                           jnp.float32)
        ctx, _ = satt.paged_attend(q, k, v, view, 0, rep, bias=bias)
        assert flash_interpreted == [(False, True)]     # the dense mask
        assert attention_dispatches() == {"prefill_masked": 1}
        np.testing.assert_allclose(
            ctx.numpy(), self._dense_reference(q, k, v, rep, bias),
            atol=1e-5)

    def test_the_engine_s_prefill_counts_a_layer_a_causal_call(
            self, flash_interpreted, attention_dispatches):
        """Tiny GPT through the engine with flash interpreted: every
        attention layer of the one prefill executable takes the flag, and
        the tokens are the sequential `generate`'s."""
        model = _gpt()
        model.__dict__.pop("_serving_jit_cache", None)   # trace afresh
        prompt = np.random.RandomState(3).randint(
            0, GPTConfig.tiny().vocab_size, (11,))
        ref = _sequential_reference(model, [prompt], max_new_tokens=4)[0]
        eng = ServingEngine(model, page_size=8, max_batch_size=2,
                            max_seq_len=32, prefill_buckets=(16, 32))
        rid = eng.add_request(prompt, max_new_tokens=4, temperature=0.0)
        out = eng.run()[rid]
        model.__dict__.pop("_serving_jit_cache", None)
        layers = GPTConfig.tiny().num_hidden_layers
        counts = attention_dispatches()
        assert counts["prefill"] == layers and not counts["prefill_masked"]
        assert flash_interpreted.count((True, False)) == layers
        assert out == ref


# -------------------------------------------------- continuous batching

class TestContinuousBatching:
    def test_staggered_arrivals_match_sequential_generate(self):
        """THE acceptance gate: 4 concurrently-scheduled requests with
        mixed prompt lengths and staggered arrivals produce tokens
        identical to per-request sequential `generate`, and the engine
        compiles a bounded executable set (asserted, not eyeballed)."""
        model = _llama()
        rng = np.random.RandomState(0)
        vocab = LlamaConfig.tiny().vocab_size
        prompts = [rng.randint(0, vocab, (n,)) for n in (5, 11, 3, 8)]
        refs = _sequential_reference(model, prompts, max_new_tokens=6)

        eng = ServingEngine(model, page_size=8, max_batch_size=4,
                            max_seq_len=32, prefill_buckets=(16, 32))
        # staggered arrivals: two up front, the rest mid-flight
        rids = [eng.add_request(p, max_new_tokens=6, temperature=0.0)
                for p in prompts[:2]]
        for _ in range(3):
            eng.step()
        rids.append(eng.add_request(prompts[2], max_new_tokens=6,
                                    temperature=0.0))
        eng.step()
        rids.append(eng.add_request(prompts[3], max_new_tokens=6,
                                    temperature=0.0))
        outs = eng.run()

        for rid, ref in zip(rids, refs):
            assert outs[rid] == ref, f"request {rid} diverged"

        # bounded compilation: every prompt fits the 16-bucket -> ONE
        # prefill executable, ONE decode executable; the sampler is fused
        # into both and builds none of its own
        counts = eng.compile_counts()
        assert counts["prefill"] == 1, counts
        assert counts["decode"] == 1, counts
        assert counts["total"] <= 4, counts

        # metrics populated for every request
        stats = eng.stats()
        assert stats["num_finished"] == 4
        assert stats["tokens_generated"] == 24
        for rid in rids:
            per = stats["requests"][rid]
            assert per["ttft_s"] is not None and per["ttft_s"] >= 0
            assert per["latency_s"] is not None
            assert per["tokens"] == 6

    def test_request_validation(self):
        model = _llama()
        eng = ServingEngine(model, page_size=8, max_batch_size=2,
                            max_seq_len=32, prefill_buckets=(16, 32))
        with pytest.raises(ValueError, match="empty"):
            eng.add_request([])
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.add_request([1] * 30, max_new_tokens=10)


# ------------------------------------------- backpressure and preemption

class TestBackpressure:
    def test_admission_deferred_until_pages_free(self):
        """Pool holds ~one request: the second arrival must WAIT (not
        fail), then complete with identical tokens once pages free up."""
        model = _llama()
        rng = np.random.RandomState(1)
        vocab = LlamaConfig.tiny().vocab_size
        prompts = [rng.randint(0, vocab, (n,)) for n in (9, 7)]
        refs = _sequential_reference(model, prompts, max_new_tokens=5)

        # 3 usable pages x page_size 8 = 24 slots; request 0 needs
        # ceil((9+5)/8)=2 pages resident -> request 1 (2 pages) cannot
        # coexist with it plus slack, forcing deferred admission
        eng = ServingEngine(model, page_size=8, max_batch_size=2,
                            max_seq_len=32, prefill_buckets=(16, 32),
                            num_pages=4)
        rids = [eng.add_request(p, max_new_tokens=5, temperature=0.0)
                for p in prompts]
        saw_waiting_while_running = False
        while eng.scheduler.has_work():
            eng.step()
            r0, r1 = (eng.requests[r] for r in rids)
            if r0.status == "running" and r1.status == "waiting":
                saw_waiting_while_running = True
        outs = {r: eng.output(r) for r in rids}
        assert saw_waiting_while_running
        for rid, ref in zip(rids, refs):
            assert outs[rid] == ref
        # pool fully reclaimed: no leaked or double-freed pages
        assert eng.cache.allocator.num_used == 0
        assert eng.cache.allocator.num_free == eng.cache.num_pages - 1

    def test_single_request_larger_than_pool_raises(self):
        model = _llama()
        eng = ServingEngine(model, page_size=8, max_batch_size=2,
                            max_seq_len=32, prefill_buckets=(16, 32),
                            num_pages=2)      # 1 usable page = 8 slots
        eng.add_request([1] * 12, max_new_tokens=4, temperature=0.0)
        with pytest.raises(RuntimeError, match="pages"):
            eng.run()

    def test_scheduler_defers_admission_while_pool_busy(self):
        alloc = BlockAllocator(6)                        # 5 usable pages
        sched = Scheduler(alloc, page_size=4, max_batch_size=2,
                          max_pages_per_seq=8)
        first = Request(prompt=[1] * 12, max_new_tokens=4,
                        sampling=SamplingParams())       # admission: 4
        second = Request(prompt=[2] * 9, max_new_tokens=2,
                         sampling=SamplingParams())      # admission: 3
        sched.add(first)
        sched.add(second)
        d = sched.schedule()
        assert d.kind == "prefill" and d.prefill is first
        free_before = alloc.num_free                     # 1 left
        d2 = sched.schedule()                            # cannot admit
        assert d2.kind == "decode" and second.status == "waiting"
        assert alloc.num_free == free_before             # nothing leaked
        sched.finish(first)
        d3 = sched.schedule()
        assert d3.kind == "prefill" and d3.prefill is second


# ----------------------------------------------------- sampling knobs

class TestServingSampling:
    def test_mixed_sampling_params_do_not_recompile(self):
        """temperature/top-k/top-p ride as traced arrays: a batch mixing
        greedy and sampled requests adds NO sampler executables."""
        model = _llama()
        eng = ServingEngine(model, page_size=8, max_batch_size=4,
                            max_seq_len=32, prefill_buckets=(16, 32))
        eng.add_request([1, 2, 3], max_new_tokens=4, temperature=0.0)
        eng.add_request([4, 5], max_new_tokens=4, temperature=0.9,
                        top_k=5, seed=11)
        eng.add_request([6], max_new_tokens=4, temperature=0.7,
                        top_p=0.8, seed=12)
        eng.run()
        greedy = ServingEngine(model, page_size=8, max_batch_size=4,
                               max_seq_len=32, prefill_buckets=(16, 32))
        for prompt in ([1, 2, 3], [4, 5], [6]):
            greedy.add_request(prompt, max_new_tokens=4, temperature=0.0)
        greedy.run()
        assert eng.compile_counts() == greedy.compile_counts()

    def test_keys_are_host_rows_and_a_seeded_stream_ignores_its_batch(self):
        """A row's key comes back with its tokens (a prefill's, a
        drained block's) and is kept as host memory, so taking a batch's
        keys apart and stacking a fresh block's costs no device
        operation a row; the chain itself is untouched: a seeded
        request samples the same tokens alone and in a batch whose
        composition changes under it (every change is a drain, a
        restack on the host and a fresh block)."""
        def engine():
            return ServingEngine(_llama(), page_size=8, max_batch_size=4,
                                 max_seq_len=32, prefill_buckets=(16, 32),
                                 decode_horizon=4)

        kw = dict(max_new_tokens=17, temperature=0.8, top_k=6, seed=21)
        alone = engine()
        rid = alone.add_request([3, 1, 4, 1, 5], **kw)
        assert not isinstance(alone._key_state[rid], np.ndarray)
        alone.step()                                    # the prefill
        assert isinstance(alone._key_state[rid], np.ndarray)
        alone.run()
        want = alone.output(rid)
        assert len(want) == 5 + 17      # prompt and sampled tokens

        eng = engine()
        rid = eng.add_request([3, 1, 4, 1, 5], **kw)
        eng.add_request([9, 2], max_new_tokens=3, temperature=0.9, seed=4)
        for _ in range(3):
            eng.step()
        eng.add_request([7, 7, 7], max_new_tokens=6, temperature=0.0)
        for _ in range(4):
            eng.step()
        eng.add_request([5], max_new_tokens=2, temperature=0.5, seed=8)
        eng.run()
        assert eng.output(rid) == want
        keys = list(eng._key_state.values())
        assert keys and all(isinstance(k, np.ndarray) and k.shape == (2,)
                            and k.dtype == np.uint32 for k in keys)


def _sample_batch_before(logits, keys, temps, top_ks, top_ps):
    """`engine._sample_batch` as it stood before a batch of greedy rows
    took the argmax alone (PR 31's body, kept here as the reference):
    every row is sorted twice whatever its temperature."""
    vocab = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1)
    t_safe = jnp.where(temps > 0.0, temps, 1.0)
    scaled = logits / t_safe[:, None]
    k_eff = jnp.where(top_ks > 0, jnp.minimum(top_ks, vocab), vocab)
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(sorted_desc, (k_eff - 1)[:, None], axis=-1)
    masked = jnp.where(scaled < kth, -jnp.inf, scaled)
    sorted_m = jnp.sort(masked, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_m, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.minimum(
        jnp.sum(cum < top_ps[:, None], axis=-1, keepdims=True), vocab - 1)
    cutoff = jnp.take_along_axis(sorted_m, cutoff_idx, axis=-1)
    masked = jnp.where(masked < cutoff, -jnp.inf, masked)
    sampled = jax.vmap(jax.random.categorical)(keys, masked)
    return jnp.where(temps == 0.0, greedy, sampled)


_ROWS, _VOCAB_S, _STEPS = 6, 97, 3

# (temperatures, top_k, top_p) of the six rows; a scalar fills every row
_SAMPLER_CASES = {
    "all_greedy": (0.0, 0, 1.0),
    # rows 4 and 5 are the padding of a power-of-two batch: 0 / 0 / 1.0
    "one_sampler_among_greedy_and_padding":
        ([0.0, 0.0, 0.7, 0.0, 0.0, 0.0], [0, 5, 40, 0, 0, 0],
         [1.0, 0.9, 0.8, 1.0, 1.0, 1.0]),
    "sampling_k0_p1": (0.9, 0, 1.0),
    "sampling_k0_p09": (0.9, 0, 0.9),
    "sampling_k5_p1": (0.9, 5, 1.0),
    "sampling_k5_p09": (0.9, 5, 0.9),
    "sampling_k_over_vocab_p1": (1.3, 4 * _VOCAB_S, 1.0),
    "sampling_k_over_vocab_p09": (1.3, 4 * _VOCAB_S, 0.9),
    "ties_greedy": (0.0, 0, 1.0),
    "ties_mixed": ([0.0, 0.6, 0.0, 1.0, 0.0, 0.0], 3, 0.95),
}


class TestSampleBatch:
    """A batch whose rows are all greedy takes the argmax alone, under a
    `lax.cond` on a predicate the sampler reads from its own
    temperatures; a batch in which any row samples runs the arithmetic
    it always ran. Every case is held to the old body bit for bit."""

    @staticmethod
    def _inputs(case, dtype):
        rng = np.random.default_rng(len(case))
        logits = rng.normal(size=(_STEPS, _ROWS, _VOCAB_S)) * 3.0
        if case.startswith("ties"):
            # a handful of distinct values under a ceiling: the maximum
            # repeats in every row, and the k-th and the nucleus cut fall
            # inside ties
            logits = np.minimum(np.round(logits), 3.0)
        temps, top_ks, top_ps = (
            np.broadcast_to(np.asarray(v, t), (_ROWS,))
            for v, t in zip(_SAMPLER_CASES[case],
                            (np.float32, np.int32, np.float32)))
        key_data = rng.integers(0, 2 ** 32, size=(_ROWS, 2),
                                dtype=np.uint32)
        return (jnp.asarray(logits, dtype), jnp.asarray(key_data),
                jnp.asarray(temps), jnp.asarray(top_ks),
                jnp.asarray(top_ps))

    @pytest.mark.parametrize("how", ["jit", "scan"])
    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    @pytest.mark.parametrize("case", sorted(_SAMPLER_CASES))
    def test_matches_the_old_body_bit_for_bit(self, case, dtype, how):
        from paddle_tpu.serving.engine import _sample_batch, _split_rows

        logits, key_data, *knobs = self._inputs(case, dtype)

        def one_step(sampler):
            _, keys = _split_rows(key_data)
            return sampler(logits[0], keys, *knobs)

        def scanned(sampler):
            # the decode block's shape: the key chain is the carry, the
            # knobs are closed over
            def body(kd, step_logits):
                kd, keys = _split_rows(kd)
                return kd, sampler(step_logits, keys, *knobs)
            return jax.lax.scan(body, key_data, logits)

        run = one_step if how == "jit" else scanned
        got = jax.jit(lambda: run(_sample_batch))()
        want = jax.jit(lambda: run(_sample_batch_before))()
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        if case.startswith("ties") and how == "jit":
            # a greedy row's token is the FIRST index of its maximum
            row = np.asarray(logits[0].astype(jnp.float32))[0]
            assert int(np.asarray(got)[0]) == int(
                np.flatnonzero(row == row.max())[0])
            assert (row == row.max()).sum() > 1

    @pytest.mark.parametrize("traffic", ["all_greedy", "mixed"])
    def test_engine_streams_and_executables_are_the_old_sampler_s(
            self, traffic, monkeypatch):
        """The same requests with the same seeds through two engines, one
        tracing today's sampler and one the old body: the same token
        streams, and `compile_counts()` is the same dictionary (no
        executable added for the greedy branch)."""
        import paddle_tpu.serving.engine as eng_mod

        def run():
            eng = ServingEngine(_llama(), page_size=8, max_batch_size=4,
                                max_seq_len=32, prefill_buckets=(16, 32),
                                decode_horizon=4)
            sampled = dict(temperature=0.8, top_k=7, top_p=0.9, seed=42)
            rids = [
                eng.add_request([3, 1, 4, 1, 5], max_new_tokens=9,
                                temperature=0.0),
                eng.add_request([2, 7, 1], max_new_tokens=6,
                                **(sampled if traffic == "mixed"
                                   else dict(temperature=0.0))),
                eng.add_request([8, 2, 8, 1, 8, 2], max_new_tokens=11,
                                temperature=0.0)]
            outs = eng.run()
            return [outs[r] for r in rids], eng.compile_counts()

        streams, counts = run()
        monkeypatch.setattr(eng_mod, "_sample_batch", _sample_batch_before)
        streams_before, counts_before = run()
        assert streams == streams_before
        assert counts == counts_before
        assert counts["decode"] >= 1


# ------------------------------------------------------- decode horizon

class TestDecodeHorizon:
    """Multi-token decode horizon: fused decode+sample blocks must be
    token-identical to horizon-1 and to sequential `generate`, reserve
    their pages up front, and cut host syncs to ~1/horizon."""

    def _staggered_run(self, model, prompts, h, max_new=6):
        eng = ServingEngine(model, page_size=8, max_batch_size=4,
                            max_seq_len=32, prefill_buckets=(16, 32),
                            decode_horizon=h)
        rids = [eng.add_request(p, max_new_tokens=max_new,
                                temperature=0.0) for p in prompts[:2]]
        for _ in range(3):
            eng.step()
        for p in prompts[2:]:
            rids.append(eng.add_request(p, max_new_tokens=max_new,
                                        temperature=0.0))
            eng.step()
        outs = eng.run()
        return eng, [outs[r] for r in rids]

    def test_horizon_matrix_token_parity(self):
        """THE acceptance gate: horizons 1/4/8 under staggered arrivals
        all emit exactly the sequential-generate tokens (and therefore
        match each other), with pow2-bucketed decode executables and no
        standalone sampler dispatch."""
        model = _llama()
        rng = np.random.RandomState(31)
        vocab = LlamaConfig.tiny().vocab_size
        prompts = [rng.randint(0, vocab, (n,)) for n in (5, 11, 3, 8)]
        refs = _sequential_reference(model, prompts, max_new_tokens=6)
        outs_by_h = {}
        for h in (1, 4, 8):
            eng, outs = self._staggered_run(model, prompts, h)
            assert outs == refs, f"horizon {h} diverged from generate"
            outs_by_h[h] = outs
            counts = eng.compile_counts()
            # decode rows are padded to pow2 widths (1/2/4 at
            # max_batch 4), so staggered batch sizes share at most
            # log2(max_batch)+1 executables instead of one per size
            assert 1 <= counts["decode"] <= 3, counts
            assert counts["total"] == counts["prefill"] + counts["decode"], \
                counts                              # sampling is fused
            assert eng.cache.allocator.num_used == 0
        assert outs_by_h[1] == outs_by_h[4] == outs_by_h[8]

    def test_eos_mid_block_trims_and_frees(self):
        """EOS landing mid-horizon: the device mask pads the rest of the
        block, the host trims at the EOS token, and the result matches
        both sequential generate and a horizon-1 engine."""
        model = _llama()
        prompt = [7, 8, 9]
        ref = _sequential_reference(model, [prompt], 8)[0]
        gen = ref[len(prompt):]
        eos = gen[2]                     # third generated token
        assert eos not in gen[:2]        # really lands MID-block
        expect = list(prompt) + gen[:3]

        def run(h):
            eng = ServingEngine(model, page_size=8, max_batch_size=4,
                                max_seq_len=32, prefill_buckets=(16, 32),
                                decode_horizon=h)
            rid = eng.add_request(prompt, max_new_tokens=8,
                                  temperature=0.0, eos_token_id=eos)
            outs = eng.run()
            assert eng.cache.allocator.num_used == 0
            return outs[rid]

        assert run(8) == expect
        assert run(1) == expect

    def test_host_syncs_drop_with_horizon(self):
        """stats() observability: host_syncs ~ prefills + ceil(tokens/
        horizon) blocks, so tokens_per_sync grows with the horizon."""
        model = _llama()
        prompt = [3, 1, 4, 1, 5]

        def run(h):
            eng = ServingEngine(model, page_size=8, max_batch_size=2,
                                max_seq_len=64, prefill_buckets=(16, 64),
                                decode_horizon=h)
            eng.add_request(prompt, max_new_tokens=24, temperature=0.0)
            eng.run()
            return eng.stats()

        s1, s8 = run(1), run(8)
        assert s1["tokens_generated"] == s8["tokens_generated"] == 24
        # horizon 1: one sync per token (+1 prefill, ±pipeline edges)
        assert s1["host_syncs"] >= 24
        # horizon 8: 23 decode tokens in ceil(23/8)=3 blocks (+1 tail
        # flush block at the pipeline edge) + 1 prefill sync
        assert s8["host_syncs"] <= 6
        assert s8["tokens_per_sync"] > 3.0 > s1["tokens_per_sync"]
        assert s8["decode_horizon"] == 8

    def test_admission_reserves_first_block(self):
        """Scheduler accounting: admission covers the whole first decode
        block, so _ensure_decode_pages allocates NOTHING before it (the
        horizon generalization of TestAdmissionPageAccounting)."""
        for h, prompt_len, max_new in [(4, 7, 12), (4, 8, 12), (8, 9, 3),
                                       (8, 16, 20), (1, 7, 4)]:
            sched = Scheduler(BlockAllocator(64), page_size=8,
                              max_batch_size=2, max_pages_per_seq=8,
                              decode_horizon=h)
            req = Request(prompt=[1] * prompt_len, max_new_tokens=max_new,
                          sampling=SamplingParams())
            sched.add(req)
            assert sched.schedule().kind == "prefill"
            assert len(req.pages) == pages_for(
                prompt_len + max(1, min(h, max_new - 1)), 8)
            req.generated.append(0)      # the token prefill emitted
            free_before = sched.allocator.num_free
            sched._ensure_decode_pages()
            assert sched.allocator.num_free == free_before, \
                f"h={h}: admission under-charged the first block"

    def test_block_demand_caps_at_request_lifetime(self):
        """_block_pages never asks for pages past prompt+max_new-1 (the
        block's own last token never gets K/V written), so a short
        request near its budget stops growing its table."""
        sched = Scheduler(BlockAllocator(64), page_size=8,
                          max_batch_size=1, max_pages_per_seq=8,
                          decode_horizon=8)
        req = Request(prompt=[1] * 9, max_new_tokens=4,
                      sampling=SamplingParams())
        req.status = "running"
        req.generated = [5]
        assert sched._block_pages(req) == pages_for(9 + 4 - 1, 8)
        req.generated = [5, 6, 7]        # one token of budget left
        assert sched._block_pages(req) == pages_for(9 + 4 - 1, 8)

    def test_one_executable_per_horizon_across_waves(self):
        """Compile-count guard: serving two separate request waves (and
        re-chaining fresh pipelines each time) still uses ONE fused
        decode executable for the engine's (batch-shape, horizon)."""
        model = _llama()
        eng = ServingEngine(model, page_size=8, max_batch_size=4,
                            max_seq_len=32, prefill_buckets=(16, 32),
                            decode_horizon=4)
        rng = np.random.RandomState(37)
        vocab = LlamaConfig.tiny().vocab_size
        for wave in range(2):
            for n in (4, 9):
                eng.add_request(rng.randint(0, vocab, (n,)),
                                max_new_tokens=5, temperature=0.0)
            eng.run()
        counts = eng.compile_counts()
        assert counts["decode"] == 1, counts

    def test_seeded_sampling_device_keys_match_host_chain(self):
        """The fused sampler's device-side key evolution reproduces the
        pre-horizon host chain: one split per generated token, starting
        from jax.random.key(seed) — asserted via cross-engine
        reproducibility at horizon 1 vs 8 while requests are alive."""
        model = _llama()

        def run(h):
            eng = ServingEngine(model, page_size=8, max_batch_size=2,
                                max_seq_len=32, prefill_buckets=(16, 32),
                                decode_horizon=h)
            rid = eng.add_request([3, 1, 4, 1, 5], max_new_tokens=6,
                                  temperature=0.8, top_k=7, seed=42)
            return eng.run()[rid]

        assert run(1) == run(8) == run(1)


# ---------------------------------------------------- observability wiring

class TestServingObservability:
    """ISSUE 4: stats()/compile_counts() are thin views over ONE metrics
    registry, per-request lifecycle spans land in chrome-trace exports,
    and a metrics-disabled engine does literally no registry work on the
    hot path. Engines here reuse the module model + fast-lane shapes, so
    no new executables compile."""

    def _run_two(self, **kw):
        model = _llama()
        eng = ServingEngine(model, page_size=8, max_batch_size=4,
                            max_seq_len=32, prefill_buckets=(16, 32), **kw)
        rids = [eng.add_request([1, 2, 3], max_new_tokens=4,
                                temperature=0.0),
                eng.add_request([4, 5, 6, 7], max_new_tokens=4,
                                temperature=0.0)]
        eng.run()
        return eng, rids

    def test_stats_is_registry_view_and_backward_compatible(self):
        eng, rids = self._run_two()
        st = eng.stats()
        # every pre-observability key survives the refactor (pin)
        assert set(st) >= {
            "prefill_steps", "decode_steps", "tokens_generated",
            "prefill_time_s", "decode_time_s", "preemptions",
            "host_syncs", "decode_tokens_per_s", "decode_horizon",
            "tokens_per_sync", "num_requests", "num_finished",
            "free_pages", "requests", "latency"}
        assert st["tokens_generated"] == 8 and st["prefill_steps"] == 2
        assert st["num_finished"] == 2 and st["host_syncs"] >= 3
        # the registry IS the source: same counter, same number
        reg = eng.metrics
        assert reg.get("serving_tokens_generated_total").value == 8
        assert reg.get("serving_host_syncs_total").value == \
            st["host_syncs"]
        assert reg.get("serving_queue_depth",
                       {"state": "running"}) is not None
        assert reg.get("serving_kv_free_pages").value >= 0
        # allocator page counters balanced after a full drain
        allocs = reg.get("serving_kv_page_allocs_total").value
        recycles = reg.get("serving_kv_page_recycles_total").value
        assert allocs == recycles > 0

    def test_latency_percentiles_from_histograms(self):
        eng, rids = self._run_two()
        lat = eng.stats()["latency"]
        for section in ("ttft", "inter_token"):
            for key in ("count", "mean", "p50", "p95", "p99"):
                assert key in lat[section], (section, key)
        assert lat["ttft"]["count"] == 2
        assert lat["ttft"]["p50"] > 0.0
        assert lat["ttft"]["p50"] <= lat["ttft"]["p95"] \
            <= lat["ttft"]["p99"]
        # inter-token: every token after each request's first
        assert lat["inter_token"]["count"] == 8 - 2
        # percentile view matches per-request ttft ground truth
        ttfts = [eng.stats()["requests"][r]["ttft_s"] for r in rids]
        assert lat["ttft"]["p99"] <= max(ttfts) * 1.01 + 1e-9

    def test_compile_counts_read_from_registry(self):
        eng, _ = self._run_two()
        counts = eng.compile_counts()
        reg_counts = {
            fam: eng.metrics.get("serving_jit_compile_misses_total",
                                 {"family": fam}).value
            for fam in ("prefill", "prefill_offset", "prefill_chunked",
                        "decode", "ragged", "spec")}
        assert counts["prefill"] == reg_counts["prefill"] == 1
        assert counts["decode"] == reg_counts["decode"] == 1
        assert set(counts) == set(reg_counts) | {"total"}
        assert counts["prefill_chunked"] == \
            reg_counts["prefill_chunked"] == 0     # chunking off
        assert counts["ragged"] == reg_counts["ragged"] == 0
        assert counts["spec"] == reg_counts["spec"] == 0  # spec off
        # dedup sets and registry counters stay in lockstep
        assert {f: len(s) for f, s in eng._exec_shapes.items()} == \
            reg_counts

    def test_exporters_over_a_live_engine_registry(self):
        import json as _json

        from paddle_tpu.observability import (registry_from_snapshot,
                                              to_prometheus)

        eng, _ = self._run_two()
        text = to_prometheus(eng.metrics)
        assert "# TYPE serving_ttft_seconds histogram" in text
        assert "serving_ttft_seconds_count 2" in text
        assert "serving_tokens_generated_total 8" in text
        snap = eng.metrics.snapshot()
        rebuilt = registry_from_snapshot(_json.loads(_json.dumps(snap)))
        assert rebuilt.snapshot() == snap
        assert rebuilt.get("serving_ttft_seconds").percentile(50) > 0

    def test_chrome_trace_contains_request_lifecycle_spans(self,
                                                           tmp_path):
        import json as _json

        from paddle_tpu import profiler as prof_mod

        model = _llama()
        eng = ServingEngine(model, page_size=8, max_batch_size=4,
                            max_seq_len=32, prefill_buckets=(16, 32))
        prof = prof_mod.Profiler(
            timer_only=True,
            on_trace_ready=prof_mod.export_chrome_tracing(str(tmp_path)))
        prof.start()
        rid = eng.add_request([1, 2, 3, 4], max_new_tokens=4,
                              temperature=0.0)
        eng.run()
        prof.stop()
        files = list(tmp_path.glob("*.json"))
        assert files
        with open(files[0]) as f:
            names = {e["name"] for e in _json.load(f)["traceEvents"]}
        for stage in ("enqueued", "admitted", "prefill", "first_token",
                      "decode_block", "finished"):
            assert f"serving.request[{rid}].{stage}" in names, stage
        # batch-level RecordEvent spans share the same timeline
        assert "serving.prefill" in names
        assert "serving.host_drain" in names

    def test_scheduler_lifecycle_ordering_under_preemption(self):
        """Span ordering pin, jit-free: a preempted request's lifecycle
        reads enqueued < admitted < preempted < requeued < admitted
        (re-admission), and the registry preemption counter matches."""
        from paddle_tpu.observability import MetricsRegistry
        from paddle_tpu.serving import ServingObs

        obs = ServingObs(MetricsRegistry())
        alloc = BlockAllocator(6)                    # 5 usable pages
        sched = Scheduler(alloc, page_size=4, max_batch_size=2,
                          max_pages_per_seq=8, obs=obs)
        a = Request(prompt=[1] * 8, max_new_tokens=8,
                    sampling=SamplingParams())       # admission: 3 pages
        b = Request(prompt=[2] * 4, max_new_tokens=8,
                    sampling=SamplingParams())       # admission: 2 pages
        sched.add(a)
        sched.add(b)
        assert sched.schedule().prefill is a
        assert sched.schedule().prefill is b         # pool now full
        a.generated = [0] * 5                        # a needs a 4th page
        b.generated = [0] * 2                        # b fits its 2 pages
        d = sched.schedule()                         # preempts youngest: b
        assert d.kind == "decode" and d.decode == [a]
        assert b.status == "waiting" and b.preemptions == 1
        assert obs.preemptions.value == 1
        assert obs.lifecycle.stages(b.request_id) == [
            "enqueued", "admitted", "preempted", "requeued"]
        sched.finish(a)                              # frees a's pages
        assert sched.schedule().prefill is b         # b re-admitted
        stages = obs.lifecycle.stages(b.request_id)
        assert stages == ["enqueued", "admitted", "preempted",
                          "requeued", "admitted"]
        assert obs.lifecycle.stages(a.request_id)[-1] == "finished"
        # timestamps are monotone in emission order
        times = [t0 for _, t0, _ in obs.lifecycle.events(b.request_id)]
        assert times == sorted(times)

    def test_metrics_disabled_hot_path_does_no_registry_work(
            self, monkeypatch):
        """THE overhead guard: with enable_metrics=False the engine holds
        no registry at all, and a steady-state serving step touches no
        metric object — pinned by making every metric entry point raise
        and running a full request through the warm engine."""
        import paddle_tpu.observability.metrics as obsm

        model = _llama()
        eng = ServingEngine(model, page_size=8, max_batch_size=4,
                            max_seq_len=32, prefill_buckets=(16, 32),
                            enable_metrics=False)
        assert eng.metrics is None and eng._obs is None
        assert eng.scheduler.obs is None
        assert eng.cache.allocator._m_alloc is None
        # warm first: tracing MAY legitimately count trace-time dispatch
        # selections in the global registry
        eng.add_request([9, 8, 7], max_new_tokens=3, temperature=0.0)
        eng.run()

        def boom(*a, **kw):
            raise AssertionError("metrics work on a disabled hot path")

        for cls, meth in [(obsm.MetricsRegistry, "counter"),
                          (obsm.MetricsRegistry, "gauge"),
                          (obsm.MetricsRegistry, "histogram"),
                          (obsm.Counter, "inc"),
                          (obsm.Gauge, "set"), (obsm.Gauge, "inc"),
                          (obsm.Histogram, "observe")]:
            monkeypatch.setattr(cls, meth, boom)
        rid = eng.add_request([1, 2, 3], max_new_tokens=4,
                              temperature=0.0)
        outs = eng.run()
        assert len(outs[rid]) == 7
        # stats() still returns the full (zeroed) shape without touching
        # any metric object
        st = eng.stats()
        assert st["tokens_generated"] == 0
        assert st["latency"]["ttft"]["count"] == 0
        assert st["num_finished"] == 2
        assert eng.compile_counts()["decode"] == 1   # set-based fallback


# ------------------------------------------------ add_request validation

class TestAddRequestRejection:
    def test_rejected_prompt_leaks_nothing(self):
        """Regression: a prompt the engine can never prefill must be
        rejected AT add_request — before pages, scheduler entries, or
        engine registration exist — not mid-_prefill after admission."""
        model = _llama()
        eng = ServingEngine(model, page_size=8, max_batch_size=2,
                            max_seq_len=32, prefill_buckets=(16, 32))
        free_before = eng.cache.allocator.num_free
        n_reqs = len(eng.requests)
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.add_request([1] * 40, max_new_tokens=4)
        # the largest-bucket guard fires even if the bucket/max_seq_len
        # invariant is sidestepped (e.g. a harness mutating the buckets)
        eng.prefill_buckets = (16,)
        with pytest.raises(ValueError, match="largest"):
            eng.add_request([1] * 20, max_new_tokens=4)
        assert eng.cache.allocator.num_free == free_before
        assert len(eng.requests) == n_reqs
        assert not eng.scheduler.waiting
        # and the engine still serves normally afterwards
        eng.prefill_buckets = (16, 32)
        rid = eng.add_request([1, 2, 3], max_new_tokens=2)
        outs = eng.run()
        assert len(outs[rid]) == 5
        assert eng.cache.allocator.num_used == 0

    def test_over_budget_request_not_registered(self):
        """scheduler.add's page-budget rejection happens before the
        engine registers the request (no orphan entries in requests/key
        state)."""
        model = _llama()
        eng = ServingEngine(model, page_size=8, max_batch_size=2,
                            max_seq_len=32, prefill_buckets=(16, 32))
        eng.max_seq_len = 64             # sidestep the length check so
        with pytest.raises(ValueError, match="max_pages_per_seq"):
            eng.add_request([1] * 30, max_new_tokens=30)
        assert not eng.requests and not eng.scheduler.waiting


# ------------------------------------------------------------ slow lane

@pytest.mark.slow
class TestServingSlow:
    """Everything here compiles beyond the fast lane's prefill-bucket +
    decode set (second model family, multi-bucket sweep, extra engine
    pool shapes / sequential-generate reference shapes)."""

    def test_stream_yields_done_flags(self):
        model = _llama()
        eng = ServingEngine(model, page_size=8, max_batch_size=2,
                            max_seq_len=32, prefill_buckets=(16, 32))
        rid = eng.add_request([1, 2, 3], max_new_tokens=4, temperature=0.0)
        events = list(eng.stream())
        assert [e[0] for e in events] == [rid] * 4
        assert [e[2] for e in events] == [False] * 3 + [True]

    def test_eos_finishes_early_and_frees_pages(self):
        model = _llama()
        eng = ServingEngine(model, page_size=8, max_batch_size=2,
                            max_seq_len=32, prefill_buckets=(16, 32))
        # eos == the greedy first token => request finishes at length 1
        ref = _sequential_reference(model, [[7, 8, 9]], 1)[0]
        eos = ref[-1]
        rid = eng.add_request([7, 8, 9], max_new_tokens=8, temperature=0.0,
                              eos_token_id=eos)
        outs = eng.run()
        assert outs[rid] == ref
        assert eng.cache.allocator.num_used == 0

    def test_preemption_requeues_and_stays_token_identical(self):
        """Pool too small for all requests' full lengths: the youngest
        running request is evicted, re-prefilled later, and still emits
        exactly the sequential tokens (recompute, never corruption)."""
        model = _llama()
        rng = np.random.RandomState(3)
        vocab = LlamaConfig.tiny().vocab_size
        prompts = [rng.randint(0, vocab, (n,)) for n in (10, 8, 12)]
        refs = _sequential_reference(model, prompts, max_new_tokens=8)

        # decode_horizon=1: the classic single-token reservation path —
        # at the default horizon this pool defers admission instead of
        # preempting (TestDecodeHorizon covers the in-horizon variant)
        eng = ServingEngine(model, page_size=8, max_batch_size=3,
                            max_seq_len=32, prefill_buckets=(16, 32),
                            num_pages=8, decode_horizon=1)
        rids = [eng.add_request(p, max_new_tokens=8, temperature=0.0)
                for p in prompts]
        outs = eng.run()
        assert eng.stats()["preemptions"] >= 1
        for rid, ref in zip(rids, refs):
            assert outs[rid] == ref
        assert eng.cache.allocator.num_used == 0

    def test_preemption_while_in_horizon_token_identical(self):
        """Preemption with decode blocks IN FLIGHT: the pool admits all
        three requests but cannot hold their full lifetimes, so
        copy-on-extend exhausts it mid-stream while an undrained block
        is pending. The scheduler's drain_hook must land those tokens
        before the victim requeues — output stays token-identical to
        sequential generate (nothing sampled is ever lost)."""
        model = _llama()
        rng = np.random.RandomState(41)
        vocab = LlamaConfig.tiny().vocab_size
        prompts = [rng.randint(0, vocab, (n,)) for n in (10, 8, 12)]
        refs = _sequential_reference(model, prompts, max_new_tokens=12)
        # h=4 < max_new-1: admission reserves only the first block
        # (2 pages each -> all admitted into 7), later blocks extend to
        # 3 pages each (9 > 7) -> someone must be preempted mid-flight
        eng = ServingEngine(model, page_size=8, max_batch_size=3,
                            max_seq_len=32, prefill_buckets=(16, 32),
                            num_pages=8, decode_horizon=4)
        rids = [eng.add_request(p, max_new_tokens=12, temperature=0.0)
                for p in prompts]
        outs = eng.run()
        assert eng.stats()["preemptions"] >= 1
        for rid, ref in zip(rids, refs):
            assert outs[rid] == ref
        assert eng.cache.allocator.num_used == 0

    def test_horizon_matrix_under_preemption_and_eos(self):
        """Heavy corner of the parity matrix: staggered arrivals + a
        small pool (preemption) + EOS mid-block, horizons 1/4/8 all
        token-identical to each other."""
        model = _llama()
        rng = np.random.RandomState(43)
        vocab = LlamaConfig.tiny().vocab_size
        prompts = [rng.randint(0, vocab, (n,)) for n in (9, 7, 11)]
        ref = _sequential_reference(model, [prompts[0]], 12)[0]
        eos = ref[9 + 5]                 # lands mid-block at h=4/8

        def run(h):
            eng = ServingEngine(model, page_size=8, max_batch_size=3,
                                max_seq_len=32, prefill_buckets=(16, 32),
                                num_pages=8, decode_horizon=h)
            rids = [eng.add_request(prompts[0], max_new_tokens=12,
                                    temperature=0.0, eos_token_id=eos)]
            eng.step()
            for p in prompts[1:]:
                rids.append(eng.add_request(p, max_new_tokens=12,
                                            temperature=0.0))
            outs = eng.run()
            assert eng.cache.allocator.num_used == 0
            return [outs[r] for r in rids]

        assert run(1) == run(4) == run(8)

    def test_request_lifecycle_spans_under_engine_preemption(self):
        """End-to-end lifecycle ordering with real preemption: the
        victim's retained spans read enqueued -> admitted -> prefill ->
        first_token -> preempted -> requeued -> admitted -> prefill
        (re-prefill) -> ... -> finished, the TTFT histogram counts each
        request ONCE (preemption never re-observes first tokens), and
        the registry preemption counter agrees with stats()."""
        model = _llama()
        rng = np.random.RandomState(3)
        vocab = LlamaConfig.tiny().vocab_size
        prompts = [rng.randint(0, vocab, (n,)) for n in (10, 8, 12)]
        eng = ServingEngine(model, page_size=8, max_batch_size=3,
                            max_seq_len=32, prefill_buckets=(16, 32),
                            num_pages=8, decode_horizon=1)
        rids = [eng.add_request(p, max_new_tokens=8, temperature=0.0)
                for p in prompts]
        eng.run()
        st = eng.stats()
        assert st["preemptions"] >= 1
        lc = eng._obs.lifecycle
        victims = [r for r in rids if "preempted" in lc.stages(r)]
        assert victims
        for rid in rids:
            stages = lc.stages(rid)
            assert stages[0] == "enqueued" and stages[-1] == "finished"
            assert stages.index("admitted") < stages.index("prefill") \
                < stages.index("first_token")
            times = [t0 for _, t0, _ in lc.events(rid)]
            assert times == sorted(times)
        for rid in victims:
            stages = lc.stages(rid)
            i_pre = stages.index("preempted")
            assert stages.index("first_token") < i_pre
            assert stages[i_pre + 1] == "requeued"
            # re-admission re-prefills: both stages appear again later
            assert "admitted" in stages[i_pre:], stages
            assert stages.count("prefill") >= 2
        assert st["latency"]["ttft"]["count"] == len(rids)
        assert eng.metrics.get("serving_preemptions_total").value == \
            st["preemptions"]

    def test_seeded_requests_reproducible_across_engines(self):
        model = _llama()

        def run_once():
            eng = ServingEngine(model, page_size=8, max_batch_size=2,
                                max_seq_len=32, prefill_buckets=(16, 32))
            rid = eng.add_request([3, 1, 4, 1, 5], max_new_tokens=6,
                                  temperature=0.8, top_k=7, seed=42)
            return eng.run()[rid]

        assert run_once() == run_once()

    def test_gpt_engine_parity(self):
        """GPT rides the same engine: absolute position embeddings take
        the ragged (b,) start_pos path in models/gpt.py."""
        model = _gpt()
        rng = np.random.RandomState(5)
        vocab = GPTConfig.tiny().vocab_size
        prompts = [rng.randint(0, vocab, (n,)) for n in (4, 9, 6, 2)]
        refs = _sequential_reference(model, prompts, max_new_tokens=5)
        eng = ServingEngine(model, page_size=8, max_batch_size=4,
                            max_seq_len=32, prefill_buckets=(16, 32))
        rids = [eng.add_request(p, max_new_tokens=5, temperature=0.0)
                for p in prompts]
        outs = eng.run()
        for rid, ref in zip(rids, refs):
            assert outs[rid] == ref

    def test_multiple_prefill_buckets_stay_bounded(self):
        """Prompts spanning several buckets: prefill executables == the
        number of DISTINCT buckets used, decode still == 1."""
        model = _llama()
        rng = np.random.RandomState(7)
        vocab = LlamaConfig.tiny().vocab_size
        prompts = [rng.randint(0, vocab, (n,)) for n in (3, 14, 20, 6)]
        refs = _sequential_reference(model, prompts, max_new_tokens=4)
        eng = ServingEngine(model, page_size=8, max_batch_size=4,
                            max_seq_len=32, prefill_buckets=(8, 16, 32))
        rids = [eng.add_request(p, max_new_tokens=4, temperature=0.0)
                for p in prompts]
        outs = eng.run()
        for rid, ref in zip(rids, refs):
            assert outs[rid] == ref
        counts = eng.compile_counts()
        assert counts["prefill"] == 3    # buckets 8, 16, 32 all touched
        assert counts["decode"] == 1

    def test_gpt_prefix_caching_parity(self):
        """GPT rides the offset prefill too: wpe positions come from the
        traced scalar start_pos (models/gpt.py's sp.ndim == 0 branch)."""
        model = _gpt()
        rng = np.random.RandomState(13)
        vocab = GPTConfig.tiny().vocab_size
        prompts = _shared_prefix_prompts(rng, vocab, prefix_pages=2,
                                         page_size=8, tails=[3, 7])
        refs = _sequential_reference(model, prompts, max_new_tokens=5)
        eng = ServingEngine(model, page_size=8, max_batch_size=2,
                            max_seq_len=32, prefill_buckets=(16, 32),
                            enable_prefix_caching=True)
        rids = [eng.add_request(p, max_new_tokens=5, temperature=0.0)
                for p in prompts]
        outs = eng.run()
        for rid, ref in zip(rids, refs):
            assert outs[rid] == ref
        assert eng.stats()["prefix_cache"]["hit_tokens"] >= 16

    def test_large_pool_eviction_stress(self):
        """Eviction stress: a stream of requests with rotating shared
        prefixes through a pool too small to cache them all. The LRU
        evictor must recycle cold prefixes (evictions > 0), every request
        must stay token-identical to sequential generate, and the pool
        must drain to zero after the final flush."""
        model = _llama()
        rng = np.random.RandomState(17)
        vocab = LlamaConfig.tiny().vocab_size
        families = [rng.randint(0, vocab, (16,)).tolist()
                    for _ in range(3)]   # 3 distinct 2-page prefixes
        prompts = [fam + rng.randint(0, vocab, (2 + i,)).tolist()
                   for i, fam in enumerate(families * 3)]
        refs = _sequential_reference(model, prompts, max_new_tokens=4)
        # 9 usable pages; three cached 2-page families plus a running
        # request's private pages overflow the pool, forcing the LRU
        # evictor to recycle cold prefixes mid-stream
        eng = ServingEngine(model, page_size=8, max_batch_size=2,
                            max_seq_len=32, prefill_buckets=(16, 32),
                            num_pages=10, enable_prefix_caching=True)
        outs = {}
        for burst in range(3):           # arrival bursts: 3 requests each
            rids = [eng.add_request(p, max_new_tokens=4, temperature=0.0)
                    for p in prompts[burst * 3:(burst + 1) * 3]]
            outs.update(eng.run())
        flat_rids = sorted(outs)
        for rid, ref in zip(flat_rids, refs):
            assert outs[rid] == ref, f"request {rid} diverged"
        st = eng.stats()["prefix_cache"]
        assert st["evictions"] > 0, st
        assert st["hit_tokens"] > 0, st
        eng.prefix_cache.flush()
        assert eng.cache.allocator.num_used == 0
        assert eng.cache.allocator.num_free == eng.cache.num_pages - 1

    def test_compile_events_via_jax_monitoring(self):
        """Secondary compile-count signal straight from jax.monitoring:
        steady-state decode fires ZERO compile events after warmup."""
        model = _llama()
        eng = ServingEngine(model, page_size=8, max_batch_size=2,
                            max_seq_len=64, prefill_buckets=(16, 64))
        eng.add_request([1, 2, 3, 4], max_new_tokens=24, temperature=0.0)
        for _ in range(6):
            eng.step()                   # prefill + warm decode steps
        events = []
        jax.monitoring.register_event_listener(
            lambda name, **kw: events.append(name))
        try:
            eng.run()                    # 18+ more pure decode steps
        finally:
            jax.monitoring.clear_event_listeners()
        compiles = [e for e in events if "compile" in e]
        assert not compiles, compiles
