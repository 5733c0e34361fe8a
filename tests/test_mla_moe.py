"""The latent-attention, routed-expert decoder (models/mla_moe.py) and
what serving it added: the model against the benchmark's plain reference,
the latent pool kind, the `mla_decode` kernel in interpret mode against
the jnp path, the absorbed decode against the expanded forward through
the cache, the dropless expert layer against a per-token loop, the
engine's counters and its refusals."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights
from chipbench.reference import mla_moe as reference
from paddle_tpu.models import MlaMoeConfig, MlaMoeForCausalLM, mla_moe
from paddle_tpu.serving import ServingEngine, attention
from paddle_tpu.serving.kv_cache import (LatentLayerCache, PagedKVCache,
                                         overflow_position, pools_from_views,
                                         views_from_pools)

# 1 dense + 2 expert layers, 16 experts top-4 + 1 shared, 4 heads of
# 32 + 16 / 32, ranks 48 and 32
CFG = MlaMoeConfig.tiny()


@pytest.fixture(scope="module")
def seeded():
    """The tiny model holding the benchmark's seeded float32 leaves."""
    cfg = dataclasses.asdict(CFG)
    leaves = weights.make(reference.shapes(cfg), 29, jnp.float32)
    model = MlaMoeForCausalLM(CFG)
    model.eval()
    params = dict(model.named_parameters())
    assert set(params) == set(leaves)
    for name, p in params.items():
        assert tuple(p.shape) == tuple(leaves[name].shape), name
        p._data = leaves[name]
    return model, leaves, cfg


@pytest.fixture
def kernel_mode(monkeypatch):
    def set_mode(mode):
        monkeypatch.setattr(attention, "KERNEL_MODE", mode)
    return set_mode


def test_model_matches_the_reference_in_float32(seeded):
    model, leaves, cfg = seeded
    ids = np.random.default_rng(0).integers(0, CFG.vocab_size, (2, 45))
    got = np.asarray(model(jnp.asarray(ids))._data)
    for row in range(2):
        want = np.asarray(reference.logits(leaves, ids[row], np.arange(45),
                                           cfg))
        assert np.abs(got[row] - want).max() < 1e-4


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_prefill_then_decode_through_the_latent_pool(seeded, kernel_mode,
                                                     mode):
    """Logits, not tokens: a prefill (the prompt ends mid-page, the
    bucket is padded) and six decode steps over three rows, one of them
    parked from the third step on, against the reference's one forward
    over each whole sequence."""
    model, leaves, cfg = seeded
    kernel_mode(mode)
    ps, max_pages = 8, 8
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, CFG.vocab_size, n).tolist()
               for n in (13, 24, 5)]
    cache = PagedKVCache.for_model(model, 40, ps)
    assert cache.kind == "latent"
    tables = np.zeros((3, max_pages), np.int32)
    tables[0, :4], tables[1, :5], tables[2, :3] = (
        [1, 2, 3, 4], [5, 6, 7, 8, 9], [10, 11, 12])
    seqs = [list(p) for p in prompts]
    for i, prompt in enumerate(prompts):
        ids = np.zeros((1, 32), np.int32)
        ids[0, :len(prompt)] = prompt
        views = cache.layer_views(jnp.asarray(tables[i:i + 1]))
        logits, new, aux = model(jnp.asarray(ids), caches=views, start_pos=0,
                                 logits_at=jnp.int32(len(prompt) - 1))
        cache.update(new)
        want = reference.logits(leaves, prompt, [len(prompt) - 1], cfg)
        assert np.abs(np.asarray(logits._data)[0, 0]
                      - np.asarray(want)[0]).max() < 1e-4
        # padding past the prompt got no expert
        assert int(aux["moe_expert_tokens"].sum()) == (
            len(prompt) * CFG.num_experts_per_tok * 2)
        seqs[i].append(int(np.argmax(np.asarray(want)[0])))
    park = overflow_position(max_pages, ps)
    for step in range(6):
        live = [True, True, step < 2]
        pos = np.array([len(s) - 1 if ok else park
                        for s, ok in zip(seqs, live)], np.int32)
        tok = np.array([[s[-1]] for s in seqs], np.int32)
        views = cache.layer_views(jnp.asarray(tables))
        logits, new, aux = model(jnp.asarray(tok), caches=views,
                                 start_pos=jnp.asarray(pos))
        cache.update(new)
        assert int(aux["moe_expert_tokens"].sum()) == (
            sum(live) * CFG.num_experts_per_tok * 2)
        for i, ok in enumerate(live):
            if not ok:
                continue
            want = np.asarray(reference.logits(
                leaves, seqs[i], [len(seqs[i]) - 1], cfg))[0]
            assert np.abs(np.asarray(logits._data)[i, 0] - want).max() < 1e-4
            seqs[i].append(int(np.argmax(want)))


def test_prefill_over_the_prompt_s_blocks_equals_the_whole_bucket(
        seeded, kernel_mode, monkeypatch):
    """The flash kernels themselves, interpreted: a prompt of 300 tokens
    in a bucket of 2,048 (one q block of 512 computed, three past the
    prompt) through the latent pool, then three decode steps. Logits and
    the pool's rows equal those of the whole-bucket kernel the prefill
    took before it was told the prompt's length, and no NaN reaches the
    pool, though its null page takes the padding's rows."""
    from paddle_tpu.ops import pallas_kernels

    model, leaves, cfg = seeded
    kernel_mode("interpret")
    monkeypatch.setattr(pallas_kernels, "flash_attention_available",
                        lambda *a, **k: True)
    whole_kernel, live_kernel = (pallas_kernels.flash_attention,
                                 pallas_kernels.flash_prefill)
    lives = []

    def flash_prefill(q, k, v, live):
        lives.append(live)
        return live_kernel(q, k, v, live, interpret=True)

    monkeypatch.setattr(pallas_kernels, "flash_attention",
                        lambda *a, **kw: whole_kernel(*a, **kw,
                                                      interpret=True))
    monkeypatch.setattr(pallas_kernels, "flash_prefill", flash_prefill)
    told = attention.latent_prefill_attention

    def untold(q, k, v, scale, live=None):
        return told(q, k, v, scale)

    ps, bucket, n = 8, 2048, 300
    prompt = np.random.default_rng(6).integers(0, CFG.vocab_size, n)
    table = np.zeros((1, bucket // ps), np.int32)
    table[0, :40] = np.arange(1, 41)

    def serve(prefill_attention):
        monkeypatch.setattr(attention, "latent_prefill_attention",
                            prefill_attention)
        cache = PagedKVCache.for_model(model, 42, ps)
        views = cache.layer_views(jnp.asarray(table))
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n] = prompt
        logits, new, _ = model(jnp.asarray(ids), caches=views, start_pos=0,
                               logits_at=jnp.int32(n - 1))
        cache.update(new)
        got, seq = [np.asarray(logits._data)[0, 0]], list(prompt)
        for step in range(3):
            seq.append(int(np.argmax(got[-1])))
            logits, new, _ = model(
                jnp.asarray([[seq[-1]]], np.int32),
                caches=cache.layer_views(jnp.asarray(table)),
                start_pos=jnp.asarray([len(seq) - 1], np.int32))
            cache.update(new)
            got.append(np.asarray(logits._data)[0, 0])
        pools = np.stack([np.asarray(p[0]) for p in cache.pools])
        return np.stack(got), pools, seq

    whole_logits, whole_pools, whole_seq = serve(untold)
    assert not lives
    live_logits, live_pools, live_seq = serve(told)
    assert len(lives) == CFG.num_hidden_layers
    assert live_seq == whole_seq
    assert np.abs(live_logits - whole_logits).max() < 1e-5
    want = np.asarray(reference.logits(leaves, prompt, [n - 1], cfg))[0]
    assert np.abs(live_logits[0] - want).max() < 1e-4
    assert np.isfinite(live_pools).all()
    # the prompt's and the decoded tokens' rows: pages 1-38
    rows = live_pools[:, 1:39].reshape(CFG.num_hidden_layers, -1, 128)
    assert np.abs(rows[:, :n + 3]
                  - whole_pools[:, 1:39].reshape(rows.shape)[:, :n + 3]
                  ).max() < 1e-5


def _latent_case(dtype=jnp.float32):
    """Five rows over a pool of 30 pages of 8: ragged lengths, one row
    exactly on a block's edge, one parked (no block), and tables whose
    tails are the null page, which holds NaN."""
    ps, max_pages, width, latent, heads = 8, 6, 48, 32, 4
    rng = np.random.default_rng(2)
    pool = rng.normal(size=(30, ps, 128)).astype(np.float32)
    pool[..., width:] = 0.0
    pool[0] = np.nan
    lens = [3, 16, 41, overflow_position(max_pages, ps), 29]
    table = np.zeros((5, max_pages), np.int32)
    nxt = 1
    for i, n in enumerate(lens):
        if n < max_pages * ps:
            k = -(-(n + 1) // ps)
            table[i, :k] = np.arange(nxt, nxt + k)
            nxt += k
    q = rng.normal(size=(5, heads, width)).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(table), jnp.asarray(lens, jnp.int32), latent)


@pytest.mark.parametrize("block_tokens", [16, 1024])
def test_mla_decode_kernel_against_the_jnp_path(monkeypatch, block_tokens):
    monkeypatch.setattr(attention, "_MLA_BLOCK_TOKENS", block_tokens)
    q, pool, table, pos, latent = _latent_case()
    want = attention._mla_decode_reference(
        q, LatentLayerCache(pool, table), pos, 0.25, latent)
    got = attention._mla_decode_pallas.__wrapped__(
        q, pool, table, pos, scale=0.25, latent=latent, interpret=True)
    live = np.asarray(pos) < 48
    assert got.shape == (5, 4, latent)
    assert np.isfinite(np.asarray(got)[live]).all()
    assert np.abs(np.asarray(got) - np.asarray(want))[live].max() < 1e-5
    # a parked row walks no block: zeros, never NaN
    assert np.asarray(got)[~live].tolist() == np.zeros(
        (1, 4, latent)).tolist()


def test_latent_write_routes_overflowing_rows_to_the_null_page():
    pool = jnp.zeros((4, 8, 128))
    cache = LatentLayerCache(pool, jnp.asarray([[2, 3], [1, 0]], jnp.int32))
    rows = jnp.ones((2, 1, 48))
    new, pos = attention.latent_write(
        rows, cache, jnp.asarray([9, overflow_position(2, 8)], jnp.int32))
    got = np.asarray(new.pool)
    assert got[3, 1, :48].tolist() == [1.0] * 48      # position 9: page 3
    assert not got[3, 1, 48:].any()                   # the tile's pad
    assert got[0, 0, :48].tolist() == [1.0] * 48      # parked: null page
    assert not got[1].any() and not got[2].any()


def _per_token_moe(x, router, bias, gate, up, down, top_k, scale):
    """The layer's definition, one token and one expert at a time; among
    equal scores the lower index is chosen."""
    x, router, bias = (np.asarray(a, np.float64) for a in (x, router, bias))
    out = np.zeros_like(x)
    counts = np.zeros(router.shape[1], np.int64)
    for t in range(x.shape[0]):
        sc = 1.0 / (1.0 + np.exp(-(x[t] @ router)))
        chosen = np.argsort(-(sc + bias), kind="stable")[:top_k]
        w = scale * sc[chosen] / sc[chosen].sum()
        for e, g in zip(chosen, w):
            h = x[t] @ np.asarray(gate[e], np.float64)
            mid = h / (1.0 + np.exp(-h)) * (x[t] @ np.asarray(up[e],
                                                              np.float64))
            out[t] += g * (mid @ np.asarray(down[e], np.float64))
            counts[e] += 1
    return out, counts


@pytest.mark.parametrize("case", ["seeded", "tie", "empty_expert",
                                  "one_expert", "padding"])
@pytest.mark.parametrize("matmul", ["ragged_dot", "gmm_interpret"])
def test_dropless_layer_against_a_per_token_loop(monkeypatch, case, matmul):
    monkeypatch.setattr(mla_moe, "GROUPED_MATMUL", matmul)
    monkeypatch.setattr(mla_moe, "_GMM_TILE_ROWS_SMALL", 8)
    t, h, f, e, k = 12, 16, 8, 8, 2
    rng = np.random.default_rng(3)
    x = rng.normal(size=(t, h)).astype(np.float32)
    router = rng.normal(size=(h, e)).astype(np.float32) * 0.3
    bias = rng.normal(size=(e,)).astype(np.float32) * 0.02
    gate, up = (rng.normal(size=(e, h, f)).astype(np.float32) * 0.2
                for _ in range(2))
    down = rng.normal(size=(e, f, h)).astype(np.float32) * 0.2
    valid = np.ones((t,), bool)
    if case == "tie":
        router[:, 5] = router[:, 2]     # experts 2 and 5 always tie
        bias[5] = bias[2]
    elif case == "empty_expert":
        bias[3] = -10.0                 # never chosen: an empty group
    elif case == "one_expert":
        bias[6], k = 10.0, 1            # every token on expert 6
    elif case == "padding":
        valid[[1, 7, 11]] = False
    got, sizes = mla_moe.dropless_moe(
        jnp.asarray(x), jnp.asarray(valid), jnp.asarray(router),
        jnp.asarray(bias), jnp.asarray(gate), jnp.asarray(up),
        jnp.asarray(down), top_k=k, scale=2.5)
    want, counts = _per_token_moe(x[valid], router, bias, gate, up, down, k,
                                  2.5)
    assert np.asarray(sizes).tolist() == counts.tolist()
    assert int(np.asarray(sizes).sum()) == valid.sum() * k   # none dropped
    assert np.abs(np.asarray(got)[valid] - want).max() < 2e-5
    assert not np.asarray(got)[~valid].any()
    if case == "tie":
        assert counts[5] <= counts[2]
    if case == "empty_expert":
        assert counts[3] == 0
    if case == "one_expert":
        assert counts[6] == t


def test_moe_layer_in_chunks_equals_the_whole(seeded, monkeypatch):
    model, _, _ = seeded
    layer = model.model.layers[1].mlp
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 37, 64)),
                    jnp.float32)
    valid = jnp.arange(37)[None] < 30
    whole, sizes = layer(x, valid)
    monkeypatch.setattr(mla_moe, "_MOE_CHUNK_TOKENS", 16)
    parts, part_sizes = layer(x, valid)
    assert np.abs(np.asarray(whole) - np.asarray(parts)).max() < 1e-5
    assert np.asarray(sizes).tolist() == np.asarray(part_sizes).tolist()


def test_latent_pool_is_a_kind_of_pool(seeded):
    model, _, _ = seeded
    cache = PagedKVCache.for_model(model, 10, 8, kv_dtype="bf16")
    # one pool a layer, a row 32 + 16 wide held in whole 128-lane tiles
    assert [tuple(p[0].shape) for p in cache.pools] == [(10, 8, 128)] * 3
    assert cache.slot_elems == 128
    assert cache.page_bytes == 3 * 8 * 128 * 2
    assert cache.pool_bytes == 10 * cache.page_bytes
    views = cache.layer_views(jnp.zeros((2, 4), jnp.int32))
    assert all(isinstance(v, LatentLayerCache) for v in views)
    assert views[0].page_size == 8
    assert [len(p) for p in pools_from_views(views)] == [1, 1, 1]
    back = views_from_pools(pools_from_views(views), views[0].page_table)
    assert jax.tree_util.tree_structure(back) == \
        jax.tree_util.tree_structure(views)
    with pytest.raises(ValueError, match="kv_dtype"):
        PagedKVCache.for_model(model, 10, 8, kv_dtype="int8")
    full = MlaMoeConfig.joyai_llm_flash()
    assert full.latent_cache_dim == 576
    big = PagedKVCache(5, 2, 16, 32, 64, jnp.bfloat16, latent_dim=576)
    assert big.page_bytes == 16 * 5 * 640 * 2      # 102,400 B a page


def test_engine_sizes_and_serves_the_latent_kind(seeded, kernel_mode):
    model, leaves, cfg = seeded
    kernel_mode("interpret")
    eng = ServingEngine(model, page_size=8, max_batch_size=4, max_seq_len=96)
    # the default pool: every slot a full-length sequence, and page 0
    assert eng.cache.num_pages == 4 * 12 + 1
    assert eng.cache.kind == "latent"
    pool_gauge = [m for m in eng.metrics.collect()
                  if m.name == "serving_kv_pool_bytes"]
    assert pool_gauge[0].value == eng.cache.pool_bytes
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, CFG.vocab_size, n).tolist()
               for n in (11, 30, 17)]
    budgets = (19, 9, 12)           # more than two decode blocks, and less
    rids = [eng.add_request(p, max_new_tokens=n, temperature=0.0, seed=0)
            for p, n in zip(prompts, budgets)]
    rids.append(eng.add_request(prompts[0], max_new_tokens=6,
                                temperature=0.8, top_k=20, seed=3))
    eng.run()
    for rid, prompt, n in zip(rids, prompts, budgets):
        req = eng.requests[rid]
        assert req.status == "finished" and len(req.generated) == n
        ids = prompt + req.generated
        want = np.asarray(reference.logits(
            leaves, ids, np.arange(len(prompt) - 1, len(ids) - 1), cfg))
        assert want.argmax(-1).tolist() == req.generated
    assert len(eng.requests[rids[-1]].generated) == 6
    counters = {m.name: m.value for m in eng.metrics.collect()
                if m.name.startswith("serving_moe_")}
    # every processed token of a request reaches 4 experts in 2 layers
    processed = sum(len(p) + n - 1 for p, n in zip(prompts, budgets)) \
        + len(prompts[0]) + 5
    assert counters["serving_moe_pairs_total"] == processed * 4 * 2
    assert 0 < counters["serving_moe_experts_touched_total"] <= \
        16 * counters["serving_moe_layer_dispatches_total"]
    assert counters["serving_moe_max_expert_tokens_total"] >= \
        counters["serving_moe_layer_dispatches_total"]
    assert eng.fault_events == 0


@pytest.mark.parametrize("option,kwargs", [
    ("tp_size", {"tp_size": 2}),
    ("kv_dtype", {"kv_dtype": "int8"}),
    ("kv_dtype", {"kv_dtype": "fp8"}),
    ("enable_prefix_caching", {"enable_prefix_caching": True}),
    ("enable_chunked_prefill", {"enable_chunked_prefill": True}),
    ("spec_config", {"spec_config": object()}),
])
def test_engine_refuses_what_a_latent_pool_cannot_run(seeded, option,
                                                      kwargs):
    model, _, _ = seeded
    with pytest.raises(ValueError, match=option):
        ServingEngine(model, page_size=8, max_batch_size=2, max_seq_len=64,
                      **kwargs)


def test_static_cache_generation_refuses_the_model_by_name(seeded):
    model, _, _ = seeded
    from paddle_tpu.models.generation import generate
    with pytest.raises(NotImplementedError, match="MlaMoeForCausalLM"):
        generate(model, jnp.zeros((1, 4), jnp.int32), max_new_tokens=2)


def test_offset_prefill_over_a_latent_pool_is_refused(seeded):
    model, _, _ = seeded
    cache = PagedKVCache.for_model(model, 10, 8)
    views = cache.layer_views(jnp.asarray([[1, 2, 3, 4]], jnp.int32))
    with pytest.raises(NotImplementedError, match="offset"):
        model(jnp.zeros((1, 8), jnp.int32), caches=views,
              start_pos=jnp.int32(8))


def test_deferred_weights_allocate_nothing():
    cfg = dataclasses.replace(MlaMoeConfig.joyai_llm_flash(),
                              num_hidden_layers=5, dtype="bfloat16",
                              deferred_weights=True)
    model = MlaMoeForCausalLM(cfg)
    params = dict(model.named_parameters())
    assert all(isinstance(p._data, jax.ShapeDtypeStruct)
               for p in params.values())
    want = reference.shapes({**dataclasses.asdict(cfg),
                             "num_hidden_layers": 5})
    assert {k: tuple(p.shape) for k, p in params.items()} == \
        {k: tuple(v[0]) for k, v in want.items()}
    # 11.12 GB in bf16: one dense and four expert layers, the vocabulary
    n = sum(int(np.prod(p.shape)) for p in params.values())
    assert abs(2 * n / 1e9 - 11.12) < 0.01
