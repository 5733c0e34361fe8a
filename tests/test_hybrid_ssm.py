"""The hybrid decoder of Mamba-2 and attention layers (models/
hybrid_ssm.py) and what serving it added: the model against the
benchmark's plain reference, state slots beside K/V pages in one cache
manager, the `ssm_decode` kernel in interpret mode against the jnp path,
the chunked scan against the token-by-token recurrence, padding, parked
rows and the null slot, slot reuse, preemption, the state's precision,
64-wide heads packed two a row through the shared paged path, the
engine's counters and its refusals. Float32 weights unless said."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights
from chipbench.reference import hybrid_ssm as reference
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models import HybridSsmConfig, HybridSsmForCausalLM
from paddle_tpu.serving import ServingEngine, attention, ssm
from paddle_tpu.serving.kv_cache import (NULL_SLOT, LayerPool, PagedKVCache,
                                         PagedLayerCache, SlotAllocator,
                                         StateLayerCache, overflow_position,
                                         pools_from_views, views_from_pools)

# Mamba-2 heads of 64 (two a 128-lane row of the stored state) with a
# state of 16, attention heads of 64 over 2 kv heads (two a row of the
# K/V pool), a scan chunk of 8 so that a prompt crosses several chunks
CFG = HybridSsmConfig.tiny(
    hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
    mamba_n_heads=8, num_hidden_layers=3,
    layer_types=("mamba", "attention", "mamba"),
    attention_multiplier=1.0 / 64)
MAMBA = [i for i, t in enumerate(CFG.layer_types) if t == "mamba"]


def _seeded(config, seed=29):
    cfg = {f.name: getattr(config, f.name)
           for f in dataclasses.fields(config)}
    leaves = reference.own_leaves(
        weights.make(reference.shapes(cfg), seed, jnp.float32), cfg, seed)
    model = HybridSsmForCausalLM(
        dataclasses.replace(config, deferred_weights=True))
    model.eval()
    params = dict(model.named_parameters())
    assert set(params) == set(leaves)
    for name, p in params.items():
        assert tuple(p.shape) == tuple(leaves[name].shape), name
        p._data = leaves[name]
    return model, leaves, cfg


@pytest.fixture(scope="module")
def seeded():
    """The tiny model holding the benchmark's seeded float32 leaves, the
    Mamba-2 draws among them."""
    return _seeded(CFG)


@pytest.fixture
def kernel_mode(monkeypatch):
    def set_mode(mode):
        monkeypatch.setattr(attention, "KERNEL_MODE", mode)
    return set_mode


def _engine(model, **kw):
    # engines over one model share its jitted steps; a test that changes
    # the kernel mode must not meet another mode's trace
    model.__dict__.pop("_serving_jit_cache", None)
    kw = {"page_size": 8, "max_batch_size": 4, "max_seq_len": 128, **kw}
    return ServingEngine(model, **kw)


def _slot_state(cache, layer, slot):
    """(H, P, N) state and (W - 1, C) tail a slot holds in a layer."""
    stored, conv = cache.pools[layer]
    state = ssm.unpack_state(stored[slot], cache.state_spec.head_pack)
    return np.asarray(state), np.asarray(conv[slot]).reshape(
        cache.state_spec.conv_width - 1, -1)


def test_the_mamba_draws_are_mamba_2_s_own(seeded):
    _, leaves, cfg = seeded
    a_log = np.asarray(leaves["model.layers.0.mamba.A_log"])
    dt_bias = np.asarray(leaves["model.layers.0.mamba.dt_bias"])
    assert a_log.dtype == np.float32 and dt_bias.dtype == np.float32
    assert (np.exp(a_log) >= 1).all() and (np.exp(a_log) <= 16).all()
    dt = np.log1p(np.exp(dt_bias))          # the softplus gives dt back
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 1e-1 * 1.001).all()
    assert (np.asarray(leaves["model.layers.0.mamba.D"]) == 1).all()
    # another seed, other draws; the attention layer has none
    other = reference.own_leaves(leaves, cfg, 30)
    assert not np.array_equal(
        np.asarray(other["model.layers.0.mamba.A_log"]), a_log)
    assert "model.layers.1.mamba.A_log" not in leaves


def test_model_matches_the_reference_in_float32(seeded):
    """The chunked scan (chunks of 8 over 45 positions, the last chunk
    padded) against the token-by-token recurrence. 2e-5: float32 sums in
    another order, on logits of magnitude 0.3."""
    model, leaves, cfg = seeded
    ids = np.random.default_rng(0).integers(0, CFG.vocab_size, (2, 45))
    got = np.asarray(model(Tensor(jnp.asarray(ids)))._data)
    for row in range(2):
        want = np.asarray(reference.logits(leaves, ids[row], np.arange(45),
                                           cfg))
        assert np.abs(got[row] - want).max() < 2e-5


@pytest.mark.parametrize("chunk", [4, 16, 64])
def test_chunk_scan_against_the_recurrence(chunk):
    """Any chunk size gives the recurrence's outputs and final state;
    positions whose dt is 0 leave the state as it stands."""
    rng = np.random.default_rng(3)
    b, s, h, p, n = 2, 37, 4, 8, 16
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(1e-3, 1e-1, (b, s, h)), jnp.float32)
    dt = dt.at[:, 30:].set(0.0)
    a = -jnp.asarray(rng.uniform(1, 16, h), jnp.float32)
    bt = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    d = jnp.ones((h,), jnp.float32)
    y, final = ssm.chunk_scan(x, dt, a, bt, ct, d, chunk)
    state = np.zeros((b, h, p, n))
    want = np.zeros((b, s, h, p))
    for t in range(s):
        dec = np.exp(np.asarray(dt[:, t]) * np.asarray(a))
        state = dec[..., None, None] * state + (
            np.asarray(dt[:, t])[..., None] * np.asarray(x[:, t])
        )[..., None] * np.asarray(bt[:, t])[:, None, None, :]
        want[:, t] = np.einsum("bhpn,bn->bhp", state,
                               np.asarray(ct[:, t])) + np.asarray(x[:, t])
        if t == 29:
            at_30 = state.copy()
    assert np.abs(np.asarray(y) - want).max() < 1e-4
    assert np.abs(np.asarray(final) - at_30).max() < 1e-5


def test_state_pools_stand_beside_kv_pools(seeded):
    model, _, _ = seeded
    cache = PagedKVCache.for_model(model, 10, 8, kv_dtype="bf16",
                                   state_slots=3)
    assert cache.kind == "kv+state"
    assert [p.kind for p in cache.pools] == ["state", "kv", "state"]
    # two heads of 64 a 128-lane row, in both kinds of pool
    assert cache.head_pack == 2 and cache.state_spec.head_pack == 2
    ssm_pool, conv_pool = cache.pools[0]
    assert ssm_pool.shape == (4, 4, 16, 128)
    assert ssm_pool.dtype == jnp.float32
    conv_dim = 512 + 2 * 16
    assert conv_pool.shape == (4, 3 * conv_dim)
    assert conv_pool.dtype == jnp.bfloat16
    assert [a.shape for a in cache.pools[1]] == [(1, 10, 8, 128)] * 2
    # the truth about both kinds: one attention layer's pages, two Mamba
    # layers' slots (the null page and the null slot among them)
    assert cache.num_kv_layers == 1
    assert cache.page_bytes == 8 * 2 * 2 * 64 * 2
    assert cache.state_slot_bytes == 2 * (8 * 64 * 16 * 4
                                          + 3 * conv_dim * 2)
    assert cache.pool_bytes == (10 * cache.page_bytes
                                + 4 * cache.state_slot_bytes)
    assert cache.pool_bytes == sum(
        a.size * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(cache.pools))
    views = cache.layer_views(jnp.zeros((2, 4), jnp.int32),
                              slots=jnp.asarray([1, 2], jnp.int32))
    assert [type(v) for v in views] == [StateLayerCache, PagedLayerCache,
                                        StateLayerCache]
    back = pools_from_views(views)
    assert [p.kind for p in back] == ["state", "kv", "state"]
    assert jax.tree_util.tree_structure(back) == \
        jax.tree_util.tree_structure(cache.pools)
    with pytest.raises(NotImplementedError, match="slots"):
        views_from_pools(cache.pools, jnp.zeros((2, 4), jnp.int32))
    with pytest.raises(ValueError, match="quantized"):
        PagedKVCache.for_model(model, 10, 8, kv_dtype="int8", state_slots=3)
    full = HybridSsmConfig.granite_4_0_h_micro().state_cache_spec
    assert sum(full.state_layers) == 36 and full.ssm_shape == (32, 128, 128)
    assert 36 * (4 * 64 * 64 * 128 + 2 * full.conv_elems) == 76_437_504


def test_a_pool_s_kind_is_its_tag_not_its_arity():
    """Two arrays a layer are K and V under "kv" and a state and a conv
    tail under "state"; the tag is static under jit and the leaves are
    the bare tuple's."""
    k = jnp.zeros((2, 4, 8, 16))
    table = jnp.zeros((1, 2), jnp.int32)
    kv = views_from_pools([LayerPool("kv", (k, k))], table)
    st = views_from_pools([LayerPool("state", (k, k[0, 0]))], table,
                          slots=jnp.zeros((1,), jnp.int32))
    assert isinstance(kv[0], PagedLayerCache)
    assert isinstance(st[0], StateLayerCache)
    quant = views_from_pools(
        [LayerPool("kv_quant", (k, k, k[..., :1], k[..., :1]))], table)
    assert quant[0].quantized and not kv[0].quantized
    assert pools_from_views(quant)[0].kind == "kv_quant"
    assert jax.tree_util.tree_leaves([LayerPool("kv", (k, k))]) == [k, k]
    out = jax.jit(lambda p: p)([LayerPool("state", (k, k))])
    assert out[0].kind == "state" and len(out[0]) == 2
    with pytest.raises(ValueError, match="unknown pool kind"):
        LayerPool("pages", (k,))


def test_slot_allocator_hands_out_every_slot_but_the_null_one():
    slots = SlotAllocator(3)
    got = [slots.alloc() for _ in range(3)]
    assert sorted(got) == [1, 2, 3] and NULL_SLOT not in got
    assert slots.alloc() is None and slots.num_free == 0
    slots.free(2)
    assert slots.num_used == 2 and slots.alloc() == 2
    with pytest.raises(ValueError, match="double free"):
        slots.free(NULL_SLOT)
    assert slots.check_consistency()


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_prefill_then_decode_against_the_reference_s_full_forward(
        seeded, kernel_mode, mode):
    """Logits, not tokens: three prompts prefilled into their slots (each
    padded to a bucket of 32 and crossing several chunks of 8) and 19
    decode steps over the three rows, one of them parked from the third
    step on, against the reference's one forward over each whole
    sequence. 5e-5: float32 in another order (chunks, then the kernel's
    sums) on logits of magnitude 0.3."""
    model, leaves, cfg = seeded
    kernel_mode(mode)
    ps, max_pages = 8, 8
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, CFG.vocab_size, n).tolist()
               for n in (13, 24, 5)]
    cache = PagedKVCache.for_model(model, 40, ps, state_slots=3)
    tables = np.zeros((3, max_pages), np.int32)
    tables[0, :6], tables[1, :7], tables[2, :4] = (
        np.arange(1, 7), np.arange(7, 14), np.arange(14, 18))
    slots = jnp.asarray([2, 3, 1], jnp.int32)
    seqs = [list(p) for p in prompts]
    for i, prompt in enumerate(prompts):
        ids = np.zeros((1, 32), np.int32)
        ids[0, :len(prompt)] = prompt
        views = cache.layer_views(jnp.asarray(tables[i:i + 1]),
                                  slots=slots[i:i + 1])
        logits, new = model(jnp.asarray(ids), caches=views, start_pos=0,
                            logits_at=jnp.int32(len(prompt) - 1))
        cache.update(new)
        want = np.asarray(reference.logits(leaves, prompt,
                                           [len(prompt) - 1], cfg))[0]
        assert np.abs(np.asarray(logits._data)[0, 0] - want).max() < 5e-5
        seqs[i].append(int(np.argmax(want)))
    park = overflow_position(max_pages, ps)
    for step in range(19):
        live = [True, True, step < 2]
        before = _slot_state(cache, 0, 1)
        null_before = _slot_state(cache, 0, NULL_SLOT)
        pos = np.array([len(s) - 1 if ok else park
                        for s, ok in zip(seqs, live)], np.int32)
        tok = np.array([[s[-1]] for s in seqs], np.int32)
        views = cache.layer_views(jnp.asarray(tables), slots=slots)
        logits, new = model(jnp.asarray(tok), caches=views,
                            start_pos=jnp.asarray(pos))
        cache.update(new)
        for i, ok in enumerate(live):
            if not ok:
                continue
            want = np.asarray(reference.logits(
                leaves, seqs[i], [len(seqs[i]) - 1], cfg))[0]
            assert np.abs(np.asarray(logits._data)[i, 0] - want).max() < 5e-5
            seqs[i].append(int(np.argmax(want)))
        if not live[2]:
            # the parked row left its own slot alone and went through
            # the null slot, which nobody owns
            after = _slot_state(cache, 0, 1)
            assert np.array_equal(before[0], after[0])
            assert np.array_equal(before[1], after[1])
            assert not np.array_equal(null_before[0],
                                      _slot_state(cache, 0, NULL_SLOT)[0])
    # what the slots hold is the reference's state after the last token
    # that was fed
    for i in (0, 1):
        want = reference.final_states(leaves, seqs[i][:-1], cfg)
        for j, layer in enumerate(MAMBA):
            got, _ = _slot_state(cache, layer, int(slots[i]))
            assert np.abs(got - np.asarray(want[j])).max() < 1e-5


def test_a_padded_prompt_leaves_the_state_of_its_exact_length(seeded,
                                                             kernel_mode):
    """13 tokens alone, and the same 13 in a bucket of 32 whose other 19
    positions hold other tokens: the same state and the same conv tail
    (the last 3 real rows), to float32 rounding in the two chunkings."""
    model, leaves, cfg = seeded
    kernel_mode("off")
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, CFG.vocab_size, 13)
    held = {}
    for width in (13, 32):
        cache = PagedKVCache.for_model(model, 12, 8, state_slots=2)
        ids = rng.integers(0, CFG.vocab_size, (1, width)).astype(np.int32)
        ids[0, :13] = prompt
        views = cache.layer_views(jnp.asarray([[1, 2, 3, 4]], jnp.int32),
                                  slots=jnp.asarray([2], jnp.int32))
        _, new = model(jnp.asarray(ids), caches=views, start_pos=0,
                       logits_at=jnp.int32(12))
        cache.update(new)
        held[width] = [_slot_state(cache, layer, 2) for layer in MAMBA]
        # nothing but the row's slot was written
        assert not np.asarray(cache.pools[0][0][1]).any()
    want = reference.final_states(leaves, prompt, cfg)
    for j in range(len(MAMBA)):
        assert np.abs(held[13][j][0] - held[32][j][0]).max() < 1e-6
        assert np.abs(held[13][j][1] - held[32][j][1]).max() < 1e-6
        assert np.abs(held[32][j][0] - np.asarray(want[j])).max() < 1e-5
    # a prompt shorter than the conv's tail keeps zeros before it
    cache = PagedKVCache.for_model(model, 12, 8, state_slots=2)
    ids = np.zeros((1, 16), np.int32)
    ids[0, :2] = prompt[:2]
    views = cache.layer_views(jnp.asarray([[1, 2]], jnp.int32),
                              slots=jnp.asarray([1], jnp.int32))
    _, new = model(jnp.asarray(ids), caches=views, start_pos=0,
                   logits_at=jnp.int32(1))
    cache.update(new)
    tail = _slot_state(cache, 0, 1)[1]
    assert not tail[0].any() and tail[1].any() and tail[2].any()


@pytest.mark.parametrize("block_bytes", [1, 1 << 20])
def test_ssm_decode_kernel_against_the_jnp_path(monkeypatch, block_bytes):
    """The kernel in interpret mode, whole and in several head blocks:
    rows at their own slots, two rows at the null slot, the pool updated
    where the rows' slots are and nowhere else."""
    monkeypatch.setattr(ssm, "_SSM_BLOCK_BYTES", block_bytes)
    rng = np.random.default_rng(5)
    b, hk, n, lanes = 5, 16, 16, 128
    assert ssm._decode_tiling(hk, n, lanes) == (8 if block_bytes == 1
                                                else 16)
    pool = jnp.asarray(rng.normal(size=(7, hk, n, lanes)), jnp.float32)
    xd = jnp.asarray(rng.normal(size=(b, hk, lanes)), jnp.float32)
    dec = jnp.asarray(rng.uniform(0.5, 1.0, (b, hk, lanes)), jnp.float32)
    bc = jnp.asarray(rng.normal(size=(b, n, 1)), jnp.float32)
    cc = jnp.asarray(rng.normal(size=(b, n, 1)), jnp.float32)
    slots = jnp.asarray([3, NULL_SLOT, 6, 1, NULL_SLOT], jnp.int32)
    y, new = ssm._ssm_decode_pallas(xd, dec, bc, cc, pool, slots,
                                    interpret=True)
    y_ref, new_ref = ssm._ssm_decode_reference(xd, dec, bc, cc, pool, slots)
    live = [0, 2, 3]
    assert np.abs(np.asarray(y)[live] - np.asarray(y_ref)[live]).max() < 1e-5
    for slot in (1, 3, 6):
        assert np.abs(np.asarray(new[slot])
                      - np.asarray(new_ref[slot])).max() < 1e-6
    for slot in (2, 4, 5):
        assert np.array_equal(np.asarray(new[slot]), np.asarray(pool[slot]))


def test_packed_state_round_trips():
    state = jnp.asarray(np.random.default_rng(6).normal(size=(3, 8, 64, 16)),
                        jnp.float32)
    stored = ssm.pack_state(state, 2)
    assert stored.shape == (3, 4, 16, 128)
    # head 2k + j of a row block in lanes [64 j, 64 j + 64), transposed
    assert np.array_equal(np.asarray(stored[1, 2, :, 64:]),
                          np.asarray(state[1, 5]).T)
    assert np.array_equal(np.asarray(ssm.unpack_state(stored, 2)),
                          np.asarray(state))


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_engine_serves_the_reference_s_tokens_over_several_blocks(
        seeded, kernel_mode, mode):
    """Through `ServingEngine`'s normal path: buckets, slots, blocks of
    8 with rows whose budgets end inside one. Every served token is the
    reference's first choice (gap 0: the logits' margins here are far
    over float32 rounding)."""
    model, leaves, cfg = seeded
    kernel_mode(mode)
    eng = _engine(model)
    assert eng.cache.kind == "kv+state" and eng.cache.state_slots == 4
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, CFG.vocab_size, n).tolist()
               for n in (11, 30, 17, 5, 21)]
    budgets = (27, 9, 12, 20, 3)
    rids = [eng.add_request(p, max_new_tokens=n, temperature=0.0, seed=0)
            for p, n in zip(prompts, budgets)]
    eng.run()
    for rid, prompt, n in zip(rids, prompts, budgets):
        req = eng.requests[rid]
        assert req.status == "finished" and len(req.generated) == n
        assert req.state_slot is None and not req.pages
        ids = prompt + req.generated
        want = np.asarray(reference.logits(
            leaves, ids, np.arange(len(prompt) - 1, len(ids) - 1), cfg))
        assert want.argmax(-1).tolist() == req.generated
    counters = {m.name: m.value for m in eng.metrics.collect()
                if m.name.startswith("serving_state_slot")}
    assert counters["serving_state_slot_allocations_total"] == 5
    assert counters["serving_state_slots_in_use"] == 0
    # five requests over four slots: the fifth waited for a row
    assert eng.cache.slot_allocator.num_free == 4
    assert eng.scheduler.check_consistency()
    assert eng.fault_events == 0
    assert set(eng.compile_counts()) == set(
        _engine(_seeded(CFG)[0]).compile_counts())


def test_the_attention_layer_s_prefill_says_causal(
        seeded, flash_interpreted, attention_dispatches):
    """With the flash kernel itself interpreted: the attention layer of
    each prefill executable takes the causal flag (dispatch path
    `prefill`, never `prefill_masked`: the model has no bias) and the
    served tokens are still the reference's."""
    model, leaves, cfg = seeded
    prompt = np.random.default_rng(9).integers(0, CFG.vocab_size, 19)
    eng = _engine(model)
    rid = eng.add_request(prompt.tolist(), max_new_tokens=5,
                          temperature=0.0, seed=0)
    eng.run()
    model.__dict__.pop("_serving_jit_cache", None)
    attention_layers = CFG.layer_types.count("attention")
    counts = attention_dispatches()
    assert counts["prefill"] == attention_layers
    assert not counts["prefill_masked"]
    assert flash_interpreted.count((True, False)) == attention_layers
    ids = prompt.tolist() + eng.requests[rid].generated
    want = np.asarray(reference.logits(
        leaves, ids, np.arange(len(prompt) - 1, len(ids) - 1), cfg))
    assert want.argmax(-1).tolist() == eng.requests[rid].generated


def test_a_reused_slot_starts_from_zero(seeded, kernel_mode):
    """One row, so that the second request takes the slot the first
    left full: its stream is the one it has on a fresh engine."""
    model, _, _ = seeded
    kernel_mode("off")
    rng = np.random.default_rng(8)
    first = rng.integers(0, CFG.vocab_size, 19).tolist()
    second = rng.integers(0, CFG.vocab_size, 7).tolist()
    eng = _engine(model, max_batch_size=1)
    a = eng.add_request(first, max_new_tokens=11)
    b = eng.add_request(second, max_new_tokens=13)
    eng.run()
    assert np.asarray(eng.cache.pools[0][0][1]).any()
    fresh = _engine(model, max_batch_size=1)
    c = fresh.add_request(second, max_new_tokens=13)
    fresh.run()
    assert eng.requests[b].generated == fresh.requests[c].generated
    assert eng.requests[a].status == "finished"


def test_a_preempted_request_resumes_its_own_stream(seeded, kernel_mode):
    """A pool too small for both rows' whole lengths: the younger is
    preempted, gives its slot and pages back, and its re-prefill
    rebuilds the state. Both streams are those of an engine that never
    preempts."""
    model, _, _ = seeded
    kernel_mode("off")
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, CFG.vocab_size, n).tolist() for n in (20, 18)]
    small = _engine(model, num_pages=9, max_batch_size=2, max_seq_len=64)
    large = _engine(model, num_pages=40, max_batch_size=2, max_seq_len=64)
    streams = []
    for eng in (small, large):
        rids = [eng.add_request(p, max_new_tokens=24) for p in prompts]
        eng.run()
        streams.append([eng.output(r) for r in rids])
        assert all(eng.requests[r].status == "finished" for r in rids)
        assert eng.scheduler.check_consistency()
    assert sum(r.preemptions for r in small.requests.values()) >= 1
    assert sum(r.preemptions for r in large.requests.values()) == 0
    assert streams[0] == streams[1]
    blocked = {m.name: m.value for m in small.metrics.collect()
               if m.name.startswith("serving_admission_blocked_on_")}
    assert blocked == {"serving_admission_blocked_on_pages_total":
                       blocked["serving_admission_blocked_on_pages_total"]}
    assert blocked["serving_admission_blocked_on_pages_total"] >= 1


def test_a_free_row_is_a_free_slot():
    """The scheduler's own: as many slots as rows, one a running
    request, so admission waits for a row and never for a slot; a
    finished request's slot is the next one's."""
    from paddle_tpu.serving.kv_cache import BlockAllocator
    from paddle_tpu.serving.scheduler import (Request, SamplingParams,
                                              Scheduler)

    slots = SlotAllocator(2)
    sched = Scheduler(BlockAllocator(64), 8, 2, 8, slot_allocator=slots)
    for _ in range(3):
        sched.add(Request(prompt=[1] * 5, max_new_tokens=4,
                          sampling=SamplingParams()))
    first, second = sched.schedule(), sched.schedule()
    assert {first.prefill.state_slot, second.prefill.state_slot} == {1, 2}
    assert sched.schedule().kind == "decode" and len(sched.waiting) == 1
    assert slots.num_free == 0 and sched.check_consistency()
    freed = first.prefill.state_slot
    sched.finish(first.prefill)
    third = sched.schedule()
    assert third.kind == "prefill" and third.prefill.state_slot == freed
    assert slots.num_free == 0 and sched.check_consistency()


def test_the_state_is_float32_and_a_bf16_state_would_fail(seeded,
                                                         kernel_mode):
    """Float32 weights and activations: after a prompt of 8 and 512
    decode steps (64 blocks of 8) the slot holds the reference's state
    to 1e-4 of its largest element, and the same reference with its
    state rounded to bf16 after every token does not come within ten
    times that: a state kept in bf16 fails here."""
    model, leaves, cfg = seeded
    kernel_mode("off")
    eng = _engine(model, max_seq_len=640, max_batch_size=1)
    prompt = np.random.default_rng(10).integers(0, CFG.vocab_size,
                                                8).tolist()
    rid = eng.add_request(prompt, max_new_tokens=513)
    eng.run()
    fed = prompt + eng.requests[rid].generated[:-1]
    assert len(fed) == 8 + 512
    exact = reference.final_states(leaves, fed, cfg)
    rounded = reference.final_states(leaves, fed, cfg,
                                     state_dtype=jnp.bfloat16)
    for j, layer in enumerate(MAMBA):
        got, _ = _slot_state(eng.cache, layer, 1)
        want = np.asarray(exact[j])
        scale = np.abs(want).max()
        assert np.abs(got - want).max() < 1e-4 * scale
        assert np.abs(np.asarray(rounded[j]) - want).max() > 1e-3 * scale


def _attend_loop(q, k, v, lengths):
    """Query head j over kv head j // rep, one row and head at a time."""
    b, heads, hd = q.shape
    rep = heads // k.shape[2]
    out = np.zeros((b, heads, hd))
    for i in range(b):
        for j in range(heads):
            kk, vv = k[i, :lengths[i], j // rep], v[i, :lengths[i], j // rep]
            s = kk @ q[i, j] / np.sqrt(hd)
            p = np.exp(s - s.max())
            out[i, j] = (p / p.sum()) @ vv
    return out


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_heads_of_64_four_queries_a_kv_head_through_paged_attend(
        kernel_mode, mode):
    """Granite's attention shapes through the shared paged path: 8 kv
    heads of 64 packed two a 128-lane row, 32 query heads, a prefill
    that writes whole pages and a decode step at ragged positions,
    against a plain loop over heads."""
    kernel_mode(mode)
    rng = np.random.default_rng(11)
    b, heads, kvh, hd, ps, max_pages = 3, 32, 8, 64, 8, 4
    cache = PagedKVCache(1, 20, ps, kvh, hd, head_pack=2)
    assert cache.pools[0][0].shape == (4, 20, ps, 128)
    table = jnp.asarray(np.arange(1, 13).reshape(b, max_pages), jnp.int32)
    s = 16
    k = rng.normal(size=(b, s + 1, kvh, hd)).astype(np.float32)
    v = rng.normal(size=(b, s + 1, kvh, hd)).astype(np.float32)
    q = rng.normal(size=(b, s + 1, heads, hd)).astype(np.float32)
    view = cache.layer_views(table)[0]
    ctx, view = attention.paged_attend(
        Tensor(jnp.asarray(q[:, :s])), Tensor(jnp.asarray(k[:, :s])),
        Tensor(jnp.asarray(v[:, :s])), view, 0, heads // kvh)
    want = _attend_loop(q[:, s - 1], k, v, [s] * b)
    assert np.abs(np.asarray(ctx._data)[:, s - 1] - want).max() < 1e-5
    # head 2k + j of a token in lanes [64 j, 64 j + 64) of row block k
    assert np.array_equal(np.asarray(view.k_pool[1, 1, 3, 64:]), k[0, 3, 3])
    # decode: row 0 appends at 16, rows 1 and 2 overwrite earlier slots
    pos = np.array([s, 9, 12], np.int32)
    ctx, view = attention.paged_attend(
        Tensor(jnp.asarray(q[:, s:])), Tensor(jnp.asarray(k[:, s:])),
        Tensor(jnp.asarray(v[:, s:])), view, jnp.asarray(pos), heads // kvh)
    for i in range(b):
        k[i, pos[i]], v[i, pos[i]] = k[i, s].copy(), v[i, s].copy()
    want = _attend_loop(q[:, s], k, v, (pos + 1).tolist())
    assert np.abs(np.asarray(ctx._data)[:, 0] - want).max() < 1e-5


def test_heads_of_128_take_the_path_they_always_took():
    """GPT's shapes: a row of one head, nothing packed, and the helpers
    that arrange packed queries hand their argument back untouched, so
    the traced program is the one it was."""
    model_free = PagedKVCache(1, 8, 16, 16, 128)
    assert model_free.head_pack == 1
    assert model_free.pools[0][0].shape == (16, 8, 16, 128)
    q = jnp.ones((2, 16, 1, 128))
    table = jnp.zeros((2, 4), jnp.int32)
    assert model_free.layer_views(table)[0].head_pack == 1
    assert attention._pack_queries(q, 1) is q
    assert attention._unpack_context(q, 1, 1, 128) is q
    # the packing is the view's to say, not the shapes': it rides from
    # the cache through the pools' tags to the views and back, static
    packed = PagedKVCache(1, 8, 16, 8, 64, head_pack=2)
    view = packed.layer_views(table)[0]
    assert view.head_pack == 2 and view.k_pool.shape == (4, 8, 16, 128)
    again = jax.jit(lambda v: v)(view)
    assert again.head_pack == 2
    assert pools_from_views([again])[0].head_pack == 2
    # unpacked heads of 64 (a sharded or quantized pool): one a row
    assert PagedKVCache(1, 8, 16, 8, 64).layer_views(table)[0].head_pack == 1
    with pytest.raises(ValueError, match="does not pack"):
        LayerPool("latent", (q,), 2)
    got = attention._pack_queries(jnp.arange(2. * 4 * 3 * 64).reshape(
        2, 4, 3, 64), 2)
    assert got.shape == (2, 2, 6, 128)
    assert not np.asarray(got[:, :, :3, 64:]).any()
    assert not np.asarray(got[:, :, 3:, :64]).any()


@pytest.mark.parametrize("option,kwargs", [
    ("tp_size", {"tp_size": 2}),
    ("kv_dtype", {"kv_dtype": "int8"}),
    ("kv_dtype", {"kv_dtype": "fp8"}),
    ("enable_prefix_caching", {"enable_prefix_caching": True}),
    ("enable_chunked_prefill", {"enable_chunked_prefill": True}),
    ("spec_config", {"spec_config": object()}),
])
def test_engine_refuses_what_state_slots_cannot_run(seeded, option, kwargs):
    model, _, _ = seeded
    with pytest.raises(ValueError, match=option):
        ServingEngine(model, page_size=8, max_batch_size=2, max_seq_len=64,
                      **kwargs)


def test_static_cache_generation_refuses_the_model_by_name(seeded):
    from paddle_tpu.models.generation import init_caches
    model, _, _ = seeded
    with pytest.raises(NotImplementedError, match="HybridSsmForCausalLM"):
        init_caches(model, 1, 32)


def test_offset_prefill_over_state_slots_is_refused(seeded):
    model, _, _ = seeded
    cache = PagedKVCache.for_model(model, 10, 8, state_slots=1)
    views = cache.layer_views(jnp.zeros((1, 4), jnp.int32),
                              slots=jnp.ones((1,), jnp.int32))
    with pytest.raises(NotImplementedError, match="offset"):
        model(jnp.zeros((1, 8), jnp.int32), caches=views,
              start_pos=jnp.int32(8))


def test_config_takes_the_published_keys_and_refuses_other_values():
    full = HybridSsmConfig.granite_4_0_h_micro()
    assert full.layer_types.count("attention") == 4
    assert [i for i, t in enumerate(full.layer_types)
            if t == "attention"] == [5, 15, 25, 35]
    assert full.d_inner == 4096 and full.conv_dim == 4352
    with pytest.raises(ValueError, match="mamba_n_groups"):
        HybridSsmConfig.tiny(mamba_n_groups=2)
    with pytest.raises(ValueError, match="layer_types"):
        HybridSsmConfig.tiny(num_hidden_layers=5)
    with pytest.raises(ValueError, match="Mamba layers alone"):
        HybridSsmConfig.tiny(layer_types=("mamba",) * 4)


def test_deferred_weights_allocate_nothing():
    model = HybridSsmForCausalLM(HybridSsmConfig(
        dtype="bfloat16", deferred_weights=True))
    params = dict(model.named_parameters())
    assert all(isinstance(p._data, jax.ShapeDtypeStruct)
               for p in params.values())
    total = sum(int(np.prod(p.shape)) for p in params.values())
    assert 3.18e9 < total < 3.2e9
    assert params["model.layers.0.mamba.A_log"]._data.dtype == jnp.float32
    assert params["model.layers.0.mamba.in_proj.weight"]._data.dtype == \
        jnp.bfloat16
