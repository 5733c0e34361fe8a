"""Sparse latent attention and a held share of routed experts
(models/mla_moe.py with `index_topk`, `n_group`, `rope_scaling`,
`router_experts` set; serving/attention.py `dsa_index`, `dsa_select`,
`mla_sparse_decode`): the model against the benchmark's plain reference,
prefill then decode through the indexed latent pool, the two kernels in
interpret mode against their jnp paths, the choice's tie rule, the share
of the experts against the whole, the group limit against a hand-written
case, YaRN against hand numbers, the pool's bytes, the engine's counters
and its refusals."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights
from chipbench.reference import mla_sparse_moe as reference
from paddle_tpu.models import MlaMoeConfig, MlaMoeForCausalLM, mla_moe
from paddle_tpu.serving import ServingEngine, attention
from paddle_tpu.serving.kv_cache import (LatentLayerCache, PagedKVCache,
                                         overflow_position, pools_from_views,
                                         views_from_pools)

# `MlaMoeConfig.tiny` with every mechanism on: 8 of up to 40 positions
# attended, 4 groups of 4 experts of which 2 stay, YaRN over 32
# positions, experts 4-7 of 16 held
CFG = MlaMoeConfig.tiny_sparse(router_experts=16, n_routed_experts=4,
                               expert_offset=4)


def _seeded(cfg, dtype=jnp.float32, seed=31):
    as_dict = dataclasses.asdict(cfg)
    leaves = weights.make(reference.shapes(as_dict), seed, dtype)
    model = MlaMoeForCausalLM(cfg)
    model.eval()
    params = dict(model.named_parameters())
    assert set(params) == set(leaves)
    for name, p in params.items():
        assert tuple(p.shape) == tuple(leaves[name].shape), name
        p._data = leaves[name]
    return model, leaves, as_dict


@pytest.fixture(scope="module")
def seeded():
    """The tiny model holding the benchmark's seeded float32 leaves."""
    return _seeded(CFG)


@pytest.fixture
def kernel_mode(monkeypatch):
    def set_mode(mode):
        monkeypatch.setattr(attention, "KERNEL_MODE", mode)
    return set_mode


def test_model_matches_the_reference_in_float32(seeded):
    """One causal forward, 40 positions: the first 8 attend everything
    before them, the others the 8 their indexer chooses."""
    model, leaves, cfg = seeded
    ids = np.random.default_rng(0).integers(0, CFG.vocab_size, (2, 40))
    got = np.asarray(model(jnp.asarray(ids))._data)
    for row in range(2):
        want = np.asarray(reference.logits(leaves, ids[row], np.arange(40),
                                           cfg))
        # float32 against float32 at `highest`: rounding alone
        assert np.abs(got[row] - want).max() < 1e-4
    # the selection is seen: attending everything gives other logits
    dense = np.asarray(reference.logits(
        leaves, ids[0], np.arange(40), {**cfg, "index_topk": None}))
    assert np.abs(dense[:8] - got[0, :8]).max() < 1e-4
    assert np.abs(dense[8:] - got[0, 8:]).max() > 1e-2


def test_long_prefill_paths_equal_the_whole(seeded, monkeypatch):
    """Queries in several blocks (the last one padded), the MLP half and
    the norms in chunks: what a 32,768-token prefill of a 7,168-wide
    model takes, at 40 tokens."""
    model, _, _ = seeded
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, CFG.vocab_size, (1, 40)))
    whole = np.asarray(model(ids)._data)
    monkeypatch.setattr(mla_moe, "_SPARSE_PREFILL_QUERIES", 16)
    monkeypatch.setattr(mla_moe, "_SPARSE_PREFILL_HEADS", 2)
    monkeypatch.setattr(attention, "_DSA_PREFILL_KEY_BLOCK", 16)
    monkeypatch.setattr(mla_moe, "_LAYER_CHUNK_ELEMENTS", 64)
    monkeypatch.setattr(mla_moe, "_MOE_CHUNK_TOKENS", 16)
    parts = np.asarray(model(ids)._data)
    assert np.abs(whole - parts).max() < 1e-5


@pytest.mark.parametrize("mode", ["off", "interpret"])
def test_prefill_then_decode_through_the_indexed_pool(seeded, kernel_mode,
                                                      mode):
    """Logits, not tokens: a prefill (the prompt ends mid-page, the
    bucket is padded) and six decode steps over three rows, against the
    reference's one forward over each whole sequence. Row 0 stays under
    `index_topk` positions for three steps (every position chosen), row
    1 is over it from its prefill on, row 2 is parked from the third
    step on."""
    model, leaves, cfg = seeded
    kernel_mode(mode)
    ps, max_pages = 8, 8
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, CFG.vocab_size, n).tolist()
               for n in (5, 24, 13)]
    cache = PagedKVCache.for_model(model, 40, ps)
    assert cache.kind == "latent" and cache.index_dim == 32
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
        # only the held experts' pairs are counted, padding's never
        assert aux["moe_expert_tokens"].shape == (2, 4)
        assert 0 < int(aux["moe_expert_tokens"].sum()) < (
            len(prompt) * CFG.num_experts_per_tok * 2)
        seqs[i].append(int(np.argmax(np.asarray(want)[0])))
    park = overflow_position(max_pages, ps)
    for step in range(6):
        live = [True, True, step < 2]
        pos = np.array([len(s) - 1 if ok else park
                        for s, ok in zip(seqs, live)], np.int32)
        tok = np.array([[s[-1]] for s in seqs], np.int32)
        views = cache.layer_views(jnp.asarray(tables))
        logits, new, _ = model(jnp.asarray(tok), caches=views,
                               start_pos=jnp.asarray(pos))
        cache.update(new)
        for i, ok in enumerate(live):
            if not ok:
                continue
            want = np.asarray(reference.logits(
                leaves, seqs[i], [len(seqs[i]) - 1], cfg))[0]
            assert np.abs(np.asarray(logits._data)[i, 0] - want).max() < 1e-4
            seqs[i].append(int(np.argmax(want)))


def test_decode_over_a_long_row_of_a_large_pool(kernel_mode):
    """What the tiny cases above do not reach, in float32 where program
    and reference differ by rounding alone: a row of 75 pages (the page
    look-up's second row of 64), page ids of 300 and over (its high
    byte), a context of 5 blocks of 128 positions and 160 of them chosen
    (the choice's compaction across blocks). A prefill of 590 tokens and
    six decode steps through the kernels in interpret mode, which is the
    path that gathers the chosen rows through `_page_lookup`."""
    cfg = MlaMoeConfig.tiny_sparse(router_experts=16, n_routed_experts=4,
                                   expert_offset=4, index_topk=160,
                                   max_position_embeddings=1024)
    model, leaves, as_dict = _seeded(cfg, seed=37)
    kernel_mode("interpret")
    ps, max_pages = 8, 80
    prompt = np.random.default_rng(5).integers(0, cfg.vocab_size,
                                               590).tolist()
    cache = PagedKVCache.for_model(model, 400, ps)
    table = np.zeros((1, max_pages), np.int32)
    table[0, :75] = 399 - np.arange(75) * 4        # 399 .. 103, not in order
    table[0, :40] = 300 + np.random.default_rng(6).permutation(40)
    ids = np.zeros((1, 640), np.int32)
    ids[0, :590] = prompt
    logits, new, _ = model(jnp.asarray(ids),
                           caches=cache.layer_views(jnp.asarray(table)),
                           start_pos=0, logits_at=jnp.int32(589))
    cache.update(new)
    seq = list(prompt)
    want = np.asarray(reference.logits(leaves, seq, [589], as_dict))[0]
    assert np.abs(np.asarray(logits._data)[0, 0] - want).max() < 1e-4
    seq.append(int(np.argmax(want)))
    for _ in range(6):
        logits, new, _ = model(
            jnp.asarray([[seq[-1]]], jnp.int32),
            caches=cache.layer_views(jnp.asarray(table)),
            start_pos=jnp.asarray([len(seq) - 1], jnp.int32))
        cache.update(new)
        want = np.asarray(reference.logits(leaves, seq, [len(seq) - 1],
                                           as_dict))[0]
        assert np.abs(np.asarray(logits._data)[0, 0] - want).max() < 1e-4
        seq.append(int(np.argmax(want)))
    # the choice is seen at this length too
    dense = np.asarray(reference.logits(
        leaves, seq[:-1], [len(seq) - 2], {**as_dict, "index_topk": None}))
    assert np.abs(dense[0] - want).max() > 1e-3


def test_bf16_through_the_engine_stays_by_the_reference(kernel_mode):
    """bf16 weights, activations, rows and index keys with the kernels in
    interpret mode, through the engine, against the float32 reference
    over the same (bf16-valued) leaves. Logits, not tokens: every served
    token's logit lies within 0.12 of the reference's best, where the
    logits' own spread is 0.16. The room is for the choices, not for
    arithmetic: with 4 index heads and 8 positions attended, a choice
    that bf16 makes otherwise swaps an eighth of a query's keys (2,048
    are attended at the published size, where the chip's readings are
    PERF.md's); attending every position instead reads up to 0.57."""
    kernel_mode("interpret")
    worst = 0.0
    for seed in (3, 4):
        cfg = dataclasses.replace(CFG, dtype="bfloat16")
        model, leaves, as_dict = _seeded(cfg, jnp.bfloat16, seed)
        eng = ServingEngine(model, page_size=8, max_batch_size=4,
                            max_seq_len=64, num_pages=40, kv_dtype="bf16")
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(0, CFG.vocab_size, n).tolist()
                   for n in (5, 33)]
        rids = [eng.add_request(p, max_new_tokens=12, temperature=0.0,
                                seed=0) for p in prompts]
        eng.run()
        for rid, prompt in zip(rids, prompts):
            served = list(eng.requests[rid].generated)
            assert len(served) == 12
            ids = prompt + served
            rows = np.arange(len(prompt) - 1, len(ids) - 1)
            want = np.asarray(reference.logits(leaves, ids, rows, as_dict))
            gap = want.max(-1) - want[np.arange(len(rows)), served]
            worst = max(worst, float(gap.max()))
    assert worst < 0.12


def _index_case(dtype=jnp.float32):
    """Five rows over a pool of 30 pages of 8 index keys: ragged
    lengths, one row exactly on a block's edge, one parked, tables whose
    tails are the null page, which holds NaN."""
    ps, max_pages, width, heads = 8, 6, 32, 4
    rng = np.random.default_rng(2)
    keys = rng.normal(size=(30, ps, width)).astype(np.float32)
    keys[0] = np.nan
    pos = [2, 15, 40, overflow_position(max_pages, ps), 28]
    table = np.zeros((5, max_pages), np.int32)
    nxt = 1
    for i, n in enumerate(pos):
        if n < max_pages * ps:
            k = -(-(n + 1) // ps)
            table[i, :k] = np.arange(nxt, nxt + k)
            nxt += k
    q = rng.normal(size=(5, heads, width)).astype(np.float32)
    w = rng.normal(size=(5, heads)).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(w), jnp.asarray(keys, dtype),
            jnp.asarray(table), jnp.asarray(pos, jnp.int32))


@pytest.mark.parametrize("block_tokens", [16, 2048])
def test_dsa_index_kernel_against_the_jnp_path(monkeypatch, block_tokens):
    monkeypatch.setattr(attention, "_DSA_INDEX_BLOCK_TOKENS", block_tokens)
    q, w, keys, table, pos = _index_case()
    cache = LatentLayerCache(jnp.zeros((30, 8, 128)), table, keys)
    want = np.asarray(attention._dsa_index_reference(q, w, cache, pos))
    got = np.asarray(attention._dsa_index_pallas(q, w, keys, table, pos,
                                                 interpret=True))
    length = want.shape[1]
    assert got.shape[1] >= length
    live = np.isfinite(want)
    # every position up to the row's own is scored, none past it, the
    # parked row none at all, and no NaN of the null page comes through
    assert (np.isfinite(got[:, :length]) == live).all()
    assert not live[3].any() and live[2, :41].all()
    assert np.isneginf(got[:, length:]).all()
    assert np.abs(got[:, :length][live] - want[live]).max() < 1e-4


def test_dsa_select_takes_the_lower_position_among_equals():
    scores = jnp.asarray([[1.0, 3.0, 3.0, 2.0, 3.0, -jnp.inf, -jnp.inf, 0.0],
                          [0.5, 0.1, -jnp.inf] + [-jnp.inf] * 5,
                          [-jnp.inf] * 8])
    chosen, n = attention.dsa_select(scores, jnp.asarray([7, 1, 8]), 3)
    assert n.tolist() == [3, 2, 0]              # the last row is parked
    assert chosen[0].tolist() == [1, 2, 4]
    assert chosen[1, :2].tolist() == [0, 1]


def _stable_choice(scores, pos, k):
    """The `k` highest of scores[: pos + 1] by a stable sort, the lower
    position first among equals, in ascending order of position."""
    order = np.argsort(-scores[:pos + 1], kind="stable")[:k]
    return np.sort(order).tolist()


def test_dsa_select_over_several_blocks_against_a_stable_sort():
    """1,000 positions are 8 blocks of 128, the last one padded; 300 are
    chosen, more than two blocks' worth. Integer scores tie by the
    hundred, so the edge of the choice is cut by position inside a block.
    Rows end inside a block, on a block's last position, on its first,
    under the choice's size, and the last one is parked. Two are built: one
    whose members fill whole blocks and leave others empty (a block's
    end count equals a member's rank exactly), one whose every score is
    the same (the first 300 positions)."""
    rng = np.random.default_rng(7)
    length, k = 1000, 300
    scores = rng.integers(0, 4, (8, length)).astype(np.float32)
    scores[5] = 0.0
    scores[5, 128:256] = 9.0            # a whole block
    scores[5, 640:768] = 9.0            # another, two empty ones between
    scores[5, 900:944] = 9.0            # the last 44 of 300, mid-block
    scores[6] = 1.0
    pos = np.array([999, 517, 255, 256, 100, 999, 999, 1000], np.int32)
    masked = np.where(np.arange(length)[None] <= pos[:, None], scores,
                      -np.inf)
    chosen, n = attention.dsa_select(jnp.asarray(masked), jnp.asarray(pos),
                                     k)
    chosen, n = np.asarray(chosen), np.asarray(n)
    assert n.tolist() == [300, 300, 256, 257, 101, 300, 300, 0]
    for row in range(7):
        assert chosen[row, :n[row]].tolist() == _stable_choice(
            scores[row], int(pos[row]), k), row
    assert chosen[5, :300].tolist() == (list(range(128, 256))
                                        + list(range(640, 768))
                                        + list(range(900, 944)))
    assert chosen[6, :300].tolist() == list(range(300))


def test_dsa_select_at_the_published_choice_against_a_stable_sort():
    """2,048 of 5,000 (40 blocks): continuous scores, and the same
    rounded to a few hundred levels so that some 20 positions tie at the
    edge."""
    rng = np.random.default_rng(8)
    scores = rng.normal(size=(4, 5000)).astype(np.float32)
    scores[2:] = np.round(scores[2:] * 100) / 100
    pos = np.array([4999, 3000, 4999, 2047], np.int32)
    masked = np.where(np.arange(5000)[None] <= pos[:, None], scores, -np.inf)
    chosen, n = attention.dsa_select(jnp.asarray(masked), jnp.asarray(pos),
                                     2048)
    assert np.asarray(n).tolist() == [2048] * 4
    for row in range(4):
        assert np.asarray(chosen)[row].tolist() == _stable_choice(
            scores[row], int(pos[row]), 2048), row


def test_page_lookup_and_gather_against_take_along_axis():
    """A table of 200 pages a row is four rows of 64 (the last one
    padded) and ids up to 60,000 have a high byte up to 234: every entry
    comes back as `take_along_axis` gives it, an index past the table as
    the table's last. The gather of chosen rows through it brings
    pool[table[b, t // ps], t % ps]."""
    rng = np.random.default_rng(9)
    table = rng.integers(0, 60000, (3, 200)).astype(np.int32)
    table[0, 63:66] = [65535, 256, 255]         # the bytes' edges
    index = rng.integers(0, 200, (3, 500)).astype(np.int32)
    index[:, :200] = np.arange(200)             # every entry once
    index[1, 300:304] = [200, 263, 4000, 199]   # past the table
    got = np.asarray(attention._page_lookup(jnp.asarray(table),
                                            jnp.asarray(index)))
    want = np.take_along_axis(table, np.clip(index, 0, 199), 1)
    assert got.tolist() == want.tolist()

    ps, pages = 8, 300
    pool = rng.normal(size=(pages, ps, 128)).astype(np.float32)
    table = np.stack([rng.permutation(pages)[:70] for _ in range(2)]
                     ).astype(np.int32)
    assert table.max() > 255
    chosen = np.stack([rng.permutation(70 * ps)[:96] for _ in range(2)]
                      ).astype(np.int32)
    rows = np.asarray(attention._gather_chosen(
        jnp.asarray(pool), jnp.asarray(table), jnp.asarray(chosen)))
    for b in range(2):
        assert (rows[b] == pool[table[b, chosen[b] // ps],
                                chosen[b] % ps]).all()


def test_mla_sparse_decode_kernel_against_the_jnp_path(monkeypatch):
    """Chosen positions of five rows (more than a block, fewer, one, and
    none), gathered through the page table; what lies past a row's count
    is NaN's page and must not come through."""
    monkeypatch.setattr(attention, "_DSA_ATTEND_TOKENS", 16)
    ps, max_pages, width, latent, heads, k = 8, 6, 48, 32, 4, 40
    rng = np.random.default_rng(3)
    pool = rng.normal(size=(30, ps, 128)).astype(np.float32)
    pool[..., width:] = 0.0
    pool[0] = np.nan
    table = np.zeros((5, max_pages), np.int32)
    table[:4] = np.arange(1, 25).reshape(4, 6)
    counts = np.array([40, 7, 1, 17, 0], np.int32)
    chosen = np.zeros((5, k), np.int32)
    for i, n in enumerate(counts):
        chosen[i, :n] = rng.permutation(max_pages * ps)[:n]
        chosen[i, n:] = max_pages * ps + 5      # past the table: page 0
    q = jnp.asarray(rng.normal(size=(5, heads, width)), jnp.float32)
    cache = LatentLayerCache(jnp.asarray(pool), jnp.asarray(table))
    want = np.asarray(attention._mla_sparse_decode_reference(
        q, cache, jnp.asarray(chosen), jnp.asarray(counts), 0.2, latent))
    got = np.asarray(attention._mla_sparse_decode_pallas(
        q, cache.pool, cache.page_table, jnp.asarray(chosen),
        jnp.asarray(counts), scale=0.2, latent=latent, interpret=True))
    assert np.isfinite(got).all()
    assert np.abs(got[:4] - want[:4]).max() < 1e-4
    assert np.abs(got[4]).max() == 0.0          # nothing chosen
    # over every position of a row the choice is dense absorbed attention
    everything = jnp.arange(48, dtype=jnp.int32)[None]
    dense = attention._mla_decode_reference(
        q[:1], LatentLayerCache(cache.pool, cache.page_table[:1]),
        jnp.asarray([47]), 0.2, latent)
    sparse = attention._mla_sparse_decode_pallas(
        q[:1], cache.pool, cache.page_table[:1], everything,
        jnp.asarray([48]), scale=0.2, latent=latent, interpret=True)
    assert np.abs(np.asarray(dense) - np.asarray(sparse)).max() < 1e-4


def test_sparse_prefill_mask_against_a_loop():
    """Scores with ties on purpose (integers), queries at positions 5 to
    12 of 16 keys, 4 attended: the loop sorts each query's causal scores
    stably, highest first."""
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.integers(-1, 2, (8, 2, 4)), jnp.float32)
    keys = jnp.asarray(rng.integers(-1, 2, (16, 4)), jnp.float32)
    w = jnp.asarray(rng.integers(1, 3, (8, 2)), jnp.float32)
    mask = np.asarray(attention.sparse_prefill_mask(q, w, keys,
                                                    jnp.int32(5), 4))
    index = np.einsum("qh,qhk->qk", np.asarray(w), np.maximum(
        np.einsum("qhw,kw->qhk", np.asarray(q), np.asarray(keys)), 0))
    for i in range(8):
        t = 5 + i
        order = np.argsort(-index[i, :t + 1], kind="stable")[:4]
        want = np.full((16,), -np.inf)
        want[order] = 0.0
        assert mask[i].tolist() == want.tolist(), i
    # fewer keys than the choice holds: every causal key
    short = np.asarray(attention.sparse_prefill_mask(q[:2], w[:2], keys[:3],
                                                     jnp.int32(1), 4))
    assert short.tolist() == [[0.0, 0.0, -np.inf], [0.0, 0.0, 0.0]]


def test_group_limit_changes_the_choice():
    """8 experts in 4 groups of 2, 2 groups stay, 3 experts a token.
    Without the limit the three highest are experts 0, 2 and 4; a
    group's score is the sum of its two, so groups 0 (0.9 + 0.1) and 3
    (0.45 + 0.5) stay, group 1 (0.8 + 0.05) and group 2 (0.7 + 0.0) go,
    and the choice is 0, 7, 6."""
    scores = jnp.asarray([[0.9, 0.1, 0.8, 0.05, 0.7, 0.0, 0.45, 0.5]])
    free = jax.lax.top_k(scores, 3)[1]
    limited = jax.lax.top_k(
        mla_moe.group_limited_scores(scores, 4, 2), 3)[1]
    assert free.tolist() == [[0, 2, 4]]
    assert limited.tolist() == [[0, 7, 6]]
    # equal groups: the lower stays
    tie = jnp.asarray([[0.5, 0.5, 0.5, 0.5, 0.6, 0.4, 0.1, 0.1]])
    kept = np.isfinite(np.asarray(
        mla_moe.group_limited_scores(tie, 4, 2)))[0]
    assert kept.tolist() == [True, True, True, True] + [False] * 4


@pytest.mark.parametrize("path", ["grouped", "dense"])
def test_shares_of_the_experts_add_up_to_the_whole(monkeypatch, path):
    """The guide's share test: 4 chips hold 4 of 16 experts each. The
    held experts' parts of the four shares, with the shared expert
    counted once, are the uncut reference's layer output; by the grouped
    matmuls a prefill takes and by the dense form a decode step's few
    rows take."""
    if path == "grouped":
        monkeypatch.setattr(mla_moe, "_DENSE_SHARE_TOKENS", 0)
    assert (37 <= mla_moe._DENSE_SHARE_TOKENS) == (path == "dense")
    whole_cfg = MlaMoeConfig.tiny_sparse()
    as_dict = dataclasses.asdict(whole_cfg)
    leaves = weights.make(reference.shapes(as_dict), 7, jnp.float32)
    p = "model.layers.1."
    layer = {k[len(p):]: v for k, v in leaves.items() if k.startswith(p)}
    x = jnp.asarray(np.random.default_rng(5).normal(size=(37, 64)),
                    jnp.float32)
    d = reference._dims(as_dict)
    with jax.default_matmul_precision("highest"):
        small = {k: v for k, v in layer.items()
                 if k.startswith(("mlp.gate.", "mlp.shared", "post_"))}
        y, chosen, g, _, with_shared = reference._route_and_share(
            x, small, eps=1e-6, top_k=d["top_k"], n_group=d["n_group"],
            topk_group=d["topk_group"], scale=2.5, offset=0, held=16,
            matmul=None)
        want = np.asarray(reference._experts(
            with_shared, y, chosen, g, layer, 0, 16, None) - x)
    valid = jnp.ones((37,), bool)
    routed, tokens = np.zeros((37, 64), np.float32), 0
    for share in range(4):
        held = slice(4 * share, 4 * share + 4)
        out, sizes = mla_moe.dropless_moe(
            y, valid, layer["mlp.gate.weight"],
            layer["mlp.gate.e_score_correction_bias"],
            layer["mlp.experts.gate_proj"][held],
            layer["mlp.experts.up_proj"][held],
            layer["mlp.experts.down_proj"][held],
            top_k=4, scale=2.5, n_group=4, topk_group=2,
            expert_offset=4 * share)
        routed += np.asarray(out)
        tokens += int(sizes.sum())
    shared = np.asarray(mla_moe._swiglu(
        y, layer["mlp.shared_experts.gate_proj.weight"],
        layer["mlp.shared_experts.up_proj.weight"],
        layer["mlp.shared_experts.down_proj.weight"]))
    assert tokens == 37 * 4                     # every pair held once
    assert np.abs(routed + shared - want).max() < 1e-5


def test_yarn_frequencies_and_scale_at_the_published_numbers():
    scaling = {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
               "mscale": 1, "mscale_all_dim": 1,
               "original_max_position_embeddings": 4096}
    got = np.asarray(mla_moe._rope_freq(64, 10000.0, scaling))
    plain = 10000.0 ** (-np.arange(32) / 32)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)   # low 10, high 23
    assert np.allclose(got, plain * (1 - ramp) + plain / 40 * ramp,
                       rtol=1e-5)
    assert np.allclose(got[:11], plain[:11], rtol=1e-6)
    assert np.allclose(got[23:], plain[23:] / 40, rtol=1e-5)
    assert np.allclose(got, reference.rope_frequencies(64, 10000.0, scaling),
                       rtol=1e-5)
    assert abs(mla_moe._softmax_mscale(scaling) - 1.87386) < 1e-4
    assert mla_moe._softmax_mscale(None) == 1.0
    with pytest.raises(NotImplementedError, match="yarn"):
        MlaMoeConfig(rope_scaling={"type": "linear", "factor": 2})


def test_indexed_latent_pool_holds_two_arrays_under_one_table(seeded):
    model, _, _ = seeded
    cache = PagedKVCache.for_model(model, 10, 8, kv_dtype="bf16")
    # a row 32 + 16 wide in whole 128-lane tiles, and a key of 32
    assert [tuple(a.shape) for a in cache.pools[0]] == [(10, 8, 128),
                                                        (10, 8, 32)]
    assert cache.slot_elems == 128 + 32
    assert cache.page_bytes == 3 * 8 * 160 * 2
    assert cache.pool_bytes == 10 * cache.page_bytes
    views = cache.layer_views(jnp.zeros((2, 4), jnp.int32))
    assert all(v.index_pool is not None for v in views)
    assert [len(p) for p in pools_from_views(views)] == [2, 2, 2]
    back = views_from_pools(pools_from_views(views), views[0].page_table)
    assert jax.tree_util.tree_structure(back) == \
        jax.tree_util.tree_structure(views)
    # at the published widths: 640 x 2 B of row + 128 x 2 B of key
    big = PagedKVCache(5, 2, 16, 128, 56, jnp.bfloat16, latent_dim=576,
                       index_dim=128)
    assert big.slot_elems * 2 == 1536           # B a token a layer
    assert big.page_bytes == 122880             # 16 tokens, 5 layers
    with pytest.raises(ValueError, match="latent_dim"):
        PagedKVCache(5, 2, 16, 128, 56, index_dim=128)
    with pytest.raises(ValueError, match="no kv-head axis"):
        cache.shard_pools(None, None)
    # written together or not at all
    with pytest.raises(ValueError, match="index keys"):
        attention.latent_write(jnp.zeros((2, 1, 48)), views[0],
                               jnp.zeros((2,), jnp.int32))


def test_indexed_pool_refuses_more_pages_than_two_bytes_name():
    """`_page_lookup` carries a page id as two bytes: a 65,537th page
    would come back as another session's. A latent pool without index
    keys is looked up by the kernel's own scalar reads and takes any."""
    with pytest.raises(ValueError, match="at most 65536 pages"):
        PagedKVCache(1, (1 << 16) + 1, 1, 1, 1, latent_dim=8, index_dim=8)
    assert PagedKVCache(1, (1 << 16) + 1, 1, 1, 1,
                        latent_dim=8).index_dim is None


def test_engine_serves_and_counts_the_keys_it_attends(seeded, kernel_mode):
    model, leaves, cfg = seeded
    kernel_mode("interpret")
    eng = ServingEngine(model, page_size=8, max_batch_size=4, max_seq_len=64)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, CFG.vocab_size, n).tolist()
               for n in (5, 30, 17)]
    rids = [eng.add_request(p, max_new_tokens=12, temperature=0.0, seed=0)
            for p in prompts]
    eng.run()
    for rid, prompt in zip(rids, prompts):
        req = eng.requests[rid]
        assert req.status == "finished" and len(req.generated) == 12
        ids = prompt + req.generated
        want = np.asarray(reference.logits(
            leaves, ids, np.arange(len(prompt) - 1, len(ids) - 1), cfg))
        assert want.argmax(-1).tolist() == req.generated
    counters = {m.name: m.value for m in eng.metrics.collect()
                if m.name.startswith(("serving_dsa_", "serving_moe_pairs"))}
    # 11 decode steps a row in 3 layers: the context of step j of a row
    # is prompt + 1 + j keys, of which at most 8 are attended
    contexts = [len(p) + 1 + j for p in prompts for j in range(11)]
    assert counters["serving_dsa_keys_in_context_total"] == 3 * sum(contexts)
    assert counters["serving_dsa_keys_selected_total"] == 3 * sum(
        min(c, 8) for c in contexts)
    # pairs of held experts only: 4 of 16 are here
    processed = sum(len(p) + 11 for p in prompts)
    assert 0 < counters["serving_moe_pairs_total"] < processed * 4 * 2
    assert eng.fault_events == 0


@pytest.mark.parametrize("option,kwargs", [
    ("tp_size", {"tp_size": 2}),
    ("kv_dtype", {"kv_dtype": "int8"}),
    ("enable_prefix_caching", {"enable_prefix_caching": True}),
    ("enable_chunked_prefill", {"enable_chunked_prefill": True}),
    ("spec_config", {"spec_config": object()}),
])
def test_engine_refuses_what_an_indexed_pool_cannot_run(seeded, option,
                                                        kwargs):
    model, _, _ = seeded
    with pytest.raises(ValueError, match=option):
        ServingEngine(model, page_size=8, max_batch_size=2, max_seq_len=64,
                      **kwargs)


def test_offset_prefill_and_static_cache_are_refused(seeded):
    model, _, _ = seeded
    cache = PagedKVCache.for_model(model, 10, 8)
    views = cache.layer_views(jnp.asarray([[1, 2, 3, 4]], jnp.int32))
    with pytest.raises(NotImplementedError, match="index"):
        model(jnp.zeros((1, 8), jnp.int32), caches=views,
              start_pos=jnp.int32(8))
    from paddle_tpu.models.generation import generate
    with pytest.raises(NotImplementedError, match="MlaMoeForCausalLM"):
        generate(model, jnp.zeros((1, 4), jnp.int32), max_new_tokens=2)


def test_config_refuses_a_share_the_router_does_not_hold():
    with pytest.raises(ValueError, match="router"):
        MlaMoeConfig(n_routed_experts=8, router_experts=16, expert_offset=12)
    with pytest.raises(ValueError, match="n_group"):
        MlaMoeConfig(n_group=3)
    # the defaults are the model without the mechanisms: no indexer
    plain = MlaMoeForCausalLM(MlaMoeConfig.tiny())
    assert not [n for n, _ in plain.named_parameters() if "indexer" in n]
    assert MlaMoeConfig.tiny().index_cache_dim is None
