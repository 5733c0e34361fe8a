"""The decode-pool cell of the configuration with sparse latent attention
and a held share of experts, on the CPU at tiny sizes: its driver's
set-up and window as a function with the kernels in interpret mode, what
`correct` has to catch (the kernels off, the three stand-ins of
`control`), what the choice alone reads under rounded index operands
(`selection_noise`), a program from before the indexer failing at import, the
operations and bytes of `flops_mla_sparse_moe.py` against hand counts,
and the loader's view of the new cell and of the configuration's file.
Nothing here is a measurement.

The tiny cell states float32 (the cell states bfloat16): with 4 index
heads and 8 positions attended a choice that bf16 makes otherwise swaps
an eighth of a query's keys and reads like a fault (0.07 to 0.13 over
three seeds, the fp8 control 0.09 to 0.46); 2,048 are attended at the
published size, where the limit is set from the chip's readings. In
float32 the program's mean gap reads 0.0, the reference in bf16 0.0038
to 0.0072, the selection dropped 0.104 to 0.113 and the share shifted
0.0045 to 0.0073 (three seeds; the widest gaps 0.07 to 0.18, 0.40 to
0.46, 0.10 to 0.17)."""
import copy
import gc
import importlib
import json
import sys
import time
import types

import jax
import pytest

from chipbench import common, flops_mla_sparse_moe as flops, lowprec, run, spec
from chipbench.drivers import serve_decodepool
from chipbench.programs import mla_sparse_moe_engine

CELL = "deepseek_v3_2_l5_e8_decodepool_c32"
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
# the structure kept: 1 dense + 2 expert layers, experts 4-7 of 16 in 4
# groups of which 2 stay, top-4 + 1 shared, 4 heads of 32 + 16 / 32, ranks
# 48 and 32, 4 index heads of 32, 8 positions attended
TINY = dict(vocab_size=512, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
            intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=4, router_experts=16, expert_offset=4,
            num_experts_per_tok=4, n_group=4, topk_group=2,
            index_n_heads=4, index_head_dim=32, index_topk=8,
            precision="float32")


def tiny() -> dict:
    s = copy.deepcopy(spec.load_cell(CELL))
    s["config"].update(TINY)
    s["config"]["rope_scaling"]["original_max_position_embeddings"] = 32
    s["config"]["program"]["engine"].update(
        max_batch_size=4, max_seq_len=160, num_pages=90, page_size=8,
        kv_dtype="fp32")
    s["traffic"].update(
        clients=4, block=4, reference_pad=128, reference_margin=0.0,
        prompt_len={"dist": "loguniform", "lo": 30, "hi": 100},
        output_len=40, sample_requests=3, trace_after_seconds=0.5,
        trace_seconds=0.5)
    s["limits"] = {"served_logit_gap_mean": 0.001}
    s["config"]["assumed"].pop("leaf_scales")
    return s


@pytest.fixture
def interpret_kernels(monkeypatch):
    from paddle_tpu.serving import attention as paged
    monkeypatch.setattr(paged, "KERNEL_MODE", "interpret")


@pytest.fixture
def bf16_below_float32(monkeypatch):
    """The tiny cell's control: the reference with its matmuls' operands
    rounded to bf16 (`reduce_precision`: a cast there and back may round
    nothing under jit)."""
    def bf16(x):
        return jax.lax.reduce_precision(x, 8, 7)

    monkeypatch.setitem(lowprec.BELOW, "float32",
                        lowprec._matmul(bf16, bf16))


def _measure(s, seed, trace=False, seconds=2.0):
    return run.measure(s, seed, seconds, trace, DEVICE, time.time())


def test_cell_end_to_end_tiny(interpret_kernels, monkeypatch, tmp_path):
    monkeypatch.setattr(common, "TRACE_DIR", str(tmp_path / "trace"))
    # the reference has the device to itself: the engine's pool (90 pages
    # here, 5.9 GB on the chip, where it left the reference 1.4 GB) is
    # gone when the first session is compared, though the engine is part
    # of cycles and no collection comes of itself
    pools_left = []
    compare = serve_decodepool.position_gaps

    def watched(*args, **kwargs):
        pools_left.append([a.shape for a in jax.live_arrays()
                           if a.shape[:1] == (90,)])
        return compare(*args, **kwargs)

    monkeypatch.setattr(serve_decodepool, "position_gaps", watched)
    gc.disable()
    try:
        out = _measure(tiny(), 2 ** 31 + 35)
    finally:
        gc.enable()
    assert pools_left and not any(pools_left), pools_left
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["attempted"] >= 4 and out["failed"] == 0
    assert set(out["checks"]) == {
        "served_logit_gap_mean", "requests_not_finished",
        "token_count_mismatches", "fault_events",
        "reference_path_dispatches", "no_dsa_index_pallas_dispatch",
        "no_mla_sparse_decode_pallas_dispatch", "compiled_in_window"}
    assert {"selection_margin_min", "served_logit_gap",
            "positions_kept_share"} <= set(out["not_compared"])
    traced = _measure(tiny(), 7, trace=True)
    assert traced["correct"], traced["checks"]
    # no TPU plane on the CPU: the device readers return nothing and the
    # line lacks them; the clocks and the program's counters are there
    assert set(traced["metrics"]) == {
        "engine_step_ms_p50.serve", "serve_mfu_pct", "tpot_p95_ms.serve",
        "tpot_p50_ms.serve", "decode_batch_occupancy_pct.serve",
        "dsa_keys_selected_pct.serve"}
    values = {k: v["value"] for k, v in traced["metrics"].items()}
    # 8 keys attended of 30 to 140 in a row's context
    assert 100 * 8 / 140 < values["dsa_keys_selected_pct.serve"] < 100 * 8 / 30
    assert 0 < values["decode_batch_occupancy_pct.serve"] <= 100
    assert values["serve_mfu_pct"] > 0
    json.dumps(traced)


def test_cell_with_the_kernels_off_is_not_correct():
    """KERNEL_MODE 'auto' on the CPU takes the jnp paths: the tokens are
    right, `*_reference*` paths were dispatched and neither kernel."""
    out = _measure(tiny(), 3)
    assert not out["correct"]
    assert out["checks"]["served_logit_gap_mean"]["ok"]
    assert [k for k, v in out["checks"].items() if not v["ok"]] == [
        "reference_path_dispatches", "no_dsa_index_pallas_dispatch",
        "no_mla_sparse_decode_pallas_dispatch"]


def test_every_stand_in_comes_out_as_not_correct(interpret_kernels,
                                                 bf16_below_float32):
    s = tiny()
    record = serve_decodepool.run(s, 11, 2.0, False, time.time())
    assert record["checks"].ok, record["checks"].as_dict()
    lines = serve_decodepool.control(s, record)
    assert set(lines) == {"control", "selection_dropped", "share_shifted"}
    for name, line in lines.items():
        assert not line["ok"], name
        assert line["failed"] == ["served_logit_gap_mean"]
    # what a program that ignored the indexer would serve is the plainest
    assert lines["selection_dropped"]["served_logit_gap"] > 0.2


def test_rounded_index_operands_alone_move_the_choice(interpret_kernels):
    """`selection_noise`: the exact algorithm with its index queries and
    keys rounded to bf16 and nothing else. At the tiny size (8 keys
    attended, float32 program) the program's own mean gap reads 0.0, so
    whatever this stand-in reads is the choice's: some queries keep all
    of their 8 keys and some lose one, the stand-in's first choice stays
    the reference's at most positions, and the program's tokens judged
    by the stand-in's logits read what the stand-in reads judged by the
    reference's, to the order."""
    s = tiny()
    record = serve_decodepool.run(s, 13, 2.0, False, time.time())
    assert record["checks"].ok, record["checks"].as_dict()
    noise = serve_decodepool.selection_noise(s, record, sessions=2)
    assert set(noise) == {
        "rounded_index", "program_against_rounded_index",
        "selection_kept_mean", "selection_kept_min",
        "rounded_index_first_choice_share"}
    # seed 13: a query in 27 loses one of its 8 keys (0.9953 kept in the
    # mean, 0.875 the least), 97.5% of the stand-in's first choices are
    # the reference's, and the means read 0.0009 and 0.0013
    assert 0.9 < noise["selection_kept_mean"] < 1.0
    assert 0.5 <= noise["selection_kept_min"] <= 7 / 8
    assert 0.8 < noise["rounded_index_first_choice_share"] < 1.0
    own = noise["rounded_index"]["served_logit_gap_mean"]
    other = noise["program_against_rounded_index"]["served_logit_gap_mean"]
    assert 0.0 < own < 0.01 and 0.0 < other < 0.01
    # the reference itself keeps all of its own choice
    from chipbench.reference import mla_sparse_moe as reference
    rec = record["replay"]["sampled"][0]
    ids, rows = serve_decodepool._sequence(rec, 128)
    _, margins = reference.logits(record["replay"]["leaves"], ids, rows,
                                  s["config"], with_margin=True)
    assert (margins["selection_kept"] == 1.0).all()


def test_leaf_scales_draw_named_leaves_their_own_way():
    from chipbench.reference import mla_sparse_moe as reference
    cfg = tiny()["config"]
    plain = serve_decodepool.make_leaves(reference, cfg, 5)
    cfg["assumed"] = {"leaf_scales": {"self_attn.q_b_proj.weight": 4.0}}
    scaled = serve_decodepool.make_leaves(reference, cfg, 5)
    for name in plain:
        ratio = 4.0 if name.endswith("self_attn.q_b_proj.weight") else 1.0
        assert float(abs(scaled[name] - ratio * plain[name]).max()) == 0.0


def test_a_program_from_before_the_indexer_fails_at_import(monkeypatch):
    """The parent commit's `MlaMoeConfig` has none of the keys: the cell
    ends at the driver's first line, before a weight is made."""
    import dataclasses

    @dataclasses.dataclass
    class Old:
        hidden_size: int = 64
        n_routed_experts: int = 16

    stub = types.ModuleType("paddle_tpu.models.mla_moe")
    stub.MlaMoeConfig, stub.MlaMoeForCausalLM = Old, object
    monkeypatch.setitem(sys.modules, "paddle_tpu.models.mla_moe", stub)
    try:
        with pytest.raises(ImportError, match="index_topk"):
            importlib.reload(mla_sparse_moe_engine)
    finally:
        monkeypatch.undo()
        importlib.reload(mla_sparse_moe_engine)


def test_reference_in_blocks_equals_the_whole(monkeypatch):
    """The reference takes its queries and its dense MLP a block of rows
    at a time; 384 rows are not whole blocks of 256 (on the chip 34,816
    were not whole blocks of 4,096, and a block moved back over rows
    already done read as a fault of the program: PERF.md section 6). A
    block of queries sees the keys up to the next multiple past its end,
    no block past the last asked position is run, and the last layer
    runs the blocks that hold an asked position alone: with blocks of
    128, 160 and 256 over 640 rows (the last of 256 the shorter) and
    positions 250-299 or 520-599 asked, the same logits and margins as
    in one block over every key."""
    import numpy as np
    from chipbench import weights
    from chipbench.reference import mla_sparse_moe as reference
    cfg = tiny()["config"]
    leaves = weights.make(reference.shapes(cfg), 3, jax.numpy.float32)
    ids = np.random.default_rng(0).integers(0, 512, (600,))
    whole = {}
    for block, mlp_rows, keys, rows in ((128, 256, 128, (250, 300)),
                                        (160, 128, 256, (250, 300)),
                                        (256, 4096, 128, (520, 600))):
        rows = np.arange(*rows)
        if rows[0] not in whole:
            monkeypatch.undo()
            whole[rows[0]] = reference.logits(leaves, ids, rows, cfg,
                                              with_margin=True)
        whole_, clear = whole[rows[0]]
        monkeypatch.setattr(reference, "_MLP_ROWS", mlp_rows)
        monkeypatch.setattr(reference, "_QUERY_BLOCK", block)
        monkeypatch.setattr(reference, "_KEY_MULTIPLE", keys)
        monkeypatch.setattr(reference, "_ROWS_MULTIPLE", 32)
        parts, margins = reference.logits(leaves, ids, rows, cfg,
                                          with_margin=True)
        assert np.abs(np.asarray(whole_) - np.asarray(parts)).max() < 1e-5
        for k, m in clear.items():
            np.testing.assert_allclose(margins[k], m, atol=1e-6)


def test_reference_choice_against_a_stable_sort():
    """`_highest`: the topk highest of a row, the lower position first
    among equals, with the scores in levels so that the edge lies among
    equals by the dozen; rows with fewer scores than topk (the others
    -inf) take them all; the edge is the last chosen and the first left
    out."""
    import numpy as np
    from chipbench.reference import mla_sparse_moe as reference
    rng = np.random.default_rng(5)
    index = np.round(rng.normal(size=(24, 300)) * 4).astype(np.float32) / 4
    index[0] = 1.0                               # all equal
    index[1, 40:] = -np.inf                      # fewer than topk
    index[2, 64:] = -np.inf                      # exactly topk
    for topk in (1, 64, 299, 300, 400):
        edge, chosen = reference._highest(jax.numpy.asarray(index), topk)
        order = np.argsort(-index, axis=-1, kind="stable")
        want = np.zeros(index.shape, bool)
        np.put_along_axis(want, order[:, :topk], True, -1)
        finite = np.isfinite(index)
        assert (np.asarray(chosen) & finite == want & finite).all()
        if topk >= index.shape[1]:
            assert edge is None
            continue
        ranked = np.take_along_axis(index, order, -1)
        np.testing.assert_array_equal(np.asarray(edge)[:, 1],
                                      ranked[:, topk - 1])
        np.testing.assert_array_equal(np.asarray(edge)[:, 0], ranked[:, topk])


def test_flops_against_hand_counts():
    cfg = spec.load_cell(CELL)["config"]
    # the issue's arithmetic: attention 187.11 M and indexer 13.96 M
    # parameters a layer, an expert 44.04 M, the dense MLP 396.36 M, the
    # router 1.84 M
    attn = 2 * (187_105_280 + 13_959_168)
    expert = 2 * 44_040_192
    dense = 2 * 396_361_728
    router = 2 * 7168 * 256
    index_pair, attend_pair = 2 * 64 * 128, 2 * 128 * (192 + 128)
    head = 2 * 7168 * 16160
    token = 5 * attn + dense + 4 * (expert + router)
    # one decode step at a context of 20,000: scores against every key,
    # attention over 2,048
    assert flops.serve_flops(cfg, 20000, 1, True) == token + 5 * (
        index_pair * 20000 + attend_pair * 2048) + head
    # under 2,048 keys every one is attended; the count grows a step
    assert flops.serve_flops(cfg, 100, 3, True) == 3 * (token + head) + 5 * (
        (index_pair + attend_pair) * (100 + 101 + 102))
    # a prefill inside the window: the 9 tokens before the first step too
    assert flops.serve_flops(cfg, 10, 1, False) == 10 * token + head + 5 * (
        (index_pair + attend_pair) * 55)
    assert flops.serve_flops(cfg, 10, 0, True) == 0.0
    assert flops.expert_flops(cfg) == expert
    # the kernels' work, a key: 256 B and 16,384 operations scored, 1,280
    # B and 278,528 operations attended
    assert flops.dsa_index_work(cfg, 1000) == {
        "flops": 1000 * 16384.0, "bytes": 1000 * 256.0}
    assert flops.mla_sparse_decode_work(cfg, 1000) == {
        "flops": 1000 * 278528.0, "bytes": 1000 * 1280.0}
    work = flops.moe_experts_work(cfg, pairs=8, experts_touched=5)
    assert work["flops"] == 8 * expert
    assert work["bytes"] == 2 * (5 * 44_040_192 + 8 * 2 * 7168)


def test_loader_gives_the_new_cell_its_metrics_and_its_file():
    new = spec.load_cell(CELL)
    names = {m["name"] for m in new["per_layer"]}
    assert names == {
        "dsa_share_pct.serve", "dsa_select_share_pct.serve",
        "dsa_index_kernel_roofline.serve",
        "mla_sparse_decode_kernel_roofline.serve",
        "dsa_keys_selected_pct.serve", "serve_mfu_pct",
        "engine_step_ms_p50.serve", "device_idle_pct.serve",
        "unscoped_device_share_pct.serve", "tpot_p50_ms.serve",
        "tpot_p95_ms.serve", "kv_write_share_pct.serve",
        "sampling_share_pct.serve", "decode_batch_occupancy_pct.serve",
        "moe_share_pct.serve", "moe_dispatch_share_pct.serve",
        "moe_experts_share_pct.serve"}
    # a decode step of a held share takes no grouped matmul: the `gmm`
    # kernel's roofline has nothing to read in this cell's window, and
    # the dense experts' share of device time is read in its place
    assert "moe_experts_roofline.serve" not in names
    assert new["traffic"]["driver"] == "serve_decodepool"
    assert {m["name"] for m in new["end_to_end"]} == {"serve_tokens_per_s",
                                                      "setup_s"}
    assert new["cell"]["chips"] == 1
    cfg = new["config"]
    published = {"hidden_size": 7168, "num_attention_heads": 128,
                 "q_lora_rank": 1536, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "intermediate_size": 18432,
                 "moe_intermediate_size": 2048, "num_experts_per_tok": 8,
                 "n_shared_experts": 1, "n_group": 8, "topk_group": 4,
                 "index_n_heads": 64, "index_head_dim": 128,
                 "index_topk": 2048, "rope_theta": 10000,
                 "routed_scaling_factor": 2.5,
                 "max_position_embeddings": 163840}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert cfg["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert [cfg[k] for k in cfg["reduced"]] == [5, 1, 8, 16160, 0]
    assert cfg["published"] == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 129280,
        "num_nextn_predict_layers": 1}
    # the share: the router's published width, rank 0's experts
    assert (cfg["router_experts"], cfg["expert_offset"]) == (256, 0)
    assert {"deployment", "assumed", "reduced_why", "precision"} <= set(cfg)
    # the pool the engine is given: a page of 16 tokens over 5 layers is
    # 122,880 B, and every session's prompt and output have a page
    engine = cfg["program"]["engine"]
    from chipbench import traffic
    prompts = traffic.lengths(new["traffic"]["prompt_len"], 32)
    assert (min(prompts), max(prompts), sum(prompts)) == (8371, 32066,
                                                          567245)
    assert new["traffic"]["output_len"] == 6144
    pages = sum(-(-(p + 6144) // 16) for p in prompts)
    assert pages < engine["num_pages"] <= pages + 64
    assert engine["max_seq_len"] >= 32768 + 6144
    # the joyai cell keeps its driver, its metrics and its kernel's metric
    old = spec.load_cell("joyai_llm_flash_l5_longctx_c32")
    old_names = {m["name"] for m in old["per_layer"]}
    assert "mla_decode_kernel_roofline.serve" in old_names
    assert not old_names & {"dsa_share_pct.serve",
                            "mla_sparse_decode_kernel_roofline.serve"}
    assert old["traffic"]["driver"] == "serve_mla_moe"
