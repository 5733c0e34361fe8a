"""The latent-attention, routed-expert serving cell on the CPU at tiny
sizes: its driver's window as a function with the kernels in interpret
mode, what `correct` has to catch (the kernel off, an altered expert
weight, the control), the operations and bytes of `flops_mla_moe.py`
against hand counts, the `path_share` reader on hand-made operations,
and the loader's view of the cells this configuration and the queue's
first cell added. Nothing here is a measurement."""
import copy
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import (common, flops_mla_moe as flops, lowprec, run, scopes,
                       spec)
from chipbench.drivers import serve_mla_moe
from chipbench.programs import mla_moe_engine
from chipbench.readers import path_share
from chipbench.reference import mla_moe as reference

CELL = "joyai_llm_flash_l5_longctx_c32"
LONGPROMPT = "gpt3_1p3b_longprompt_c4"
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
# the structure kept: 1 dense + 2 expert layers, 16 experts top-4 + 1
# shared, 4 heads of 32 + 16 / 32, ranks 48 and 32
TINY = dict(vocab_size=512, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
            intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=16, num_experts_per_tok=4)


def tiny() -> dict:
    s = copy.deepcopy(spec.load_cell(CELL))
    s["config"].update(TINY)
    s["config"]["program"]["engine"].update(
        max_batch_size=4, max_seq_len=128, num_pages=40)
    # 16 experts here: margins are wide, and every position is compared
    s["traffic"].update(
        clients=4, block=8, reference_pad=128, reference_margin=0.0,
        prompt_len={"dist": "loguniform", "lo": 5, "hi": 60},
        output_len={"dist": "loguniform", "lo": 6, "hi": 24},
        sample_requests=8, trace_after_seconds=1.0, trace_seconds=1.0,
        warmup={"prompt_lens": [10, 20, 40, 60], "rows": [1, 2, 4],
                "new_tokens": 10})
    # at these sizes the program reads up to 0.0024 and the fp8 control
    # 0.016 to 0.038 (eight runs on the CPU)
    s["limits"] = {"served_logit_gap": 0.006}
    return s


@pytest.fixture
def interpret_kernels(monkeypatch):
    from paddle_tpu.serving import attention as paged
    monkeypatch.setattr(paged, "KERNEL_MODE", "interpret")


def _measure(s, seed, trace=False, seconds=3.0):
    return run.measure(s, seed, seconds, trace, DEVICE, time.time())


def test_cell_end_to_end_tiny(interpret_kernels, monkeypatch, tmp_path):
    # a trace directory of its own: another worker may be tracing the
    # other serving cell's tiny run at this moment
    monkeypatch.setattr(common, "TRACE_DIR", str(tmp_path / "trace"))
    out = _measure(tiny(), 2 ** 31 + 29)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["checks"]) == {
        "served_logit_gap", "requests_not_finished",
        "token_count_mismatches", "fault_events",
        "reference_path_dispatches", "no_mla_decode_kernel_dispatch",
        "compiled_in_window"}
    traced = _measure(tiny(), 7, trace=True, seconds=5.0)
    assert traced["correct"], traced["checks"]
    # no TPU plane on the CPU: the device readers return nothing and the
    # line lacks them; the clocks and the program's counters are there
    assert set(traced["metrics"]) == {
        "prefill_time_share_pct.serve", "engine_step_ms_p50.serve",
        "serve_mfu_pct", "ttft_p95_ms.serve", "ttft_p50_ms.serve",
        "tpot_p95_ms.serve", "tpot_p50_ms.serve",
        "decode_batch_occupancy_pct.serve", "queue_wait_ms_mean.serve",
        "moe_expert_load_max_over_mean.serve",
        "moe_experts_touched_pct.serve"}
    values = {k: v["value"] for k, v in traced["metrics"].items()}
    # the metric's scale is the configuration's 256 experts; 16 here
    assert 1.0 <= values["moe_expert_load_max_over_mean.serve"] / 16 <= 16.0
    assert 0 < values["moe_experts_touched_pct.serve"] <= 100.0 * 16 / 256
    assert 0 < values["decode_batch_occupancy_pct.serve"] <= 100
    json.dumps(traced)


def test_cell_with_the_kernel_off_is_not_correct():
    """KERNEL_MODE 'auto' on the CPU takes the jnp path: the tokens are
    right, a `*_reference*` path was dispatched and `mla_decode` never."""
    out = _measure(tiny(), 3, seconds=2.0)
    assert not out["correct"]
    assert out["checks"]["served_logit_gap"]["ok"]
    assert not out["checks"]["reference_path_dispatches"]["ok"]
    assert not out["checks"]["no_mla_decode_kernel_dispatch"]["ok"]


def test_an_altered_expert_weight_is_not_correct(monkeypatch,
                                                 interpret_kernels):
    real = mla_moe_engine.build

    def altered(cfg, program, leaves):
        name = "model.layers.1.mlp.experts.down_proj"
        return real(cfg, program, {**leaves, name: leaves[name] * 3.0})

    monkeypatch.setattr(mla_moe_engine, "build", altered)
    out = _measure(tiny(), 9)
    assert not out["correct"]
    assert [k for k, v in out["checks"].items() if not v["ok"]] == [
        "served_logit_gap"]


def test_control_comes_out_as_not_correct(interpret_kernels):
    s = tiny()
    record = serve_mla_moe.run(s, 11, 3.0, False, time.time())
    assert record["checks"].ok
    line = serve_mla_moe.control(s, record)["control"]
    assert not line["ok"] and line["failed"] == ["served_logit_gap"]


def test_reference_margin_and_router_stay_float32():
    """The margin between the last expert chosen and the first left out
    is the reference's own, and the control's matmul never reaches the
    router: the fp8 stand-in routes as float32 does."""
    from chipbench import weights
    cfg = {**spec.load_cell(CELL)["config"], **TINY}
    leaves = weights.make(reference.shapes(cfg), 5, jnp.float32)
    ids = np.random.default_rng(0).integers(0, 512, 40)
    rows = np.arange(40)
    exact, margin = reference.logits(leaves, ids, rows, cfg,
                                     with_margin=True)
    assert margin.shape == (40,) and float(margin.min()) >= 0.0
    low, low_margin = reference.logits(
        leaves, ids, rows, cfg, lowprec.BELOW["bfloat16"], with_margin=True)
    assert 0 < float(jnp.abs(low - exact).max())
    # the first expert layer sees inputs the precision has not reached
    # past the attention: margins move a little, never collapse
    assert float(jnp.abs(low_margin - margin).max()) < 0.05


def test_flops_against_hand_counts():
    cfg = spec.load_cell(CELL)["config"]
    # ISSUE 29's arithmetic: projections 52.7 M, nine experts 84.9 M, the
    # dense MLP 88.1 M, 20,480 a position attended a layer
    one = flops.serve_flops(cfg, 1, 1)
    attn = 2 * 26_345_472
    experts = 9 * 9_437_184 + 2 * 2048 * 256
    dense = 2 * 3 * 2048 * 7168
    assert one == 5 * attn + dense + 4 * experts + 5 * 20480 \
        + 2 * 2048 * 129280
    assert abs(attn / 1e6 - 52.7) < 0.05 and abs(dense / 1e6 - 88.1) < 0.05
    # the last emitted token is never fed back
    assert flops.serve_flops(cfg, 10, 3) - flops.serve_flops(cfg, 10, 2) == \
        5 * attn + dense + 4 * experts + 5 * 20480 * 12 + 2 * 2048 * 129280
    assert flops.serve_flops(cfg, 10, 0) == 0.0
    # the absorbed decode: 1,152 B and 69,632 operations a cached token a
    # layer
    assert flops.mla_decode_flops(cfg, 1000) == 5 * 1000 * 69632
    assert flops.mla_decode_bytes(cfg, 1000, 0) == 5 * 1000 * 1152
    assert flops.mla_decode_bytes(cfg, 0, 1) == 5 * 2 * 32 * (576 + 512)
    work = flops.moe_experts_work(cfg, pairs=256, experts_touched=162)
    assert work["flops"] == 256 * 9_437_184
    assert work["bytes"] == 162 * 9_437_184 + 256 * 2 * 2048 * 2


def test_path_share_on_hand_made_operations():
    def op(name, start, dur, path):
        return scopes.ScopedOp("/device:TPU:0", name, start, dur, path)

    ops = [op("%gmm.1", 0.0, 3.0, "jit(prefill)/mlp/moe_experts/gmm"),
           op("%sort.1", 3.0, 1.0, "jit(prefill)/mlp/moe_dispatch/sort"),
           op("%fusion.2", 4.0, 2.0, "jit(prefill)/mlp/dot_general"),
           op("%fusion.3", 6.0, 4.0, "jit(prefill)/not_moe_experts/x")]
    record = {"trace_path": "unused", "scoped_ops": ops}
    both = {"components": ["moe_experts", "moe_dispatch"]}
    assert path_share.read(record, [], both) == pytest.approx(40.0)
    assert path_share.read(record, [], {"components": ["moe_dispatch"]}) \
        == pytest.approx(10.0)
    # a program without the sub-scopes: nothing, not 0
    assert path_share.read(record, [], {"components": ["mla_absorb"]}) \
        is None
    assert path_share.read(record, None, both) is None


def test_loader_gives_each_new_cell_its_metrics():
    new = spec.load_cell(CELL)
    names = {m["name"] for m in new["per_layer"]}
    assert {"mla_decode_kernel_roofline.serve", "moe_experts_roofline.serve",
            "moe_share_pct.serve", "moe_dispatch_share_pct.serve",
            "moe_expert_load_max_over_mean.serve",
            "moe_experts_touched_pct.serve", "serve_mfu_pct",
            "kv_write_share_pct.serve", "decode_batch_occupancy_pct.serve",
            "queue_wait_ms_mean.serve"} <= names
    assert not names & {"paged_decode_kernel_roofline.serve",
                        "paged_attention_overhead_share_pct.serve",
                        "paged_decode_roofline.serve"}
    assert new["traffic"]["driver"] == "serve_mla_moe"
    assert {m["name"] for m in new["end_to_end"]} == {"serve_tokens_per_s",
                                                      "setup_s"}
    cfg = new["config"]
    published = {"hidden_size": 2048, "num_attention_heads": 32,
                 "q_lora_rank": 1536, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "intermediate_size": 7168,
                 "moe_intermediate_size": 768, "n_routed_experts": 256,
                 "num_experts_per_tok": 8, "n_shared_experts": 1,
                 "vocab_size": 129280}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers",
                              "num_nextn_predict_layers"]
    assert (cfg["num_hidden_layers"], cfg["num_nextn_predict_layers"]) == \
        (5, 0)
    old = spec.load_cell(LONGPROMPT)
    assert old["traffic"]["driver"] == "serve"
    assert old["config"]["name"] == "gpt3_1p3b"
    old_names = {m["name"] for m in old["per_layer"]}
    assert "paged_decode_kernel_roofline.serve" in old_names
    assert "paged_decode_roofline.serve" not in old_names
    assert not old_names & {"mla_decode_kernel_roofline.serve",
                            "moe_share_pct.serve"}
    # the accepted serving cell reports what it reported
    chat = {m["name"] for m in
            spec.load_cell("gpt3_1p3b_chat_c16")["per_layer"]}
    assert not chat & {"decode_batch_occupancy_pct.serve",
                       "queue_wait_ms_mean.serve",
                       "mla_decode_kernel_roofline.serve"}


def test_gap_is_read_where_the_reference_routes_clearly():
    gaps = [np.array([0.9, 0.01, 0.02]), np.array([0.0, 0.5])]
    margins = [np.array([0.001, 0.006, 0.004]), np.array([0.02, 0.0039])]
    checks = common.Checks()
    serve_mla_moe.read_gaps(checks, gaps, margins, 0.004, 0.15)
    row = checks.as_dict()["served_logit_gap"]
    assert row["value"] == pytest.approx(0.02) and row["ok"]
    assert checks.notes["positions_kept_share"] == pytest.approx(0.6)
    assert checks.notes["served_logit_gap_all_positions"] == \
        pytest.approx(0.9)
    # a wide gap where the routing is clear is not excused
    checks = common.Checks()
    serve_mla_moe.read_gaps(checks, gaps, [m + 0.01 for m in margins],
                            0.004, 0.15)
    assert not checks.ok
    # nothing kept, nothing compared: not correct
    checks = common.Checks()
    serve_mla_moe.read_gaps(checks, gaps, margins, 1.0, 0.15)
    assert not checks.ok
    checks = common.Checks()
    serve_mla_moe.read_gaps(checks, [], [], 0.004, 0.15)
    assert not checks.ok
