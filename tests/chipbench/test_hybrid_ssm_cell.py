"""The hybrid (Mamba-2 + attention) serving cell on the CPU at tiny
sizes: its driver's window as a function with the kernels in interpret
mode, what `correct` has to catch (a kernel off, an altered Mamba leaf,
the fp8 control, a state whose carry is dropped), the third stand-in's
reading (a bf16 state), the operations and bytes of `flops_hybrid_ssm.py` against hand
counts, and the loader's view of the cell this configuration added.
Nothing here is a measurement."""
import copy
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import common, flops_hybrid_ssm as flops, run, spec
from chipbench.drivers import serve_hybrid_ssm
from chipbench.programs import hybrid_ssm_engine
from chipbench.reference import hybrid_ssm as reference

CELL = "granite_4_0_h_micro_sessions_c64"
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
# the structure kept: Mamba-2 heads of 64 and attention heads of 64 (two
# a 128-lane row in both pools), one attention layer among three Mamba
# layers, a scan chunk of 8
TINY = dict(vocab_size=512, hidden_size=256, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2,
            shared_intermediate_size=256, intermediate_size=256,
            layer_types=["mamba", "attention", "mamba", "mamba"],
            mamba_n_heads=8, mamba_d_state=16, mamba_chunk_size=8,
            # the published 12 and 0.22 would leave a model this narrow
            # and shallow to its embedding alone
            embedding_multiplier=1.0, residual_multiplier=1.0)


def tiny() -> dict:
    s = copy.deepcopy(spec.load_cell(CELL))
    s["config"].update(TINY)
    s["config"]["program"]["engine"].update(
        max_batch_size=4, max_seq_len=128, num_pages=40)
    s["traffic"].update(
        clients=4, block=8, reference_pad=128,
        prompt_len={"dist": "loguniform", "lo": 5, "hi": 60},
        output_len={"dist": "loguniform", "lo": 6, "hi": 24},
        sample_requests=8, trace_after_seconds=1.0, trace_seconds=1.0,
        warmup={"prompt_lens": [10, 20, 40, 60], "rows": [1, 2, 4],
                "new_tokens": 10})
    s["limits"] = {"served_logit_gap": TINY_LIMIT}
    return s


# at these sizes the program (bf16) reads up to 0.00015 and the fp8
# control 0.0013 to 0.0043 (six runs each on the CPU); the stand-ins of
# the state read 0.0 (bf16) and 0.00002 to 0.00027 (carry dropped every
# 8 tokens): over sequences of under 90 tokens the state has hardly
# built up, and they are not told apart from the program here
TINY_LIMIT = 0.0006


@pytest.fixture
def interpret_kernels(monkeypatch):
    from paddle_tpu.serving import attention as paged
    monkeypatch.setattr(paged, "KERNEL_MODE", "interpret")


def _measure(s, seed, trace=False, seconds=3.0):
    return run.measure(s, seed, seconds, trace, DEVICE, time.time())


def test_cell_end_to_end_tiny(interpret_kernels, monkeypatch, tmp_path):
    monkeypatch.setattr(common, "TRACE_DIR", str(tmp_path / "trace"))
    out = _measure(tiny(), 2 ** 31 + 33)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["checks"]) == {
        "served_logit_gap", "requests_not_finished",
        "token_count_mismatches", "fault_events",
        "reference_path_dispatches", "no_ssm_decode_kernel_dispatch",
        "no_pallas_decode_dispatch", "compiled_in_window"}
    traced = _measure(tiny(), 7, trace=True, seconds=5.0)
    assert traced["correct"], traced["checks"]
    # no TPU plane on the CPU: the device readers return nothing and the
    # line lacks them; the clocks and the program's counters are there
    assert set(traced["metrics"]) == {
        "prefill_time_share_pct.serve", "engine_step_ms_p50.serve",
        "serve_mfu_pct", "ttft_p95_ms.serve", "ttft_p50_ms.serve",
        "tpot_p95_ms.serve", "tpot_p50_ms.serve",
        "decode_batch_occupancy_pct.serve", "queue_wait_ms_mean.serve"}
    values = {k: v["value"] for k, v in traced["metrics"].items()}
    assert 0 < values["decode_batch_occupancy_pct.serve"] <= 100
    json.dumps(traced)


def test_cell_with_the_kernels_off_is_not_correct():
    """KERNEL_MODE 'auto' on the CPU takes the jnp paths: the tokens are
    right, `*_reference*` paths were dispatched and neither kernel."""
    out = _measure(tiny(), 3, seconds=2.0)
    assert not out["correct"]
    assert out["checks"]["served_logit_gap"]["ok"]
    assert not out["checks"]["reference_path_dispatches"]["ok"]
    assert not out["checks"]["no_ssm_decode_kernel_dispatch"]["ok"]
    assert not out["checks"]["no_pallas_decode_dispatch"]["ok"]


def test_an_altered_mamba_leaf_is_not_correct(monkeypatch,
                                              interpret_kernels):
    """One Mamba layer without its skip term (`D` = 0): only the
    comparison with the reference can see it. (A fault in the state
    alone shows at the cell's lengths and not at these: the test of the
    reference's hooks below, and PERF.md section 2.)"""
    real = hybrid_ssm_engine.build

    def altered(cfg, program, leaves):
        name = "model.layers.2.mamba.D"
        return real(cfg, program, {**leaves, name: leaves[name] * 0.0})

    monkeypatch.setattr(hybrid_ssm_engine, "build", altered)
    out = _measure(tiny(), 9)
    assert not out["correct"]
    assert [k for k, v in out["checks"].items() if not v["ok"]] == [
        "served_logit_gap"]


def test_control_comes_out_as_not_correct(interpret_kernels):
    s = tiny()
    record = serve_hybrid_ssm.run(s, 11, 3.0, False, time.time())
    assert record["checks"].ok
    lines = serve_hybrid_ssm.control(s, record)
    # both are held to the cell's limit, as `chipbench.control` holds
    # every entry: the fp8 reference fails it here; the dropped carry is
    # read (at these lengths the state has hardly built up: on the chip
    # it fails, PERF.md section 2)
    assert set(lines) == {"control", "state_carry_dropped"}
    line = lines["control"]
    assert not line["ok"] and line["failed"] == ["served_logit_gap"]
    assert lines["state_carry_dropped"]["served_logit_gap"] > 0.0
    # the third stand-in is read and printed, and held to nothing
    assert line["state_bf16_served_logit_gap"] >= 0.0


def test_the_reference_sees_a_dropped_carry_and_a_bf16_state():
    """The stand-ins' hooks, on the reference alone at the published
    Mamba-2 widths (one layer of each kind, a small vocabulary): with
    the conv drawn as Mamba-2 draws it the logits move when the state's
    carry is dropped at position 256, from that position on and not
    before it; a state rounded to bf16 moves them a hundredth as far."""
    cfg = copy.deepcopy(spec.load_cell(CELL)["config"])
    cfg.update(num_hidden_layers=2, layer_types=["mamba", "attention"],
               vocab_size=1024)
    from chipbench import weights
    leaves = reference.own_leaves(
        weights.make(reference.shapes(cfg), 3, jnp.bfloat16), cfg, 3)
    ids = np.random.default_rng(3).integers(0, 1024, 320).astype(np.int32)
    rows = np.arange(320)
    exact = np.asarray(reference.logits(leaves, ids, rows, cfg))
    dropped = np.abs(np.asarray(reference.logits(
        leaves, ids, rows, cfg, carry_every=256)) - exact).max(-1)
    rounded = np.abs(np.asarray(reference.logits(
        leaves, ids, rows, cfg, state_dtype=jnp.bfloat16)) - exact).max(-1)
    assert dropped[:256].max() == 0.0
    scale = exact.std()
    assert dropped[256:].max() > 0.05 * scale
    assert 0.0 < rounded.max() < 0.1 * dropped[256:].max()


def test_the_conv_is_drawn_as_mamba_2_draws_it():
    cfg = {**spec.load_cell(CELL)["config"], **TINY}
    from chipbench import weights
    made = weights.make(reference.shapes(cfg), 5, jnp.bfloat16)
    leaves = reference.own_leaves(made, cfg, 5)
    for leaf in ("conv1d.weight", "conv1d.bias"):
        name = "model.layers.0.mamba." + leaf
        got = np.asarray(leaves[name], np.float32)
        assert leaves[name].dtype == jnp.bfloat16
        assert got.shape == tuple(made[name].shape)
        # U(-1/sqrt(4), 1/sqrt(4)): within the bound, and spread over it
        assert np.abs(got).max() <= 0.5 and np.abs(got).max() > 0.45
        assert abs(got.std() - 0.5 / np.sqrt(3)) < 0.03
    assert not np.array_equal(
        np.asarray(leaves["model.layers.0.mamba.conv1d.weight"]),
        np.asarray(leaves["model.layers.2.mamba.conv1d.weight"]))


def test_the_driver_draws_its_own_leaves_from_the_seed():
    cfg = {**spec.load_cell(CELL)["config"], **TINY}
    from chipbench import weights
    made = weights.make(reference.shapes(cfg), 5, jnp.bfloat16)
    leaves = reference.own_leaves(made, cfg, 5)
    again = reference.own_leaves(made, cfg, 5)
    name = "model.layers.0.mamba.A_log"
    assert leaves[name].dtype == jnp.float32
    assert np.array_equal(np.asarray(leaves[name]), np.asarray(again[name]))
    assert made[name].dtype == jnp.bfloat16
    # a seed past 32 bits is a seed
    big = reference.own_leaves(made, cfg, 2 ** 31 + 7)
    assert not np.array_equal(np.asarray(big[name]),
                              np.asarray(leaves[name]))


def test_flops_against_hand_counts():
    cfg = spec.load_cell(CELL)["config"]
    # ISSUE 33's arithmetic: a Mamba mixer's two matrices 25.82 M, the
    # MLP 50.33 M, an attention mixer's four 10.49 M
    mamba = 2048 * 8512 + 4096 * 2048
    mlp = 2048 * 16384 + 8192 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert abs(mamba / 1e6 - 25.82) < 0.01 and abs(mlp / 1e6 - 50.33) < 0.01
    assert abs(attn / 1e6 - 10.49) < 0.01
    state = 64 * 64 * 128
    one = flops.serve_flops(cfg, 1, 1)
    assert one == (2 * (40 * mlp + 36 * mamba + 4 * attn)
                   + 36 * 5 * state + 4 * 4 * 2048 + 2 * 2048 * 100352)
    # the last emitted token is never fed back
    assert flops.serve_flops(cfg, 10, 3) - flops.serve_flops(cfg, 10, 2) == \
        (2 * (40 * mlp + 36 * mamba + 4 * attn) + 36 * 5 * state
         + 4 * 4 * 2048 * 12 + 2 * 2048 * 100352)
    assert flops.serve_flops(cfg, 10, 0) == 0.0
    # a padded bucket or the chunked form's extra products raise nothing:
    # the count depends on the tokens alone
    assert flops.ssm_decode_flops(cfg, 7) == 7 * 36 * 5 * state
    assert flops.ssm_decode_bytes(cfg, 1) == 36 * (
        2 * 2_097_152 + 4 * (4096 + 128 + 128 + 64 + 4096))
    # 4 layers of 8 kv heads of 64, unpadded: 8,192 B a token
    assert flops.paged_decode_bytes(cfg, 1000, 0) == 1000 * 8192
    assert flops.paged_decode_bytes(cfg, 0, 1) == 4 * 2 * 2048 * 2
    assert flops.paged_decode_flops(cfg, 1000) == 4 * 4 * 2048 * 1000


def test_loader_gives_the_new_cell_its_metrics():
    new = spec.load_cell(CELL)
    names = {m["name"] for m in new["per_layer"]}
    assert {"ssm_decode_kernel_roofline.serve", "ssm_share_pct.serve",
            "ssm_state_update_share_pct.serve",
            "ssm_chunk_scan_share_pct.serve", "serve_mfu_pct",
            "paged_decode_kernel_roofline.serve",
            "unscoped_device_share_pct.serve", "kv_write_share_pct.serve",
            "decode_batch_occupancy_pct.serve",
            "queue_wait_ms_mean.serve"} <= names
    assert not names & {"paged_attention_overhead_share_pct.serve",
                        "paged_decode_roofline.serve",
                        "mla_decode_kernel_roofline.serve",
                        "moe_share_pct.serve"}
    by_name = {m["name"]: m for m in new["per_layer"]}
    assert by_name["ssm_decode_kernel_roofline.serve"]["args"]["match"] == \
        "ssm_decode"
    assert by_name["ssm_share_pct.serve"]["args"]["components"] == [
        "ssm_in_proj", "ssm_conv", "ssm_state_update", "ssm_chunk_scan",
        "ssm_gate_out"]
    assert new["traffic"]["driver"] == "serve_hybrid_ssm"
    assert {m["name"] for m in new["end_to_end"]} == {"serve_tokens_per_s",
                                                      "setup_s"}
    assert new["cell"]["chips"] == 1
    cfg = new["config"]
    published = {
        "hidden_size": 2048, "num_hidden_layers": 40,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "shared_intermediate_size": 8192, "intermediate_size": 8192,
        "vocab_size": 100352, "mamba_n_heads": 64, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_d_conv": 4, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_chunk_size": 256,
        "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 8,
        "rms_norm_eps": 1e-05, "num_local_experts": 0,
        "tie_word_embeddings": True, "position_embedding_type": "nope",
        "max_position_embeddings": 131072}
    assert {k: cfg[k] for k in published} == published
    assert [i for i, t in enumerate(cfg["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    assert cfg["reduced"] == [] and cfg["precision"] == "bfloat16"
    assert cfg["program"]["engine"] == {
        "page_size": 16, "max_batch_size": 64, "max_seq_len": 3200,
        "kv_dtype": "bf16", "num_pages": 6600}
    mix = new["traffic"]
    assert (mix["clients"], mix["block"], mix["loop"]) == (64, 32, "closed")
    assert mix["prompt_len"] == {"dist": "loguniform", "lo": 128,
                                 "hi": 2048}
    assert mix["output_len"] == {"dist": "loguniform", "lo": 128,
                                 "hi": 1024}
    assert mix["warmup"] == {"prompt_lens": [150, 300, 600, 1200, 2000],
                             "rows": [1, 2, 4, 8, 16, 32, 64],
                             "new_tokens": 9}
    assert (mix["sample_requests"], mix["reference_pad"]) == (4, 3200)
    # the accepted serving cells report what they reported
    for cell in ("gpt3_1p3b_chat_c16", "joyai_llm_flash_l5_longctx_c32"):
        assert not {m["name"] for m in spec.load_cell(cell)["per_layer"]} \
            & {"ssm_decode_kernel_roofline.serve", "ssm_share_pct.serve"}


def test_the_program_builder_needs_the_model_at_import():
    """A checkout without `models/hybrid_ssm.py` (the parent of the PR
    that brought it) fails as the driver loads the builder, before a
    weight is made."""
    import inspect
    head = inspect.getsource(hybrid_ssm_engine).split("def build")[0]
    assert "from paddle_tpu.models.hybrid_ssm import" in head
    src = inspect.getsource(serve_hybrid_ssm.run)
    assert src.index("load_program(") < src.index("weights.make(")
