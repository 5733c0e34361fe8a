"""chipbench on the CPU at tiny sizes: the arithmetic of the yardstick
(operations, trace reductions), the loader, both drivers' windows as
functions with the kernels in interpret mode, both references against
the program's models, the controls and the planted faults that
`correct` has to catch, and the refusal to run without a chip. Nothing
here is a measurement: no number of these runs is a device metric."""
import copy
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import (common, compare, flops, lowprec, run, spec, traffic,
                       weights)
from chipbench import trace as tr
from chipbench.drivers import serve, train
from chipbench.programs import ernie_zero, gpt_engine
from chipbench.reference import ernie as ernie_ref, gpt as gpt_ref

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAIN = "ernie_base_nodropout_pretrain_b32s512"
SERVE = "gpt3_1p3b_chat_c16"
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
TINY = dict(vocab_size=1024, hidden_size=128, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=256,
            max_position_embeddings=128)


def tiny(cell: str) -> dict:
    """The cell's own files with the sizes cut to what a test can hold
    and the limits that belong to those sizes."""
    s = copy.deepcopy(spec.load_cell(cell))
    s["config"].update(TINY)
    mix = s["traffic"]
    if mix["driver"] == "train":
        mix.update(rows=8, seq=32, reference_block_rows=4)
        s["limits"] = {"loss_gap_step3": 1e-3, "grad_norm_gap": 0.05,
                       "grad_diff_gap": 0.5, "change_norm_gap": 0.2}
    else:
        s["config"]["program"]["engine"].update(max_batch_size=4,
                                                max_seq_len=128)
        mix.update(
            clients=4, block=8, reference_pad=128,
            prompt_len={"dist": "loguniform", "lo": 5, "hi": 60},
            output_len={"dist": "loguniform", "lo": 6, "hi": 24},
            sample_requests=8, trace_after_seconds=1.0, trace_seconds=1.0,
            warmup={"prompt_lens": [10, 20, 40, 60], "rows": [1, 2, 4],
                    "new_tokens": 10})
        s["limits"] = {"served_logit_gap": 0.004}
    return s


@pytest.fixture
def interpret_kernels(monkeypatch):
    from paddle_tpu.serving import attention as paged
    monkeypatch.setattr(paged, "KERNEL_MODE", "interpret")


# ------------------------------------------------------------- arithmetic

def test_flops_against_hand_counts():
    ernie = dict(hidden_size=768, intermediate_size=3072,
                 num_hidden_layers=12, vocab_size=18000)
    layers = 12 * (2 * (4 * 768 ** 2 + 2 * 768 * 3072) + 4 * 512 * 768)
    head = 77 / 512 * (2 * 768 ** 2 + 2 * 768 * 18000)
    assert flops.ernie_train_flops_per_token(ernie, 512, 77 / 512) \
        == pytest.approx(3 * (layers + head))
    assert 575e6 < 3 * (layers + head) < 585e6      # ISSUE 26: about 580 M
    gpt = dict(hidden_size=2048, intermediate_size=8192,
               num_hidden_layers=24, vocab_size=50304)
    # prompt 3, two new tokens: 4 tokens processed, contexts 1+2+3+4,
    # two rows of logits
    per_token = 24 * 2 * (4 * 2048 ** 2 + 2 * 2048 * 8192)
    want = 4 * per_token + 4 * 24 * 2048 * 10 + 2 * 2 * 2048 * 50304
    assert flops.gpt_serve_flops(gpt, 3, 2) == pytest.approx(want)
    assert flops.gpt_serve_flops(gpt, 3, 0) == 0.0
    call = flops.flash_train_call(2, 12, 512, 64)
    assert call["flops"] == 6 * 2 * 2 * 12 * 512 * 512 * 64
    assert call["bytes"] == 12 * 2 * 12 * 512 * 64 * 2
    assert flops.paged_decode_bytes(gpt, 1000, 16) \
        == 24 * (2 * 1000 * 2048 * 2 + 2 * 16 * 2048 * 2)


def _ev(name, start, dur, plane="/device:TPU:0", line="XLA Ops"):
    return tr.Event(plane, line, name, float(start), float(dur))


def test_trace_reductions_on_hand_made_events():
    events = [
        _ev("while", 0, 10), _ev("fusion.1", 1, 3),
        _ev("%flash_fwd.2 = bf16[8] custom-call(%fusion.1)", 5, 4),
        _ev("fusion.1", 12, 2), _ev("copy", 20, 1),
        _ev("serving.host_drain", 14.5, 5, "/host:CPU", "python3"),
        _ev("chipbench.engine_step", 9, 12, "/host:CPU", "python3"),
        _ev("noise", 0, 100, "/host:CPU", "other"),
    ]
    ops = tr.device_ops(events)["/device:TPU:0"]
    assert tr.busy_intervals(ops) == [(0, 10), (12, 14), (20, 21)]
    busy, window = tr.busy_and_window(events)
    assert (busy, window) == (13, 21)
    flash = "%flash_fwd.2 = bf16[8] custom-call(%fusion.1)"
    assert tr.self_times(ops) == {"while": 3, "fusion.1": 5, flash: 4,
                                  "copy": 1}
    assert tr.short_name(flash) == "flash_fwd bf16[8]"
    assert tr.named_time(ops, ["fusion"]) == (5, 2)     # not the operand
    assert tr.named_time(ops, ["while", "flash"]) == (10, 1)   # outermost
    gaps = tr.idle_gaps(ops)
    assert gaps == [(10, 12), (14, 20)]
    # each gap goes to the shortest span over its middle
    assert tr.attribute_gaps(gaps, tr.host_spans(events)) == {
        "chipbench.engine_step": 2, "serving.host_drain": 6}
    out = tr.breakdown(events)
    assert out["device_ops"][0] == ["fusion.1", 5]
    assert out["idle_gaps"][0] == ["serving.host_drain", 6]
    from chipbench.readers import device_idle
    assert device_idle.read({}, events, {}) == pytest.approx(100 * 8 / 21)
    assert device_idle.read({}, None, {}) is None
    assert device_idle.read({}, [], {}) is None     # nothing ran: no 0
    from chipbench.readers import kernel_roofline
    record = {"peaks": {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0},
              "kernel_work": {"unit": {"flops": 50.0, "bytes": 10.0}}}
    args = {"match": "flash", "count": "flash_fwd", "work": "unit"}
    # one unit of work, 1 s at the roofline (bytes bound it), 4 s taken
    assert kernel_roofline.read(record, events, args) == pytest.approx(25)
    assert kernel_roofline.read(record, events, dict(
        args, match="paged", count="paged")) is None    # no such kernel


def test_trace_load_reads_a_recorded_xplane(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("chipbench.step"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = [os.path.join(r, n) for r, _d, names in os.walk(tmp_path)
            for n in names if n.endswith(".xplane.pb")][0]
    events = tr.load(path)
    spans = tr.host_spans(events)
    assert [s.name for s in spans] == ["chipbench.step"]
    assert 0 < spans[0].duration < 10
    assert tr.busy_and_window(events) == (0.0, 0.0)     # no TPU plane


# ----------------------------------------------------------------- loader

def test_loader_finds_files_by_name_and_new_ones_need_no_edit(tmp_path):
    for cell in (TRAIN, SERVE):
        s = spec.load_cell(cell)
        assert s["config"]["name"] == s["cell"]["config"]
        assert {m["name"] for m in s["end_to_end"]} >= {"setup_s"}
        assert s["per_layer"] and all(
            callable(spec.load_reader(m["reader"])) for m in s["per_layer"])
        assert callable(spec.load_driver(s["traffic"]["driver"]).run)
        assert any("mfu" in m["name"] for m in s["per_layer"])
        assert callable(spec.load_program(
            s["config"]["program"]["builder"]).build)
        assert "setup_s" in {m["name"] for m in s["end_to_end"]}
        # every per-layer metric moves an end-to-end metric of the cell
        assert {m["moves"] for m in s["per_layer"]} \
            <= {m["name"] for m in s["end_to_end"]}
    # a later PR: one more cell, traffic mix, limits, metric — new files
    # and new entries only, nothing that is there is edited
    root = tmp_path / "chipbench"
    shutil.copytree(spec.ROOT, root, ignore=shutil.ignore_patterns(
        "__pycache__"))
    bench = spec.load_benchmark()
    mix = json.load(open(root / "traffic" / "chat_c16.json"))
    mix.update(name="chat_c4", clients=4)
    json.dump(mix, open(root / "traffic" / "chat_c4.json", "w"))
    json.dump({"cell": "gpt3_1p3b_chat_c4",
               "limits": {"served_logit_gap": 0.2}},
              open(root / "limits" / "gpt3_1p3b_chat_c4.json", "w"))
    json.dump({"reader": "window_stat",
               "args": {"key": "engine_step_ms", "percentile": 99}},
              open(root / "metrics" / "engine_step_ms_p99.serve.json", "w"))
    bench["workloads"].append({
        "name": "gpt3_1p3b_chat_c4", "config": "gpt3_1p3b",
        "traffic": "chat_c4", "chips": 1, "why": "four clients"})
    bench["per_layer"].append({
        "name": "engine_step_ms_p99.serve", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "engine",
        "moves": "serve_tokens_per_s", "workloads": ["gpt3_1p3b_chat_c4"]})
    new = spec.load_cell("gpt3_1p3b_chat_c4", bench, str(root))
    assert new["traffic"]["clients"] == 4
    assert new["limits"] == {"served_logit_gap": 0.2}
    assert "engine_step_ms_p99.serve" in {m["name"] for m in new["per_layer"]}
    assert "device_idle_pct.serve" not in {m["name"]
                                           for m in new["per_layer"]}
    old = spec.load_cell(SERVE, bench, str(root))
    assert "engine_step_ms_p99.serve" not in {m["name"]
                                              for m in old["per_layer"]}
    # names and units outside the allowed characters are refused
    for bad in ("a b", "a,b", "a/b", "", "-a", "x" * 65, "μs"):
        with pytest.raises(spec.SpecError):
            spec.check_name(bad)
    for bad in ("tokens per s", "μs", "", "x" * 17):
        with pytest.raises(spec.SpecError):
            spec.check_unit(bad)
    assert spec.check_unit("tokens/s") and spec.check_name("mfu.train")
    with pytest.raises(spec.SpecError):
        spec.load_cell("no_such_cell")
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v9")            # an unknown chip has no default
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_every_seed_sends_the_same_work():
    mix = spec.load_cell(SERVE)["traffic"]
    seen = []
    for seed in (1, 2 ** 31 + 77):
        feed = traffic.request_blocks(mix, seed, 50304)
        block = [next(feed) for _ in range(mix["block"])]
        seen.append(block)
        assert all(96 <= len(p) <= 1024 and 32 <= o <= 256
                   for p, o in block)
    a, b = seen
    # the same requests in the same order; only the ids differ
    assert [(len(p), o) for p, o in a] == [(len(p), o) for p, o in b]
    assert len({len(p) for p, _ in a}) == mix["block"]
    assert [p for p, _ in a] != [p for p, _ in b]
    ids, labels = next(traffic.mlm_batches(
        {"rows": 4, "seq": 512, "labelled_share": 0.15}, 2 ** 31 + 5, 18000))
    assert ((labels >= 0).sum(axis=1) == 77).all()
    assert (labels[labels >= 0] == ids[labels >= 0]).all()
    assert len({row.tobytes() for row in ids}) == 4     # rows all differ


# ------------------------------------------------------------- references

def test_ernie_reference_matches_the_program_model():
    from paddle_tpu.jit.functional import call_functional, extract_state
    from paddle_tpu.models import ErnieConfig, ErnieForPretraining
    cfg = dict(tiny(TRAIN)["config"])
    leaves = weights.make(ernie_ref.shapes(cfg), 7)
    model = ErnieForPretraining(ErnieConfig(
        **TINY, hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        fused_mlm_loss=True))
    model.train()
    params, buffers = extract_state(model)
    assert {k: v.shape for k, v in params.items()} \
        == {k: v.shape for k, v in leaves.items()}
    ids, labels = next(traffic.mlm_batches(
        {"rows": 4, "seq": 32, "labelled_share": 0.15}, 7, 1024))

    def loss_fn(p):
        (loss, _), _ = call_functional(
            model, p, buffers, (jnp.asarray(ids), None, None, None,
                                jnp.asarray(labels)), training=True)
        return loss

    got, got_g = jax.value_and_grad(loss_fn)(leaves)
    want, want_g = ernie_ref.loss_and_grads(
        leaves, jnp.asarray(ids), jnp.asarray(labels), cfg, 2)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for k in want_g:
        np.testing.assert_allclose(got_g[k], want_g[k], rtol=2e-3,
                                   atol=1e-6, err_msg=k)
    # the key's bias is the nought the rule on leaves is there for
    norms = {k: float(v) for k, v in ernie_ref.leaf_norms(want_g).items()}
    skipped = set(norms) - set(compare.moved_leaves(norms))
    assert {k for k in skipped if "layers" in k} == {
        f"ernie.layers.{i}.attention.key.bias" for i in range(2)}


def test_gpt_reference_matches_the_program_model():
    from paddle_tpu.jit.functional import call_functional, extract_state
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    cfg = dict(tiny(SERVE)["config"])
    leaves = weights.make(gpt_ref.shapes(cfg), 7)
    model = GPTForCausalLM(GPTConfig(**TINY))
    model.eval()
    params, buffers = extract_state(model)
    assert {k: v.shape for k, v in params.items()} \
        == {k: v.shape for k, v in leaves.items()}
    ids = np.random.default_rng(7).integers(0, 1024, 48)
    got, _ = call_functional(model, leaves, buffers,
                             (jnp.asarray(ids)[None],), training=False)
    want = gpt_ref.logits(leaves, ids, np.arange(48), cfg)
    np.testing.assert_allclose(got[0], want, rtol=1e-4, atol=1e-4)
    padded = np.concatenate([ids, np.zeros(16, ids.dtype)])
    np.testing.assert_allclose(
        gpt_ref.logits(leaves, padded, np.arange(40, 48), cfg), want[40:],
        rtol=1e-5, atol=1e-5)           # padding cannot reach back


# ---------------------------------------------------------------- drivers

def _measure(s, seed, trace=False, seconds=1.0):
    return run.measure(s, seed, seconds, trace, DEVICE, time.time())


def test_train_cell_end_to_end_tiny():
    out = _measure(tiny(TRAIN), 2 ** 31 + 11)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(out)[-1] == "checks"        # the numbers compared come last
    assert set(out["checks"]) == {
        "loss_gap_step3", "grad_norm_gap", "grad_diff_gap",
        "change_norm_gap", "compiled_in_window"}
    assert set(out["not_compared"]) == {"loss_gap_step1", "loss_gap_step2"}
    traced = _measure(tiny(TRAIN), 5, trace=True, seconds=4.0)
    assert traced["correct"], traced["checks"]
    # no TPU plane on the CPU: the device readers return nothing, and
    # the line lacks them rather than carrying a 0
    assert set(traced["metrics"]) == {"train_step_ms_p50", "train_mfu_pct"}
    json.dumps(traced)
    assert not os.path.exists(common.TRACE_DIR)


def test_serve_cell_end_to_end_tiny(interpret_kernels):
    out = _measure(tiny(SERVE), 2 ** 31 + 11, seconds=3.0)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    traced = _measure(tiny(SERVE), 5, trace=True, seconds=5.0)
    assert traced["correct"], traced["checks"]
    assert set(traced["metrics"]) == {
        "prefill_time_share_pct.serve", "engine_step_ms_p50.serve",
        "serve_mfu_pct", "ttft_p95_ms.serve", "ttft_p50_ms.serve",
        "tpot_p95_ms.serve", "tpot_p50_ms.serve"}
    tails = {k: v["value"] for k, v in traced["metrics"].items()}
    assert 0 < tails["ttft_p50_ms.serve"] <= tails["ttft_p95_ms.serve"]
    assert 0 < tails["tpot_p50_ms.serve"] <= tails["tpot_p95_ms.serve"]
    assert 0 < traced["metrics"]["prefill_time_share_pct.serve"]["value"] \
        < 100


def test_serve_cell_fails_on_a_reference_attention_path():
    """KERNEL_MODE 'auto' on the CPU takes the jnp reference path: the
    tokens are right and the run is still not correct."""
    out = _measure(tiny(SERVE), 3, seconds=2.0)
    assert not out["correct"]
    assert not out["checks"]["reference_path_dispatches"]["ok"]


# ------------------------------------------------------ controls and faults

def test_controls_come_out_as_not_correct():
    """The reference computed one precision below the configuration's,
    in the program's place, held to limits as a run is, at sizes a test
    can hold: `ok` is false, by the number that is there to catch it.
    The limits here belong to these sizes (the program's largest reading
    of six seeds on the CPU against the control's smallest: 0.047 and
    0.096 for the gradient, 0.0008 and 0.053 for the served logit); the
    cells' own are set the same way from chip runs (PERF.md)."""
    # serving: the first choice flips too rarely at two layers of 128,
    # so the reference alone at six layers of 256, the program stood in
    # for by the reference in bfloat16 (what the engine computes in)
    cfg = dict(vocab_size=4096, hidden_size=256, num_hidden_layers=6,
               num_attention_heads=4, intermediate_size=1024,
               max_position_embeddings=256, layer_norm_eps=1e-5,
               precision="bfloat16", reference="gpt")
    leaves = weights.make(gpt_ref.shapes(cfg), 3, jnp.bfloat16)
    ids = np.random.default_rng(3).integers(0, 4096, 256).tolist()
    rec = {"prompt": ids[:56], "generated": ids[56:]}
    s = {"config": cfg, "traffic": {"reference_pad": 256},
         "limits": {"served_logit_gap": 0.02}}

    def bf16(x, w):
        return (x.astype(jnp.bfloat16).astype(x.dtype)
                @ w.astype(jnp.bfloat16).astype(w.dtype))

    # neither stand-in decodes: at each position of the same sequence,
    # the gap of the token that the precision puts first
    program = serve.reference_gap(gpt_ref, leaves, cfg, rec, 256,
                                  stand_in=bf16)
    assert program <= s["limits"]["served_logit_gap"]
    control = serve.control(s, {"replay": {"leaves": leaves,
                                           "sampled": [rec]}})["control"]
    assert not control["ok"] and control["failed"] == ["served_logit_gap"]
    assert control["served_logit_gap"] > 3 * max(program, 0.004)
    with pytest.raises(ValueError):     # no number is no failed control
        serve.control(s, {"replay": {"leaves": leaves, "sampled": []}})
    # training: the program itself, tiny
    s = tiny(TRAIN)
    s["limits"]["grad_diff_gap"] = 0.07
    record = train.run(s, 21, 0.5, False, time.time())
    assert record["checks"].ok, record["checks"].as_dict()
    stand_ins = train.control(s, record)
    # fp8 both ways: the first gradient is off by what a norm hides
    assert not stand_ins["control"]["ok"]
    assert stand_ins["control"]["failed"] == ["grad_diff_gap"]
    # half of every batch left out: the first gradient's norm shows it
    assert not stand_ins["half_batch"]["ok"]
    assert "grad_norm_gap" in stand_ins["half_batch"]["failed"]
    assert lowprec.BELOW == {"bfloat16": lowprec.fp8}


class _BrokenTrainer:
    """A `ZeroTrainStep` with a fault planted under the driver."""

    def __init__(self, trainer, fault):
        self.trainer, self.fault = trainer, fault

    def init_state(self, params):
        return self.trainer.init_state(params)

    def __call__(self, params, state, batch, lr, t):
        if self.fault == "half_batch":
            batch = tuple(x[:x.shape[0] // 2] for x in batch)
        loss, new_params, new_state = self.trainer(params, state, batch,
                                                   lr, t)
        if self.fault == "state_unchanged":
            return loss, params, state
        return loss, new_params, new_state


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_train_step_is_not_correct(monkeypatch, fault):
    build = ernie_zero.build
    monkeypatch.setattr(ernie_zero, "build",
                        lambda *a: _BrokenTrainer(build(*a), fault))
    out = _measure(tiny(TRAIN), 9, seconds=0.5)
    assert not out["correct"]
    failed = {k for k, v in out["checks"].items() if not v["ok"]}
    if fault == "state_unchanged":
        # nothing moved: Adam's first moment is nought, so is the change
        assert {"grad_norm_gap", "grad_diff_gap", "change_norm_gap"} \
            <= failed
        assert out["checks"]["change_norm_gap"]["value"] \
            == pytest.approx(1.0)
    else:
        assert {"grad_norm_gap", "grad_diff_gap"} <= failed


def test_an_altered_token_is_not_correct(monkeypatch, interpret_kernels):
    """One token in eight altered where the engine emits it to the
    host."""
    build = gpt_engine.build

    def broken(*a):
        engine = build(*a)
        emit = engine._emit

        def altered(req, token, now):
            if len(req.generated) % 8 == 5:
                token = (int(token) + 1) % TINY["vocab_size"]
            return emit(req, token, now)

        engine._emit = altered
        return engine

    monkeypatch.setattr(gpt_engine, "build", broken)
    out = _measure(tiny(SERVE), 9, seconds=3.0)
    assert not out["correct"]
    assert not out["checks"]["served_logit_gap"]["ok"]
    assert out["checks"]["requests_not_finished"]["ok"]


# ------------------------------------------------------------ no CPU mode

def test_run_without_a_chip_runs_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", TRAIN,
         "--seed", str(2 ** 31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout == ""
    assert "needs a TPU" in done.stderr
    bench = spec.load_benchmark()
    assert bench["command"] == ["python3", "-m", "chipbench.run"]
    assert not any(w in " ".join(bench["command"])
                   for w in ("bench.py", "chip_smoke"))
