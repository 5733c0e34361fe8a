"""The readers that read the program's own names (scopes, executables,
counters) on hand-made events and on one recorded CPU trace. Nothing
here is a measurement: no number of these runs is a device metric."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import scopes, spec
from chipbench import trace as tr
from chipbench.readers import counter_ratio, module_share, scope_share

NEW = {
    "gpt3_1p3b_chat_c16": {
        "paged_decode_kernel_roofline.serve",
        "paged_attention_overhead_share_pct.serve",
        "kv_write_share_pct.serve", "prefill_device_share_pct.serve",
        "unscoped_device_share_pct.serve"},
    "ernie_base_nodropout_pretrain_b32s512": {
        "mlm_head_loss_share_pct.train", "optimizer_update_share_pct.train",
        "unscoped_device_share_pct.train"},
}


def _op(name, start, dur, op_name="", plane="/device:TPU:0"):
    return scopes.ScopedOp(plane, name, float(start), float(dur), op_name)


DECODE = "jit(decode_block)/while/body/closed_call/"
KERNEL = ("%paged_decode.3 = bf16[16,16,8,128] custom-call(%copy.7), "
          "custom_call_target=\"tpu_custom_call\"")
OPS = [
    # a loop of 20 s whose body runs 3 + 4 + 2 + 5 = 14 s: 6 s its own
    _op("%while.1 = () while()", 0, 20, "jit(decode_block)/while"),
    _op("%fusion.1 = bf16[16,2048] fusion()", 1, 3, DECODE + "mlp/dot_general"),
    _op(KERNEL, 5, 4, DECODE + "paged_attention/pallas_call"),
    # a relayout in front of the kernel, under the same scope; its
    # operand's name must not count as the kernel
    _op("%copy.7 = bf16[16,2049,16,128] copy(%paged_decode.2)", 9, 2,
        DECODE + "paged_attention/jit(_pad)/pad"),
    _op("%sort.2 = f32[16,50304] sort()", 12, 5, "sort"),
    # a second chip, busy 10 s under one scope
    _op("%fusion.9 = f32[8] fusion()", 0, 10, DECODE + "sampling/add",
        plane="/device:TPU:1"),
]


def test_scopes_of_reads_path_components_through_transformations():
    of = scopes.scopes_of
    assert of(DECODE + "mlp/dot_general") == {"mlp"}
    assert of("jit(zero_train_step)/transpose(jvp(ffn))/jvp(ffn)/checkpoint"
              "/rematted_computation/cos") == {"ffn"}
    assert of("jit(zero_train_step)/jvp(mlm_head_loss)/while/body/"
              "closed_call/jit(take_along_axis)/gather") == {"mlm_head_loss"}
    assert of("jit(zero_train_step)/optimizer_update/sub") \
        == {"optimizer_update"}
    assert of("jit(zero_train_step)/checkpoint(attention)/add") \
        == {"attention"}
    # a piece of a name is not a component, a jitted helper is no scope
    assert of("jit(f)/jit(mlp)/add") == set()
    assert of("jit(f)/my_mlp/mlp_out/add") == set()
    assert of("jit(f)/jvp(jit(_flash_attention_data))/pallas_call") == set()
    assert of("sort") == set() and of("") == set()


def test_scope_share_on_hand_made_events():
    assert scopes.self_seconds(OPS) == [6, 3, 4, 2, 5, 10]
    assert scopes.share(OPS, "mlp") == pytest.approx(10.0)
    assert scopes.share(OPS, "paged_attention") == pytest.approx(20.0)
    assert scopes.share(OPS, "paged_attention", "paged_decode") \
        == pytest.approx(100 * 2 / 30)
    assert scopes.share(OPS, "sampling") == pytest.approx(100 * 10 / 30)
    assert scopes.share(OPS, "lm_head") == 0.0
    # the loop's own time and the pathless sort lie under no scope
    assert scopes.share(OPS, "") == pytest.approx(100 * 11 / 30)
    # a program that names nothing gives nothing to read, not 100 or 0
    bare = [op._replace(op_name="jit(decode_block)/while/body/add")
            for op in OPS]
    assert scopes.share(bare, "") is None
    assert scopes.share(bare, "mlp") is None
    assert scopes.share([op._replace(op_name="") for op in OPS], "") is None
    assert scopes.share([], "mlp") is None


def test_scope_share_reader_opens_the_trace_once(monkeypatch):
    calls = []
    monkeypatch.setattr(scopes, "load",
                        lambda path: calls.append(path) or OPS)
    record = {"trace_path": "/somewhere/t.xplane.pb"}
    assert scope_share.read(record, [], {"scope": "mlp"}) \
        == pytest.approx(10.0)
    assert scope_share.read(record, [], {
        "scope": "paged_attention", "exclude": "paged_decode"}) \
        == pytest.approx(100 * 2 / 30)
    assert calls == ["/somewhere/t.xplane.pb"]
    assert scope_share.read({"trace_path": None}, None, {"scope": ""}) is None
    assert scope_share.read({}, None, {"scope": "mlp"}) is None


def _ev(name, start, dur, plane="/device:TPU:0", line="XLA Modules"):
    return tr.Event(plane, line, name, float(start), float(dur))


def test_module_share_on_hand_made_events():
    events = [
        _ev("jit_decode_block(7)", 0, 6), _ev("jit_prefill(9)", 6, 1),
        _ev("jit_prefill_offset(11)", 7, 1), _ev("jit_decode_block(7)", 8, 2),
        _ev("jit_prefill(9)", 0, 50, line="XLA Ops"),       # another line
        _ev("jit_prefill(9)", 0, 50, plane="/host:CPU"),    # no device
    ]
    assert module_share.read({}, events, {"match": "jit_prefill"}) \
        == pytest.approx(20.0)
    assert module_share.read({}, events, {"match": "jit_decode_block"}) \
        == pytest.approx(80.0)
    # a name that went away reads as nothing, not as no time
    assert module_share.read({}, events, {"match": "jit_ragged"}) is None
    assert module_share.read({}, [], {"match": "jit_prefill"}) is None
    assert module_share.read({}, None, {"match": "jit_prefill"}) is None


def test_counter_ratio():
    record = {"counters": {"serving_decode_rows_live_total": 30,
                           "serving_decode_rows_dispatched_total": 32,
                           "serving_queue_wait_seconds_total": 0.5,
                           "serving_admissions_total": 4, "idle": 0}}
    occupancy = {"over": "serving_decode_rows_live_total",
                 "under": "serving_decode_rows_dispatched_total",
                 "scale": 100}
    assert counter_ratio.read(record, None, occupancy) \
        == pytest.approx(93.75)
    assert counter_ratio.read(record, None, {
        "over": "serving_queue_wait_seconds_total",
        "under": "serving_admissions_total", "scale": 1000}) \
        == pytest.approx(125.0)
    assert counter_ratio.read(record, None, {
        "over": "serving_admissions_total", "under": "idle"}) is None
    # a program without the counters (the parent) gives nothing
    assert counter_ratio.read({"counters": {}}, None, occupancy) is None
    assert counter_ratio.read({}, None, occupancy) is None


def test_a_recorded_trace_carries_attributes_and_paths(tmp_path):
    """One trace recorded here: the program's span attributes arrive as
    the host event's stats. The CPU's trace has no device plane: the
    device's `tf_op` is read in the next test."""
    from paddle_tpu.profiler import RecordEvent

    def decode_block(x):
        with jax.named_scope("mlp"):
            return jnp.tanh(x @ x)

    f = jax.jit(decode_block)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with RecordEvent("serving.decode_block", rows=3, rows_dispatched=4,
                     horizon=8):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = [os.path.join(r, n) for r, _d, names in os.walk(tmp_path)
            for n in names if n.endswith(".xplane.pb")][0]

    spans = [dict(ev.stats)
             for plane in jax.profiler.ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name == "serving.decode_block"]
    assert spans == [{"rows": 3, "rows_dispatched": 4, "horizon": 8}]
    assert [e.name for e in tr.host_spans(tr.load(path))] \
        == ["serving.decode_block"]

    assert scopes.load(path) == []                  # no device plane


V5E_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "v5e_tiny.xplane.pb")


def test_scopes_load_reads_the_paths_of_a_v5e_trace():
    """A trace recorded on one v5e chip (PR 27): five calls of a jitted
    `decode_block(x, w)` on bf16[1024,1024] that runs `tanh(x @ w)`
    under scope `mlp`, a `lax.scan` of three `c + sin(c @ w)` under
    `attn_out`, and a `sort` under `sampling`. The device's plane keeps
    each operation's `op_name` as the `tf_op` stat of the event's
    metadata, which `jax.profiler.ProfileData` does not show."""
    ops = scopes.load(V5E_TRACE)
    assert len(ops) == 80 and {op.plane for op in ops} == {"/device:TPU:0"}
    by_own = {}
    for op in ops:
        by_own.setdefault(tr.own_name(op.name), set()).add(op.op_name)
    assert by_own["%convolution_tanh_fusion"] \
        == {"jit(decode_block)/mlp/dot_general"}
    assert by_own["%fusion.10"] == {
        "jit(decode_block)/while/body/closed_call/attn_out/dot_general"}
    # a copy that layout assignment put into the loop has the loop's
    # path and no scope; a copy-start has no path at all
    assert by_own["%copy.13"] == {"jit(decode_block)/while"}
    assert by_own["%copy-start"] == {""}
    assert by_own["%while"] == {""}
    # the same events at the same times as the reader of the other metrics
    seen = [e for e in tr.load(V5E_TRACE)
            if e.plane == "/device:TPU:0" and e.line == tr.OPS_LINE]
    assert [e.name for e in seen] == [op.name for op in ops]
    assert max(abs(e.start - op.start) + abs(e.duration - op.duration)
               for e, op in zip(seen, ops)) < 3e-9
    shares = {s: scopes.share(ops, s)
              for s in ("mlp", "attn_out", "sampling", "lm_head", "")}
    assert shares["lm_head"] == 0.0
    assert shares["sampling"] > shares["attn_out"] > shares["mlp"] > 1.0
    assert sum(shares.values()) == pytest.approx(100.0)
    assert 0 < shares[""] < 3.0


def test_the_benchmark_and_the_program_name_the_same_scopes():
    from paddle_tpu.profiler import scopes as program

    assert scopes.SERVE_SCOPES == program.SERVE_SCOPES
    assert scopes.TRAIN_SCOPES == program.TRAIN_SCOPES


@pytest.mark.parametrize("cell", sorted(NEW))
def test_new_metrics_are_found_by_name(cell):
    by_name = {m["name"]: m for m in spec.load_cell(cell)["per_layer"]}
    assert NEW[cell] <= set(by_name)
    for name in NEW[cell]:
        spec.load_reader(by_name[name]["reader"])
        if by_name[name]["reader"] == "scope_share":
            assert by_name[name]["args"]["scope"] in scopes.SCOPES | {""}
    # the successor reads the same work by the same formula as the
    # metric it follows
    if "paged_decode_kernel_roofline.serve" in NEW[cell]:
        old = by_name["paged_decode_roofline.serve"]
        new = by_name["paged_decode_kernel_roofline.serve"]
        assert new["reader"] == old["reader"] == "kernel_roofline"
        assert new["args"]["work"] == old["args"]["work"]
        assert new["args"]["match"] == "paged_decode"


def test_counter_metric_files_name_counters_the_engine_keeps():
    """`decode_batch_occupancy_pct.serve` and `queue_wait_ms_mean.serve`
    have their files; their entries in BENCHMARK.json wait for an edit
    to a test that this benchmark already had (PERF.md, open
    questions)."""
    from paddle_tpu.observability import MetricsRegistry
    from paddle_tpu.serving import ServingObs

    registry = MetricsRegistry()
    ServingObs(registry)
    kept = {m.name for m in registry.collect()
            if type(m).__name__ == "Counter" and not m.labels}
    for name in ("decode_batch_occupancy_pct.serve",
                 "queue_wait_ms_mean.serve"):
        with open(os.path.join(spec.ROOT, "metrics", name + ".json")) as f:
            metric = json.load(f)
        assert metric["reader"] == "counter_ratio"
        assert {metric["args"]["over"], metric["args"]["under"]} <= kept
