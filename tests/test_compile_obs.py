"""Set-up accounting (`paddle_tpu/observability/compiles.py`): the
executables the serving engine and the ZeRO trainer build, counted by
family, with the seconds of each phase and what the persistent compile
cache said, and the benchmark's reader of those counters."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import MetricsRegistry, compiles, \
    global_registry
from paddle_tpu.parallel import zero_train_step
from paddle_tpu.serving import ServingEngine

from chipbench.readers import setup_compile

_TRACE = "/jax/core/compile/jaxpr_trace_duration"


def _counts() -> dict:
    """(counter, family, phase or cache) -> value, of every series the
    accounting keeps in the process-wide registry."""
    return {(m.name, m.labels["family"],
             m.labels.get("phase", m.labels.get("cache"))): m.value
            for m in global_registry().collect()
            if m.name in (compiles.SECONDS, compiles.EXECUTABLES)}


def _moved(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def _executables(moved: dict) -> dict:
    out = {}
    for (name, family, _), v in moved.items():
        if name == compiles.EXECUTABLES:
            out[family] = out.get(family, 0) + v
    return out


def _seconds(moved: dict, phase: str) -> dict:
    return {family: v for (name, family, p), v in moved.items()
            if name == compiles.SECONDS and p == phase}


def _gpt():
    paddle.seed(1234)
    model = GPTForCausalLM(GPTConfig.tiny())
    model.eval()
    return model


def _serve(model):
    """One prompt in each of two buckets, then a decode of two rows."""
    eng = ServingEngine(model, page_size=8, max_batch_size=2,
                        max_seq_len=64, prefill_buckets=(16, 64))
    eng.add_request([1, 2, 3], max_new_tokens=4, temperature=0.0)
    eng.add_request(list(range(1, 21)), max_new_tokens=4, temperature=0.0)
    eng.run()
    return eng


class _TimedSpan(compiles.span):
    """The engine's span, with its wall time kept by family."""

    __slots__ = ("_t0",)
    walls: dict = {}

    def __enter__(self):
        self._t0 = time.perf_counter()
        return super().__enter__()

    def __exit__(self, *exc):
        out = super().__exit__(*exc)
        walls = type(self).walls
        walls[self.family] = (walls.get(self.family, 0.0)
                              + time.perf_counter() - self._t0)
        return out


def test_warm_up_counts_each_family_once_and_a_second_engine_none(
        monkeypatch):
    monkeypatch.setattr(_TimedSpan, "walls", {})
    monkeypatch.setattr(compiles, "span", _TimedSpan)
    raw = []

    def every_trace(event, seconds, **_):
        if event == _TRACE and compiles._STATE.families:
            raw.append(seconds)

    model = _gpt()
    before = _counts()
    jax.monitoring.register_event_duration_secs_listener(every_trace)
    try:
        eng = _serve(model)
    finally:
        jax.monitoring.unregister_event_duration_listener(every_trace)
    moved = _moved(before, _counts())
    built = _executables(moved)
    steps = {f: n for f, n in eng.compile_counts().items()
             if f != "total" and n}
    assert steps == {"prefill": 2, "decode": 1}
    assert {f: built[f] for f in steps} == steps
    # every phase of each family's build is there, on its own
    for family in steps:
        for phase in ("trace", "lower", "backend"):
            assert _seconds(moved, phase)[family] > 0, (family, phase)
    # nested traces (jitted jnp functions inside the step's trace) fire
    # events of their own: counted once, a family's trace seconds stay
    # inside the wall time of its serving.compile spans
    traced = _seconds(moved, "trace")
    assert sum(raw) > sum(traced[f] for f in steps)
    for family in steps:
        assert traced[family] <= _TimedSpan.walls[family]

    again = _counts()
    second = _serve(model)
    assert second.compile_counts() == eng.compile_counts()
    assert _executables(_moved(again, _counts())) == {}


def test_an_eager_op_the_engine_runs_counts_as_host_ops():
    before = _counts()
    # a pool of a shape nothing has made yet: its zeros compile, outside
    # any step family
    ServingEngine(_gpt(), page_size=8, max_batch_size=2, max_seq_len=64,
                  num_pages=37)
    assert _executables(_moved(before, _counts())).get(
        compiles.HOST_OPS, 0) >= 1


def test_a_jit_outside_the_program_is_not_counted():
    compiles.install()
    before = _counts()
    x = jnp.arange(12.0).reshape(3, 4)
    jax.jit(lambda a: (a @ a.T - 7.0).sum())(x).block_until_ready()
    assert _moved(before, _counts()) == {}


def test_install_puts_back_a_listener_dropped_mid_count():
    """`jax.monitoring.clear_event_listeners()` drops the end-of-phase
    listener and keeps the start one: phases then start and never end.
    The next engine's or trainer's `install()` puts the listener back
    and counts the next build whole."""
    compiles.install()
    x = jnp.arange(6.0)
    jax.monitoring.unregister_event_duration_listener(compiles._on_end)
    try:
        with compiles.span("test.compile", "dropped"):
            jax.jit(lambda a: a * 5.0 - 2.0)(x).block_until_ready()
    finally:
        compiles.install()
    before = _counts()
    with compiles.span("test.compile", "put_back"):
        jax.jit(lambda a: a * 6.0 + 1.0)(x).block_until_ready()
    moved = _moved(before, _counts())
    assert _executables(moved) == {"put_back": 1}
    assert set(_seconds(moved, "trace")) == {"put_back"}


def test_the_persistent_cache_misses_then_holds(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    compiles.install()
    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    x = jnp.arange(16.0).reshape(4, 4)

    def build():
        before = _counts()
        with compiles.span("test.compile", "cache_probe", key=4):
            jax.jit(lambda a: jnp.tanh(a) * 3.0 + 1.0)(x).block_until_ready()
        return {c: v for (name, _f, c), v in _moved(before, _counts()).items()
                if name == compiles.EXECUTABLES}

    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()
        assert build() == {"miss": 1}
        jax.clear_caches()
        assert build() == {"hit": 1}
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    jax.clear_caches()
    assert build() == {"off": 1}         # no cache: neither held nor kept


def test_zero_train_step_counts_its_step_once_a_shape():
    rng = np.random.RandomState(0)
    x = rng.randn(16, 8).astype("float32")
    y = rng.randn(16, 4).astype("float32")
    paddle.seed(7)
    net = nn.Sequential(nn.Linear(8, 24), nn.ReLU(), nn.Linear(24, 4))
    opt = paddle.optimizer.Adam(learning_rate=0.01,
                                parameters=net.parameters())
    step = zero_train_step(net, opt, stage=1, dp=2)
    params, state = step.init_state()
    before = _counts()
    for t in range(1, 4):
        _loss, params, state = step(params, state, (x, y), 0.01, t)
    moved = _moved(before, _counts())
    assert _executables(moved).get("zero_train_step") == 1
    assert _seconds(moved, "backend")["zero_train_step"] > 0
    again = _counts()
    step(params, state, (x[:8], y[:8]), 0.01, 4)
    assert _executables(_moved(again, _counts())).get(
        "zero_train_step") == 1


_FILLED = {("trace", "prefill"): 2.0, ("lower", "prefill"): 1.0,
           ("backend", "prefill"): 4.0, ("trace", "host_ops"): 0.25,
           ("backend", "host_ops"): 0.5}
_BUILT = {("prefill", "hit"): 3, ("prefill", "miss"): 1,
          ("host_ops", "off"): 2}


def test_reader_off_a_tpu_and_on_one(monkeypatch):
    args = {"setup_compile_s": {"read": "seconds",
                                "phases": ["trace", "lower", "backend"]},
            "setup_trace_lower_s": {"read": "seconds",
                                    "phases": ["trace", "lower"]},
            "setup_executables": {"read": "executables"},
            "setup_cache_misses": {"read": "misses"}}
    # the CPU: a compile there says nothing of the chip's set-up
    assert all(setup_compile.read({}, None, a) is None
               for a in args.values())
    import paddle_tpu.observability as obs

    registry = MetricsRegistry()
    monkeypatch.setattr(obs, "global_registry", lambda: registry)
    monkeypatch.setattr(setup_compile.jax, "default_backend", lambda: "tpu")
    # a program without the counters: nothing, not zero
    assert all(setup_compile.read({}, None, a) is None
               for a in args.values())
    for (phase, family), v in _FILLED.items():
        registry.counter(setup_compile.SECONDS, labels={
            "family": family, "phase": phase}).inc(v)
    for (family, cache), n in _BUILT.items():
        registry.counter(setup_compile.EXECUTABLES, labels={
            "family": family, "cache": cache}).inc(n)
    got = {k: setup_compile.read({}, None, a) for k, a in args.items()}
    assert got == {"setup_compile_s": 7.75, "setup_trace_lower_s": 3.25,
                   "setup_executables": 6.0, "setup_cache_misses": 3.0}
