"""The engine's counters at dispatch and admission, and the attributes
its spans carry: host integers and clocks, counted exactly, and not at
all with `enable_metrics=False`; among them the launches whose rows were
all greedy, which took the argmax alone."""
import functools
import time

import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import MetricsRegistry
from paddle_tpu.serving import (BlockAllocator, Request, SamplingParams,
                                Scheduler, ServingEngine, ServingObs,
                                SpecConfig)

HORIZON = 4
MAX_BATCH = 4


@functools.lru_cache(maxsize=None)
def _gpt():
    paddle.seed(1234)
    m = GPTForCausalLM(GPTConfig.tiny())
    m.eval()
    return m


def _engine(**kw):
    return ServingEngine(_gpt(), page_size=8, max_batch_size=MAX_BATCH,
                         max_seq_len=32, prefill_buckets=(16, 32),
                         decode_horizon=HORIZON, **kw)


def _counts(obs):
    return (obs.decode_rows_live.value, obs.decode_rows_dispatched.value,
            obs.admissions.value, obs.queue_wait_seconds.value)


def test_dispatch_and_admission_counters_count_exactly():
    eng = _engine()
    obs = eng._obs
    assert _counts(obs) == (0, 0, 0, 0)
    budgets = [9, 6, 13]
    for i, n in enumerate(budgets):
        eng.add_request([3 + i, 5, 7, 11][:2 + i], max_new_tokens=n,
                        temperature=0.0)
    seen = _counts(obs)
    while eng.scheduler.has_work() or eng._pending is not None:
        eng.step()
        now = _counts(obs)
        live, rows = now[0] - seen[0], now[1] - seen[1]
        # a step dispatches at most one decode block, of a power-of-two
        # row count, and no more live rows than it dispatches
        assert rows in (0, 1, 2, 4) and 0 <= live <= rows
        assert (rows > 0) == (live > 0)
        assert now[2] >= seen[2] and now[3] >= seen[3]
        seen = now
    live, rows, admitted, waited = seen
    assert admitted == len(budgets)         # nothing was preempted
    assert waited > 0
    # a request is live in exactly the blocks that owe it tokens: the
    # prefill gives the first, every block up to HORIZON more
    assert live == sum(-(-(n - 1) // HORIZON) for n in budgets)
    assert rows >= live
    assert [len(eng.requests[r].generated)
            for r in sorted(eng.requests)] == budgets


_SAMPLED = dict(temperature=0.8, top_k=7, top_p=0.9, seed=42)

# every family of launch the engine has: the bucketed prefill and the
# decode block; the chained chunk pipeline; the ragged step; the
# speculative block
_FAMILIES = {
    "prefill_and_decode": {},
    "chunk": dict(enable_chunked_prefill=True, prefill_chunk_tokens=8,
                  enable_ragged_step=False),
    "ragged": dict(enable_chunked_prefill=True, prefill_chunk_tokens=8),
    "spec": dict(spec_config=SpecConfig(lookahead=2)),
}


def _dispatches(obs):
    return obs.dispatches.value, obs.greedy_dispatches.value


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_greedy_dispatches_count_the_launches_that_skipped_the_sort(family):
    """`serving_greedy_dispatches_total` over `serving_dispatches_total`
    is the share of launches whose rows were all at temperature 0: all of
    them in a greedy run, none of a sampled request's own, and the
    difference is exactly the launches that carried a sampling row."""
    eng = _engine(**_FAMILIES[family])
    obs = eng._obs
    assert eng.metrics.get("serving_greedy_dispatches_total") is \
        obs.greedy_dispatches
    for prompt, n in (([3, 5, 7, 11, 13, 17, 19, 23, 29, 31], 9),
                      ([2, 4, 6], 6)):
        eng.add_request(prompt, max_new_tokens=n, temperature=0.0)
    eng.run()
    launched, greedy = _dispatches(obs)
    assert launched == greedy > 2
    # a sampling request alone: none of its launches is greedy (its
    # prompt is one chunk: a ragged step gives an intermediate chunk's
    # row, which samples nothing, temperature 0)
    eng.add_request([1, 2, 3, 4, 5, 6], max_new_tokens=7, **_SAMPLED)
    eng.run()
    launched_2, greedy_2 = _dispatches(obs)
    assert launched_2 > launched + 1 and greedy_2 == greedy
    # mixed: the greedy request outlives the sampling one, so the batch
    # turns greedy again and the launches after that count
    eng.add_request([5, 4, 3], max_new_tokens=3, **_SAMPLED)
    eng.add_request([9, 8, 7, 6], max_new_tokens=14, temperature=0.0)
    eng.run()
    launched_3, greedy_3 = _dispatches(obs)
    assert greedy_2 < greedy_3 < greedy_2 + (launched_3 - launched_2)


def test_a_chained_block_inherits_its_first_block_s_reading():
    """A chained decode block takes its knobs from the block before it
    (no host arrays): it counts as that block counted."""
    eng = _engine()
    eng.add_request([1, 2, 3], max_new_tokens=3 * HORIZON + 1,
                    temperature=0.0)
    eng.run()
    assert eng._obs.decode_steps.value >= 3
    assert _dispatches(eng._obs)[0] == _dispatches(eng._obs)[1]
    eng.add_request([1, 2, 3], max_new_tokens=3 * HORIZON + 1, **_SAMPLED)
    before = _dispatches(eng._obs)
    eng.run()
    after = _dispatches(eng._obs)
    assert after[0] - before[0] >= 4 and after[1] == before[1]


def test_a_requeued_request_counts_again_from_its_requeue():
    obs = ServingObs(MetricsRegistry())
    sched = Scheduler(BlockAllocator(6), page_size=4, max_batch_size=2,
                      max_pages_per_seq=8, obs=obs)
    a = Request(prompt=[1] * 8, max_new_tokens=8, sampling=SamplingParams())
    b = Request(prompt=[2] * 4, max_new_tokens=8, sampling=SamplingParams())
    b.arrival_t = time.perf_counter() - 100.0       # queued for 100 s
    sched.add(a)
    sched.add(b)
    assert sched.schedule().prefill is a
    assert sched.schedule().prefill is b
    assert obs.admissions.value == 2
    first = obs.queue_wait_seconds.value
    assert 100.0 <= first < 101.0
    a.generated, b.generated = [0] * 5, [0] * 2     # a needs b's pages
    assert sched.schedule().decode == [a]
    assert b.status == "waiting" and b.requeue_t is not None
    sched.finish(a)
    assert sched.schedule().prefill is b
    assert obs.admissions.value == 3
    # the second wait runs from the requeue, not from the arrival
    assert 0.0 <= obs.queue_wait_seconds.value - first < 1.0


@pytest.mark.parametrize("knobs", [dict(temperature=0.0), _SAMPLED],
                         ids=["greedy", "sampled"])
def test_metrics_off_touches_no_counter(monkeypatch, knobs):
    import paddle_tpu.observability.metrics as obsm

    eng = _engine(enable_metrics=False)
    assert eng._obs is None and eng.scheduler.obs is None
    # warm first: tracing may count its dispatch selections in the
    # global registry
    eng.add_request([9, 8, 7], max_new_tokens=6, **knobs)
    eng.run()

    def boom(*a, **kw):
        raise AssertionError("metrics work on a disabled hot path")

    monkeypatch.setattr(obsm.MetricsRegistry, "counter", boom)
    monkeypatch.setattr(obsm.Counter, "inc", boom)
    rid = eng.add_request([1, 2, 3], max_new_tokens=6, **knobs)
    assert len(eng.run()[rid]) == 3 + 6


def test_spans_carry_what_the_host_dispatched(monkeypatch):
    import paddle_tpu.profiler as prof

    spans = []

    class Annotation:
        def __init__(self, name, **attrs):
            spans.append((name, attrs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(prof, "_TraceAnnotation", Annotation)
    eng = _engine()
    rid = eng.add_request([1, 2, 3, 4, 5], max_new_tokens=6,
                          temperature=0.0)
    eng.run()
    by_name = {}
    for name, attrs in spans:
        by_name.setdefault(name, []).append(attrs)
    assert by_name["serving.prefill"] == [
        {"bucket": 16, "prompt_tokens": 5, "rid": rid}]
    blocks = by_name["serving.decode_block"]
    assert blocks and all(
        set(b) == {"rows", "rows_dispatched", "horizon"} for b in blocks)
    assert blocks[0] == {"rows": 1, "rows_dispatched": 1, "horizon": HORIZON}
    assert all(type(v) is int for b in blocks for v in b.values())
    assert by_name["serving.host_drain"][0] == {}


@pytest.mark.parametrize("n,rows", [(1, 1), (2, 2), (3, 4), (4, 4), (9, 4)])
def test_decode_rows_is_the_next_power_of_two(n, rows):
    assert _engine()._decode_rows(n) == rows
