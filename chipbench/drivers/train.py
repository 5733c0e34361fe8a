"""Driver of a pretraining cell: `ZeroTrainStep` over a model's loss.

Set-up builds one trainer with its state from the benchmark's own
weights, drives it through its first `check_steps` steps (which also
compile and warm it up) and hands the same object to the window. The
window dispatches step t+1 before it blocks on step t-1's loss. Once
the window has closed and the program's state is freed, the plain
reference follows the first steps and `correct` compares them.
"""
from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp

from .. import common, compare, flops, traffic, weights
from ..spec import load_program, load_reference


def _slots(state: dict, slot: str, back: dict) -> dict:
    """One optimizer slot of every leaf, under the reference's names."""
    return {back.get(k, k): s[slot] for k, s in state.items()
            if isinstance(s, dict) and slot in s}


BETA1 = 0.9      # Adam's, as the configuration's optimizer has it


def first_steps(step, params, state, batches, lr, start, back,
                reference) -> tuple:
    """Drive `step` over `batches` through the window's own call, and
    read what `correct` compares: each loss; the first gradient as the
    optimizer got it, from Adam's first moment after one step
    (m = (1 - beta1) g), taken to the host so that it does not weigh on
    the window's memory; every leaf's change since `start`, on the fp32
    masters the trainer keeps. The reductions are the reference's own."""
    losses, first = [], None
    for t, batch in enumerate(batches, start=1):
        loss, params, state = step(params, state, batch, lr, t)
        losses.append(float(loss))
        if first is None:
            first = {k: v / (1 - BETA1) for k, v in jax.device_get(
                _slots(state, "moment1", back)).items()}
    masters = _slots(state, "master_weight", back)
    now = {back.get(k, k): masters.get(back.get(k, k), v)
           for k, v in params.items()}
    return params, state, {
        "losses": losses, "first_gradient": first,
        "grad_norms": {k: float(v) for k, v in
                       reference.leaf_norms(first).items()},
        "change_norms": reference.difference_norms(now, start)}


def window(step, params, state, feed, lr, t, seconds: float,
           trace: common.TraceSession = None) -> dict:
    """Dispatch step t+1 before blocking on step t-1: the device always
    has the next step queued and the host never runs further ahead."""
    pending = collections.deque()
    ends = []
    # the longest the host spent in each phase of one turn: where a
    # step that read far off was held up
    longest = {"dispatch": 0.0, "feed": 0.0, "wait": 0.0}
    batch = next(feed)
    t0 = time.perf_counter()
    while True:
        a = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.dispatch"):
            loss, params, state = step(params, state, batch, lr, t)
        pending.append(loss)
        t += 1
        b = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.feed"):
            batch = next(feed)
        c = time.perf_counter()
        if len(pending) == 2:
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                pending.popleft().block_until_ready()
            ends.append(time.perf_counter())
        now = time.perf_counter()
        for phase, took in (("dispatch", b - a), ("feed", c - b),
                            ("wait", now - c)):
            longest[phase] = max(longest[phase], 1e3 * took)
        elapsed = now - t0
        if trace is not None:
            trace.tick(elapsed)
        if elapsed >= seconds:
            break
    while pending:
        pending.popleft().block_until_ready()
        ends.append(time.perf_counter())
    profiler_s = trace.overhead if trace is not None else 0.0
    if trace is not None:
        trace.stop()
    return {"t0": t0, "t1": ends[-1], "steps": len(ends),
            "profiler_s": profiler_s, "longest_ms": longest,
            "step_ms": [1e3 * (y - x) for x, y in zip(ends, ends[1:])],
            "params": params, "state": state}


def _compare(checks, reference, got: dict, want: dict, limits: dict):
    """The first gradients are whole trees: their difference is reduced
    to norms here, by the reference's own reduction, and dropped."""
    got["grad_diff_norms"] = reference.difference_norms(
        got.pop("first_gradient"), want["first_gradient"])
    compare.training(checks, got, want, limits)


def run(spec: dict, seed: int, seconds: float, trace: bool,
        started: float) -> dict:
    cfg, mix = spec["config"], spec["traffic"]
    program = cfg["program"]
    reference = load_reference(cfg["reference"])
    rename = cfg.get("param_names", {})
    lr = float(program["optimizer"]["learning_rate"])
    check_steps = int(mix["check_steps"])

    start = weights.make(reference.shapes(cfg), seed, jnp.float32)
    trainer = load_program(program["builder"]).build(cfg, program)
    params, state = trainer.init_state(
        {rename.get(k, k): v for k, v in start.items()})
    back = {v: k for k, v in rename.items()}

    def put(batch):
        return tuple(jax.device_put(x) for x in batch)

    host_feed = traffic.mlm_batches(mix, seed, cfg["vocab_size"])
    first = [next(host_feed) for _ in range(check_steps)]
    params, state, got = first_steps(
        trainer, params, state, [put(b) for b in first], lr, start, back,
        reference)

    session = (common.TraceSession(mix["trace_seconds"],
                                   after=mix["trace_after_seconds"])
               if trace else None)
    compiles = common.CompileCounter()
    marks = {"cache": dict(common.CACHE)}
    setup_s = time.time() - started
    w = window(trainer, params, state, (put(b) for b in host_feed), lr,
               check_steps + 1, seconds, session)
    compiled_in_window = compiles.close()
    peak = common.peak_bytes()
    del params, state, trainer, w["params"], w["state"]

    want = reference.follow(start, first, cfg, lr,
                            block_rows=int(mix["reference_block_rows"]))
    checks = common.Checks()
    _compare(checks, reference, got, want, spec["limits"])
    checks.equal("compiled_in_window", compiled_in_window, 0)

    rows, seq = int(mix["rows"]), int(mix["seq"])
    tokens = w["steps"] * rows * seq
    elapsed = w["t1"] - w["t0"]
    per_token = flops.ernie_train_flops_per_token(
        cfg, seq, round(mix["labelled_share"] * seq) / seq)
    return {
        "end_to_end": {"train_tokens_per_s": tokens / elapsed,
                       "setup_s": setup_s},
        "attempted": w["steps"], "failed": 0,
        "checks": checks, "peak_bytes": peak,
        "window_s": elapsed - w["profiler_s"],
        "step_ms": w["step_ms"],
        "slowest_ms": sorted(w["step_ms"])[-3:],
        "longest_ms": w["longest_ms"], "setup_marks": marks,
        "model_flops": per_token * tokens,
        "kernel_work": {"flash_train_layer": flops.flash_train_call(
            rows, cfg["num_attention_heads"], seq,
            cfg["hidden_size"] // cfg["num_attention_heads"])},
        "trace_path": session.path() if session else None,
        "got": got, "want": want,
        "replay": {"start": start, "batches": first, "lr": lr},
    }


def control(spec: dict, record: dict) -> dict:
    """What the numbers read when the reference stands in the program's
    place, computed one precision below the configuration's (the
    control), and with half of every batch left out and the mean taken
    over the rest (a fault): each compared with the reference as a run
    is, against the cell's own limits (each one's `ok` has to come out
    false). Read on the chip by `chipbench.control`; no benchmark run
    calls this."""
    from .. import lowprec

    cfg, mix = spec["config"], spec["traffic"]
    reference = load_reference(cfg["reference"])
    r, want = record["replay"], record["want"]
    rows = int(mix["rows"])
    block = int(mix["reference_block_rows"])
    stand_ins = {
        "control": reference.follow(
            r["start"], r["batches"], cfg, r["lr"], block_rows=block,
            matmul=lowprec.BELOW[cfg["precision"]]),
        "half_batch": reference.follow(
            r["start"], [(i[:rows // 2], l[:rows // 2])
                         for i, l in r["batches"]],
            cfg, r["lr"], block_rows=min(block, rows // 2)),
    }
    out = {}
    for name, got in stand_ins.items():
        checks = common.Checks()
        _compare(checks, reference, got, want, spec["limits"])
        out[name] = common.stand_in_line(checks)
    return out
