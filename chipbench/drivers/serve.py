"""Driver of a serving cell: a closed loop of clients over
`ServingEngine.add_request` / `step()`.

Set-up builds the model from the benchmark's own bf16 weights, builds a
default engine, and warms up each prompt bucket and each decode row
count the traffic can reach, then drains. In the window every client
submits its next request the moment its last one finished; tokens are
timed on the harness's clock at the return of `engine.step()`. Once the
window has closed and the engine is freed, the plain reference runs
once over a sample of the finished requests, the longest among them,
and `correct` compares every served token of the sample.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import common, compare, flops, traffic, weights
from ..spec import load_program, load_reference

_LIVE = ("waiting", "running")


def _dispatch_counts() -> dict:
    """`serving_attention_dispatch_total` by path: global to the
    process and counted at trace time, so callers take differences."""
    from paddle_tpu.observability import global_registry
    return {m.labels["path"]: m.value for m in global_registry().collect()
            if m.name == "serving_attention_dispatch_total"}


def _counters(engine) -> dict:
    if engine.metrics is None:
        return {}
    return {m.name: m.value for m in engine.metrics.collect()
            if type(m).__name__ == "Counter" and not m.labels}


def warm_up(engine, mix: dict, vocab: int) -> None:
    """One wave for each decode row count, prompts cycling through one
    length of each bucket, every wave drained before the next."""
    w = mix["warmup"]
    rng = np.random.default_rng(0)
    lens, i = w["prompt_lens"], 0
    for rows in w["rows"]:
        for _ in range(rows):
            engine.add_request(
                rng.integers(0, vocab, lens[i % len(lens)]).tolist(),
                max_new_tokens=int(w["new_tokens"]), temperature=0.0,
                seed=0)
            i += 1
        for _ in engine.stream():
            pass


def window(engine, feed, clients: int, seconds: float,
           trace: common.TraceSession = None) -> dict:
    live, done, step_ms = {}, [], []
    tokens = 0

    def submit():
        prompt, out = next(feed)
        rid = engine.add_request(prompt, max_new_tokens=out,
                                 temperature=0.0, seed=0)
        live[rid] = {"prompt": prompt, "out": out, "first": None,
                     "last": None, "n": 0, "submit": time.perf_counter()}

    t0 = time.perf_counter()
    for _ in range(clients):
        submit()
    while True:
        a = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.engine_step"):
            events = engine.step()
        now = time.perf_counter()
        step_ms.append(1e3 * (now - a))
        tokens += len(events)
        for rid, _tok in events:
            r = live.get(rid)
            if r is None:
                continue
            r["n"] += 1
            r["last"] = now
            if r["first"] is None:
                r["first"] = now
        closing = now - t0 >= seconds
        for rid in [r for r in live
                    if engine.requests[r].status not in _LIVE]:
            rec = live.pop(rid)
            req = engine.requests[rid]
            rec.update(status=req.status, generated=list(req.generated))
            done.append(rec)
            if not closing:
                submit()
        if trace is not None:
            trace.tick(now - t0)
        if closing:
            break
    t1 = now
    profiler_s = trace.overhead if trace is not None else 0.0
    stalls = list(trace.stalls) if trace is not None else []
    if trace is not None:
        trace.stop()
    in_flight = list(live.values())
    for rid in list(live):          # past the window: empty the engine
        engine.cancel(rid)
    for _ in range(64):
        if not engine.step() and not engine.scheduler.running \
                and not engine.scheduler.waiting:
            break
    return {"t0": t0, "t1": t1, "tokens": tokens, "done": done,
            "in_flight": in_flight, "step_ms": step_ms,
            "profiler_s": profiler_s, "stalls": stalls}


def sample(done: list, n: int, seed: int) -> list:
    """The longest finished request and n - 1 others drawn from the
    seed."""
    if not done:
        return []
    order = sorted(range(len(done)), key=lambda i: -(
        len(done[i]["prompt"]) + len(done[i]["generated"])))
    rest = order[1:]
    rng = np.random.default_rng(seed)
    picks = [order[0]] + [rest[i] for i in rng.permutation(len(rest))[:n - 1]]
    return [done[i] for i in picks]


def reference_gap(reference, leaves: dict, cfg: dict, rec: dict, pad: int,
                  stand_in=None) -> float:
    """Run the reference once over a request's prompt and served tokens
    and read the widest gap of a served token under its best. With
    `stand_in` (a lower precision) the tokens judged are not the served
    ones but those the reference puts first when computed in it."""
    prompt, served = rec["prompt"], rec["generated"]
    width = max(pad, -(-(len(prompt) + len(served)) // 128) * 128)
    ids = np.zeros((width,), np.int32)
    ids[:len(prompt) + len(served)] = prompt + served
    rows = np.arange(len(prompt) - 1, len(prompt) + len(served) - 1)
    logits = np.asarray(reference.logits(leaves, ids, rows, cfg))
    if stand_in is not None:
        served = np.argmax(np.asarray(
            reference.logits(leaves, ids, rows, cfg, stand_in)), axis=-1)
    return compare.served_gap(logits, served)


def run(spec: dict, seed: int, seconds: float, trace: bool,
        started: float) -> dict:
    cfg, mix = spec["config"], spec["traffic"]
    program = cfg["program"]
    reference = load_reference(cfg["reference"])
    vocab = cfg["vocab_size"]

    paths_before = _dispatch_counts()
    marks = {"begin": time.time() - started}
    leaves = jax.block_until_ready(
        weights.make(reference.shapes(cfg), seed, jnp.bfloat16))
    marks["weights"] = time.time() - started
    engine = load_program(program["builder"]).build(cfg, program, leaves)
    marks["engine"] = time.time() - started
    warm_up(engine, mix, vocab)
    marks["warm_up"] = time.time() - started
    marks["cache"] = dict(common.CACHE)

    session = (common.TraceSession(mix["trace_seconds"],
                                   after=mix["trace_after_seconds"])
               if trace else None)
    counters_before = _counters(engine)
    compiles = common.CompileCounter()
    setup_s = time.time() - started
    w = window(engine, traffic.request_blocks(mix, seed, vocab),
               int(mix["clients"]), seconds, session)
    compiled_in_window = compiles.close()
    counters_after = _counters(engine)
    paths = {p: n - paths_before.get(p, 0)
             for p, n in _dispatch_counts().items()}
    fault_events = engine.fault_events
    peak = common.peak_bytes()
    # free the program's state before the reference runs; the engine
    # parks its jitted steps on the model, so they go with it
    engine.model.__dict__.pop("_serving_jit_cache", None)
    del engine

    done = w["done"]
    checks = common.Checks()
    finished = [r for r in done if r["status"] == "finished"]
    sampled = sample(finished, int(mix["sample_requests"]), seed)
    gaps = [reference_gap(reference, leaves, cfg, r, int(mix["reference_pad"]))
            for r in sampled]
    # nothing finished, nothing compared: far over any limit
    checks.most("served_logit_gap", max(gaps, default=1e30),
                spec["limits"]["served_logit_gap"])
    not_finished = sum(r["status"] != "finished" for r in done)
    checks.equal("requests_not_finished", not_finished, 0)
    checks.equal("token_count_mismatches",
                 sum(len(r["generated"]) != r["out"] for r in done
                     if r["status"] == "finished"), 0)
    checks.equal("fault_events", fault_events, 0)
    checks.equal("reference_path_dispatches",
                 sum(n for p, n in paths.items() if "reference" in p), 0)
    checks.equal("no_pallas_decode_dispatch",
                 int(not any(n > 0 for p, n in paths.items()
                             if p.startswith("decode_pallas"))), 0)
    checks.equal("compiled_in_window", compiled_in_window, 0)

    elapsed = w["t1"] - w["t0"]
    # the tails of every request that ended in the window, on the
    # harness's clock; a failed request counts as the window's length.
    # In a traced run the host dispatches nothing while the profiler
    # starts and stops: requests in flight then are left out, unless
    # no other ended
    worst = 1e3 * elapsed
    clear = [r for r in done if not any(
        r["submit"] < b and a < (r["last"] or w["t1"])
        for a, b in w["stalls"])] or done
    ttft = [1e3 * (r["first"] - r["submit"])
            if r["status"] == "finished" else worst for r in clear]
    tpot = [1e3 * (r["last"] - r["first"]) / (r["n"] - 1)
            if r["status"] == "finished" and r["n"] > 1 else worst
            for r in clear]
    everyone = done + w["in_flight"]
    model_flops = sum(flops.gpt_serve_flops(cfg, len(r["prompt"]), r["n"])
                      for r in everyone)
    # what the paged decode kernel had to do: token k >= 1 of a request
    # attends its prompt and the k tokens before it
    decoded = sum(max(r["n"] - 1, 0) for r in everyone)
    context = sum(len(r["prompt"]) * max(r["n"] - 1, 0)
                  + r["n"] * (r["n"] - 1) // 2 for r in everyone)
    seconds = elapsed - w["profiler_s"]
    return {
        "end_to_end": {"serve_tokens_per_s": w["tokens"] / elapsed,
                       "setup_s": setup_s},
        "attempted": len(done), "failed": not_finished,
        "checks": checks, "peak_bytes": peak,
        "window_s": seconds, "engine_step_ms": w["step_ms"],
        "ttft_ms": ttft, "tpot_ms": tpot,
        "slowest_ms": sorted(w["step_ms"])[-3:],
        "model_flops": model_flops,
        "kernel_work": {"paged_decode": {
            "flops_per_s": flops.paged_decode_flops(cfg, context) / seconds,
            "bytes_per_s": flops.paged_decode_bytes(cfg, context, decoded)
            / seconds}},
        "counters": {k: counters_after[k] - counters_before.get(k, 0)
                     for k in counters_after},
        "setup_marks": marks,
        "trace_path": session.path() if session else None,
        "replay": {"leaves": leaves, "sampled": sampled,
                   "finished": finished},
    }


def control(spec: dict, record: dict) -> dict:
    """What the number reads when the reference, computed one precision
    below the configuration's, stands in the program's place: at every
    position of the same prompts and served tokens, the gap of the token
    that the lower precision puts first, held to the cell's own limit
    (`ok` has to come out false). Read on the chip by
    `chipbench.control`; no benchmark run calls this."""
    from .. import lowprec

    cfg, mix = spec["config"], spec["traffic"]
    reference = load_reference(cfg["reference"])
    below = lowprec.BELOW[cfg["precision"]]
    replay = record["replay"]
    gaps = [reference_gap(reference, replay["leaves"], cfg, rec,
                          int(mix["reference_pad"]), stand_in=below)
            for rec in replay["sampled"]]
    if not gaps:
        raise ValueError("no finished request to read the control on: "
                         "the window is too short for the mix")
    checks = common.Checks()
    checks.most("served_logit_gap", max(gaps),
                spec["limits"]["served_logit_gap"])
    return {"control": common.stand_in_line(checks)}
