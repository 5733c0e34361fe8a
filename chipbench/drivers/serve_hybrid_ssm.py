"""Driver of a serving cell whose model has Mamba-2 layers over state
slots beside attention layers over K/V pages: `drivers/serve.py`'s
closed loop, warm-up, sample and comparison, and four things of its own.
The leaves this configuration draws its own way (`reference/
hybrid_ssm.py` `own_leaves`: a Mamba layer's three small float32 leaves
and its conv, the embedding's width) are mapped after `weights.make`;
the operations and bytes come from `flops_hybrid_ssm.py`; both decode kernels have to have been dispatched,
`ssm_decode` for the state and the paged kernel for the K/V pages; and
the control holds two stand-ins to the limit, the fp8 reference and the
exact reference with its state's carry dropped, and reads a third, the
reference with its state rounded to bf16 after every token.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import common, flops_hybrid_ssm as flops, traffic, weights
from ..spec import load_program, load_reference
from .serve import (_counters, _dispatch_counts, reference_gap, sample,
                    warm_up, window)


def _mark(what: str, started: float) -> None:
    """A line on standard error as a phase ends: a run that is cut says
    how far it got."""
    print(f"chipbench: {time.time() - started:8.1f} s  {what}",
          file=sys.stderr, flush=True)


def run(spec: dict, seed: int, seconds: float, trace: bool,
        started: float) -> dict:
    cfg, mix = spec["config"], spec["traffic"]
    program = cfg["program"]
    reference = load_reference(cfg["reference"])
    vocab = cfg["vocab_size"]
    # first: a program that lacks the model fails here, at once
    builder = load_program(program["builder"])

    paths_before = _dispatch_counts()
    marks = {"begin": time.time() - started}
    leaves = reference.own_leaves(
        weights.make(reference.shapes(cfg), seed, jnp.bfloat16), cfg, seed)
    leaves = jax.block_until_ready(leaves)
    marks["weights"] = time.time() - started
    engine = builder.build(cfg, program, leaves)
    marks["engine"] = time.time() - started
    _mark("weights made, engine built", started)
    warm_up(engine, mix, vocab)
    marks["warm_up"] = time.time() - started
    _mark("warmed up", started)
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
    _mark(f"window closed, {len(w['done'])} requests ended", started)
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
    gaps = []
    for r in sampled:
        gaps.append(reference_gap(reference, leaves, cfg, r,
                                  int(mix["reference_pad"])))
        _mark(f"reference over {len(r['prompt']) + len(r['generated'])} "
              f"tokens: gap {gaps[-1]:.4g}", started)
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
    checks.equal("no_ssm_decode_kernel_dispatch",
                 int(not any(n > 0 for p, n in paths.items()
                             if p.startswith("ssm_decode_pallas"))), 0)
    checks.equal("no_pallas_decode_dispatch",
                 int(not any(n > 0 for p, n in paths.items()
                             if p.startswith("decode_pallas"))), 0)
    checks.equal("compiled_in_window", compiled_in_window, 0)

    elapsed = w["t1"] - w["t0"]
    # the tails as `drivers/serve.py` takes them
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
    model_flops = sum(flops.serve_flops(cfg, len(r["prompt"]), r["n"])
                      for r in everyone)
    # what the decode kernels had to do: token k >= 1 of a request
    # updates every Mamba layer's state once and attends its prompt and
    # the k tokens before it
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
        "kernel_work": {
            "ssm_decode": {
                "flops_per_s": flops.ssm_decode_flops(cfg, decoded)
                / seconds,
                "bytes_per_s": flops.ssm_decode_bytes(cfg, decoded)
                / seconds},
            "paged_decode": {
                "flops_per_s": flops.paged_decode_flops(cfg, context)
                / seconds,
                "bytes_per_s": flops.paged_decode_bytes(cfg, context,
                                                        decoded) / seconds}},
        "counters": {k: counters_after[k] - counters_before.get(k, 0)
                     for k in counters_after},
        "setup_marks": marks,
        "trace_path": session.path() if session else None,
        "replay": {"leaves": leaves, "sampled": sampled,
                   "finished": finished},
    }


def control(spec: dict, record: dict) -> dict:
    """Three stand-ins in the program's place, each judged as
    `drivers/serve.py` judges its control (the tokens the stand-in puts
    first, held to the float32 reference's logits and the cell's limit).
    Two have to come out as not correct: `control`, the reference one
    precision below the configuration's in every matmul, and
    `state_carry_dropped`, the exact reference whose recurrent state is
    lost in front of every `mamba_chunk_size`-th position, as a scan
    that drops its carry between chunks or a decode step on the wrong
    slot would leave it: what the cell exists to run. The third is read
    and printed and held to nothing: exact matmuls with the state
    rounded to bf16 after every token (`state_bf16_served_logit_gap`;
    PERF.md section 2 says which way it fell and what holds the state's
    precision). The reference's own logits are computed once a request
    for all three."""
    from .. import compare, lowprec

    cfg, mix = spec["config"], spec["traffic"]
    reference = load_reference(cfg["reference"])
    stand_ins = {
        "control": {"matmul": lowprec.BELOW[cfg["precision"]]},
        "state_carry_dropped": {"carry_every": cfg["mamba_chunk_size"]},
        "state_bf16": {"state_dtype": jnp.bfloat16}}
    replay = record["replay"]
    if not replay["sampled"]:
        raise ValueError("no finished request to read the control on: "
                         "the window is too short for the mix")
    gaps = {name: 0.0 for name in stand_ins}
    for rec in replay["sampled"]:
        ids, rows = _padded(rec, int(mix["reference_pad"]))
        exact = np.asarray(reference.logits(replay["leaves"], ids, rows,
                                            cfg))
        for name, how in stand_ins.items():
            first = np.argmax(np.asarray(reference.logits(
                replay["leaves"], ids, rows, cfg, **how)), axis=-1)
            gaps[name] = max(gaps[name], compare.served_gap(exact, first))
    lines = {}
    for name in ("control", "state_carry_dropped"):
        checks = common.Checks()
        checks.most("served_logit_gap", gaps[name],
                    spec["limits"]["served_logit_gap"])
        if name == "control":
            checks.note("state_bf16_served_logit_gap", gaps["state_bf16"])
        lines[name] = common.stand_in_line(checks)
    return lines


def _padded(rec: dict, pad: int):
    """A request's prompt and served tokens at the reference's one
    width, and the positions that chose a served token, as
    `drivers/serve.py` `reference_gap` lays them out."""
    prompt, served = rec["prompt"], rec["generated"]
    width = max(pad, -(-(len(prompt) + len(served)) // 128) * 128)
    ids = np.zeros((width,), np.int32)
    ids[:len(prompt) + len(served)] = prompt + served
    return ids, np.arange(len(prompt) - 1, len(prompt) + len(served) - 1)
