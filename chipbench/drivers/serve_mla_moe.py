"""Driver of a serving cell whose model has latent attention and routed
experts: `drivers/serve.py`'s closed loop, warm-up and sample, and four
things of its own. The operations and bytes come from
`flops_mla_moe.py`; the decode kernel that has to have been dispatched
is `mla_decode`, not the K/V pools' paged kernel; the expert layer's
counters (pairs routed, distinct experts touched) give the grouped
matmuls' work, never an assumed count; and the comparison with the
reference is read a position at a time, over the positions where the
reference's own routing is clear of a tie (`read_gaps`).
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import common, flops_mla_moe as flops, traffic, weights
from ..spec import load_program, load_reference
from .serve import _counters, _dispatch_counts, sample, warm_up, window

def position_gaps(reference, leaves: dict, cfg: dict, rec: dict, pad: int,
                  stand_in=None) -> tuple:
    """As `drivers/serve.py`'s `reference_gap`, a position at a time: by
    how much each served token's logit lies under the reference's best
    (with `stand_in`, the token the reference puts first when computed in
    that lower precision), and the reference's own routing margin there:
    the least, over the expert layers, between the score of the last
    expert chosen and the first left out."""
    prompt, served = rec["prompt"], rec["generated"]
    width = max(pad, -(-(len(prompt) + len(served)) // 128) * 128)
    ids = np.zeros((width,), np.int32)
    ids[:len(prompt) + len(served)] = prompt + served
    rows = np.arange(len(prompt) - 1, len(prompt) + len(served) - 1)
    logits, margin = reference.logits(leaves, ids, rows, cfg,
                                      with_margin=True)
    logits = np.asarray(logits, np.float32)
    if stand_in is not None:
        served = np.argmax(np.asarray(
            reference.logits(leaves, ids, rows, cfg, stand_in)), axis=-1)
    gaps = logits.max(axis=-1) - logits[np.arange(len(rows)),
                                        np.asarray(served)]
    return gaps, np.asarray(margin)


def read_gaps(checks, gaps, margins, margin: float, limit: float) -> None:
    """`served_logit_gap`: the widest gap over the sampled positions whose
    routing margin in the reference is at least `margin` in every expert
    layer. Routing is discrete: where two experts' scores all but tie,
    bf16 and float32 choose differently, and the gap there says nothing
    of precision. Printed beside it, not compared: the share of positions
    kept and the widest gap over all of them."""
    gaps = np.concatenate(gaps) if gaps else np.zeros((0,))
    margins = np.concatenate(margins) if margins else np.zeros((0,))
    kept = margins >= margin
    # nothing finished or nothing kept, nothing compared: over any limit
    checks.most("served_logit_gap", gaps[kept].max() if kept.any() else 1e30,
                limit)
    checks.note("positions_kept_share", kept.mean() if kept.size else 0.0)
    checks.note("served_logit_gap_all_positions",
                gaps.max() if gaps.size else 1e30)


def _mark(what: str, started: float) -> None:
    """A line on standard error as a phase ends: a run of this cell is
    minutes long, and one that is cut says how far it got."""
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
    leaves = jax.block_until_ready(
        weights.make(reference.shapes(cfg), seed, jnp.bfloat16))
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
    gaps, margins = [], []
    for r in sampled:
        g, m = position_gaps(reference, leaves, cfg, r,
                             int(mix["reference_pad"]))
        gaps.append(g)
        margins.append(m)
        _mark(f"reference over {len(r['prompt']) + len(r['generated'])} "
              f"tokens: gap {g.max():.4g}", started)
    read_gaps(checks, gaps, margins, float(mix["reference_margin"]),
              spec["limits"]["served_logit_gap"])
    not_finished = sum(r["status"] != "finished" for r in done)
    checks.equal("requests_not_finished", not_finished, 0)
    checks.equal("token_count_mismatches",
                 sum(len(r["generated"]) != r["out"] for r in done
                     if r["status"] == "finished"), 0)
    checks.equal("fault_events", fault_events, 0)
    checks.equal("reference_path_dispatches",
                 sum(n for p, n in paths.items() if "reference" in p), 0)
    checks.equal("no_mla_decode_kernel_dispatch",
                 int(not any(n > 0 for p, n in paths.items()
                             if p.startswith("mla_decode_pallas"))), 0)
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
    # what the decode kernel had to do: token k >= 1 of a request attends
    # its prompt and the k tokens before it
    decoded = sum(max(r["n"] - 1, 0) for r in everyone)
    context = sum(len(r["prompt"]) * max(r["n"] - 1, 0)
                  + r["n"] * (r["n"] - 1) // 2 for r in everyone)
    seconds = elapsed - w["profiler_s"]
    counters = {k: counters_after[k] - counters_before.get(k, 0)
                for k in counters_after}
    experts = flops.moe_experts_work(
        cfg, counters.get("serving_moe_pairs_total", 0),
        counters.get("serving_moe_experts_touched_total", 0))
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
            "mla_decode": {
                "flops_per_s": flops.mla_decode_flops(cfg, context) / seconds,
                "bytes_per_s": flops.mla_decode_bytes(cfg, context, decoded)
                / seconds},
            "moe_experts": {"flops_per_s": experts["flops"] / seconds,
                            "bytes_per_s": experts["bytes"] / seconds}},
        "counters": counters,
        "setup_marks": marks,
        "trace_path": session.path() if session else None,
        "replay": {"leaves": leaves, "sampled": sampled,
                   "finished": finished},
    }


def control(spec: dict, record: dict) -> dict:
    """As `drivers/serve.py`'s: the reference one precision below the
    configuration's in the program's place, held to the cell's limit
    (`ok` has to come out false)."""
    from .. import lowprec

    cfg, mix = spec["config"], spec["traffic"]
    reference = load_reference(cfg["reference"])
    below = lowprec.BELOW[cfg["precision"]]
    replay = record["replay"]
    read = [position_gaps(reference, replay["leaves"], cfg, rec,
                          int(mix["reference_pad"]), stand_in=below)
            for rec in replay["sampled"]]
    if not read:
        raise ValueError("no finished request to read the control on: "
                         "the window is too short for the mix")
    checks = common.Checks()
    read_gaps(checks, [g for g, _ in read], [m for _, m in read],
              float(mix["reference_margin"]),
              spec["limits"]["served_logit_gap"])
    return {"control": common.stand_in_line(checks)}
