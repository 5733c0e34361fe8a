"""Driver of a decode-pool cell: the decode instance of a
prefill/decode-disaggregated service. Every client's session is
prefilled in set-up, through the engine's own prefill, and the window
opens when all of them decode: it counts the tokens that reach the host
in it, and has no prefill in it unless a session ends (its client's
next then breaks in, as in `drivers/serve.py`).

Set-up builds the model from the benchmark's own weights, in the type
the configuration states, builds the engine, then submits the sessions
in the mix's fixed order and steps the engine until each has its first
token and the full batch has run a few blocks. Those steps are the
warm-up: the sessions' own prefills run every prefill bucket the mix
uses, and as they join one at a time, every decode row count from one up
to the full batch. The window has no other shape in it, so nothing is
left to compile there (`compiled_in_window` holds that), and no prompt
is prefilled twice.

Once the window has closed the engine is freed and the plain reference
runs over prompt + served tokens of a sample of the sessions, which are
still **in flight** (at the cell's size none has finished): the longest
context and others drawn from the seed. `served_logit_gap_mean` is read a position at a time over the
positions where the reference's own discrete choices that reach the
experts held here (experts, groups) are clear of a tie, as
`drivers/serve_mla_moe.py` reads it over all of them. `selection_noise`
reads, on a run's sessions, what the choice of keys alone does to that
number under bf16 index operands; no benchmark run calls it.

The operations come from `flops_mla_sparse_moe.py`; the two kernels
that have to have been dispatched are `dsa_index` and
`mla_sparse_decode`; their work, and the held experts', comes from the
program's counters, never from an assumed count.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import common, flops_mla_sparse_moe as flops, traffic, weights
from ..spec import load_program, load_reference
from .serve import _LIVE, _counters, _dispatch_counts, sample
from .serve_mla_moe import _mark

# the kernels a run must have dispatched, by the first part of their path
# in `serving_attention_dispatch_total`
_KERNELS = ("dsa_index_pallas", "mla_sparse_decode_pallas")
# steps of the full batch before the window opens: the last session's
# first token comes from its prefill, and the decode block of every row
# has to have run (and been compiled) by then, and be chained
_FULL_BATCH_STEPS = 4


def make_leaves(reference, cfg: dict, seed: int):
    """The benchmark's weights, and what the configuration draws its own
    way (`assumed.leaf_scales`: a leaf whose name ends in the key is
    multiplied by the value)."""
    leaves = weights.make(reference.shapes(cfg), seed,
                          jnp.dtype(cfg["precision"]))
    for suffix, scale in cfg.get("assumed", {}).get("leaf_scales",
                                                    {}).items():
        for name in leaves:
            if name.endswith(suffix):
                leaves[name] = (leaves[name].astype(jnp.float32)
                                * scale).astype(leaves[name].dtype)
    return jax.block_until_ready(leaves)


def window(engine, feed, clients: int, seconds: float, started: float,
           trace: common.TraceSession = None, before=None) -> dict:
    """Submit `clients` sessions, step until each has its first token,
    call `before()` (the counters' first reading), then measure for
    `seconds`."""
    live, done, step_ms = {}, [], []

    def submit():
        prompt, out = next(feed)
        rid = engine.add_request(prompt, max_new_tokens=out,
                                 temperature=0.0, seed=0)
        live[rid] = {"prompt": prompt, "out": out, "first": None,
                     "last": None, "n": 0, "context": len(prompt),
                     "prefilled": False}

    for _ in range(clients):
        submit()
    full = 0        # steps since every session has its first token
    while full < _FULL_BATCH_STEPS:
        engine.step()
        if any(engine.requests[rid].status not in _LIVE for rid in live):
            raise RuntimeError("a session ended before the window opened")
        full += all(engine.requests[rid].generated for rid in live)
    _mark(f"{clients} sessions prefilled and decoding", started)
    for rid, r in live.items():
        r["context"] = len(r["prompt"]) + len(engine.requests[rid].generated)
        r["prefilled"] = True
    if before is not None:
        before()

    tokens = 0
    t0 = time.perf_counter()
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
            rec["status"] = engine.requests[rid].status
            rec["generated"] = list(engine.requests[rid].generated)
            done.append(rec)
            if not closing:
                submit()
        if trace is not None:
            trace.tick(now - t0)
        if closing:
            break
    t1 = now
    profiler_s = trace.overhead if trace is not None else 0.0
    if trace is not None:
        trace.stop()
    in_flight = []
    for rid, rec in live.items():
        rec["status"] = engine.requests[rid].status
        rec["generated"] = list(engine.requests[rid].generated)
        in_flight.append(rec)
    for rid in list(live):          # past the window: empty the engine
        engine.cancel(rid)
    for _ in range(64):
        if not engine.step() and not engine.scheduler.running \
                and not engine.scheduler.waiting:
            break
    return {"t0": t0, "t1": t1, "tokens": tokens, "done": done,
            "in_flight": in_flight, "step_ms": step_ms,
            "profiler_s": profiler_s}


def _sequence(rec: dict, pad: int) -> tuple:
    """A session's prompt + served tokens, padded, and the positions
    whose logits chose a served token."""
    prompt, served = rec["prompt"], rec["generated"]
    width = max(pad, -(-(len(prompt) + len(served)) // 128) * 128)
    ids = np.zeros((width,), np.int32)
    ids[:len(prompt) + len(served)] = prompt + served
    return ids, np.arange(len(prompt) - 1, len(prompt) + len(served) - 1)


def position_gaps(reference, leaves: dict, cfg: dict, rec: dict, pad: int,
                  want=None, stand_in=None, stand_in_cfg=None) -> tuple:
    """By how much each served token's logit lies under the reference's
    best, a position at a time, with the reference's margins there; or,
    with a stand-in (a lower precision `stand_in`, or another
    configuration `stand_in_cfg`: the selection dropped, the share
    shifted), the same for the token the stand-in puts first, judged by
    the reference's logits `want` that an earlier call returned."""
    served = rec["generated"]
    ids, rows = _sequence(rec, pad)
    if want is None:
        logits, margins = reference.logits(leaves, ids, rows, cfg,
                                           with_margin=True)
        want = (np.asarray(logits, np.float32), margins)
    logits, margins = want
    if stand_in is not None or stand_in_cfg is not None:
        served = np.argmax(np.asarray(reference.logits(
            leaves, ids, rows, stand_in_cfg or cfg, stand_in)), axis=-1)
    gaps = logits.max(axis=-1) - logits[np.arange(len(rows)),
                                        np.asarray(served)]
    return gaps, margins, want


def read_mean_gap(checks, gaps, margins, margin: float, limit: float) -> None:
    """`served_logit_gap_mean`: the **mean** gap of a served token under
    the reference's best, over the sampled positions whose routing margin
    in the reference is at least `margin` (`drivers/serve_mla_moe.py`
    `read_gaps` compares the widest of them; here it is printed, with
    the widest over all positions and the share kept, and not compared).
    The choice of 2,048 keys is discrete: the float32 reference with
    nothing but its index queries and keys rounded to bfloat16
    (`selection_noise`) changes 5 of a query's 2,048 keys and is by that
    alone as wide as the program at its widest, and within a factor of
    two of the controls' (chip readings in PERF.md section 2). The mean
    is steady from run to run and an order of magnitude apart."""
    gaps = np.concatenate(gaps) if gaps else np.zeros((0,))
    margins = np.concatenate(margins) if margins else np.zeros((0,))
    kept = margins >= margin
    # nothing sampled or nothing kept, nothing compared: over any limit
    checks.most("served_logit_gap_mean",
                gaps[kept].mean() if kept.any() else 1e30, limit)
    checks.note("served_logit_gap", gaps[kept].max() if kept.any() else 1e30)
    checks.note("positions_kept_share", kept.mean() if kept.size else 0.0)
    checks.note("served_logit_gap_all_positions",
                gaps.max() if gaps.size else 1e30)


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
    leaves = make_leaves(reference, cfg, seed)
    marks["weights"] = time.time() - started
    engine = builder.build(cfg, program, leaves)
    marks["engine"] = time.time() - started
    _mark("weights made, engine built", started)

    session = (common.TraceSession(mix["trace_seconds"],
                                   after=mix["trace_after_seconds"])
               if trace else None)
    opened = {}

    def before():
        marks["sessions"] = time.time() - started
        marks["cache"] = dict(common.CACHE)
        opened["counters"] = _counters(engine)
        opened["compiles"] = common.CompileCounter()
        opened["setup_s"] = time.time() - started

    w = window(engine, traffic.request_blocks(mix, seed, vocab),
               int(mix["clients"]), seconds, started, session, before)
    compiled_in_window = opened["compiles"].close()
    _mark(f"window closed, {w['tokens']} tokens", started)
    counters_after = _counters(engine)
    paths = {p: n - paths_before.get(p, 0)
             for p, n in _dispatch_counts().items()}
    fault_events = engine.fault_events
    peak = common.peak_bytes()
    # free the program's state before the reference runs; the engine
    # parks its jitted steps on the model, so they go with it
    engine.model.__dict__.pop("_serving_jit_cache", None)
    del engine, before      # the closure holds the engine, and it its pool
    # the engine is part of cycles, so it goes with a collection and not
    # with its last name: until then its 5.9 GB pool leaves the reference
    # 1.4 GB of the device
    gc.collect()

    done, in_flight = w["done"], w["in_flight"]
    checks = common.Checks()
    # the sessions in flight, which at the cell's size is all of them; one
    # that ended in the window is as good
    sampled = sample([r for r in in_flight + done if r["generated"]],
                     int(mix["sample_requests"]), seed)
    gaps, clear, selection = [], [], []
    for r in sampled:
        try:
            g, m, r["want"] = position_gaps(reference, leaves, cfg, r,
                                            int(mix["reference_pad"]))
        except Exception as e:      # noqa: BLE001 — the run's own numbers
            # are worth its line even where the reference could not be
            # computed: nothing compared, so not correct
            _mark(f"reference failed: {type(e).__name__}: {e}"[:400],
                  started)
            gaps, clear, selection = [], [], []
            break
        gaps.append(g)
        clear.append(m["routing"])
        selection.append(m["selection"])
        _mark(f"reference over {len(r['prompt']) + len(r['generated'])} "
              f"tokens: gap {g.max():.4g}", started)
    read_mean_gap(checks, gaps, clear, float(mix["reference_margin"]),
                  spec["limits"]["served_logit_gap_mean"])
    if selection:
        # the index score of the last position attended less the first
        # left out, least over layers and positions: read, not compared
        checks.note("selection_margin_min",
                    min(float(s.min()) for s in selection))
    not_finished = (sum(r["status"] != "finished" for r in done)
                    + sum(r["status"] not in _LIVE for r in in_flight))
    checks.equal("requests_not_finished", not_finished, 0)
    checks.equal("token_count_mismatches",
                 sum(len(r["generated"]) != r["out"] for r in done
                     if r["status"] == "finished"), 0)
    checks.equal("fault_events", fault_events, 0)
    checks.equal("reference_path_dispatches",
                 sum(n for p, n in paths.items() if "reference" in p), 0)
    for kernel in _KERNELS:
        checks.equal(f"no_{kernel}_dispatch",
                     int(not any(n > 0 for p, n in paths.items()
                                 if p.startswith(kernel))), 0)
    checks.equal("compiled_in_window", compiled_in_window, 0)

    elapsed = w["t1"] - w["t0"]
    everyone = done + in_flight
    tpot = [1e3 * (r["last"] - r["first"]) / (r["n"] - 1)
            for r in everyone if r["n"] > 1]
    seconds = elapsed - w["profiler_s"]
    counters = {k: counters_after[k] - opened["counters"].get(k, 0)
                for k in counters_after}
    pairs = counters.get("serving_moe_pairs_total", 0)
    experts = flops.moe_experts_work(
        cfg, pairs, counters.get("serving_moe_experts_touched_total", 0))
    model_flops = pairs * flops.expert_flops(cfg) + sum(
        flops.serve_flops(cfg, r["context"], r["n"], r["prefilled"])
        for r in everyone)
    index = flops.dsa_index_work(
        cfg, counters.get("serving_dsa_keys_in_context_total", 0))
    attend = flops.mla_sparse_decode_work(
        cfg, counters.get("serving_dsa_keys_selected_total", 0))

    def rate(work):
        return {"flops_per_s": work["flops"] / seconds,
                "bytes_per_s": work["bytes"] / seconds}

    return {
        "end_to_end": {"serve_tokens_per_s": w["tokens"] / elapsed,
                       "setup_s": opened["setup_s"]},
        "attempted": len(everyone), "failed": not_finished,
        "checks": checks, "peak_bytes": peak,
        "window_s": seconds, "engine_step_ms": w["step_ms"],
        "tpot_ms": tpot,
        "slowest_ms": sorted(w["step_ms"])[-3:],
        "model_flops": model_flops,
        "kernel_work": {"dsa_index": rate(index),
                        "mla_sparse_decode": rate(attend),
                        "moe_experts": rate(experts)},
        "counters": counters,
        "setup_marks": marks,
        "trace_path": session.path() if session else None,
        "replay": {"leaves": leaves, "sampled": sampled},
    }


def control(spec: dict, record: dict) -> dict:
    """What the number reads with a stand-in in the program's place, each
    held to the cell's limit (`ok` has to come out false): `control`, the
    reference one precision below the configuration's; and two faults a
    reading has to catch, both the float32 reference over another
    configuration: `selection_dropped` attends every position (what a
    program computes that ignores the indexer) and `share_shifted` holds
    the experts one further on (the held range off by one)."""
    from .. import lowprec

    cfg, mix = spec["config"], spec["traffic"]
    reference = load_reference(cfg["reference"])
    replay = record["replay"]
    if not replay["sampled"]:
        raise ValueError("no session to read the controls on")
    stand_ins = {
        "control": {"stand_in": lowprec.BELOW[cfg["precision"]]},
        "selection_dropped": {"stand_in_cfg": {**cfg, "index_topk": None}},
        "share_shifted": {"stand_in_cfg": {
            **cfg, "expert_offset": cfg["expert_offset"] + 1}},
    }
    out = {}
    for name, how in stand_ins.items():
        read = [position_gaps(reference, replay["leaves"], cfg, rec,
                              int(mix["reference_pad"]), want=rec["want"],
                              **how)[:2] for rec in replay["sampled"]]
        checks = common.Checks()
        read_mean_gap(checks, [g for g, _ in read],
                      [m["routing"] for _, m in read],
                      float(mix["reference_margin"]),
                      spec["limits"]["served_logit_gap_mean"])
        out[name] = common.stand_in_line(checks)
    return out


def selection_noise(spec: dict, record: dict, sessions: int = 1) -> dict:
    """Why `served_logit_gap_mean` and not the widest gap: what the
    numbers read for **the exact algorithm with nothing but the indexer's
    queries and keys rounded to bfloat16** (the precision the program
    caches its index keys in), over the first `sessions` of a run's
    sampled sessions. Not a control: it has to come out as the program
    does, correct by the mean and as wide as the program at its widest.
    `rounded_index` is that stand-in's first choice judged by the
    reference's logits, as a control is; `selection_kept_*` the share of
    the exact choice of `index_topk` keys that the rounded scores keep
    (least over the layers, a position at a time); and
    `program_against_rounded_index` the program's served tokens judged
    by the stand-in's logits in the reference's place. No benchmark run
    calls this; PERF.md section 2 has its chip readings."""
    cfg, mix = spec["config"], spec["traffic"]
    reference = load_reference(cfg["reference"])
    replay = record["replay"]
    rounded = {**cfg, "index_operand_mantissa_bits": 7}
    own, against, clear, kept = [], [], [], []
    for rec in replay["sampled"][:sessions]:
        ids, rows = _sequence(rec, int(mix["reference_pad"]))
        logits, margins = reference.logits(replay["leaves"], ids, rows,
                                           rounded, with_margin=True)
        logits = np.asarray(logits, np.float32)
        want, exact = rec["want"]
        at = np.arange(len(rows))
        own.append(want.max(-1) - want[at, np.argmax(logits, -1)])
        against.append(logits.max(-1)
                       - logits[at, np.asarray(rec["generated"])])
        clear.append(exact["routing"])
        kept.append(margins["selection_kept"])
    out = {}
    for name, gaps in (("rounded_index", own),
                       ("program_against_rounded_index", against)):
        checks = common.Checks()
        read_mean_gap(checks, gaps, clear, float(mix["reference_margin"]),
                      spec["limits"]["served_logit_gap_mean"])
        out[name] = common.stand_in_line(checks)
    kept = np.concatenate(kept)
    out["selection_kept_mean"] = float(kept.mean())
    out["selection_kept_min"] = float(kept.min())
    out["rounded_index_first_choice_share"] = float(
        (np.concatenate(own) == 0).mean())
    return out
