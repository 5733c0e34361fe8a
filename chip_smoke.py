#!/usr/bin/env python3
"""The quickest proof that paddle-tpu still starts on the chip.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # the sharded paths only, four chips

One process drives the library through the entry points a user calls,
at the full width of one model each, with weights made from `--seed`:

- kernels: every main-path Pallas kernel against its plain jnp reference;
- train:   ERNIE-base, batch 32 x 512, through `ZeroTrainStep`;
- serve:   GPT-3 1.3B in bf16 through `ServingEngine`, checked against a
           dense forward of the same model.

Each phase prints one JSON object. The last line of the output is
`{"ok": true, "device": {...}}`, printed only when every check of every
phase held. There is no CPU mode: without a TPU the script exits
non-zero before any phase. The phases are plain functions of their
sizes, so `tests/test_chip_smoke.py` runs them small on the CPU with the
kernels in interpret mode.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit.functional import call_functional, extract_state
from paddle_tpu.models import (
    ErnieConfig, ErnieForPretraining, GPTConfig, GPTForCausalLM,
)
from paddle_tpu.observability import global_registry
from paddle_tpu.ops import nn_ops, pallas_kernels
from paddle_tpu.parallel import ZeroTrainStep
from paddle_tpu.serving import ServingEngine, attention as paged
from paddle_tpu.serving.kv_cache import PagedLayerCache
from paddle_tpu.utils.compile_cache import place_compile_cache

# normalised max error allowed between a bf16 kernel and its fp32
# reference on the same bf16 inputs: forward, and gradients
_FWD_TOL = 3e-2
_BWD_TOL = 6e-2
# near-tie rule for a greedy token: its reference logit may sit this far
# under the maximum, as a share of the largest logit (four bf16 ulps)
_TIE_TOL = 4 * 2.0 ** -8


def _emit(phase: str, checks: dict, **facts) -> dict:
    """Print one phase's line; the phase passed iff every check held."""
    out = {"phase": phase, "ok": all(checks.values()), "checks": checks,
           **facts}
    print(json.dumps(out), flush=True)
    return out


def _err(got, want) -> float:
    """max |got - want| over max |want|, in fp32 on the host."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / (np.max(np.abs(want)) + 1e-6))


def _widths(cfg) -> dict:
    return {"layers": cfg.num_hidden_layers, "hidden": cfg.hidden_size,
            "heads": cfg.num_attention_heads, "vocab": cfg.vocab_size}


def _peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# ------------------------------------------------------------------ kernels

def _f32(*xs):
    return tuple(x.astype(jnp.float32) for x in xs)


def _sum_sq(f):
    """A scalar loss over `f`'s output, to compare gradients through."""
    return lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2)


def _flash_case(rng, shape, *, causal, interpret):
    """Flash attention forward and backward against the sdpa op."""
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
               for _ in range(3))

    def kernel(q, k, v):
        return pallas_kernels.flash_attention(
            Tensor(q), Tensor(k), Tensor(v), is_causal=causal,
            interpret=interpret)._data

    def reference(q, k, v):
        return nn_ops.scaled_dot_product_attention(q, k, v,
                                                   is_causal=causal)

    out = kernel(q, k, v)
    grads = jax.grad(_sum_sq(kernel), argnums=(0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):
        ref = reference(*_f32(q, k, v))
        ref_grads = jax.grad(_sum_sq(reference), argnums=(0, 1, 2))(
            *_f32(q, k, v))
    return {"fwd": _err(out, ref),
            "bwd": max(_err(g, r) for g, r in zip(grads, ref_grads))}


def _norm_case(rng, shape, *, rms, interpret):
    """Fused layer/RMS norm forward and backward against nn_ops."""
    x = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    w = jnp.asarray(1.0 + 0.1 * rng.standard_normal(shape[-1]),
                    jnp.bfloat16)
    b = jnp.asarray(0.1 * rng.standard_normal(shape[-1]), jnp.bfloat16)
    if rms:
        def kernel(x, w, b):
            return pallas_kernels.rms_norm_fused(x, w, 1e-6,
                                                 interpret=interpret)

        def reference(x, w, b):
            return nn_ops.rms_norm(x, w, 1e-6)
    else:
        def kernel(x, w, b):
            return pallas_kernels.layer_norm_fused(x, w, b, 1e-5,
                                                   interpret=interpret)

        def reference(x, w, b):
            return nn_ops.layer_norm(x, w, b, 1e-5)

    out = kernel(x, w, b)
    dx = jax.grad(_sum_sq(kernel))(x, w, b)
    ref = reference(*_f32(x, w, b))
    ref_dx = jax.grad(_sum_sq(reference))(*_f32(x, w, b))
    return {"fwd": _err(out, ref), "bwd": _err(dx, ref_dx)}


def _paged_pool(rng, *, kv_heads, head_dim, page_size, num_pages):
    shape = (kv_heads, num_pages, page_size, head_dim)
    return (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16),
            jnp.asarray(rng.standard_normal(shape), jnp.bfloat16))


def _paged_decode_case(rng, *, batch, heads, head_dim, page_size,
                       num_pages, max_pages):
    """`paged_decode_attention` (the engine's dispatcher) against its
    gather reference, every row at its own length."""
    kp, vp = _paged_pool(rng, kv_heads=heads, head_dim=head_dim,
                         page_size=page_size, num_pages=num_pages)
    table = jnp.asarray(rng.integers(1, num_pages, (batch, max_pages)),
                        jnp.int32)
    pos = jnp.asarray(rng.integers(0, max_pages * page_size, batch),
                      jnp.int32)
    q = Tensor(jnp.asarray(
        rng.standard_normal((batch, 1, heads, head_dim)), jnp.bfloat16))
    cache = PagedLayerCache(kp, vp, table)
    out = paged.paged_decode_attention(q, cache, pos, 1)
    ref = paged._paged_decode_reference(
        Tensor(q._data.astype(jnp.float32)),
        PagedLayerCache(*_f32(kp, vp), table), pos, 1)
    return {"fwd": _err(out._data, ref._data)}


def _ragged_case(rng, *, tokens, rows, heads, head_dim, page_size,
                 num_pages, max_pages):
    """`ragged_paged_attention` against its reference on a flat batch of
    decode rows, one prefill chunk and parked padding."""
    kp, vp = _paged_pool(rng, kv_heads=heads, head_dim=head_dim,
                         page_size=page_size, num_pages=num_pages)
    table = jnp.asarray(rng.integers(1, num_pages, (rows, max_pages)),
                        jnp.int32)
    capacity = max_pages * page_size
    n_decode = rows - 1
    chunk = tokens // 2
    pos = np.full((tokens,), capacity, np.int32)     # padding parks here
    row_ids = np.zeros((tokens,), np.int32)
    pos[:n_decode] = rng.integers(0, capacity, n_decode)
    row_ids[:n_decode] = np.arange(n_decode)
    start = int(rng.integers(0, capacity - chunk))
    pos[n_decode:n_decode + chunk] = start + np.arange(chunk)
    row_ids[n_decode:n_decode + chunk] = rows - 1
    valid = n_decode + chunk
    q = Tensor(jnp.asarray(
        rng.standard_normal((1, tokens, heads, head_dim)), jnp.bfloat16))
    pos = jnp.asarray(pos)[None]
    row_ids = jnp.asarray(row_ids)
    out = paged.ragged_paged_attention(
        q, PagedLayerCache(kp, vp, table, row_ids), pos, 1)
    ref = paged._ragged_attention_reference(
        Tensor(q._data.astype(jnp.float32)),
        PagedLayerCache(*_f32(kp, vp), table, row_ids), pos, 1)
    return {"fwd": _err(out._data[0, :valid], ref._data[0, :valid])}


def _dispatch_counts() -> dict:
    """`serving_attention_dispatch_total` by path. The counter is global
    to the process and counts at trace time: callers take differences."""
    return {m.labels["path"]: m.value for m in global_registry().collect()
            if m.name == "serving_attention_dispatch_total"}


def _dispatch_delta(before: dict) -> dict:
    after = _dispatch_counts()
    return {p: n - before.get(p, 0) for p, n in after.items()
            if n != before.get(p, 0)}


def _pallas_only(delta: dict) -> bool:
    """A paged Pallas path was taken and no reference path was."""
    took = any(p.startswith(("decode_pallas", "ragged_pallas"))
               for p in delta)
    fell_back = any(p.startswith(("decode_reference", "ragged_reference"))
                    for p in delta)
    return took and not fell_back


def kernels_phase(*, seed: int, interpret: bool = False,
                  flash_cases=(((2, 512, 12, 64), False),
                               ((1, 512, 4, 128), True)),
                  norm_shape=(512, 768), paged_heads: int = 4,
                  paged_head_dim: int = 128, paged_tokens: int = 32) -> dict:
    """Each main-path kernel against its plain reference, bf16 inputs.
    `interpret` runs flash and the norms in Pallas interpret mode; the
    paged kernels follow `serving.attention.KERNEL_MODE`."""
    rng = np.random.default_rng(seed)
    errors = {}
    for shape, causal in flash_cases:       # ERNIE's heads, then GPT's
        errors["flash_%dx%dx%dx%d" % shape] = _flash_case(
            rng, shape, causal=causal, interpret=interpret)
    errors["layer_norm"] = _norm_case(rng, norm_shape, rms=False,
                                      interpret=interpret)
    errors["rms_norm"] = _norm_case(rng, norm_shape, rms=True,
                                    interpret=interpret)
    before = _dispatch_counts()
    geometry = dict(heads=paged_heads, head_dim=paged_head_dim,
                    page_size=16, num_pages=24, max_pages=4)
    errors["paged_decode"] = _paged_decode_case(rng, batch=4, **geometry)
    errors["ragged_paged"] = _ragged_case(rng, tokens=paged_tokens, rows=4,
                                          **geometry)
    dispatch = _dispatch_delta(before)
    checks = {"paged_kernels_dispatched": _pallas_only(dispatch)}
    for name, err in errors.items():
        checks[name] = (err["fwd"] <= _FWD_TOL
                        and err.get("bwd", 0.0) <= _BWD_TOL)
    return _emit("kernels", checks, errors=errors, dispatch=dispatch,
                 tolerance={"fwd": _FWD_TOL, "bwd": _BWD_TOL})


# -------------------------------------------------------------------- train

def _mlm_batch(rng, batch: int, seq: int, vocab: int):
    """One masked-LM batch: random ids, 15% of positions carry a label."""
    ids = rng.integers(0, vocab, (batch, seq), dtype=np.int32)
    labels = np.where(rng.random((batch, seq)) < 0.15, ids, -100)
    return jnp.asarray(ids), jnp.asarray(labels.astype(np.int32))


def _ernie_trainer(model, *, seed: int, stage: int, dp, param_dtype):
    """`ZeroTrainStep` over the model's fused MLM loss with Adam."""
    _, buffers = extract_state(model)
    key = jax.random.key(seed)

    def loss_fn(params, ids, labels):
        (loss, _nsp), _ = call_functional(
            model, params, buffers, (ids, None, None, None, labels),
            rng_key=key, training=True)
        return loss.astype(jnp.float32)

    opt = paddle.optimizer.Adam(learning_rate=1e-4,
                                parameters=model.parameters())
    return ZeroTrainStep(model, opt, loss_fn, stage=stage, dp=dp,
                         param_dtype=param_dtype)


def _ernie(cfg: ErnieConfig, seed: int):
    paddle.seed(seed)
    model = ErnieForPretraining(
        dataclasses.replace(cfg, fused_mlm_loss=True))
    model.train()
    return model


def train_phase(cfg: ErnieConfig, *, batch: int, seq: int, steps: int,
                seed: int) -> dict:
    """`steps` ZeRO-2 Adam steps on one fixed batch, bf16 working weights
    over fp32 masters."""
    model = _ernie(cfg, seed)
    trainer = _ernie_trainer(model, seed=seed, stage=2, dp=1,
                             param_dtype="bf16")
    params, state = trainer.init_state()
    data = _mlm_batch(np.random.default_rng(seed), batch, seq,
                      cfg.vocab_size)
    losses, seconds = [], []
    for t in range(1, steps + 1):
        t0 = time.perf_counter()
        loss, params, state = jax.block_until_ready(
            trainer(params, state, data, 1e-4, t))
        seconds.append(time.perf_counter() - t0)
        losses.append(float(loss))
    # the text of the executable the steps above ran (a cache hit)
    text = trainer._step.lower(
        params, state, data, jnp.float32(1e-4),
        jnp.int32(1)).compile().as_text()
    steady = statistics.median(seconds[1:])
    checks = {
        "losses_finite": bool(np.all(np.isfinite(losses))),
        "loss_fell": losses[-1] < losses[0],
        "tpu_custom_call": "tpu_custom_call" in text,
    }
    return _emit(
        "train", checks, model=_widths(cfg), batch=batch, seq=seq,
        param_dtype="bf16", stage=trainer.stage, dp=trainer.dp,
        losses=losses, first_step_seconds=seconds[0],
        compile_seconds=seconds[0] - steady, median_step_seconds=steady,
        tpu_custom_calls=text.count("tpu_custom_call"),
        peak_bytes_in_use=_peak_bytes())


# -------------------------------------------------------------------- serve

def _gpt(cfg: GPTConfig, seed: int):
    paddle.seed(seed)
    model = GPTForCausalLM(cfg).bfloat16()
    model.eval()
    return model


def _prompts(rng, lens, vocab: int):
    return [rng.integers(0, vocab, n).tolist() for n in lens]


def _dense_logits(model, prompts):
    """Last-position logits of a plain forward over each prompt: no
    cache, no paging, no engine. Prompts are right-padded to one length
    so that one executable serves them all; under causal attention the
    padding cannot reach the position that is read."""
    params, buffers = extract_state(model)
    width = -(-max(len(p) for p in prompts) // 128) * 128

    @jax.jit
    def last_logits(params, ids, last):
        logits, _ = call_functional(model, params, buffers, (ids,),
                                    training=False)
        return logits[0, last].astype(jnp.float32)

    out = []
    for p in prompts:
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(p)] = p
        out.append(np.asarray(last_logits(params, jnp.asarray(ids),
                                          len(p) - 1)))
    return out


def _near_tie(logits, token: int) -> bool:
    """`token` is the argmax of `logits`, or ties it within bf16
    rounding: randomly initialised weights leave the top logits close."""
    top = float(np.max(logits))
    return top - float(logits[token]) <= _TIE_TOL * float(
        np.max(np.abs(logits)))


def _serve(engine, prompts, max_new_tokens: int, stagger: int = 3):
    """Submit the prompts two at a time with `stagger` engine steps in
    between, so that later prefills meet running decodes; drive the
    engine to the end. Returns the requests, their times to first token
    and the total seconds, on the host's clock."""
    t0 = time.perf_counter()
    submitted, first, rids = {}, {}, []

    def note(rid):
        first.setdefault(rid, time.perf_counter() - submitted[rid])

    for i, p in enumerate(prompts):
        if i and i % 2 == 0:
            for _ in range(stagger):
                for rid, _tok in engine.step():
                    note(rid)
        rid = engine.add_request(p, max_new_tokens=max_new_tokens,
                                 temperature=0.0, seed=i)
        submitted[rid] = time.perf_counter()
        rids.append(rid)
    for rid, _tok, _done in engine.stream():
        note(rid)
    total = time.perf_counter() - t0
    return ([engine.requests[r] for r in rids],
            [first.get(r) for r in rids], total)


def serve_phase(cfg: GPTConfig, *, prompt_lens, max_new_tokens: int,
                seed: int, max_batch_size: int = 8,
                max_seq_len: int = 2048) -> dict:
    """Greedy requests through a default `ServingEngine`, twice: the
    first pass compiles, the second is timed warm. First tokens are held
    to a dense forward of the same model."""
    model = _gpt(cfg, seed)
    prompts = _prompts(np.random.default_rng(seed), prompt_lens,
                       cfg.vocab_size)
    before = _dispatch_counts()
    engine = ServingEngine(model, page_size=16,
                           max_batch_size=max_batch_size,
                           max_seq_len=max_seq_len, kv_dtype="bf16")
    cold_reqs, _, cold_total = _serve(engine, prompts, max_new_tokens)
    warm_reqs, ttft, warm_total = _serve(engine, prompts, max_new_tokens)
    dispatch = _dispatch_delta(before)
    reference = _dense_logits(model, prompts)
    reqs = cold_reqs + warm_reqs
    checks = {
        "all_finished": all(r.status == "finished" for r in reqs),
        "token_counts": all(len(r.generated) == max_new_tokens
                            for r in reqs),
        "no_fault_events": engine.fault_events == 0,
        "paged_kernels_dispatched": _pallas_only(dispatch),
        "first_token_matches_dense": all(
            r.generated and _near_tie(reference[i % len(prompts)],
                                      r.generated[0])
            for i, r in enumerate(reqs)),
    }
    return _emit(
        "serve", checks, model=_widths(cfg), prompt_lens=list(prompt_lens),
        max_new_tokens=max_new_tokens,
        statuses=[r.status for r in reqs],
        first_tokens=[r.generated[:1] for r in warm_reqs],
        dense_argmax=[int(np.argmax(x)) for x in reference],
        warm_equals_cold=[a.generated == b.generated
                          for a, b in zip(cold_reqs, warm_reqs)],
        cold_total_seconds=cold_total, total_seconds=warm_total,
        compile_seconds=cold_total - warm_total,
        ttft_seconds=ttft, compile_counts=engine.compile_counts(),
        dispatch=dispatch, fault_events=engine.fault_events,
        peak_bytes_in_use=_peak_bytes())


# ---------------------------------------------------------------- four chips

def _shard_devices(tree) -> int:
    """Distinct devices holding an addressable shard of any leaf."""
    return len({s.device for x in jax.tree_util.tree_leaves(tree)
                for s in x.addressable_shards})


def sharded_train_phase(cfg: ErnieConfig, *, batch: int, seq: int,
                        steps: int, seed: int, dp: int = 4) -> dict:
    """ZeRO-2 at `dp` against the replicated stage-0 baseline at the
    same `dp`, both fp32, on the same batches. The README promises
    bit-identical parameters; whether the chip keeps that promise is
    reported, and the phase passes on the losses agreeing to 1e-3."""
    model = _ernie(cfg, seed)
    rng = np.random.default_rng(seed)
    batches = [_mlm_batch(rng, batch, seq, cfg.vocab_size)
               for _ in range(steps)]
    runs = {}
    for stage in (2, 0):
        trainer = _ernie_trainer(model, seed=seed, stage=stage, dp=dp,
                                 param_dtype=None)
        params, state = trainer.init_state()
        losses = []
        for t, data in enumerate(batches, start=1):
            loss, params, state = trainer(params, state, data, 1e-4, t)
            losses.append(float(loss))
        runs[stage] = {
            "losses": losses,
            "params": {k: np.asarray(v) for k, v in params.items()},
            "state_bytes_per_chip": trainer.bytes_per_chip(state),
            "state_devices": _shard_devices(state),
        }
        del params, state, trainer
    sharded, replicated = runs[2], runs[0]
    loss_diff = max(abs(a - b) / abs(b) for a, b in
                    zip(sharded["losses"], replicated["losses"]))
    identical = all(np.array_equal(sharded["params"][k], v)
                    for k, v in replicated["params"].items())
    # the replicated state on one chip is the dp = 1 figure; ZeRO pads
    # every leaf to a multiple of dp, hence the tolerance
    share = (sharded["state_bytes_per_chip"]
             / replicated["state_bytes_per_chip"])
    checks = {
        "losses_finite": bool(np.all(np.isfinite(sharded["losses"]))),
        "losses_agree": loss_diff <= 1e-3,
        "state_on_every_chip": sharded["state_devices"] == dp,
        "state_is_one_dpth": abs(share * dp - 1.0) <= 1e-3,
    }
    return _emit(
        "train_sharded", checks, model=_widths(cfg), dp=dp, batch=batch,
        seq=seq, param_dtype="fp32", losses_stage2=sharded["losses"],
        losses_stage0=replicated["losses"],
        max_relative_loss_difference=loss_diff,
        params_bit_identical=identical,
        state_bytes_per_chip=sharded["state_bytes_per_chip"],
        replicated_state_bytes_per_chip=replicated["state_bytes_per_chip"],
        state_devices=sharded["state_devices"])


def sharded_serve_phase(cfg: GPTConfig, *, prompt_lens,
                        max_new_tokens: int, seed: int, tp: int = 4,
                        max_batch_size: int = 8,
                        max_seq_len: int = 2048) -> dict:
    """`ServingEngine(tp_size=tp)` against the one-chip engine on the
    same requests: first tokens equal, or tied by the serve phase's rule
    on the dense forward's logits."""
    model = _gpt(cfg, seed)
    prompts = _prompts(np.random.default_rng(seed), prompt_lens,
                       cfg.vocab_size)
    reference = _dense_logits(model, prompts)
    runs = {}
    for size in (tp, 1):
        engine = ServingEngine(model, page_size=16,
                               max_batch_size=max_batch_size,
                               max_seq_len=max_seq_len, kv_dtype="bf16",
                               tp_size=size)
        reqs, _, total = _serve(engine, prompts, max_new_tokens)
        runs[size] = {"reqs": reqs, "seconds": total,
                      "faults": engine.fault_events,
                      "pool_devices": _shard_devices(engine.cache.pools)}
        del engine
    wide, one = runs[tp], runs[1]
    reqs = wide["reqs"] + one["reqs"]
    checks = {
        "all_finished": all(r.status == "finished" for r in reqs),
        "token_counts": all(len(r.generated) == max_new_tokens
                            for r in reqs),
        "no_fault_events": wide["faults"] == one["faults"] == 0,
        "kv_pool_on_every_chip": wide["pool_devices"] == tp,
        "first_token_matches_one_chip": all(
            a.generated and b.generated
            and _near_tie(ref, b.generated[0])
            and (a.generated[0] == b.generated[0]
                 or _near_tie(ref, a.generated[0]))
            for a, b, ref in zip(wide["reqs"], one["reqs"], reference)),
    }
    return _emit(
        "serve_sharded", checks, model=_widths(cfg), tp=tp,
        prompt_lens=list(prompt_lens), max_new_tokens=max_new_tokens,
        first_tokens_tp=[r.generated[:1] for r in wide["reqs"]],
        first_tokens_one_chip=[r.generated[:1] for r in one["reqs"]],
        streams_equal=[a.generated == b.generated
                       for a, b in zip(wide["reqs"], one["reqs"])],
        dense_argmax=[int(np.argmax(x)) for x in reference],
        seconds_tp_with_compile=wide["seconds"],
        seconds_one_chip_with_compile=one["seconds"],
        kv_pool_devices=wide["pool_devices"])


# --------------------------------------------------------------------- main

def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs only the sharded phases and what "
                             "they are compared with")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    cache_dir = place_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} on {len(devices)} chip(s)",
              file=sys.stderr)
        return 1
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(json.dumps({"phase": "setup", "compile_cache_dir": cache_dir,
                      "jax": jax.__version__, "device": device,
                      "seed": args.seed}), flush=True)

    if args.chips == 4:
        phases = [
            lambda: sharded_train_phase(
                ErnieConfig.ernie_base(), batch=32, seq=512, steps=3,
                seed=args.seed),
            lambda: sharded_serve_phase(
                GPTConfig.gpt3_1p3b(), prompt_lens=(300, 1500),
                max_new_tokens=48, seed=args.seed),
        ]
    else:
        lens = np.random.default_rng(args.seed).integers(64, 1501, 4)
        phases = [
            lambda: kernels_phase(seed=args.seed),
            lambda: train_phase(
                ErnieConfig.ernie_base(), batch=32, seq=512, steps=6,
                seed=args.seed),
            lambda: serve_phase(
                GPTConfig.gpt3_1p3b(),
                prompt_lens=(64, 1500, *(int(n) for n in lens)),
                max_new_tokens=48, seed=args.seed),
        ]
    # every phase runs, so that one chip run shows every fault; a phase
    # that raised has failed, and so has the run
    passed = True
    for phase in phases:
        try:
            passed &= phase()["ok"]
        except Exception:
            traceback.print_exc()
            passed = False
    if not passed:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
