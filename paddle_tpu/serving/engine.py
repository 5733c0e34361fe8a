"""ServingEngine: continuous-batching generation over a paged KV cache.

Multiplexes an arbitrary request stream onto a decoder model with a
BOUNDED set of compiled programs (T3's rule: every hot-loop step is one
jitted dispatch):

- one prefill executable per prompt bucket (prompt padded up to the
  bucket; one request per prefill step) — plus, when
  `enable_prefix_caching=True`, ONE offset-aware variant per bucket that
  prefills only the suffix left uncovered by the radix prefix cache
  (shared pages ride in through the page table, see prefix_cache.py).
  Sampling is fused into the prefill executable (per-row PRNG key state
  rides in as device key data);
- ONE fused decode+sample executable per decode horizon: a
  `decode_horizon=N` block runs N decode iterations inside one jitted
  `lax.scan` — model step, sampling (traced per-row temperature/top-k/
  top-p, device PRNG key state), EOS/budget masking, and position
  advance through the page table all on device — and returns an (b, N)
  token block. Rows that finish mid-block emit PAD and park their write
  position at the table-overflow slot (routed to the null page), so the
  host syncs ONCE per N tokens instead of once per token;
- async host/device overlap: the engine dispatches block k+1 (inputs
  taken straight from block k's device-resident carries) BEFORE pulling
  block k's tokens to the host, so Python bookkeeping and scheduling
  run while the device computes. The scheduler reserves each block's
  pages up front (`_ensure_decode_pages` with in-flight upper bounds)
  and drains the pipeline before any preemption, keeping emitted
  streams token-identical to `decode_horizon=1`;
- chunked prefill (`enable_chunked_prefill=True`, Sarathi-Serve style):
  prompts run in page-aligned chunks of `prefill_chunk_tokens` (default
  256), co-scheduled with the step's decode block under a
  `max_num_batched_tokens` budget, so a long prompt never stalls the
  running decoders for a full bucket-padded forward pass. Each chunk is
  a prefill at a TRACED start offset with a TRACED valid length, so the
  whole per-bucket `prefill`/`prefill_offset` executable family
  collapses into ONE `prefill_chunked` executable for every prompt
  length, and padding waste is capped at one chunk (the prompt's final
  one) instead of up-to-2x of a power-of-two bucket. Intermediate
  chunks never sync the host and leave the per-request PRNG state
  untouched (one key split per EMITTED token), so token streams stay
  bit-identical to the unchunked engine.

The engine talks to any decoder model that follows the
`forward(input_ids, caches=..., start_pos=...)` cache protocol of
models/generation.py (LLaMA, GPT); the per-layer cache objects it passes
are `PagedLayerCache` views, which `attend_with_cache` dispatches to the
ragged paged attention op.

Observability (ISSUE 4): every counter lives in ONE
paddle_tpu.observability MetricsRegistry per engine — `stats()` and
`compile_counts()` are thin views over it, `ServingObs` resolves all
handles once at construction so the hot path never looks anything up,
and `enable_metrics=False` removes even that (a None check per site).
On top of the batch-level RecordEvent spans ("serving.prefill" /
"serving.decode_block" / "serving.host_drain"), a LifecycleTracker
emits per-request spans (`serving.request[<rid>].<stage>` for
enqueued/admitted/prefill/first_token/decode_block/preempted/requeued/
finished) into the profiler's chrome-trace host tracer, and TTFT /
inter-token latency histograms back `stats()["latency"]`'s p50/p95/p99.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..jit.functional import call_functional, extract_state
from ..observability import Histogram, LifecycleTracker, MetricsRegistry
from ..observability import compiles
from ..observability.flight_recorder import (
    build_postmortem as _build_bundle, dump_postmortem as _dump_bundle)
from ..observability.slo import SloTracker
from ..ops.pallas_kernels import flash_prefill_steps
from ..profiler import RecordEvent, scopes
from .attention import advance_positions
from .kv_cache import (PagedKVCache, PagedLayerCache, overflow_position,
                       pages_for, pools_from_views, views_from_pools)
from .prefix_cache import PrefixCache
from .ragged import build_ragged_inputs
from .ragged import token_buckets as ragged_token_buckets
from .recovery import EngineSnapshot, RequestSnapshot, replay_key_state
from .resilience import (TERMINAL_STATUSES, describe_fault, is_fatal,
                         is_transient)
from .scheduler import (Request, SamplingParams, Scheduler,
                        reserve_request_ids)

__all__ = ["ServingEngine", "ServingObs", "PAD_TOKEN"]

# emitted by dead rows inside a decode block (finished / padding); the
# host drain trims each row at its first PAD
PAD_TOKEN = -1


def _default_buckets(max_seq_len: int) -> Tuple[int, ...]:
    """Power-of-two prompt buckets up to max_seq_len (always included):
    a handful of prefill compilations covers every prompt length."""
    buckets = []
    b = 16
    while b < max_seq_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_seq_len)
    return tuple(buckets)


@jax.named_scope(scopes.SAMPLING)
def _sample_batch(logits, keys, temps, top_ks, top_ps):
    """Per-row sampling with TRACED knobs (the batch mixes requests with
    different sampling params). Mirrors generation._sample row-wise:
    greedy where temperature == 0, else temperature -> top-k -> top-p ->
    categorical. A batch whose rows are ALL greedy (padding and parked
    rows carry temperature 0) takes the argmax alone: the conditional's
    predicate is a scalar the program computes from its own input, so
    the device runs one branch and no executable is added."""
    return jax.lax.cond(
        jnp.all(temps == 0.0),
        lambda: jnp.argmax(logits.astype(jnp.float32), axis=-1),
        lambda: _sample_rows(logits, keys, temps, top_ks, top_ps))


def _sample_rows(logits, keys, temps, top_ks, top_ps):
    """The branch of `_sample_batch` for a batch in which some row
    samples: two sorts, a softmax and a cumulative sum over the whole
    vocabulary, for every row; greedy rows among them keep the argmax."""
    vocab = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1)
    t_safe = jnp.where(temps > 0.0, temps, 1.0)
    scaled = logits / t_safe[:, None]
    # top-k as a rank threshold (top_k <= 0 disables by keeping all V)
    k_eff = jnp.where(top_ks > 0, jnp.minimum(top_ks, vocab), vocab)
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(sorted_desc, (k_eff - 1)[:, None], axis=-1)
    masked = jnp.where(scaled < kth, -jnp.inf, scaled)
    # top-p over the top-k-masked distribution (generation._sample order)
    sorted_m = jnp.sort(masked, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_m, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.minimum(
        jnp.sum(cum < top_ps[:, None], axis=-1, keepdims=True), vocab - 1)
    cutoff = jnp.take_along_axis(sorted_m, cutoff_idx, axis=-1)
    masked = jnp.where(masked < cutoff, -jnp.inf, masked)
    sampled = jax.vmap(jax.random.categorical)(keys, masked)
    return jnp.where(temps == 0.0, greedy, sampled)


@jax.named_scope(scopes.SAMPLING)
def _split_rows(key_data):
    """One split per row, entirely on device: key_data (b, 2) uint32 ->
    (new key_data, sample keys). Bit-identical to the host-side
    `jax.random.split` chain the pre-horizon sampler ran per token."""
    keys = jax.random.wrap_key_data(key_data)
    pair = jax.vmap(jax.random.split)(keys)
    return jax.random.key_data(pair[:, 0]), pair[:, 1]


class ServingObs:
    """Every observability handle the serving hot path touches, resolved
    ONCE against the engine's MetricsRegistry (metric name lookups never
    run per step), plus the per-request LifecycleTracker. The scheduler
    receives this same object and calls the small hooks below at queue
    transitions; with `enable_metrics=False` the engine passes None
    everywhere and the hot path does literally no metrics work
    (tests/test_serving.py pins that with a raise-on-touch guard)."""

    FAMILIES = ("prefill", "prefill_offset", "prefill_chunked", "decode",
                "ragged", "spec")

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        self.lifecycle = LifecycleTracker()
        c, g, h = registry.counter, registry.gauge, registry.histogram
        self.prefill_steps = c("serving_prefill_steps_total",
                               "prefill dispatches")
        self.prefill_chunks = c("serving_prefill_chunks_total",
                                "chunked-prefill chunk dispatches")
        self.decode_steps = c("serving_decode_steps_total",
                              "fused decode-block dispatches")
        self.ragged_steps = c("serving_ragged_steps_total",
                              "flat ragged mixed-step dispatches (one "
                              "executable carrying the step's decode "
                              "rows AND prefill chunks)")
        self.tokens = c("serving_tokens_generated_total",
                        "tokens emitted to the host")
        self.host_syncs = c("serving_host_syncs_total",
                            "device->host sync points")
        self.dispatches = c("serving_dispatches_total",
                            "device program launches of any family "
                            "(prefill, chunk, decode block, or ragged "
                            "step) — the per-step launch cost the "
                            "ragged executable collapses to one")
        self.greedy_dispatches = c(
            "serving_greedy_dispatches_total",
            "device program launches whose rows were all at "
            "temperature 0: the sampler took the argmax alone (over "
            "serving_dispatches_total, the share of launches that "
            "skipped the sort of the vocabulary)")
        self.preemptions = c("serving_preemptions_total",
                             "requests preempted and requeued")
        self.prefill_seconds = c("serving_prefill_seconds_total",
                                 "wall time in prefill dispatch+sync")
        self.decode_seconds = c(
            "serving_decode_seconds_total",
            "decode wall time (async-overlap deduplicated)")
        # batch occupancy and queue wait, counted where the engine
        # dispatches and admits, from host integers and clocks only:
        # live over dispatched is the share of the decode batch the
        # device computes for a request that still wants tokens
        self.decode_rows_live = c(
            "serving_decode_rows_live_total",
            "decode-block rows with token budget left at dispatch")
        self.decode_rows_dispatched = c(
            "serving_decode_rows_dispatched_total",
            "decode-block rows the device computes (the power-of-two "
            "row count of each dispatched block)")
        self.admissions = c(
            "serving_admissions_total",
            "requests admitted from the waiting queue (a requeued "
            "request counts again)")
        self.queue_wait_seconds = c(
            "serving_queue_wait_seconds_total",
            "seconds from arrival (or from the last requeue) to "
            "admission, summed over admissions")
        self.compile_miss = {
            fam: c("serving_jit_compile_misses_total",
                   "distinct executables per step family "
                   "(this engine's jit-cache misses)",
                   labels={"family": fam})
            for fam in self.FAMILIES}
        self.ttft = h("serving_ttft_seconds",
                      "request arrival to first token on the host")
        self.inter_token = h(
            "serving_inter_token_seconds",
            "per-token gap between host-visible emissions (a decode "
            "block's gap is spread evenly over its tokens)")
        # the head-of-line metric chunked prefill exists to shrink: the
        # wall gap between consecutive decode-block DISPATCHES while at
        # least one request is running — an unchunked engine shows a
        # full bucket-padded prefill here whenever a prompt arrives
        # mid-decode, a chunked one at most ~one chunk's compute
        self.decode_stall = h(
            "serving_decode_stall_seconds",
            "gap between consecutive decode-block dispatches while "
            "requests are running")
        # resilience counters (ISSUE 6): one labelled series per
        # non-finished terminal status, plus retry/park events
        self.terminated = {
            status: c("serving_requests_terminated_total",
                      "requests reaching a non-finished terminal status",
                      labels={"status": status})
            for status in ("cancelled", "expired", "failed", "shed")}
        self.retries = c("serving_transient_retries_total",
                         "dispatch/drain sites retried after a "
                         "transient fault")
        self.parked_total = c("serving_requests_parked_total",
                              "preemption-storm guard trips (victim "
                              "requeued at the back of the queue)")
        # step-phase breakdown (ISSUE 13): wall time per step split into
        # schedule (policy + page reservation), assemble (host-side batch
        # packing: buckets, tables, padding), dispatch (jitted launch
        # until control returns to the host — async, so this is NOT
        # device time) and drain (the ONE host sync pulling tokens back).
        # device_residency estimates device occupancy as dispatch-time to
        # drain-time of the same block — the denominator ROADMAP 5's
        # overlap fraction needs.
        self.step_phase = {
            phase: h("serving_step_phase_seconds",
                     "per-step wall time by phase (schedule / assemble "
                     "/ dispatch / drain)", labels={"phase": phase})
            for phase in ("schedule", "assemble", "dispatch", "drain")}
        self.device_residency = h(
            "serving_device_residency_seconds",
            "dispatch-to-drain wall per block: how long work was "
            "resident on the device side of the async overlap")
        self.queue_waiting = g("serving_queue_depth",
                               "scheduler queue depth",
                               labels={"state": "waiting"})
        self.queue_running = g("serving_queue_depth",
                               "scheduler queue depth",
                               labels={"state": "running"})
        self.free_pages = g("serving_kv_free_pages",
                            "allocatable KV pages right now")
        self.kv_util = g("serving_kv_page_utilization",
                         "fraction of allocatable KV pages in use")
        # tensor-parallel handles, bound by bind_tp() only when the
        # engine runs with tp_size>1 — None means zero TP metrics work
        self.tp_collective = None
        self.tp_free_pages = None
        # speculative-decoding handles, bound by bind_spec() only when
        # the engine runs with spec_config — None means zero spec
        # metrics work (the enable_metrics=False discipline)
        self.spec_drafted = None
        self.spec_accepted = None
        self.spec_wasted = None
        self.spec_target_steps = None
        self.spec_tokens_per_step = None
        # expert-layer handles, bound by bind_moe() only for a model
        # whose steps return an expert histogram
        self.moe_pairs = None
        # state-slot handles, bound by bind_state_slots() only for a
        # model with recurrent layers
        self.slots_in_use = None
        # sparse-attention handles, bound by bind_dsa() only for a model
        # whose queries attend the keys an indexer chooses
        self.dsa_keys_selected = None
        # prefill-flash handles, bound by bind_prefill_flash() only for a
        # model whose prefill hands flash the prompt's length
        self.prefill_flash_steps = None

    def dispatched(self, greedy: bool) -> None:
        """One device program launched; `greedy` is the host's reading of
        the predicate `_sample_batch` takes on the device, from the same
        float32 temperatures (the host's own arrays: no sync)."""
        self.dispatches.inc()
        if greedy:
            self.greedy_dispatches.inc()

    def bind_moe(self) -> None:
        """Expert-layer observability: counters fed from the (layers,
        experts) histogram of tokens an expert that every prefill and
        every step of a decode block returns with its tokens (no host
        sync of its own). A layer dispatch is one expert layer in one
        model step that routed at least one token."""
        c = self.registry.counter
        self.moe_pairs = c(
            "serving_moe_pairs_total",
            "(token, expert) pairs routed, padding and parked rows "
            "left out")
        self.moe_experts_touched = c(
            "serving_moe_experts_touched_total",
            "distinct experts with a token, summed over layer dispatches")
        self.moe_layer_dispatches = c(
            "serving_moe_layer_dispatches_total",
            "expert layers run, one a layer a model step")
        self.moe_max_expert_tokens = c(
            "serving_moe_max_expert_tokens_total",
            "tokens of the fullest expert, summed over layer dispatches")

    def bind_dsa(self, layers: int, topk: int) -> None:
        """Sparse-attention observability of a model with an indexer in
        each of its `layers` attention layers: the keys a decode row had
        in its context and the keys it attended, min(context, `topk`),
        summed over rows, layers and the steps of a block, from the
        positions the host dispatches with (no sync)."""
        c = self.registry.counter
        self._dsa = (int(layers), int(topk))
        self.dsa_keys_in_context = c(
            "serving_dsa_keys_in_context_total",
            "cached keys in a decode row's context, summed over rows, "
            "layers and steps")
        self.dsa_keys_selected = c(
            "serving_dsa_keys_selected_total",
            "keys a decode row attended: min(context, index_topk), "
            "summed over rows, layers and steps")

    def dsa_block(self, contexts, steps) -> int:
        """Count one dispatched decode block: `contexts[i]` keys in row
        i's context at its first step, one more at each of its
        `steps[i]` steps. Returns the keys attended."""
        layers, topk = self._dsa
        in_context = selected = 0
        for c0, n in zip(contexts, steps):
            in_context += n * c0 + n * (n - 1) // 2
            under = min(max(topk - c0, 0), n)   # steps still under topk
            selected += (under * c0 + under * (under - 1) // 2
                         + (n - under) * topk)
        self.dsa_keys_in_context.inc(layers * in_context)
        self.dsa_keys_selected.inc(layers * selected)
        return layers * selected

    def bind_prefill_flash(self) -> None:
        """Prefill-flash observability of a model whose prefill hands the
        flash kernel the prompt's length: the grid steps a (head, layer)
        computed over the prompt's own causal blocks, and those the
        whole bucket's causal triangle holds. Their ratio is the share of
        the bucket's steps computed; host integers at dispatch, no
        sync."""
        c = self.registry.counter
        self.prefill_flash_steps = c(
            "serving_prefill_flash_steps_total",
            "causal flash steps a prefill computed over its prompt's own "
            "blocks, a head a layer")
        self.prefill_flash_bucket_steps = c(
            "serving_prefill_flash_bucket_steps_total",
            "causal flash steps of the prefill's whole bucket, a head a "
            "layer")

    def prefill_flash(self, bucket: int, prompt: int) -> None:
        computed, whole = flash_prefill_steps(bucket, prompt)
        self.prefill_flash_steps.inc(computed)
        self.prefill_flash_bucket_steps.inc(whole)

    def bind_state_slots(self) -> None:
        """State-slot observability of a model with recurrent layers:
        how many slots are held, how many were handed out, and how often
        the K/V pages held an admission back (the slots cannot: there is
        one a row). Host integers the scheduler already has: no sync."""
        r = self.registry
        self.slots_in_use = r.gauge(
            "serving_state_slots_in_use",
            "state slots held by running requests")
        self.slot_allocations = r.counter(
            "serving_state_slot_allocations_total",
            "state slots handed out at admission")
        self.blocked_on_pages = r.counter(
            "serving_admission_blocked_on_pages_total",
            "scheduling turns in which the head of the queue was not "
            "admitted for want of pages")

    def state_slots(self, allocator, allocated: bool = False) -> None:
        self.slots_in_use.set(allocator.num_used)
        if allocated:
            self.slot_allocations.inc()

    def admission_blocked_on_pages(self) -> None:
        self.blocked_on_pages.inc()

    def moe_block(self, hist, family: str) -> None:
        """Count one drained block's histograms, (..., layers, experts),
        and leave the distinct experts it touched on a marker span (a
        span's attributes are fixed when it starts, and this number
        comes back with the tokens)."""
        hist = np.asarray(hist).reshape(-1, hist.shape[-1])
        touched = np.count_nonzero(hist, axis=-1)
        pairs, experts = int(hist.sum()), int(touched.sum())
        self.moe_pairs.inc(pairs)
        self.moe_experts_touched.inc(experts)
        self.moe_layer_dispatches.inc(int(np.count_nonzero(touched)))
        self.moe_max_expert_tokens.inc(int(hist.max(axis=-1).sum()))
        with RecordEvent("serving.moe_stats", family=family,
                         experts_touched=experts, pairs=pairs):
            pass

    def bind_tp(self, tp_size: int, overlap: bool = False) -> None:
        """TP observability (ISSUE 10): the measured all-reduce latency
        histogram — labelled `overlap="on"/"off"` since ISSUE 18, so
        dashboards can compare the serial wall against the
        ring-overlapped one without mixing samples — one free-page gauge
        per shard (page accounting is shard-replicated, so every shard
        reports the same number; the label keeps per-shard dashboards
        well-formed), and a `tp=N` tag appended to every lifecycle span
        name."""
        r = self.registry
        self.tp_collective = r.histogram(
            "serving_tp_collective_seconds",
            "measured all-reduce wall seconds on the engine's tp "
            "sub-mesh (decode-step payload shape)",
            labels={"overlap": "on" if overlap else "off"})
        self.tp_free_pages = [
            r.gauge("serving_kv_pages_free",
                    "free KV pages per tensor-parallel shard",
                    labels={"shard": str(i)})
            for i in range(tp_size)]
        self.lifecycle.tag = f"tp={tp_size}"

    def bind_kv_pool(self, kv_dtype: str, pool_bytes: int,
                     fp32_pool_bytes: int,
                     rms_error: Optional[float] = None) -> None:
        """KV-pool capacity observability (ISSUE 15): pool bytes (data +
        scale slabs) labelled by storage format for every engine, plus —
        quantized pools only — the capacity ratio against an equal-page
        fp32 pool and the construction-time quantization-error probe
        (the hot path keeps no fp32 originals, so error is characterized
        once, offline)."""
        r = self.registry
        r.gauge("serving_kv_pool_bytes",
                "bytes held by the paged KV pools (data + scale slabs)",
                labels={"kv_dtype": kv_dtype}).set(pool_bytes)
        if rms_error is not None:
            r.gauge("serving_kv_capacity_ratio",
                    "fp32 pool bytes / this pool's bytes at equal page "
                    "count (resident-sequence capacity multiplier)"
                    ).set(fp32_pool_bytes / pool_bytes)
            r.gauge("serving_kv_quant_rms_error",
                    "quantize->dequantize RMS relative error, one-shot "
                    "construction-time probe on gaussian K/V"
                    ).set(rms_error)

    def bind_spec(self) -> None:
        """Speculative-decoding observability (ISSUE 17): drafted /
        accepted / wasted draft-token counters, the target-model pass
        counter their accept-rate divides into, and the per-request
        tokens-per-target-step histogram — the multiplier speculation
        exists to raise (1.0 = non-speculative; the goodput interplay
        shows up through the existing SLO plane, whose TPOT samples
        simply arrive in bigger per-block bursts)."""
        c = self.registry.counter
        self.spec_drafted = c(
            "serving_spec_drafted_tokens_total",
            "draft tokens submitted to fused verification")
        self.spec_accepted = c(
            "serving_spec_accepted_tokens_total",
            "draft tokens accepted by rejection sampling")
        self.spec_wasted = c(
            "serving_spec_wasted_tokens_total",
            "draft tokens rejected (verified but not emitted)")
        self.spec_target_steps = c(
            "serving_spec_target_steps_total",
            "target-model verify passes over speculative rows")
        self.spec_tokens_per_step = self.registry.histogram(
            "serving_spec_tokens_per_target_step",
            "tokens emitted per target-model pass, one sample per "
            "request per drained speculative block")

    # --------------------------------------------------- scheduler hooks
    def enqueued(self, req) -> None:
        self.lifecycle.point(req.request_id, "enqueued", req.arrival_t)

    def admitted(self, req) -> None:
        now = time.perf_counter()
        since = req.arrival_t if req.requeue_t is None else req.requeue_t
        self.admissions.inc()
        self.queue_wait_seconds.inc(max(now - since, 0.0))
        self.lifecycle.point(req.request_id, "admitted", now)

    def preempted(self, req) -> None:
        self.preemptions.inc()
        now = time.perf_counter()
        req.requeue_t = now
        self.lifecycle.point(req.request_id, "preempted", now)
        self.lifecycle.point(req.request_id, "requeued", now)

    def finished(self, req) -> None:
        self.lifecycle.point(req.request_id, "finished", req.finish_t)

    def terminal(self, req, status: str) -> None:
        """A request reached cancelled/expired/failed/shed: count it and
        stamp the lifecycle so chrome traces and `trace_summary
        --requests` show how the request ended."""
        self.terminated[status].inc()
        self.lifecycle.point(req.request_id, status, req.finish_t)

    def parked(self, req) -> None:
        self.parked_total.inc()
        self.lifecycle.point(req.request_id, "parked")

    def sample_queues(self, waiting: int, running: int, allocator) -> None:
        self.queue_waiting.set(waiting)
        self.queue_running.set(running)
        free = allocator.num_free
        total = allocator.num_allocatable        # page 0 never allocates
        self.free_pages.set(free)
        self.kv_util.set(1.0 - free / total if total else 0.0)
        if self.tp_free_pages is not None:
            for shard_gauge in self.tp_free_pages:
                shard_gauge.set(free)


class ServingEngine:
    def __init__(self, model, *, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 max_batch_size: int = 8,
                 max_seq_len: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 cache_dtype=jnp.float32,
                 kv_dtype: str = "fp32",
                 enable_prefix_caching: bool = False,
                 decode_horizon: int = 8,
                 spec_config=None,
                 enable_chunked_prefill: bool = False,
                 prefill_chunk_tokens: int = 256,
                 max_num_batched_tokens: Optional[int] = None,
                 enable_ragged_step: bool = True,
                 enable_metrics: bool = True,
                 metrics: Optional[MetricsRegistry] = None,
                 max_waiting: Optional[int] = None,
                 max_queue_wait_s: Optional[float] = None,
                 max_preemptions: Optional[int] = 8,
                 fault_injector=None,
                 retry_backoff_s: float = 0.02,
                 journal=None,
                 tp_size: int = 1,
                 devices: Optional[Sequence] = None,
                 tp_quantized_allreduce: bool = False,
                 tp_overlap: bool = False,
                 tp_overlap_chunks: int = 2,
                 slo_classes: Optional[Sequence] = None,
                 slo_refresh_every: int = 64,
                 flight_recorder=None,
                 postmortem_dir: Optional[str] = None):
        from ..models.generation import _config_of

        # set-up accounting: what JAX builds for this engine, by family
        compiles.install()
        self.model = model
        model.eval()
        cfg = _config_of(model)
        # quantized serving (ISSUE 15): `kv_dtype` names the KV pool
        # storage format. "fp32"/"bf16" resolve HERE, without importing
        # serving.quant (zero-touch guarantee, raise-on-touch pinned);
        # "int8"/"fp8" are validated lazily by quant.resolve_kv_dtype
        # inside PagedKVCache. `cache_dtype` stays as the legacy spelling
        # of the unquantized formats; a conflict between the two knobs is
        # an error, not a silent preference.
        legacy = {"float32": "fp32", "bfloat16": "bf16"}.get(
            jnp.dtype(cache_dtype).name)
        if legacy is None:
            raise ValueError(
                f"unsupported cache_dtype {cache_dtype!r}: pools take "
                "float32/bfloat16, or use kv_dtype='int8'/'fp8'")
        kv_dtype = str(kv_dtype)
        if kv_dtype == "fp32" and legacy != "fp32":
            kv_dtype = legacy
        elif legacy != "fp32" and kv_dtype != legacy:
            raise ValueError(
                f"conflicting cache_dtype={jnp.dtype(cache_dtype).name} "
                f"and kv_dtype={kv_dtype!r}: pick one knob")
        if kv_dtype not in ("fp32", "bf16", "int8", "fp8"):
            raise ValueError(
                f"unknown kv_dtype {kv_dtype!r}: expected one of "
                "'fp32', 'bf16', 'int8', 'fp8'")
        self.kv_dtype = kv_dtype
        # a model with latent attention (its config names the cached
        # row's width) gets a latent pool, one with recurrent layers (its
        # config names them) state slots beside its K/V pages; what is
        # not yet written over either is refused here, by the option's
        # name, never taken down a path that would compute something
        # else. Recovery needs no refusal: a snapshot holds requests,
        # never pages or states, and a restored request re-prefills
        self._has_state = getattr(cfg, "state_cache_spec", None) is not None
        held_in = ("a latent KV pool"
                   if getattr(cfg, "latent_cache_dim", None) is not None
                   else "state slots beside its K/V pages"
                   if self._has_state else None)
        if held_in is not None:
            refused = [name for name, on in (
                ("tp_size", int(tp_size) > 1),
                ("kv_dtype", kv_dtype in ("int8", "fp8")),
                ("enable_prefix_caching", bool(enable_prefix_caching)),
                ("enable_chunked_prefill", bool(enable_chunked_prefill)),
                ("spec_config", spec_config is not None)) if on]
            if refused:
                raise ValueError(
                    f"{type(model).__name__} serves over {held_in}, "
                    f"which does not support {', '.join(refused)} yet "
                    "(tensor parallelism, quantized pages, and any "
                    "prefill at an offset: prefix cache, chunked prefill "
                    "and the ragged step, speculative verify)")
        # the serving protocol's optional parts (models/mla_moe.py):
        # `logits_at` makes a prefill return one position's logits, the
        # cache path returns a third value whose expert histogram feeds
        # the serving_moe_* counters, and a prefill's flash walks the
        # prompt's own blocks, which the serving_prefill_flash_* count
        self._logits_at = bool(getattr(model, "serving_logits_at", False))
        self._has_aux = bool(getattr(model, "serving_aux", False))
        self._prefill_live = bool(getattr(model, "serving_prefill_live",
                                          False))
        self.tp_quantized_allreduce = bool(tp_quantized_allreduce)
        if self.tp_quantized_allreduce and int(tp_size) < 2:
            raise ValueError(
                "tp_quantized_allreduce replaces the row-parallel psum "
                "and needs tp_size >= 2 (tp_size=1 has no collective)")
        # collective/compute overlap (ISSUE 18): split each row-parallel
        # all-reduce into `tp_overlap_chunks` micro-row ring chunks that
        # interleave with the consumer matmuls, tokens bit-identical to
        # the serial psum. chunks=1 degenerates to the serial schedule
        # (TPContext normalizes it off and reuses the serial
        # executables); tp_size=1 has no collective to hide
        self.tp_overlap = bool(tp_overlap)
        self.tp_overlap_chunks = int(tp_overlap_chunks)
        if self.tp_overlap:
            if int(tp_size) < 2:
                raise ValueError(
                    "tp_overlap pipelines the row-parallel all-reduce "
                    "and needs tp_size >= 2 (tp_size=1 has no "
                    "collective to hide)")
            if self.tp_overlap_chunks < 1:
                raise ValueError(
                    f"tp_overlap_chunks must be >= 1, got "
                    f"{tp_overlap_chunks}")
        # tensor parallelism (ISSUE 10): tp_size>1 shards the model
        # weights (Megatron column/row specs) and the KV pools' kv-head
        # axis over a sub-mesh of `devices` (sorted by id; default the
        # first tp_size of jax.devices()) and wraps every jitted step in
        # shard_map. The import stays inside the branch: the tp_size=1
        # path runs ZERO tp code (pinned by a raise-on-touch test)
        self.tp_size = int(tp_size)
        if self.tp_size < 1:
            raise ValueError(f"tp_size must be >= 1, got {tp_size}")
        if self.tp_size > 1:
            from .tp import TPContext

            self._tp = TPContext(
                model, self.tp_size, devices=devices,
                quantized_allreduce=self.tp_quantized_allreduce,
                overlap=self.tp_overlap,
                overlap_chunks=self.tp_overlap_chunks)
        else:
            self._tp = None
        self.page_size = page_size
        self.max_batch_size = max_batch_size
        self.max_seq_len = max_seq_len or cfg.max_position_embeddings
        self.max_pages_per_seq = pages_for(self.max_seq_len, page_size)
        self.decode_horizon = int(decode_horizon)
        if self.decode_horizon < 1:
            raise ValueError("decode_horizon must be >= 1")
        # speculative decoding (ISSUE 17): model-free drafts (n-gram
        # prompt-lookup / prefix-cache continuation) verified inside the
        # fused decode/ragged executables with on-device rejection
        # sampling. The import stays inside the branch: a spec-off
        # engine runs ZERO spec code (raise-on-touch pinned in
        # tests/test_spec.py), and its non-spec streams are byte
        # identical to pre-spec engines
        if spec_config is not None:
            from . import spec as _spec_module

            self._spec_mod = _spec_module
            self.spec_config = spec_config.validate()
        else:
            self._spec_mod = None
            self.spec_config = None
        self._spec_lookahead = (self.spec_config.lookahead
                                if self.spec_config is not None else 0)
        # chunked prefill (Sarathi-Serve): prompts run in page-aligned
        # chunks co-scheduled with decode under a per-step token budget.
        # Off by default; when on, the chunk width must be a positive
        # multiple of page_size (chunk starts stay page-aligned so every
        # non-final chunk's page charge is exact) and the budget must fit
        # at least one chunk or prefill could never progress
        self.enable_chunked_prefill = bool(enable_chunked_prefill)
        if self.enable_chunked_prefill:
            self.prefill_chunk_tokens = int(prefill_chunk_tokens)
            if self.prefill_chunk_tokens < page_size or \
                    self.prefill_chunk_tokens % page_size:
                raise ValueError(
                    f"prefill_chunk_tokens ({prefill_chunk_tokens}) must "
                    f"be a positive multiple of page_size ({page_size})")
            if max_num_batched_tokens is None:
                # default: one full chunk always fits alongside a full
                # decode batch (decoders charge a block's worst case —
                # under speculation that is horizon × (1+lookahead))
                max_num_batched_tokens = (
                    self.prefill_chunk_tokens
                    + max_batch_size * self.decode_horizon
                    * (1 + self._spec_lookahead))
            self.max_num_batched_tokens = int(max_num_batched_tokens)
            if self.max_num_batched_tokens < self.prefill_chunk_tokens:
                raise ValueError(
                    f"max_num_batched_tokens ({max_num_batched_tokens}) "
                    "must be >= prefill_chunk_tokens "
                    f"({self.prefill_chunk_tokens})")
            # ragged mixed steps (on by default under chunking): a step
            # that carries chunk work dispatches ONE flat executable —
            # decode rows and chunks share it — keyed on a small set of
            # total-token buckets, instead of the decode block plus one
            # dispatch per chunk. `enable_ragged_step=False` keeps the
            # PR 6 chained pipeline (no caller but tests: ROADMAP.md D2)
            self.enable_ragged_step = bool(enable_ragged_step)
            self.token_buckets = (
                ragged_token_buckets(max_batch_size,
                                     self.max_num_batched_tokens)
                if self.enable_ragged_step else None)
        else:
            self.prefill_chunk_tokens = None
            self.max_num_batched_tokens = None
            self.enable_ragged_step = False
            self.token_buckets = None
        if num_pages is None:
            # worst case every slot runs a full-length sequence, +1 null
            num_pages = max_batch_size * self.max_pages_per_seq + 1
        # one state slot a row of the decode batch: a running request
        # always has one, so the pages alone decide how many run
        self.cache = PagedKVCache.for_model(
            model, num_pages, page_size, cache_dtype,
            kv_dtype=self.kv_dtype, pack_heads=self._tp is None,
            state_slots=max_batch_size if self._has_state else 0)
        if self._tp is not None:
            self.cache.shard_pools(self._tp.mesh, self._tp.pool_spec)
        # observability: ONE registry per engine is the single source of
        # truth behind stats()/compile_counts() and the exporters. Pass
        # `metrics=` to aggregate several engines into a shared registry,
        # or `enable_metrics=False` to strip every metrics/lifecycle call
        # off the hot path (stats() then returns the same shape zeroed).
        self.metrics = metrics if metrics is not None else (
            MetricsRegistry() if enable_metrics else None)
        self._obs = (ServingObs(self.metrics)
                     if self.metrics is not None else None)
        if self._obs is not None and self._tp is not None:
            self._obs.bind_tp(self.tp_size, overlap=self._tp.overlap)
        if self.metrics is not None:
            self.cache.allocator.bind_metrics(self.metrics)
        if self._obs is not None:
            # equal-page fp32 baseline for the capacity gauge, computed
            # WITHOUT touching serving.quant
            c = self.cache
            fp32_bytes = (c.num_kv_layers * c.num_pages * c.page_size
                          * c.slot_elems * 4)
            rms = None
            if c.quantized:
                from .quant import measure_roundtrip_error
                rms = measure_roundtrip_error(c.quant_spec, c.head_dim)
            self._obs.bind_kv_pool(c.kv_dtype, c.pool_bytes, fp32_bytes,
                                   rms)
        if self._obs is not None and self.spec_config is not None:
            self._obs.bind_spec()
        if self._obs is not None and self._has_aux:
            self._obs.bind_moe()
        if self._obs is not None and self._prefill_live:
            self._obs.bind_prefill_flash()
        if self._obs is not None and self._has_state:
            self._obs.bind_state_slots()
        if self._obs is not None \
                and getattr(cfg, "index_cache_dim", None) is not None:
            self._obs.bind_dsa(cfg.num_hidden_layers, cfg.index_topk)
        # SLO accounting (ISSUE 13): per-request-class TTFT/TPOT targets
        # feeding windowed attainment gauges + a goodput counter. Rides
        # on the metrics registry, so it requires one; with no classes
        # registered the engine holds None and executes zero SLO code
        # (raise-on-touch pinned, like enable_metrics=False).
        if slo_classes:
            if self.metrics is None:
                raise ValueError(
                    "slo_classes requires metrics (SLO accounting lives "
                    "in the registry); drop enable_metrics=False")
            self._slo = SloTracker(self.metrics, slo_classes,
                                   refresh_every=slo_refresh_every)
        else:
            self._slo = None
        # flight recorder (ISSUE 13): bounded ring of control-plane
        # events. None = the engine executes no recorder code at all.
        # Independent of metrics — forensics work even on a metrics-off
        # engine, and vice versa.
        self._recorder = flight_recorder
        # where quarantine/death post-mortem bundles land; None = build
        # bundles only on explicit dump_postmortem(directory=...) calls
        self._postmortem_dir = postmortem_dir
        self.last_postmortem_path: Optional[str] = None
        # automatic prefix caching (full-page granularity, LRU eviction):
        # finished/prefilled prompts leave their full pages in a radix
        # tree; a later prompt sharing a page-aligned prefix reuses them
        # and prefills only its suffix
        self.prefix_cache = (PrefixCache(self.cache.allocator, page_size,
                                         metrics=self.metrics)
                             if enable_prefix_caching else None)
        # resilience (ISSUE 6): bounded queue + queue-wait shedding,
        # per-request deadlines (add_request(deadline_s=...)), transient
        # retry with backoff, preemption-storm parking, and seeded fault
        # injection. Everything strips to a None/empty check when unused
        # — the enable_metrics=False discipline.
        self._max_queue_wait_s = (float(max_queue_wait_s)
                                  if max_queue_wait_s is not None else None)
        self.retry_backoff_s = float(retry_backoff_s)
        self._faults = fault_injector
        # crash recovery (ISSUE 8): the journal is the exactly-once
        # delivery ledger — tokens are appended at the moment a step
        # RETURNS them, never at drain time (recovery.py). None = no
        # journaling, and the only cost is one None check per step.
        self._journal = journal
        # engine-level fault count (every fault _guarded_call or the
        # device_lost gate observes, transient or not): the supervisor's
        # fault-storm window reads deltas of this — a plain int, so it
        # works with metrics off
        self.fault_events = 0
        # live request ids carrying a deadline; the expiry sweep is
        # skipped entirely while this is empty and no queue-wait bound
        # is set, so deadline-free serving runs zero resilience code
        self._deadlined: set = set()
        if fault_injector is not None:
            self.cache.allocator.bind_faults(fault_injector)
            if self.prefix_cache is not None:
                self.prefix_cache.bind_faults(fault_injector)
        self.prefill_buckets = tuple(sorted(
            prefill_buckets or _default_buckets(self.max_seq_len)))
        if self.prefill_buckets[-1] < self.max_seq_len:
            raise ValueError("prefill_buckets must cover max_seq_len "
                             "(preempted requests re-prefill at their "
                             "full current length)")
        self.scheduler = Scheduler(self.cache.allocator, page_size,
                                   max_batch_size, self.max_pages_per_seq,
                                   prefix_cache=self.prefix_cache,
                                   decode_horizon=self.decode_horizon,
                                   drain_hook=self._drain_for_scheduler,
                                   obs=self._obs,
                                   recorder=flight_recorder,
                                   max_waiting=max_waiting,
                                   max_preemptions=max_preemptions,
                                   # chunked prefill handles any folded
                                   # length — no bucket ceiling to guard
                                   max_prefill_tokens=(
                                       None if self.enable_chunked_prefill
                                       else self.prefill_buckets[-1]),
                                   prefill_chunk_tokens=
                                   self.prefill_chunk_tokens,
                                   max_num_batched_tokens=
                                   self.max_num_batched_tokens,
                                   ragged_steps=self.enable_ragged_step,
                                   spec_lookahead=self._spec_lookahead,
                                   slot_allocator=self.cache.slot_allocator)
        self.params, self.buffers = extract_state(model)
        if self._tp is not None:
            self.params = self._tp.shard_params(self.params)
            self.buffers = self._tp.replicate(self.buffers)
        self.requests: Dict[int, Request] = {}
        # per-request PRNG state as raw (2,) uint32 key data — sampling
        # never splits keys on the host: the device's carry comes back
        # with the tokens of a prefill or a drained block as a host row,
        # so a batch's keys take no device operation a row to take apart
        # or put together (a key not yet used is still a device array)
        self._key_state: Dict[int, object] = {}
        # the dispatched-but-undrained decode block (async overlap depth
        # 1): emitted tokens + the device carries the next chained block
        # consumes without any host round-trip
        self._pending: Optional[dict] = None
        # events produced when the scheduler's drain_hook fires inside
        # schedule(); step() returns them ahead of its own
        self._spill: List[Tuple[int, int]] = []
        self._last_drain_t = 0.0
        # decode-stall observability: perf_counter of the most recent
        # decode-block dispatch, cleared whenever the running set
        # empties, so the serving_decode_stall_seconds histogram only
        # sees gaps while some request was actually being served
        self._last_decode_dispatch_t: Optional[float] = None
        # jitted steps are memoized ON THE MODEL (generation.py's trick):
        # the closures only capture `model`, so engines over the same model
        # — restarts, tests, multiple pools — share compiled executables,
        # and jax retraces per aval set exactly when shapes differ
        self._jit_cache: Dict[object, object] = model.__dict__.setdefault(
            "_serving_jit_cache", {})
        # this engine's distinct per-family input avals == its jit cache
        # misses (the shared caches' _cache_size would count OTHER
        # engines' shapes too); compile_counts() reports these
        self._exec_shapes: Dict[str, set] = {
            fam: set() for fam in ServingObs.FAMILIES}
        # measure this sub-mesh's all-reduce latency ONCE at construction
        # (a few samples of the decode-step payload shape) — blocking on
        # a probe per step would measure device-queue time, not the
        # collective; the bench phase takes denser samples when asked
        if self._tp is not None and self._obs is not None:
            for dt in self._tp.collective_seconds(
                    samples=3, rows=self.max_batch_size):
                self._obs.tp_collective.observe(dt)

    # ----------------------------------------------------------- request API
    def add_request(self, prompt_ids, max_new_tokens: int = 32,
                    temperature: float = 0.0, top_k: int = 0,
                    top_p: float = 1.0, seed: Optional[int] = None,
                    eos_token_id: Optional[int] = None,
                    deadline_s: Optional[float] = None,
                    slo_class: Optional[str] = None) -> int:
        """Queue one prompt; returns a request id. Non-blocking — the
        request runs as `step()`/`stream()` turn the crank. ALL
        validation happens up front: a rejected request leaves no trace
        (no page allocation, no engine/scheduler registration). Raises
        `EngineOverloaded` when the bounded waiting queue
        (`max_waiting`) is full. `deadline_s` bounds the request's TOTAL
        latency from arrival: past it, a waiting request is expired
        before admission and a running one is cancelled at the next
        block boundary (terminal status "expired" either way).
        `slo_class` opts the request into per-class SLO accounting; it
        must name a class registered via the engine's `slo_classes=`."""
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0 (got {deadline_s})")
        if slo_class is not None and (
                self._slo is None or not self._slo.has_class(slo_class)):
            raise ValueError(
                f"unknown SLO class {slo_class!r}; register it via "
                "ServingEngine(slo_classes=[SloClass(...)])")
        if len(prompt) + max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_seq_len "
                f"{self.max_seq_len}")
        if not self.enable_chunked_prefill \
                and len(prompt) > self.prefill_buckets[-1]:
            # belt over the constructor's buckets-cover-max_seq_len check:
            # admitting this request would allocate pages and then blow up
            # in _bucket_for mid-prefill, leaking them. Chunked prefill
            # has no bucket ceiling — any prompt under max_seq_len runs
            # chunk by chunk
            raise ValueError(
                f"prompt length {len(prompt)} exceeds the largest "
                f"prefill bucket {self.prefill_buckets[-1]}")
        req = Request(prompt=prompt, max_new_tokens=max_new_tokens,
                      sampling=SamplingParams(temperature, top_k, top_p,
                                              seed),
                      eos_token_id=eos_token_id, slo_class=slo_class)
        if deadline_s is not None:
            req.deadline_t = req.arrival_t + deadline_s
        # scheduler.add validates the page budget and the bounded queue
        # and may raise (ValueError / EngineOverloaded) — only register
        # the request with the engine once it is accepted
        self.scheduler.add(req)
        self.requests[req.request_id] = req
        if deadline_s is not None:
            self._deadlined.add(req.request_id)
        if seed is None:
            seed = int(np.random.randint(0, 2 ** 31 - 1))
        self._key_state[req.request_id] = jax.random.key_data(
            jax.random.key(seed))
        if self._journal is not None:
            # the EFFECTIVE seed (drawn above when the caller passed
            # None) and a wall-clock deadline anchor go in the ledger:
            # both are what a post-crash rebuild continues from
            now_wall = time.time()
            self._journal.submit(
                request_id=req.request_id, prompt=prompt,
                max_new_tokens=max_new_tokens, temperature=temperature,
                top_k=top_k, top_p=top_p, seed=seed,
                eos_token_id=eos_token_id,
                deadline_wall=(now_wall + deadline_s
                               if deadline_s is not None else None),
                arrival_wall=now_wall)
        return req.request_id

    def output(self, request_id: int) -> List[int]:
        """prompt + generated tokens so far. For a preempted request the
        prompt absorbs already-generated tokens, so this is always the
        full sequence."""
        req = self.requests[request_id]
        return list(req.prompt) + list(req.generated)

    def status(self, request_id: int) -> Tuple[str, Optional[str]]:
        """(status, error) for one request — error is set only for
        status "failed" (the isolated failure, as text)."""
        req = self.requests[request_id]
        return req.status, req.error

    def cancel(self, request_id: int) -> bool:
        """Cancel a request in ANY state: waiting (dequeued before it
        ever runs), running (pages released through the refcounted path,
        so shared prefix pages survive for the other holders), or
        mid-decode-block with tokens in flight — the pending block is
        DRAINED first, so already-sampled tokens surface through the
        next `step()` and no dispatched computation keeps writing into
        released pages. Returns True if the request was live and is now
        "cancelled"; False for unknown/already-terminal ids (including a
        request whose in-flight tokens completed it during the drain)."""
        req = self.requests.get(request_id)
        if req is None or req.status in TERMINAL_STATUSES:
            return False
        if self._pending is not None \
                and request_id in self._pending["rids"]:
            # drain first: the in-flight block's tokens reach host state
            # (and the caller, via the spill queue) before teardown
            self._spill.extend(self._drain_pending())
            if req.status in TERMINAL_STATUSES:
                return False      # the drained tokens finished it
        return self._finalize(req, "cancelled")

    # ----------------------------------------------------------- resilience
    def _finalize(self, req: Request, status: str,
                  error: Optional[str] = None) -> bool:
        """Terminal transition through the scheduler (queues + refcounted
        page release) plus engine-side deadline bookkeeping. The journal
        records the terminal status here — the one place every
        failure-side ending (cancelled/expired/failed/shed) funnels
        through — so replay never resurrects a request that already
        ended."""
        done = self.scheduler.finalize(req, status, error=error)
        if done and self._journal is not None \
                and self._journal.known(req.request_id):
            self._journal.terminal(req.request_id, status, error)
        if self._deadlined:
            self._deadlined.discard(req.request_id)
        return done

    def _expire_and_shed(self) -> None:
        """Deadline/queue-wait sweep, run at the top of `step()` — i.e.
        at a block boundary — only while armed (some live request has a
        deadline, or `max_queue_wait_s` is set): waiting requests past
        their deadline expire and ones waiting longer than
        `max_queue_wait_s` are shed, both BEFORE admission can spend
        pages or a prefill on them; running requests past their deadline
        are cancelled here, draining any in-flight block first."""
        now = time.perf_counter()
        for req in list(self.scheduler.waiting):
            if req.deadline_t is not None and now >= req.deadline_t:
                self._finalize(req, "expired")
            elif self._max_queue_wait_s is not None and \
                    now - req.arrival_t >= self._max_queue_wait_s:
                self._finalize(req, "shed")
        expired = [r for r in self.scheduler.running
                   if r.deadline_t is not None and now >= r.deadline_t]
        if expired:
            if self._pending is not None:
                # block boundary discipline: surface in-flight tokens
                # and stop the device writing before releasing pages
                self._spill.extend(self._drain_pending())
            for req in expired:
                if req.status == "running":   # drain may have finished it
                    self._finalize(req, "expired")

    def _guarded_call(self, site: str, fn):
        """Failure-isolation wrapper for one jitted-dispatch or drain
        site: consults the fault injector (when bound), retries a
        TRANSIENT fault exactly once after `retry_backoff_s`, and
        otherwise returns the exception for the caller to quarantine
        with the right drain ordering. A FATAL fault (`is_fatal`) is
        re-raised untouched — the engine is the casualty, and retrying
        or quarantining would hide that from the supervisor. Every
        fault observed here bumps `fault_events` (the supervisor's
        fault-storm signal). Returns (result, None) on success,
        (None, exc) on isolation. The happy path runs no resilience
        code beyond one None check."""
        fi = self._faults
        try:
            if fi is not None:
                fi.check(site)
            return fn(), None
        except Exception as e:  # noqa: BLE001 — isolation boundary
            self.fault_events += 1
            if self._recorder is not None:
                self._recorder.record("fault", site=site,
                                      error=str(e), **describe_fault(e))
            if is_fatal(e):
                raise
            if not is_transient(e):
                return None, e
            if self._obs is not None:
                self._obs.retries.inc()
            if self.retry_backoff_s > 0:
                time.sleep(self.retry_backoff_s)
            try:
                if fi is not None:
                    fi.check(site)
                return fn(), None
            except Exception as e2:  # noqa: BLE001
                self.fault_events += 1
                if self._recorder is not None:
                    self._recorder.record("fault", site=site, retry=True,
                                          error=str(e2),
                                          **describe_fault(e2))
                if is_fatal(e2):
                    raise
                return None, e2

    def _quarantine(self, reqs: Sequence[Request], exc: BaseException,
                    site: str) -> None:
        """Isolate a failed dispatch/drain to exactly the implicated
        requests: status "failed" with the error recorded on each
        Request, pages released via refcounts, and the allocator +
        scheduler invariants re-audited so the survivors keep serving on
        a provably consistent pool. Any pending block belonging to the
        implicated set is discarded (its device carries are suspect and
        its writes target pages being released)."""
        err = f"{site}: {type(exc).__name__}: {exc}"
        rids = {r.request_id for r in reqs}
        if self._recorder is not None:
            self._recorder.record("quarantine", site=site, error=err,
                                  rids=sorted(rids))
        if self._pending is not None \
                and rids & set(self._pending["rids"]):
            rec, self._pending = self._pending, None
            for i, r in enumerate(rec["reqs"]):
                r.inflight = max(r.inflight - rec["incr"][i], 0)
        for req in reqs:
            if req.status not in TERMINAL_STATUSES:
                self._finalize(req, "failed", error=err)
        self.scheduler.check_consistency()
        if self._postmortem_dir is not None:
            # a quarantine is a casualty worth forensics even though the
            # engine survives: dump a bundle, but never let the dump
            # itself take the engine down
            try:
                self.dump_postmortem(f"quarantine-{site}")
            except Exception:  # noqa: BLE001 — forensics must not kill
                pass

    # ---------------------------------------------------------------- steps
    def step(self) -> List[Tuple[int, int]]:
        """One scheduler decision + (at most) one jitted dispatch.
        Returns the (request_id, token) pairs that reached the host this
        step — with a decode horizon and async overlap, a decode block's
        tokens surface one step AFTER its dispatch (the drain overlaps
        the next block's device time). This wrapper is also the crash
        recovery boundary: the injector's `device_lost` site fires here
        (fatal by default — it propagates untouched for the supervisor),
        and the step's returned events are journaled at this exact
        point, the host-visible delivery moment that exactly-once
        replay keys on."""
        fi = self._faults
        if fi is not None:
            try:
                fi.check("device_lost")
            except Exception as e:
                self.fault_events += 1
                if self._recorder is not None:
                    self._recorder.record("fault", site="device_lost",
                                          error=str(e),
                                          **describe_fault(e))
                raise
        events = self._step_impl()
        if self._journal is not None and events:
            self._journal_delivery(events)
        if self._slo is not None:
            self._slo.step_tick()
        return events

    def _step_impl(self) -> List[Tuple[int, int]]:
        if self._deadlined or self._max_queue_wait_s is not None:
            self._expire_and_shed()            # may spill drained tokens
        if not any(r.prefill_done for r in self.scheduler.running):
            # decode-stall gaps are only meaningful while some request
            # continuously WANTED decode steps; a wave boundary — or a
            # stretch where every running request is still mid-prefill
            # with nobody decode-ready — resets the gap clock
            self._last_decode_dispatch_t = None
        if self._pending is not None and self._pending.get("kind") == "spec":
            # A spec block's drain reverts its worst-case page charge
            # (`revert_spec_pages`), so it must run BEFORE schedule()
            # charges the NEXT block's worst case — draining after would
            # free pages the new block's table already needs covered,
            # silently sinking its KV writes into the null page. The
            # early drain costs nothing: spec blocks never chain on
            # device carries, so _spec_decode would sync here anyway.
            self._spill.extend(self._drain_pending())
        t_sched = time.perf_counter()
        decision = self.scheduler.schedule()   # drain_hook may spill here
        if self._obs is not None:
            self._obs.step_phase["schedule"].observe(
                time.perf_counter() - t_sched)
        if self._recorder is not None:
            self._recorder.record(
                "schedule", decision=decision.kind,
                prefill=(decision.prefill.request_id
                         if decision.prefill is not None else None),
                decode=len(decision.decode), chunks=len(decision.chunks))
        spilled, self._spill = self._spill, []
        if decision.kind == "prefill":
            return spilled + self._prefill(decision.prefill)
        if decision.kind == "decode":
            return spilled + self._decode_path(decision.decode)
        if decision.kind == "ragged":
            return spilled + self._ragged_step(decision)
        if decision.kind == "mixed":
            return spilled + self._mixed_step(decision)
        return spilled + self._drain_pending()

    def _mixed_step(self, decision) -> List[Tuple[int, int]]:
        """One chunked-prefill step: the decode block dispatches FIRST
        (async — its drain below overlaps the chunks' device time), then
        every scheduled chunk chains on the block's donated pools, so the
        device serializes decode-block -> chunks while the host runs
        ahead. One shared drain: the block's tokens surface through the
        ordinary pending-drain path; intermediate chunks sync nothing."""
        events: List[Tuple[int, int]] = []
        if decision.decode:
            events.extend(self._decode_path(decision.decode))
        elif self._pending is not None:
            # belt: every pending block's requests are running decoders,
            # so an empty decode batch should imply no pending block
            events.extend(self._drain_pending())
        for task in decision.chunks:
            if task.req.status != "running":
                continue    # finalized mid-step (cancel/expiry/fault)
            if task.start != task.req.num_computed_tokens:
                # stale extent: the request was preempted (and possibly
                # re-admitted with a fresh first chunk) after this task
                # was queued — its pages and cursor no longer match
                continue
            events.extend(self._chunk_prefill(task))
        return events

    def _journal_delivery(self, events: List[Tuple[int, int]]) -> None:
        """Append just-returned events to the journal — called at the
        single point tokens become host-visible to a `step()`/`stream()`
        consumer, never at drain time (a drained-but-unreturned token
        must stay recomputable, not re-deliverable). Consecutive
        same-request runs land as one block record; a request whose
        delivered stream just completed gets its `finished` terminal
        record here, after its tokens."""
        j = self._journal
        t_wall = time.time()
        i = 0
        while i < len(events):
            rid = events[i][0]
            k = i + 1
            while k < len(events) and events[k][0] == rid:
                k += 1
            if j.known(rid):
                j.tokens(rid, [t for _, t in events[i:k]], t_wall=t_wall)
            i = k
        for rid in dict.fromkeys(r for r, _ in events):
            if j.known(rid) and self.requests[rid].status == "finished":
                j.terminal(rid, "finished")

    def drain_all(self) -> List[Tuple[int, int]]:
        """Flush everything already computed out to the caller: spilled
        events (cancel/expiry drained them outside a step) plus the
        pending block — journaled exactly like a step's return."""
        spilled, self._spill = self._spill, []
        events = spilled + self._drain_pending()
        if self._journal is not None and events:
            self._journal_delivery(events)
        return events

    def stream(self):
        """Generator of (request_id, token, done) events until every
        queued request completes."""
        while (self.scheduler.has_work() or self._pending is not None
               or self._spill):
            if self.scheduler.has_work():
                events = self.step()
            else:
                # no schedulable work left: flush the spill plus the
                # pending block
                events = self.drain_all()
            for i, (rid, tok) in enumerate(events):
                done = (self.requests[rid].status == "finished"
                        and all(r != rid for r, _ in events[i + 1:]))
                yield rid, tok, done

    def run(self) -> Dict[int, List[int]]:
        """Drain all queued requests; returns request_id -> full tokens."""
        for _ in self.stream():
            pass
        return {rid: self.output(rid) for rid in self.requests}

    def _slots_of(self, reqs: Sequence[Request],
                  rows: Optional[int] = None) -> dict:
        """The `slots=` argument of a step over a model with recurrent
        layers: each row's state slot, the null slot for padding rows;
        nothing at all for every other model, whose executables are
        called as they always were."""
        if not self._has_state:
            return {}
        slots = [r.state_slot for r in reqs]
        slots += [None] * ((rows or len(slots)) - len(slots))
        return {"slots": self.cache.slot_array(slots)}

    def _note_exec(self, family: str, aval):
        """Record one step family's input aval. A NEW aval is a jit-cache
        miss, counted into the registry's compile-miss counter (the set
        stays the dedup structure, the registry holds the count), and
        the one dispatch that builds an executable: hold what this
        returns around it, a `serving.compile` span that charges the
        build to `family` (`key`: the aval's first entry, the bucket or
        the rows); for a known aval it is a context that does nothing."""
        shapes = self._exec_shapes[family]
        if aval in shapes:
            return compiles.BUILT
        shapes.add(aval)
        if self._obs is not None:
            self._obs.compile_miss[family].inc()
        return compiles.span("serving.compile", family, key=aval[0])

    # -------------------------------------------------------------- prefill
    def _bucket_for(self, n: int) -> int:
        for b in self.prefill_buckets:
            if b >= n:
                return b
        raise ValueError(f"prompt length {n} exceeds largest bucket")

    def _prefill_jit(self, bucket: int):
        # TP engines key per (tp degree, device subset) — the cache is
        # shared model-wide, and cluster replicas on different sub-meshes
        # must never exchange executables; tp_size=1 keys are UNCHANGED,
        # so this PR compiles the exact same executables as before
        tp = self._tp
        key = ("prefill", bucket) + (tp.jit_key if tp is not None else ())
        if key not in self._jit_cache:
            model = self.model if tp is None else tp.shard_model

            logits_at = self._logits_at

            def prefill(params, buffers, ids, pools, page_table, last_idx,
                        key_data, temps, top_ks, top_ps, slots=None):
                # `slots`: the row's state slot, passed (by name) over a
                # model with recurrent layers only
                views = views_from_pools(pools, page_table, slots=slots)
                kwargs = {"caches": views, "start_pos": 0}
                if logits_at:
                    # the model computes that position's logits alone
                    kwargs["logits_at"] = last_idx
                out, _ = call_functional(
                    model, params, buffers, (Tensor(ids),), kwargs=kwargs,
                    training=False)
                logits, new_views = out[0], out[1]
                last = logits[:, 0] if logits_at else (
                    jax.lax.dynamic_slice_in_dim(
                        logits, last_idx, 1, axis=1)[:, 0])
                key_data, subs = _split_rows(key_data)
                tok = _sample_batch(last, subs, temps, top_ks, top_ps)
                # a model with auxiliary outputs (out[2]) hands them on
                return (tok.astype(jnp.int32), key_data,
                        pools_from_views(new_views)) + tuple(out[2:])

            if tp is not None:
                prefill = tp.wrap_prefill_exec(prefill)
            self._jit_cache[key] = jax.jit(
                scopes.named(prefill, "prefill"), donate_argnums=(3,))
        return self._jit_cache[key]

    def _prefill_offset_jit(self, bucket: int):
        """The offset-aware prefill variant (prefix-cache hits): same
        bucket shapes, but start_pos is a TRACED scalar — the suffix
        tokens sit at positions offset..offset+bucket-1 and attend over
        the cached prefix pages through the page table. One extra
        executable per bucket, shared by every hit length."""
        tp = self._tp
        key = (("prefill_offset", bucket)
               + (tp.jit_key if tp is not None else ()))
        if key not in self._jit_cache:
            model = self.model if tp is None else tp.shard_model

            def prefill(params, buffers, ids, pools, page_table, last_idx,
                        offset, key_data, temps, top_ks, top_ps):
                views = views_from_pools(pools, page_table)
                (logits, new_views), _ = call_functional(
                    model, params, buffers, (Tensor(ids),),
                    kwargs={"caches": views, "start_pos": offset},
                    training=False)
                last = jax.lax.dynamic_slice_in_dim(
                    logits, last_idx, 1, axis=1)[:, 0]
                key_data, subs = _split_rows(key_data)
                tok = _sample_batch(last, subs, temps, top_ks, top_ps)
                return (tok.astype(jnp.int32), key_data,
                        pools_from_views(new_views))

            if tp is not None:
                prefill = tp.wrap_prefill_exec(prefill)
            self._jit_cache[key] = jax.jit(
                scopes.named(prefill, "prefill_offset"), donate_argnums=(3,))
        return self._jit_cache[key]

    def _emit(self, req: Request, token: int, now: float
              ) -> Tuple[int, int]:
        req.generated.append(token)
        o = self._obs
        if o is not None:
            o.tokens.inc()
        if req.first_token_t is None:
            req.first_token_t = now
            if o is not None:
                ttft = max(now - req.arrival_t, 0.0)
                o.ttft.observe(ttft)
                o.lifecycle.point(req.request_id, "first_token", now)
                if self._slo is not None:
                    self._slo.first_token(req.slo_class, ttft)
        req.last_token_t = now
        if req.is_done():
            req.finish_t = now
            self.scheduler.finish(req)   # obs.finished fires in there
        return (req.request_id, token)

    def _prefill(self, req: Request) -> List[Tuple[int, int]]:
        # prefix-cache hit: only the uncached suffix runs through the
        # model (bucketed on the SUFFIX length, so a long shared prompt
        # with a short question prefills in the smallest bucket)
        t_in = time.perf_counter()
        n_cached = req.cached_tokens
        suffix = req.prompt[n_cached:]
        bucket = self._bucket_for(len(suffix))
        family = "prefill_offset" if n_cached else "prefill"
        building = self._note_exec(
            family, (bucket, self.cache.num_pages, self.max_pages_per_seq))
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :len(suffix)] = suffix
        page_table = self.cache.page_table_array([req.pages],
                                                 self.max_pages_per_seq)
        sp = req.sampling
        # host arrays, sent with the call: made on the device each is
        # an operation of its own, dispatched with the device waiting
        knobs = (np.full((1,), sp.temperature, np.float32),
                 np.full((1,), sp.top_k, np.int32),
                 np.full((1,), sp.top_p, np.float32))
        key_data = self._key_state[req.request_id][None]

        def dispatch():
            aux = ()
            if n_cached:
                tok, new_kd, pools = self._prefill_offset_jit(bucket)(
                    self.params, self.buffers, ids,
                    self.cache.pools, page_table,
                    np.int32(len(suffix) - 1), np.int32(n_cached),
                    key_data, *knobs)
            else:
                tok, new_kd, pools, *aux = self._prefill_jit(bucket)(
                    self.params, self.buffers, ids,
                    self.cache.pools, page_table,
                    np.int32(len(suffix) - 1), key_data, *knobs,
                    **self._slots_of([req]))
            self.cache.pools = pools
            # the row's key, and a model's histogram, ride the token's
            # own transfer
            tok, kd, *hist = jax.device_get(  # noqa: HOST-SYNC — the prefill's one sync
                (tok, new_kd) + tuple(a["moe_expert_tokens"] for a in aux[:1]))
            self._key_state[req.request_id] = kd[0]
            if hist and self._obs is not None:
                self._obs.moe_block(hist[0], "prefill")
            return int(tok[0])

        t0 = time.perf_counter()
        if self._recorder is not None:
            self._recorder.record("dispatch", family=family,
                                  rid=req.request_id, tokens=len(suffix))
        with RecordEvent("serving.prefill", bucket=bucket,
                         prompt_tokens=len(suffix), rid=req.request_id):
            with building:
                token, err = self._guarded_call("dispatch", dispatch)
        if token is None:
            # isolate THIS request; any pending decode block belongs to
            # other (already-prefilled) requests and keeps flying
            self._quarantine([req], err, "prefill")
            return []
        req.num_computed_tokens = len(req.prompt)
        if self.prefix_cache is not None:
            # register the prompt's full pages for future reuse (the
            # partial last page never enters the tree); in-flight
            # requests can hit them immediately
            self.prefix_cache.insert(req.prompt, req.pages)
        now = time.perf_counter()
        o = self._obs
        prev_t = req.last_token_t            # set => this is a re-prefill
        if o is not None:
            o.prefill_steps.inc()
            if o.prefill_flash_steps is not None:
                o.prefill_flash(bucket, len(suffix))
            o.dispatched(np.float32(sp.temperature) == 0.0)
            o.host_syncs.inc()
            o.prefill_seconds.inc(now - t0)
            o.lifecycle.span(req.request_id, "prefill", t0, now)
            o.step_phase["assemble"].observe(t0 - t_in)
            # prefill's drain is fused into the dispatch (the sampled
            # token syncs inside it), so the whole span lands here
            o.step_phase["dispatch"].observe(now - t0)
        events = [self._emit(req, token, now)]
        if o is not None and prev_t is not None:
            # requeued request: the gap since its last pre-preemption
            # token is honest inter-token latency
            gap = max(now - prev_t, 0.0)
            o.inter_token.observe(gap)
            if self._slo is not None:
                self._slo.decode_tokens(req.slo_class, gap, 1)
        return events

    # ------------------------------------------------------ chunked prefill
    def _chunked_prefill_jit(self):
        """THE chunked-prefill executable — one per engine, not per
        bucket: ids are a fixed (1, prefill_chunk_tokens) window, the
        start offset and the valid length (via `last_idx`) are TRACED
        scalars, and attention reaches the earlier chunks' (and cached
        prefix's) K/V through the page table, exactly the machinery the
        prefix-cache offset prefill proved out. Every chunk of every
        prompt length shares this single compiled program; only its
        final chunk carries padding. The sampled token and split key are
        computed unconditionally (same trace for every chunk) but the
        host ADOPTS them only on the final chunk."""
        tp = self._tp
        key = (("prefill_chunked", self.prefill_chunk_tokens)
               + (tp.jit_key if tp is not None else ()))
        if key not in self._jit_cache:
            model = self.model if tp is None else tp.shard_model

            def prefill(params, buffers, ids, pools, page_table, last_idx,
                        offset, key_data, temps, top_ks, top_ps):
                views = views_from_pools(pools, page_table)
                (logits, new_views), _ = call_functional(
                    model, params, buffers, (Tensor(ids),),
                    kwargs={"caches": views, "start_pos": offset},
                    training=False)
                last = jax.lax.dynamic_slice_in_dim(
                    logits, last_idx, 1, axis=1)[:, 0]
                key_data, subs = _split_rows(key_data)
                tok = _sample_batch(last, subs, temps, top_ks, top_ps)
                return (tok.astype(jnp.int32), key_data,
                        pools_from_views(new_views))

            if tp is not None:
                prefill = tp.wrap_prefill_exec(prefill)
            self._jit_cache[key] = jax.jit(
                scopes.named(prefill, "prefill_chunk"), donate_argnums=(3,))
        return self._jit_cache[key]

    def _chunk_prefill(self, task) -> List[Tuple[int, int]]:
        """Dispatch one scheduled prefill chunk. Intermediate chunks
        write K/V and return WITHOUT a host sync (their sampled token is
        discarded and the per-request key state stays untouched — one
        key split per emitted token keeps streams bit-identical to
        unchunked); the final chunk adopts the sampled first token,
        exactly like the tail of `_prefill`. Padding lanes inside the
        chunk are harmless by construction: sub-prompt padding is
        overwritten by the next chunk before anything reads it, tail
        padding past the prompt is overwritten by the first decode
        steps, and positions past the page table's capacity route to
        the null page."""
        t_in = time.perf_counter()
        req, start, n = task.req, task.start, task.length
        rid = req.request_id
        chunk = self.prefill_chunk_tokens
        final = task.is_final
        building = self._note_exec("prefill_chunked",
                                   (chunk, self.cache.num_pages,
                                    self.max_pages_per_seq))
        ids = np.zeros((1, chunk), np.int32)
        ids[0, :n] = req.prompt[start:start + n]
        page_table = self.cache.page_table_array([req.pages],
                                                 self.max_pages_per_seq)
        sp = req.sampling
        knobs = (jnp.asarray([sp.temperature], jnp.float32),
                 jnp.asarray([sp.top_k], jnp.int32),
                 jnp.asarray([sp.top_p], jnp.float32))
        key_data = self._key_state[rid][None]

        def dispatch():
            tok, new_kd, pools = self._chunked_prefill_jit()(
                self.params, self.buffers, jnp.asarray(ids),
                self.cache.pools, page_table, jnp.int32(n - 1),
                jnp.int32(start), key_data, *knobs)
            self.cache.pools = pools
            if not final:
                return PAD_TOKEN          # async: no host round-trip
            self._key_state[rid] = new_kd[0]
            return int(np.asarray(tok)[0])

        t0 = time.perf_counter()
        if self._recorder is not None:
            self._recorder.record("dispatch", family="prefill_chunk",
                                  rid=rid, tokens=n, final=final)
        with RecordEvent("serving.prefill_chunk"):
            with building:
                token, err = self._guarded_call("dispatch", dispatch)
        if token is None:
            # fault mid-chunk: quarantine ONLY this request — the cursor
            # never advanced, so finalize releases exactly its
            # chunk-to-date pages; the decode block and its peers'
            # chunks keep flying (their pools/pages are disjoint)
            self._quarantine([req], err, "prefill_chunk")
            return []
        req.num_computed_tokens = start + n
        now = time.perf_counter()
        o = self._obs
        if o is not None:
            o.prefill_chunks.inc()
            o.dispatched(np.float32(sp.temperature) == 0.0)
            o.prefill_seconds.inc(now - t0)
            # profiler-only spans for intermediate chunks (retained
            # lifecycle lists must not grow per chunk); the final chunk
            # is the retained "prefill" stage
            o.lifecycle.span(rid, "prefill", t0, now, retain=final)
            o.step_phase["assemble"].observe(t0 - t_in)
            o.step_phase["dispatch"].observe(now - t0)
        if not final:
            return []
        if self.prefix_cache is not None:
            self.prefix_cache.insert(req.prompt, req.pages)
        prev_t = req.last_token_t            # set => this is a re-prefill
        if o is not None:
            o.prefill_steps.inc()
            o.host_syncs.inc()
        events = [self._emit(req, token, now)]
        if o is not None and prev_t is not None:
            gap = max(now - prev_t, 0.0)
            o.inter_token.observe(gap)
            if self._slo is not None:
                self._slo.decode_tokens(req.slo_class, gap, 1)
        return events

    # ---------------------------------------------------------- ragged step
    def _ragged_jit(self, t_bucket: int):
        """ONE executable for a whole mixed step, keyed on the flat
        token bucket: iteration 0 is a single flat (1, T) forward
        carrying every row's input tokens — each decode row's one token
        AND every prefill chunk's extent, routed through their own
        page-table rows by the ragged attention path — followed by the
        decode block's usual (horizon-1)-iteration lax.scan over the
        decode rows. Sampling/EOS/budget masking after the flat forward
        is the decode body's own arithmetic on per-row gathers, so
        decode streams are bit-identical to the chained block; a final
        chunk is a row with an emit budget of 1 (its sampled first
        token, one key split, then it parks), an intermediate chunk a
        row with budget 0 (writes K/V, emits PAD, keeps its key).
        Per-row key-state selection happens IN the executable
        (scan-carried for decode rows, the iteration-0 split for final
        chunks, the untouched input for everything else), so the drain's
        blanket key adoption stays correct for every row class."""
        tp = self._tp
        key = (("ragged", t_bucket, self.decode_horizon,
                self.max_batch_size, self.page_size)
               + (tp.jit_key if tp is not None else ()))
        if key not in self._jit_cache:
            model = self.model if tp is None else tp.shard_model
            page_size = self.page_size
            horizon = self.decode_horizon

            def ragged_block(params, buffers, flat_ids, pools,
                             page_tables, flat_pos, row_ids, last_idx,
                             tokens, positions, key_data, temps, top_ks,
                             top_ps, eos_ids, remaining, decode_mask,
                             final_mask):
                max_pages = page_tables.shape[1]
                key_in = key_data
                views = views_from_pools(pools, page_tables, row_ids)
                (logits, new_views), _ = call_functional(
                    model, params, buffers, (Tensor(flat_ids),),
                    kwargs={"caches": views, "start_pos": flat_pos},
                    training=False)
                pools = pools_from_views(new_views)
                # iteration-0 postlude == the decode body's arithmetic,
                # with each row's logits gathered from its last flat
                # token
                key_data, subs = _split_rows(key_data)
                key_split1 = key_data
                nxt = _sample_batch(logits[0, last_idx], subs, temps,
                                    top_ks, top_ps).astype(jnp.int32)
                alive = remaining > 0
                hit_eos = alive & (eos_ids >= 0) & (nxt == eos_ids)
                emit0 = jnp.where(alive, nxt, jnp.int32(PAD_TOKEN))
                remaining = jnp.where(alive, remaining - 1, remaining)
                remaining = jnp.where(hit_eos, jnp.int32(0), remaining)
                tokens = jnp.where(alive, nxt, tokens)
                positions = advance_positions(
                    positions, remaining > 0, max_pages, page_size)

                def body(carry, _):
                    tokens, pools, positions, key_data, remaining = carry
                    views = views_from_pools(pools, page_tables)
                    (logits, new_views), _ = call_functional(
                        model, params, buffers, (Tensor(tokens[:, None]),),
                        kwargs={"caches": views, "start_pos": positions},
                        training=False)
                    pools = pools_from_views(new_views)
                    key_data, subs = _split_rows(key_data)
                    nxt = _sample_batch(logits[:, 0], subs, temps,
                                        top_ks, top_ps).astype(jnp.int32)
                    alive = remaining > 0
                    hit_eos = alive & (eos_ids >= 0) & (nxt == eos_ids)
                    emit = jnp.where(alive, nxt, jnp.int32(PAD_TOKEN))
                    remaining = jnp.where(alive, remaining - 1, remaining)
                    remaining = jnp.where(hit_eos, jnp.int32(0), remaining)
                    tokens = jnp.where(alive, nxt, tokens)
                    positions = advance_positions(
                        positions, remaining > 0, max_pages, page_size)
                    return (tokens, pools, positions, key_data,
                            remaining), emit

                carry = (tokens, pools, positions, key_data, remaining)
                (tokens, pools, positions, key_data, remaining), rest = \
                    jax.lax.scan(body, carry, None, length=horizon - 1)
                emitted = jnp.concatenate(
                    [emit0[:, None], jnp.transpose(rest)], axis=1)
                key_out = jnp.where(
                    decode_mask[:, None], key_data,
                    jnp.where(final_mask[:, None], key_split1, key_in))
                return emitted, pools, key_out

            if tp is not None:
                ragged_block = tp.wrap_ragged_exec(ragged_block)
            self._jit_cache[key] = jax.jit(
                scopes.named(ragged_block, "ragged_block"),
                donate_argnums=(3,))
        return self._jit_cache[key]

    def _ragged_step(self, decision) -> List[Tuple[int, int]]:
        """One flat ragged step: the whole mixed step — the decode
        rows' horizon block AND every scheduled chunk — is a single
        jitted dispatch (N+1 chained dispatches before). Flat inputs
        are built from host request state, so any pending block drains
        FIRST (a ragged step never chains on device carries); async
        overlap is preserved in the other direction — the record this
        step leaves behind drains under the next step's device time.
        A final chunk's sampled token therefore surfaces at the next
        drain instead of synchronously, one step later than the chained
        path; stream CONTENT is unchanged."""
        events = self._drain_pending()
        t_in = time.perf_counter()      # assemble starts after the drain
        decode = [r for r in decision.decode if r.status == "running"]
        chunks = [t for t in decision.chunks
                  if t.req.status == "running"
                  and t.start == t.req.num_computed_tokens]
        if not chunks:
            # every chunk went stale (finalized/preempted during the
            # drain): fall through to the plain decode pipeline
            return events + (self._decode_path(decode) if decode else [])
        spec_on = self.spec_config is not None
        L = self._spec_lookahead
        # a spec ragged step's decode rows can emit 1 (iteration 0) +
        # (horizon-1) × (1+lookahead) tokens; the in-flight bound (the
        # only thing build_ragged_inputs' horizon feeds) scales with it
        cap_horizon = (1 + (self.decode_horizon - 1) * (1 + L)
                       if spec_on else self.decode_horizon)
        batch = build_ragged_inputs(
            decode, chunks, buckets=self.token_buckets,
            max_batch=self.max_batch_size, horizon=cap_horizon,
            page_size=self.page_size, max_pages=self.max_pages_per_seq)
        if batch is None:
            return events
        building = self._note_exec("spec" if spec_on else "ragged",
                                   (batch.t_bucket, self.max_batch_size,
                                    self.decode_horizon, L,
                                    self.cache.num_pages,
                                    self.max_pages_per_seq))
        page_tables = self.cache.page_table_array(
            batch.page_lists, self.max_pages_per_seq)
        kds = [self._key_state[r.request_id] for r in batch.reqs]
        kds.extend([jnp.zeros((2,), jnp.uint32)]
                   * (self.max_batch_size - len(batch.reqs)))
        key_data = jnp.stack(kds)
        rids = tuple(r.request_id for r in batch.reqs)
        if spec_on:
            # drafts for the decode rows only (rows 0..d-1 of the flat
            # batch); chunk rows stay PAD — a final chunk emits its one
            # iteration-0 token and parks, so drafts could never land
            dbuf = self._spec_mod.build_draft_buffer(
                decode, self.max_batch_size,
                self.decode_horizon * (1 + L), self.spec_config,
                self.prefix_cache)

        def dispatch():
            if spec_on:
                out = self._spec_ragged_jit(batch.t_bucket)(
                    self.params, self.buffers,
                    jnp.asarray(batch.flat_ids), self.cache.pools,
                    page_tables, jnp.asarray(dbuf),
                    jnp.asarray(batch.flat_pos),
                    jnp.asarray(batch.row_ids),
                    jnp.asarray(batch.last_idx),
                    jnp.asarray(batch.tokens),
                    jnp.asarray(batch.positions), key_data,
                    jnp.asarray(batch.temps), jnp.asarray(batch.top_ks),
                    jnp.asarray(batch.top_ps),
                    jnp.asarray(batch.eos_ids),
                    jnp.asarray(batch.remaining),
                    jnp.asarray(batch.decode_mask),
                    jnp.asarray(batch.final_mask))
            else:
                out = self._ragged_jit(batch.t_bucket)(
                    self.params, self.buffers,
                    jnp.asarray(batch.flat_ids), self.cache.pools,
                    page_tables, jnp.asarray(batch.flat_pos),
                    jnp.asarray(batch.row_ids),
                    jnp.asarray(batch.last_idx),
                    jnp.asarray(batch.tokens),
                    jnp.asarray(batch.positions), key_data,
                    jnp.asarray(batch.temps), jnp.asarray(batch.top_ks),
                    jnp.asarray(batch.top_ps),
                    jnp.asarray(batch.eos_ids),
                    jnp.asarray(batch.remaining),
                    jnp.asarray(batch.decode_mask),
                    jnp.asarray(batch.final_mask))
            self.cache.pools = out[1]
            return out

        t0 = time.perf_counter()
        if self._recorder is not None:
            self._recorder.record("dispatch", family="ragged",
                                  rows=len(batch.reqs),
                                  decode=len(decode), chunks=len(chunks),
                                  t_bucket=batch.t_bucket)
        with RecordEvent("serving.ragged_step"):
            with building:
                out, err = self._guarded_call("dispatch", dispatch)
        if out is None:
            # one dispatch carries every row, so a fault implicates the
            # whole step's requests — coarser than the chained path's
            # per-site isolation, the price of sharing one executable
            self._quarantine(
                [r for r in batch.reqs if r.status == "running"], err,
                "ragged")
            return events
        emitted, pools, key_out = out[0], out[1], out[2]
        for req, n in zip(batch.reqs, batch.incr):
            req.inflight += n
        now = time.perf_counter()
        o = self._obs
        for task in chunks:
            req = task.req
            req.num_computed_tokens = task.start + task.length
            if o is not None:
                o.prefill_chunks.inc()
                o.lifecycle.span(req.request_id, "prefill", t0, now,
                                 retain=task.is_final)
            if task.is_final:
                # pages are complete once this dispatch lands; later
                # dispatches ordering behind it through the donated
                # pools may share them immediately
                if self.prefix_cache is not None:
                    self.prefix_cache.insert(req.prompt, req.pages)
                if o is not None:
                    o.prefill_steps.inc()
        if o is not None:
            o.ragged_steps.inc()
            o.dispatched(not batch.temps.any())
            o.step_phase["assemble"].observe(t0 - t_in)
            o.step_phase["dispatch"].observe(now - t0)
            if decode:
                o.decode_steps.inc()
                if self._last_decode_dispatch_t is not None:
                    o.decode_stall.observe(
                        max(t0 - self._last_decode_dispatch_t, 0.0))
        if decode:
            self._last_decode_dispatch_t = t0
        if decode or any(t.is_final for t in chunks):
            self._pending = {
                "kind": "ragged", "rids": rids, "reqs": list(batch.reqs),
                "incr": list(batch.incr), "emitted": emitted,
                "key_data": key_out, "t0": t0,
            }
            if spec_on:
                self._pending["spec_stats"] = out[3]
                self._pending["windows"] = (
                    (1,) + (L + 1,) * (self.decode_horizon - 1))
        # else: intermediate chunks only — nothing can emit and no key
        # state moved, so dropping the record outright saves a drain
        # (and its host sync) that would deliver zero tokens
        return events

    # --------------------------------------------------------------- decode
    def _decode_block_jit(self, horizon: int):
        """ONE fused decode+sample executable per horizon: N model steps
        + sampling + EOS/budget masking + position advance inside one
        jitted lax.scan. Returns the (b, N) emitted block plus the
        device carries (tokens/positions/keys/budgets) the next chained
        block consumes without a host round-trip."""
        tp = self._tp
        key = ("decode", horizon) + (tp.jit_key if tp is not None else ())
        if key not in self._jit_cache:
            model = self.model if tp is None else tp.shard_model
            page_size = self.page_size

            def decode_block(params, buffers, tokens, pools, page_tables,
                             positions, key_data, temps, top_ks, top_ps,
                             eos_ids, remaining, slots=None):
                max_pages = page_tables.shape[1]

                def body(carry, _):
                    tokens, pools, positions, key_data, remaining = carry
                    views = views_from_pools(pools, page_tables,
                                             slots=slots)
                    out, _ = call_functional(
                        model, params, buffers, (Tensor(tokens[:, None]),),
                        kwargs={"caches": views, "start_pos": positions},
                        training=False)
                    logits, new_views = out[0], out[1]
                    pools = pools_from_views(new_views)
                    key_data, subs = _split_rows(key_data)
                    nxt = _sample_batch(logits[:, 0], subs, temps,
                                        top_ks, top_ps).astype(jnp.int32)
                    alive = remaining > 0
                    hit_eos = alive & (eos_ids >= 0) & (nxt == eos_ids)
                    emit = jnp.where(alive, nxt, jnp.int32(PAD_TOKEN))
                    remaining = jnp.where(alive, remaining - 1, remaining)
                    remaining = jnp.where(hit_eos, jnp.int32(0), remaining)
                    tokens = jnp.where(alive, nxt, tokens)
                    positions = advance_positions(
                        positions, remaining > 0, max_pages, page_size)
                    # a model's auxiliary outputs (out[2]) stack over the
                    # block's steps beside the tokens
                    return (tokens, pools, positions, key_data,
                            remaining), (emit,) + tuple(out[2:])

                carry = (tokens, pools, positions, key_data, remaining)
                (tokens, pools, positions, key_data, remaining), \
                    (emitted, *aux) = jax.lax.scan(
                        body, carry, None, length=horizon)
                return (jnp.transpose(emitted), pools, tokens, positions,
                        key_data, remaining) + tuple(aux)

            if tp is not None:
                decode_block = tp.wrap_decode_exec(decode_block)
            self._jit_cache[key] = jax.jit(
                scopes.named(decode_block, "decode_block"),
                donate_argnums=(3,))
        return self._jit_cache[key]

    def _decode_rows(self, n: int) -> int:
        """Dispatched decode row count: the next power of two >= n,
        capped at max_batch_size — a 2-request batch stops paying a
        full max_batch-row step. Chained blocks stay consistent for
        free: chaining requires identical rids, hence identical n."""
        b = 1
        while b < n:
            b *= 2
        return min(b, self.max_batch_size)

    def _decode(self, reqs: Sequence[Request]) -> List[Tuple[int, int]]:
        t_in = time.perf_counter()
        reqs = [r for r in reqs if r.status == "running"]
        if not reqs:
            return self._drain_pending()
        h = self.decode_horizon
        rids = tuple(r.request_id for r in reqs)
        events_prev: List[Tuple[int, int]] = []
        prev = self._pending
        if prev is not None and (prev.get("kind", "decode") != "decode"
                                 or prev["rids"] != rids):
            # batch composition changed (admission/finish/preemption),
            # or the pending record is a ragged step (its carries are
            # per-ROW-class and must never seed a decode chain): sync
            # and go fresh
            events_prev = self._drain_pending()
            reqs = [r for r in reqs if r.status == "running"]
            if not reqs:
                return events_prev
            rids = tuple(r.request_id for r in reqs)
            prev = None
        b = self._decode_rows(len(reqs))
        building = self._note_exec(
            "decode", (b, h, self.cache.num_pages, self.max_pages_per_seq))
        page_lists: List[Sequence[int]] = [()] * b
        for i, req in enumerate(reqs):
            page_lists[i] = req.pages
        page_tables = self.cache.page_table_array(page_lists,
                                                  self.max_pages_per_seq)
        if prev is None:
            # fresh block: inputs from (drained, accurate) host state
            park = overflow_position(self.max_pages_per_seq,
                                     self.page_size)
            tokens = np.zeros((b,), np.int32)
            positions = np.full((b,), park, np.int32)
            remaining = np.zeros((b,), np.int32)
            temps = np.zeros((b,), np.float32)
            top_ks = np.zeros((b,), np.int32)
            top_ps = np.ones((b,), np.float32)
            eos_ids = np.full((b,), PAD_TOKEN, np.int32)
            kds = []
            for i, req in enumerate(reqs):
                tokens[i] = (req.generated[-1] if req.generated
                             else req.prompt[-1])
                # the input token's K/V lands at its own position; the
                # step predicts the token after it
                positions[i] = req.num_tokens - 1
                remaining[i] = req.max_new_tokens - len(req.generated)
                sp = req.sampling
                temps[i], top_ks[i], top_ps[i] = (sp.temperature,
                                                  sp.top_k, sp.top_p)
                if req.eos_token_id is not None:
                    eos_ids[i] = req.eos_token_id
                kds.append(self._key_state[req.request_id])
            kds.extend([np.zeros((2,), np.uint32)] * (b - len(reqs)))
            knobs = (jnp.asarray(temps), jnp.asarray(top_ks),
                     jnp.asarray(top_ps), jnp.asarray(eos_ids))
            greedy = not temps.any()
            tokens = jnp.asarray(tokens)
            positions = jnp.asarray(positions)
            remaining = jnp.asarray(remaining)
            # the keys are host rows (`_key_state`), stacked there and
            # sent once: a stack on the device is an operation a row,
            # dispatched while the device waits
            key_data = jnp.asarray(np.stack(kds))
        else:
            # chained block: consume the pending block's device carries —
            # no host sync anywhere on this path
            tokens, positions = prev["tokens"], prev["positions"]
            key_data, remaining = prev["key_data"], prev["remaining"]
            knobs, greedy = prev["knobs"], prev["greedy"]
        # in-flight accounting: the block may add up to min(h, budget)
        # tokens per row before the host sees them; _ensure_decode_pages
        # reserves against this bound before the NEXT block (applied
        # only once the dispatch actually succeeds)
        incr = []
        for req in reqs:
            cap = req.max_new_tokens - len(req.generated) - req.inflight
            incr.append(max(min(h, cap), 0))
        live = sum(1 for n in incr if n > 0)

        def dispatch():
            out = self._decode_block_jit(h)(
                self.params, self.buffers, tokens, self.cache.pools,
                page_tables, positions, key_data, *knobs, remaining,
                **self._slots_of(reqs, b))
            self.cache.pools = out[1]
            return out

        t0 = time.perf_counter()
        if self._recorder is not None:
            self._recorder.record("dispatch", family="decode",
                                  rows=len(reqs), horizon=h)
        attrs = ({"state_slots": self.cache.slot_allocator.num_used}
                 if self._has_state else {})
        if self._obs is not None and self._obs.dsa_keys_selected is not None:
            attrs["keys_selected"] = self._obs.dsa_block(
                [r.num_tokens + r.inflight for r in reqs], incr)
        with RecordEvent("serving.decode_block", rows=live,
                         rows_dispatched=b, horizon=h, **attrs):
            with building:
                out, err = self._guarded_call("dispatch", dispatch)
        if out is None:
            # a decode dispatch implicates the whole batch. Drain the
            # previous block FIRST (its tokens are sound and its writes
            # must stop before pages are released), then isolate
            # whatever is still running
            ev = self._drain_pending()
            self._quarantine(
                [r for r in reqs if r.status == "running"], err,
                "decode")
            return events_prev + ev
        emitted, pools, tokens, positions, key_data, remaining, *aux = out
        for req, n in zip(reqs, incr):
            req.inflight += n
        if self._obs is not None:
            t1 = time.perf_counter()
            self._obs.step_phase["assemble"].observe(t0 - t_in)
            self._obs.step_phase["dispatch"].observe(t1 - t0)
            self._obs.decode_steps.inc()
            self._obs.dispatched(greedy)
            self._obs.decode_rows_live.inc(live)
            self._obs.decode_rows_dispatched.inc(b)
            if self._last_decode_dispatch_t is not None:
                # dispatch-to-dispatch gap while requests were running:
                # whatever kept the engine away from decode (a prefill,
                # scheduling, host work) shows up here
                self._obs.decode_stall.observe(
                    max(t0 - self._last_decode_dispatch_t, 0.0))
        self._last_decode_dispatch_t = t0
        self._pending = {
            "kind": "decode",
            "rids": rids, "reqs": list(reqs), "incr": incr,
            "emitted": emitted, "tokens": tokens, "positions": positions,
            "key_data": key_data, "remaining": remaining, "knobs": knobs,
            "greedy": greedy, "t0": t0,
        }
        if aux:
            self._pending["moe_hist"] = aux[0]["moe_expert_tokens"]
        if prev is not None:
            # async overlap: block k+1 is dispatched and running; pulling
            # block k's tokens now costs (at most) the device time block
            # k+1 is already spending
            return events_prev + self._drain_record(prev)
        return events_prev

    # --------------------------------------------------------- speculative
    def _decode_path(self, reqs: Sequence[Request]) -> List[Tuple[int, int]]:
        """Route a decode batch to the speculative block when spec is
        on; the spec-off path is the unchanged `_decode` (byte-identical
        streams, zero spec code executed)."""
        if self.spec_config is not None:
            return self._spec_decode(reqs)
        return self._decode(reqs)

    def _spec_block_jit(self, horizon: int):
        """ONE fused speculative decode-block executable per (horizon,
        lookahead): `horizon` verify windows, each a (b, 1+lookahead)
        target pass + on-device rejection sampling + the decode body's
        EOS/budget masking (spec.make_spec_decode_fn)."""
        tp = self._tp
        L = self.spec_config.lookahead
        key = (("spec", horizon, L, self.page_size)
               + (tp.jit_key if tp is not None else ()))
        if key not in self._jit_cache:
            model = self.model if tp is None else tp.shard_model
            fn = self._spec_mod.make_spec_decode_fn(
                model, horizon=horizon, lookahead=L,
                page_size=self.page_size)
            if tp is not None:
                fn = tp.wrap_spec_exec(fn)
            self._jit_cache[key] = jax.jit(
                scopes.named(fn, "spec_decode_block"), donate_argnums=(3,))
        return self._jit_cache[key]

    def _spec_ragged_jit(self, t_bucket: int):
        """The ragged mixed-step executable with speculation fused in:
        iteration 0 is the plain flat forward (chunk rows need it),
        the remaining horizon-1 iterations are verify windows over the
        decode rows (spec.make_spec_ragged_fn)."""
        tp = self._tp
        L = self.spec_config.lookahead
        key = (("spec_ragged", t_bucket, self.decode_horizon, L,
                self.max_batch_size, self.page_size)
               + (tp.jit_key if tp is not None else ()))
        if key not in self._jit_cache:
            model = self.model if tp is None else tp.shard_model
            fn = self._spec_mod.make_spec_ragged_fn(
                model, horizon=self.decode_horizon, lookahead=L,
                page_size=self.page_size)
            if tp is not None:
                fn = tp.wrap_spec_ragged_exec(fn)
            self._jit_cache[key] = jax.jit(
                scopes.named(fn, "spec_ragged_block"), donate_argnums=(3,))
        return self._jit_cache[key]

    def _spec_decode(self, reqs: Sequence[Request]) -> List[Tuple[int, int]]:
        """Speculative decode block (ISSUE 17). Structurally `_decode`
        with two differences: drafts are proposed from HOST request
        state, so the pending block always drains FIRST — spec blocks
        never chain on device carries (async overlap is preserved in
        the other direction: this block's record drains under the NEXT
        dispatch) — and the block can emit up to horizon×(1+lookahead)
        tokens per row, whose worst-case page charge the drain reverts
        down to actual acceptance via `revert_spec_pages`."""
        events = self._drain_pending()
        t_in = time.perf_counter()
        reqs = [r for r in reqs if r.status == "running"]
        if not reqs:
            return events
        h = self.decode_horizon
        L = self.spec_config.lookahead
        cap_tokens = h * (1 + L)
        rids = tuple(r.request_id for r in reqs)
        b = self._decode_rows(len(reqs))
        building = self._note_exec("spec", (b, h, L, self.cache.num_pages,
                                            self.max_pages_per_seq))
        page_lists: List[Sequence[int]] = [()] * b
        for i, req in enumerate(reqs):
            page_lists[i] = req.pages
        page_tables = self.cache.page_table_array(page_lists,
                                                  self.max_pages_per_seq)
        park = overflow_position(self.max_pages_per_seq, self.page_size)
        tokens = np.zeros((b,), np.int32)
        positions = np.full((b,), park, np.int32)
        remaining = np.zeros((b,), np.int32)
        temps = np.zeros((b,), np.float32)
        top_ks = np.zeros((b,), np.int32)
        top_ps = np.ones((b,), np.float32)
        eos_ids = np.full((b,), PAD_TOKEN, np.int32)
        kds = []
        for i, req in enumerate(reqs):
            tokens[i] = (req.generated[-1] if req.generated
                         else req.prompt[-1])
            positions[i] = req.num_tokens - 1
            remaining[i] = req.max_new_tokens - len(req.generated)
            sp = req.sampling
            temps[i], top_ks[i], top_ps[i] = (sp.temperature,
                                              sp.top_k, sp.top_p)
            if req.eos_token_id is not None:
                eos_ids[i] = req.eos_token_id
            kds.append(self._key_state[req.request_id])
        kds.extend([jnp.zeros((2,), jnp.uint32)] * (b - len(reqs)))
        # drafts ride in as one (b, cap) PAD-padded buffer; each verify
        # window slides its per-row cursor by the emitted count
        dbuf = self._spec_mod.build_draft_buffer(
            reqs, b, cap_tokens, self.spec_config, self.prefix_cache)
        incr = []
        for req in reqs:
            cap = req.max_new_tokens - len(req.generated) - req.inflight
            incr.append(max(min(cap_tokens, cap), 0))
        live = sum(1 for n in incr if n > 0)

        def dispatch():
            out = self._spec_block_jit(h)(
                self.params, self.buffers, jnp.asarray(tokens),
                self.cache.pools, page_tables, jnp.asarray(dbuf),
                jnp.asarray(positions), jnp.stack(kds),
                jnp.asarray(temps), jnp.asarray(top_ks),
                jnp.asarray(top_ps), jnp.asarray(eos_ids),
                jnp.asarray(remaining))
            self.cache.pools = out[1]
            return out

        t0 = time.perf_counter()
        if self._recorder is not None:
            self._recorder.record("dispatch", family="spec",
                                  rows=len(reqs), horizon=h, lookahead=L)
        with RecordEvent("serving.spec_block"):
            with building:
                out, err = self._guarded_call("dispatch", dispatch)
        if out is None:
            self._quarantine(
                [r for r in reqs if r.status == "running"], err, "spec")
            return events
        emitted, _pools, _tok, _pos, key_data, _rem, sstats = out
        for req, n in zip(reqs, incr):
            req.inflight += n
        if self._obs is not None:
            t1 = time.perf_counter()
            self._obs.step_phase["assemble"].observe(t0 - t_in)
            self._obs.step_phase["dispatch"].observe(t1 - t0)
            self._obs.decode_steps.inc()
            self._obs.dispatched(not temps.any())
            self._obs.decode_rows_live.inc(live)
            self._obs.decode_rows_dispatched.inc(b)
            if self._last_decode_dispatch_t is not None:
                self._obs.decode_stall.observe(
                    max(t0 - self._last_decode_dispatch_t, 0.0))
        self._last_decode_dispatch_t = t0
        self._pending = {
            "kind": "spec", "rids": rids, "reqs": list(reqs),
            "incr": incr, "emitted": emitted, "key_data": key_data,
            "spec_stats": sstats, "windows": (L + 1,) * h, "t0": t0,
        }
        return events

    # ---------------------------------------------------------------- drain
    def _drain_for_scheduler(self) -> None:
        """Scheduler drain_hook: the emitted events surface through
        step()'s spill queue so callers still see every token."""
        self._spill.extend(self._drain_pending())

    def _drain_pending(self) -> List[Tuple[int, int]]:
        rec, self._pending = self._pending, None
        if rec is None:
            return []
        return self._drain_record(rec)

    def _drain_record(self, rec: dict) -> List[Tuple[int, int]]:
        """THE host sync: pull one block's (b, N) token buffer, append
        per-request tokens trimmed at EOS/budget (device already masked
        past-the-end steps to PAD), finish requests, refresh per-request
        key state from the block's device carries."""
        o = self._obs
        t_in = time.perf_counter()
        sstats = rec.get("spec_stats")
        windows = rec.get("windows")
        moe_hist = rec.get("moe_hist")
        with RecordEvent("serving.host_drain"):
            # the rows' keys come back with the tokens, and with them a
            # model's expert histogram or a spec block's accept counters
            extra = moe_hist if moe_hist is not None else sstats
            pulled, err = self._guarded_call(
                "drain", lambda: jax.device_get((rec["emitted"], rec["key_data"], extra)))  # noqa: HOST-SYNC — THE one sync per block, in a single transfer (PR 3 contract)
            toks, kd, extra = (pulled if pulled is not None
                               else (None, None, None))
            if moe_hist is not None:
                moe_hist = extra
            else:
                sstats = extra
        if toks is None:
            # the block's tokens are unrecoverable: give back the
            # in-flight reservation and isolate exactly the block's
            # still-running requests (rec is already detached from
            # self._pending, so teardown releases pages directly)
            for i, req in enumerate(rec["reqs"]):
                req.inflight = max(req.inflight - rec["incr"][i], 0)
            self._quarantine(
                [r for r in rec["reqs"] if r.status == "running"], err,
                "drain")
            return []
        if o is not None:
            o.host_syncs.inc()
            if moe_hist is not None:
                o.moe_block(moe_hist, "decode")
        now = time.perf_counter()
        events: List[Tuple[int, int]] = []
        for i, req in enumerate(rec["reqs"]):
            req.inflight = max(req.inflight - rec["incr"][i], 0)
            self._key_state[req.request_id] = kd[i]    # a row of host memory
            if req.status != "running":
                continue
            prev_t = req.last_token_t
            k0 = len(events)
            row = toks[i]
            if windows is not None:
                # speculative emit layout: PAD-terminated windows, a
                # row's later windows restarting after each one — the
                # parse flattens them back to one PAD-free stream
                row = self._spec_mod.parse_emitted_row(row, windows)
            for t in row:
                t = int(t)
                if t == PAD_TOKEN:
                    break
                events.append(self._emit(req, t, now))
                if req.status != "running":
                    break
            k = len(events) - k0
            if sstats is not None:
                d_cnt, a_cnt, s_cnt = (int(v) for v in sstats[i])
                req.spec_drafted += d_cnt
                req.spec_accepted += a_cnt
                req.spec_target_steps += s_cnt
                req.spec_emitted += k
                if o is not None and o.spec_drafted is not None:
                    o.spec_drafted.inc(d_cnt)
                    o.spec_accepted.inc(a_cnt)
                    o.spec_wasted.inc(d_cnt - a_cnt)
                    o.spec_target_steps.inc(s_cnt)
                    if s_cnt:
                        o.spec_tokens_per_step.observe(k / s_cnt)
                    if req.status != "running" and req.spec_target_steps:
                        acc = (req.spec_accepted
                               / max(req.spec_drafted, 1))
                        tps = (req.spec_emitted
                               / req.spec_target_steps)
                        o.lifecycle.point(
                            req.request_id,
                            f"spec[a={acc:.2f},t/s={tps:.1f}]", now)
            if o is not None and k:
                # one lifecycle span per request per drained block
                # (profiler-only: per-token volume must not grow the
                # tracker's retained event lists)
                o.lifecycle.span(req.request_id, "decode_block",
                                 rec["t0"], now, retain=False)
                if prev_t is not None:
                    # the block lands as a burst: spread its host-visible
                    # gap evenly over the k tokens it carried
                    per_tok = max(now - prev_t, 0.0) / k
                    for _ in range(k):
                        o.inter_token.observe(per_tok)
                    if self._slo is not None:
                        self._slo.decode_tokens(req.slo_class, per_tok, k)
        if windows is not None:
            # roll the speculative worst-case page charge back to what
            # was actually accepted; the next block's reservation
            # re-tops through the ordinary _ensure_decode_pages path
            for req in rec["reqs"]:
                if req.status == "running":
                    self.scheduler.revert_spec_pages(req)
        # decode wall time without double-counting overlapped block spans
        start = max(rec["t0"], self._last_drain_t)
        if o is not None:
            o.decode_seconds.inc(max(now - start, 0.0))
            o.step_phase["drain"].observe(now - t_in)
            # dispatch-to-drain span of THIS block: how long its work
            # was resident device-side (the async overlap means host
            # wall and device wall differ — this is the device-side
            # estimate ROADMAP 5's overlap fraction divides by)
            o.device_residency.observe(max(now - rec["t0"], 0.0))
        if self._recorder is not None:
            self._recorder.record("drain",
                                  family=rec.get("kind", "decode"),
                                  rows=len(rec["reqs"]),
                                  tokens=len(events))
        self._last_drain_t = now
        return events

    # ------------------------------------------------------------- recovery
    def attach_journal(self, journal) -> None:
        """Attach the RequestJournal this engine appends to (the
        exactly-once delivery ledger; recovery.py). Must happen before
        any request is added — a request unknown to the journal cannot
        be recovered."""
        self._journal = journal

    def salvage(self) -> List[Tuple[int, int]]:
        """Recovery-side best-effort drain (the supervisor's restart
        step 1): surface whatever a still-answering device can deliver —
        spilled events plus the pending block — and journal it, so the
        rebuild folds it into prompts instead of recomputing it. Unlike
        the steady-state drain path this NEVER quarantines: a block the
        device cannot hand back is simply discarded — its tokens were
        never delivered, so the journal never saw them and the rebuilt
        engine recomputes them bit-identically — and its requests stay
        live for re-admission. The injector's `drain` site is consulted
        so chaos schedules can kill the salvage too."""
        events = list(self._spill)
        self._spill = []
        rec, self._pending = self._pending, None
        if rec is not None:
            toks = None
            try:
                fi = self._faults
                if fi is not None:
                    fi.check("drain")
                toks = np.asarray(jax.device_get(rec["emitted"]))
            except Exception:  # noqa: BLE001 — the device may be gone
                self.fault_events += 1
            for i, req in enumerate(rec["reqs"]):
                req.inflight = max(req.inflight - rec["incr"][i], 0)
            if toks is not None:
                now = time.perf_counter()
                kd = rec["key_data"]
                windows = rec.get("windows")
                for i, req in enumerate(rec["reqs"]):
                    self._key_state[req.request_id] = kd[i]
                    if req.status != "running":
                        continue
                    row = toks[i]
                    if windows is not None:
                        row = self._spec_mod.parse_emitted_row(
                            row, windows)
                    for t in row:
                        t = int(t)
                        if t == PAD_TOKEN:
                            break
                        events.append(self._emit(req, t, now))
                        if req.status != "running":
                            break
        if self._journal is not None and events:
            self._journal_delivery(events)
        return events

    def snapshot(self) -> EngineSnapshot:
        """Serializable boundary state of every journal-live request:
        original prompt, delivered tokens, sampling knobs + effective
        seed, wall-clock-anchored deadlines/timestamps, and the PRNG
        key state replayed from the seed by delivered count — never the
        live `_key_state`, which a crash can leave AHEAD of what was
        actually delivered (a lost spill), and delivered is what
        restore continues from. KV pages and the pending block are
        deliberately absent: restore re-prefills the fold instead of
        checkpointing pools. Requires an attached journal."""
        if self._journal is None:
            raise RuntimeError(
                "snapshot() needs an attached journal — the journal is "
                "the source of truth for what each consumer was shown")
        snaps: List[RequestSnapshot] = []
        for rec in self._journal.live_records():
            live = self.requests.get(rec.request_id)
            kd = replay_key_state(rec.seed, len(rec.delivered))
            snaps.append(RequestSnapshot(
                request_id=rec.request_id, prompt=list(rec.prompt),
                delivered=list(rec.delivered),
                max_new_tokens=rec.max_new_tokens,
                temperature=rec.temperature, top_k=rec.top_k,
                top_p=rec.top_p, seed=rec.seed,
                eos_token_id=rec.eos_token_id,
                deadline_wall=rec.deadline_wall,
                arrival_wall=rec.arrival_wall,
                first_token_wall=rec.first_token_wall,
                last_token_wall=rec.last_token_wall,
                preemptions=live.preemptions if live is not None else 0,
                parked=live.parked if live is not None else False,
                key_data=tuple(int(x) for x in np.asarray(kd))))
        config = {
            "page_size": self.page_size,
            "max_batch_size": self.max_batch_size,
            "max_seq_len": self.max_seq_len,
            "decode_horizon": self.decode_horizon,
            "enable_chunked_prefill": self.enable_chunked_prefill,
            "enable_prefix_caching": self.prefix_cache is not None,
            # informational only: the journal's token record is device-
            # independent, so a snapshot taken at one tp degree restores
            # at ANY tp degree (restore() never reads this key)
            "tp_size": self.tp_size,
        }
        return EngineSnapshot(config=config, requests=snaps,
                              taken_wall=time.time())

    def restore(self, snapshot: EngineSnapshot,
                cancelled: Sequence[int] = ()) -> List[int]:
        """Rebuild request state on a FRESH engine from a snapshot.
        Each unfinished request is re-admitted (in submission order,
        with its ORIGINAL request id) as a folded prompt — original
        prompt + delivered tokens, the preemption trick — so its
        re-prefill rides the ordinary chunked-prefill / prefix-cache
        paths and its continuation is bit-identical to never having
        crashed. A request whose delivered stream already satisfies its
        stopping rule is reconstructed as finished (nothing recomputed);
        one named in `cancelled` (a cancel issued while the restore was
        in flight) ends "cancelled"; one whose wall-clock deadline
        passed during the outage ends "expired" — never resurrected.
        Returns the re-admitted request ids."""
        if self.requests:
            raise RuntimeError(
                "restore() needs a fresh engine — this one already "
                f"holds {len(self.requests)} requests")
        if snapshot.config.get("max_seq_len", self.max_seq_len) > \
                self.max_seq_len:
            raise ValueError(
                f"restore target's max_seq_len ({self.max_seq_len}) is "
                "smaller than the snapshot's "
                f"({snapshot.config['max_seq_len']}) — folded prompts "
                "may not fit")
        if snapshot.requests:
            reserve_request_ids(max(r.request_id
                                    for r in snapshot.requests))
        cancelled = set(cancelled)
        now_wall = time.time()
        # translate the snapshot's wall-clock anchors back into this
        # process's perf_counter timeline: deadlines keep counting down
        # across the outage, and TTFT/latency metrics stay honest
        offset = time.perf_counter() - now_wall
        readmitted: List[int] = []
        for rs in snapshot.requests:
            rid = rs.request_id
            done = (len(rs.delivered) >= rs.max_new_tokens
                    or (rs.eos_token_id is not None and rs.delivered
                        and rs.delivered[-1] == rs.eos_token_id))
            sampling = SamplingParams(rs.temperature, rs.top_k,
                                      rs.top_p, rs.seed)
            if done:
                # everything was delivered before the crash and only the
                # `finished` record was lost: reconstruct, never
                # recompute
                req = Request(prompt=list(rs.prompt),
                              max_new_tokens=rs.max_new_tokens,
                              sampling=sampling,
                              eos_token_id=rs.eos_token_id,
                              request_id=rid)
                req.generated = list(rs.delivered)
                req.num_computed_tokens = len(rs.prompt)
                self._restore_times(req, rs, offset)
                req.finish_t = time.perf_counter()
                self.requests[rid] = req
                self._key_state[rid] = jnp.asarray(rs.key_data,
                                                   dtype=jnp.uint32)
                self.scheduler.finish(req)
                if self._journal is not None \
                        and self._journal.known(rid):
                    self._journal.terminal(rid, "finished")
                continue
            req = Request(prompt=list(rs.prompt) + list(rs.delivered),
                          max_new_tokens=(rs.max_new_tokens
                                          - len(rs.delivered)),
                          sampling=sampling,
                          eos_token_id=rs.eos_token_id,
                          request_id=rid)
            req.preemptions = rs.preemptions
            req.parked = rs.parked
            self._restore_times(req, rs, offset)
            self.requests[rid] = req
            self._key_state[rid] = jnp.asarray(rs.key_data,
                                               dtype=jnp.uint32)
            if rid in cancelled:
                # a cancel issued mid-restore wins over re-admission
                self._finalize(req, "cancelled")
                continue
            if rs.deadline_wall is not None:
                req.deadline_t = rs.deadline_wall + offset
                if now_wall >= rs.deadline_wall:
                    # the deadline passed during the outage: expired
                    # requests may NOT be resurrected by replay
                    self._finalize(req, "expired")
                    continue
            self.scheduler.add(req, force=True)
            if req.deadline_t is not None:
                self._deadlined.add(rid)
            if self._obs is not None:
                self._obs.lifecycle.point(rid, "recovered")
            readmitted.append(rid)
        return readmitted

    @staticmethod
    def _restore_times(req: Request, rs: RequestSnapshot,
                       offset: float) -> None:
        req.arrival_t = rs.arrival_wall + offset
        if rs.first_token_wall is not None:
            req.first_token_t = rs.first_token_wall + offset
        if rs.last_token_wall is not None:
            req.last_token_t = rs.last_token_wall + offset

    def adopt_request(self, *, prompt: List[int],
                      delivered: Sequence[int] = (),
                      max_new_tokens: int,
                      temperature: float = 0.0, top_k: int = 0,
                      top_p: float = 1.0, seed: int,
                      eos_token_id: Optional[int] = None,
                      deadline_wall: Optional[float] = None,
                      key_splits: int = 0,
                      request_id: Optional[int] = None,
                      slo_class: Optional[str] = None) -> int:
        """Re-admit another engine's in-flight request into THIS engine
        while it keeps serving — the cluster's migration/hedging
        primitive. `restore()` demands a fresh engine (it rebuilds a
        whole snapshot); this is the single-request equivalent for a
        running survivor: the request enters as a folded prompt
        (`prompt + delivered`) with the REMAINING budget, its PRNG chain
        replayed to `key_splits + len(delivered)` splits past `seed`, so
        the continuation is bit-identical to the stream the dead replica
        would have produced. `request_id=None` mints a fresh id (hedge
        clones); passing one keeps the consumer-visible id across a
        migration (`reserve_request_ids` fences the global counter
        either way). If a journal is attached and does not already know
        the id, the FOLD is journaled as a new submission carrying the
        accumulated split count — a later crash of this engine replays
        correctly however many folds deep the request is. A
        `deadline_wall` already in the past finalizes the request as
        "expired" on arrival (never resurrected), mirroring restore().
        Returns the request id under which the request now runs."""
        prompt = [int(t) for t in prompt]
        delivered = [int(t) for t in delivered]
        if not prompt:
            raise ValueError("empty prompt")
        if slo_class is not None and (
                self._slo is None or not self._slo.has_class(slo_class)):
            # a migrated request's class may not exist on the adopting
            # replica; dropping to class-less beats rejecting the
            # migration, but an explicit unknown class is caller error
            raise ValueError(
                f"unknown SLO class {slo_class!r} on adopting engine")
        remaining = max_new_tokens - len(delivered)
        if remaining < 1:
            raise ValueError(
                f"nothing left to generate: {len(delivered)} of "
                f"{max_new_tokens} tokens already delivered")
        folded = prompt + delivered
        if len(folded) + remaining > self.max_seq_len:
            raise ValueError(
                f"folded prompt ({len(folded)}) + remaining budget "
                f"({remaining}) exceeds max_seq_len {self.max_seq_len}")
        if not self.enable_chunked_prefill \
                and len(folded) > self.prefill_buckets[-1]:
            raise ValueError(
                f"folded prompt length {len(folded)} exceeds the "
                f"largest prefill bucket {self.prefill_buckets[-1]}")
        if request_id is not None:
            if request_id in self.requests:
                raise ValueError(
                    f"request {request_id} already lives on this engine")
            reserve_request_ids(request_id)
        req = Request(prompt=folded, max_new_tokens=remaining,
                      sampling=SamplingParams(temperature, top_k, top_p,
                                              seed),
                      eos_token_id=eos_token_id, slo_class=slo_class,
                      **({"request_id": request_id}
                         if request_id is not None else {}))
        rid = req.request_id
        now_wall = time.time()
        offset = time.perf_counter() - now_wall
        expired = (deadline_wall is not None
                   and now_wall >= deadline_wall)
        if not expired:
            # may raise on the page budget — before any registration,
            # so a rejected adoption leaves no trace (add_request's
            # discipline); force=True because this request was already
            # admitted once, by the engine that died holding it
            self.scheduler.add(req, force=True)
        self.requests[rid] = req
        self._key_state[rid] = jnp.asarray(
            replay_key_state(seed, key_splits + len(delivered)),
            dtype=jnp.uint32)
        if self._journal is not None and not self._journal.known(rid):
            self._journal.submit(
                request_id=rid, prompt=folded,
                max_new_tokens=remaining, temperature=temperature,
                top_k=top_k, top_p=top_p, seed=seed,
                eos_token_id=eos_token_id, deadline_wall=deadline_wall,
                arrival_wall=now_wall,
                key_splits=key_splits + len(delivered))
        if deadline_wall is not None:
            req.deadline_t = deadline_wall + offset
            if expired:
                self._finalize(req, "expired")
                return rid
            self._deadlined.add(rid)
        if self._obs is not None:
            self._obs.lifecycle.point(rid, "adopted")
        if self._recorder is not None:
            self._recorder.record("adopt", rid=rid,
                                  delivered=len(delivered),
                                  remaining=remaining)
        return rid

    # -------------------------------------------------------------- metrics
    def stats(self) -> Dict[str, object]:
        """Aggregate serving metrics — a THIN VIEW over the metrics
        registry (the single source of truth; the engine keeps no
        parallel hand-maintained counters). All pre-observability keys
        are preserved; the `latency` section adds p50/p95/p99 TTFT and
        inter-token seconds straight from the registry histograms. With
        `enable_metrics=False` the same shape comes back zeroed (only
        request-derived fields are populated)."""
        o = self._obs
        if o is not None:
            s = {
                "prefill_steps": int(o.prefill_steps.value),
                "prefill_chunks": int(o.prefill_chunks.value),
                "decode_steps": int(o.decode_steps.value),
                "ragged_steps": int(o.ragged_steps.value),
                "dispatches": int(o.dispatches.value),
                "tokens_generated": int(o.tokens.value),
                "prefill_time_s": float(o.prefill_seconds.value),
                "decode_time_s": float(o.decode_seconds.value),
                "preemptions": int(o.preemptions.value),
                "host_syncs": int(o.host_syncs.value),
            }
        else:
            s = {
                "prefill_steps": 0, "prefill_chunks": 0,
                "decode_steps": 0, "ragged_steps": 0, "dispatches": 0,
                "tokens_generated": 0, "prefill_time_s": 0.0,
                "decode_time_s": 0.0,
                "preemptions": sum(r.preemptions
                                   for r in self.requests.values()),
                "host_syncs": 0,
            }
        dt = s["decode_time_s"]
        s["decode_tokens_per_s"] = (
            s["tokens_generated"] / dt if dt > 0 else 0.0)
        s["decode_horizon"] = self.decode_horizon
        s["tp_size"] = self.tp_size
        if self._tp is not None:
            s["tp"] = self._tp.describe()
        s["kv_dtype"] = self.kv_dtype
        if self.cache.quantized:
            c = self.cache
            s["quant"] = {
                "kv_dtype": c.kv_dtype,
                "pool_bytes": c.pool_bytes,
                "page_bytes": c.page_bytes,
                "fp32_pool_bytes": (c.num_layers * c.num_pages
                                    * c.page_size * 2 * c.num_kv_heads
                                    * c.head_dim * 4),
                "tp_quantized_allreduce": self.tp_quantized_allreduce,
            }
        s["tokens_per_sync"] = (
            s["tokens_generated"] / s["host_syncs"]
            if s["host_syncs"] else 0.0)
        s["num_requests"] = len(self.requests)
        s["num_finished"] = sum(r.status == "finished"
                                for r in self.requests.values())
        # resilience outcomes, derived from request state so the shape
        # is identical with metrics off (the registry keeps the same
        # counts under serving_requests_terminated_total{status=})
        term = {st: 0 for st in ("cancelled", "expired", "failed", "shed")}
        for r in self.requests.values():
            if r.status in term:
                term[r.status] += 1
        s["terminal"] = term
        s["transient_retries"] = (int(o.retries.value)
                                  if o is not None else 0)
        s["parked"] = sum(r.parked for r in self.requests.values())
        s["free_pages"] = self.cache.allocator.num_free
        s["latency"] = {
            "ttft": (o.ttft.summary() if o is not None
                     else Histogram.empty_summary()),
            "inter_token": (o.inter_token.summary() if o is not None
                            else Histogram.empty_summary()),
            "decode_stall": (o.decode_stall.summary() if o is not None
                             else Histogram.empty_summary()),
        }
        # step-phase breakdown (ISSUE 13): where a step's wall time goes
        # — scheduling, host-side batch assembly, the jitted launch, and
        # THE host sync — plus the dispatch-to-drain device-residency
        # estimate (ROADMAP 5's overlap-fraction denominator)
        if o is not None:
            s["step_breakdown"] = {
                "schedule": o.step_phase["schedule"].summary(),
                "assemble": o.step_phase["assemble"].summary(),
                "dispatch": o.step_phase["dispatch"].summary(),
                "drain": o.step_phase["drain"].summary(),
                "device_residency": o.device_residency.summary(),
            }
        else:
            s["step_breakdown"] = {
                "schedule": Histogram.empty_summary(),
                "assemble": Histogram.empty_summary(),
                "dispatch": Histogram.empty_summary(),
                "drain": Histogram.empty_summary(),
                "device_residency": Histogram.empty_summary(),
            }
        # SLO/goodput (ISSUE 13): per-class targets, windowed TTFT/TPOT
        # percentiles and attainment, plus the all-class goodput counter
        # next to raw tokens_generated
        if self._slo is not None:
            self._slo.refresh(advance=False)
            s["slo"] = self._slo.summary()
            s["goodput_tokens"] = self._slo.goodput_tokens
        else:
            s["slo"] = {}
            s["goodput_tokens"] = 0
        s["prefill_chunk_tokens"] = self.prefill_chunk_tokens
        s["max_num_batched_tokens"] = self.max_num_batched_tokens
        if self.prefix_cache is not None:
            s["prefix_cache"] = self.prefix_cache.stats()
        # speculative decoding (ISSUE 17): derived from request state so
        # the shape is identical with metrics off (the registry keeps
        # the same counts under serving_spec_*_total)
        if self.spec_config is not None:
            drafted = sum(r.spec_drafted for r in self.requests.values())
            accepted = sum(r.spec_accepted
                           for r in self.requests.values())
            steps = sum(r.spec_target_steps
                        for r in self.requests.values())
            emitted = sum(r.spec_emitted for r in self.requests.values())
            s["spec"] = {
                "lookahead": self.spec_config.lookahead,
                "method": self.spec_config.method,
                "drafted_tokens": drafted,
                "accepted_tokens": accepted,
                "wasted_tokens": drafted - accepted,
                "accept_rate": accepted / drafted if drafted else 0.0,
                "target_steps": steps,
                "tokens_per_target_step": (emitted / steps
                                           if steps else 0.0),
                "tokens_per_step": (
                    o.spec_tokens_per_step.summary()
                    if o is not None else Histogram.empty_summary()),
            }
        per_req = {}
        for rid, req in self.requests.items():
            per_req[rid] = {
                "ttft_s": (req.first_token_t - req.arrival_t
                           if req.first_token_t else None),
                "latency_s": (req.finish_t - req.arrival_t
                              if req.finish_t else None),
                "tokens": len(req.generated),
                "preemptions": req.preemptions,
                "status": req.status,
                "slo_class": req.slo_class,
            }
        s["requests"] = per_req
        return s

    # ------------------------------------------------------------ forensics
    def build_postmortem(self, reason: str,
                         info: Optional[Dict[str, object]] = None
                         ) -> Dict[str, object]:
        """Assemble (but do not write) a post-mortem bundle from this
        engine's recorder ring, metrics registry, request table and
        journal tail. Works with any subset of those attached — a
        recorder-less engine still gets metrics + request rows."""
        return _build_bundle(reason, recorder=self._recorder,
                             registry=self.metrics,
                             requests=self.requests.values(),
                             journal=self._journal, info=info)

    def dump_postmortem(self, reason: str,
                        directory: Optional[str] = None,
                        info: Optional[Dict[str, object]] = None) -> str:
        """Build a bundle and write it to ``directory`` (default: the
        engine's ``postmortem_dir``). Returns the path, also stashed on
        ``last_postmortem_path``."""
        directory = directory or self._postmortem_dir
        if directory is None:
            raise ValueError(
                "no directory: pass one or set postmortem_dir= on the "
                "engine")
        path = _dump_bundle(self.build_postmortem(reason, info=info),
                            directory)
        self.last_postmortem_path = path
        return path

    def compile_counts(self) -> Dict[str, int]:
        """Distinct executables THIS engine's step stream needs, i.e. its
        jit-cache miss count per family (prefill buckets, one fused
        decode+sample block per horizon) — the serving tests assert these
        stay bounded. Counted from the engine's own input avals because
        the underlying compiled caches are deliberately shared across
        engines on the same model; with metrics on, the counts are read
        from the registry's `serving_jit_compile_misses_total{family=}`
        counters (kept in lockstep by `_note_exec`)."""
        if self._obs is not None:
            counts = {fam: int(c.value)
                      for fam, c in self._obs.compile_miss.items()}
        else:
            counts = {name: len(shapes)
                      for name, shapes in self._exec_shapes.items()}
        counts["total"] = sum(counts.values())
        return counts
