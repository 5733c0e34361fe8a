"""Continuous-batching scheduler (Orca-style iteration-level scheduling).

Every engine step is ONE fixed-shape jitted call; the scheduler's job is
to decide which call. Policy:

- admission by free-page budget: a waiting request is admitted only when
  the pool can hold its whole prompt plus the first generated token —
  admitted requests get their prompt pages up front, so a prefill can
  never fail mid-flight;
- prefill priority, one request per step: a newly admitted request is
  prefilled alone (padded to the smallest prompt bucket), keeping the
  compiled-program set to one prefill executable per bucket;
- decode batches every running request into the fixed (max_batch_size)
  decode step — rows beyond the running set are padding aimed at the
  null page;
- copy-on-extend: before a decode step, each running request crossing a
  page boundary gets a fresh page appended to its page table; when the
  pool is exhausted the YOUNGEST running request is preempted — its pages
  return to the free list and it re-queues (front) with prompt+generated
  tokens, to be re-prefilled when pages free up. Eviction therefore costs
  recompute, never correctness;
- decode horizon (`decode_horizon=N`): the engine runs N decode
  iterations per jitted block, so page demand is per BLOCK, not per
  token — admission reserves the first block's pages up front and
  `_ensure_decode_pages` tops every running request up to its next
  block's worst case (`num_tokens + inflight` undrained upper bound),
  so no allocation is ever needed mid-block. With the engine's async
  overlap one block may be in flight undrained; before preempting
  anyone the scheduler calls `drain_hook` so a victim's already-sampled
  tokens are folded into its prompt instead of lost;
- prefix caching (optional): admission first asks the PrefixCache for the
  longest cached full-page prefix of the prompt and charges the pool only
  for the UNCACHED suffix; release paths go through the refcounted
  allocator, so shared pages outlive any one request, and on pool
  pressure unreferenced cached pages are evicted before anyone is
  preempted;
- chunked prefill (`prefill_chunk_tokens=C`, Sarathi-Serve style): the
  prefill-XOR-decode policy above is replaced by MIXED steps assembled
  under a per-step token budget (`max_num_batched_tokens`). A prompt (or
  its uncached suffix) runs in page-aligned chunks of C tokens, tracked
  by a `num_computed_tokens` cursor on the request; every step schedules
  ALL running decoders first (decode never waits behind a long prompt —
  the head-of-line fix), then as many prefill chunks as the leftover
  budget allows, admitting multiple new requests per step when it fits.
  Page accounting charges chunks incrementally — admission reserves only
  the FIRST chunk's pages, each later chunk tops the request up, and the
  final chunk reserves through the first decode block exactly like
  unchunked `_admission_pages` — so a half-prefilled request holds pages
  only for the tokens it has actually computed.

Tensor parallelism (serving.tp) changes NOTHING in this module: the
scheduler runs on the host once per engine regardless of tp_size, and
all of its state — free-page budget, page tables, chunk cursors,
request ids — is shard-replicated by construction. One logical page
simply denotes tp physical slabs of num_kv_heads/tp heads each, so
admission, preemption and prefix-cache accounting are byte-identical
to the tp_size=1 engine. Keeping the policy degree-blind is what makes
cross-degree snapshot/restore and migration work without translation.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import List, Optional, Sequence, Tuple

from .kv_cache import NULL_PAGE, BlockAllocator, SlotAllocator, pages_for
from .resilience import (EngineOverloaded, InjectedFault,
                         TERMINAL_STATUSES)

__all__ = ["ChunkTask", "Request", "SamplingParams", "Scheduler",
           "ScheduleDecision", "reserve_request_ids"]

_REQUEST_IDS = itertools.count()


def reserve_request_ids(up_to: int) -> None:
    """Advance the global request-id counter past `up_to`. Restore-time
    re-admission rebuilds Requests with their ORIGINAL ids (stream
    consumers and the journal key on them), so a rebuilt engine must
    never hand a new request an id the snapshot already owns."""
    global _REQUEST_IDS
    nxt = next(_REQUEST_IDS)
    _REQUEST_IDS = itertools.count(max(nxt, up_to + 1))


@dataclasses.dataclass
class SamplingParams:
    temperature: float = 0.0            # 0.0 = greedy
    top_k: int = 0
    top_p: float = 1.0
    seed: Optional[int] = None


@dataclasses.dataclass
class Request:
    """One generation request plus its serving-side bookkeeping."""

    prompt: List[int]
    max_new_tokens: int
    sampling: SamplingParams
    eos_token_id: Optional[int] = None
    request_id: int = dataclasses.field(
        default_factory=lambda: next(_REQUEST_IDS))

    # scheduler state: waiting | running, then exactly one terminal
    # status — finished | cancelled | expired | failed | shed
    # (resilience.TERMINAL_STATUSES)
    status: str = "waiting"
    generated: List[int] = dataclasses.field(default_factory=list)
    pages: List[int] = dataclasses.field(default_factory=list)
    # the state slot of a model with recurrent layers (kv_cache.
    # SlotAllocator): held from admission to finish or preemption, as
    # the pages are; None for every other model and while waiting
    state_slot: Optional[int] = None
    preemptions: int = 0
    # absolute perf_counter deadline (arrival_t + deadline_s); None =
    # no deadline. Expired waiting requests are shed before admission;
    # expired running requests are cancelled at the next block boundary
    deadline_t: Optional[float] = None
    # set when status lands on "failed": the isolated failure, as text
    error: Optional[str] = None
    # preemption-storm guard tripped: the request was requeued at the
    # BACK of the waiting queue instead of the front
    parked: bool = False
    # prompt tokens whose K/V came from the prefix cache (page-aligned);
    # prefill starts at this offset. pages[:cached_tokens // page_size]
    # are shared — the request holds a reference, never writes them
    cached_tokens: int = 0
    # upper bound on tokens sampled by a dispatched-but-undrained decode
    # block (the engine's async overlap): page demand must cover them,
    # and host state (generated/num_tokens) lags behind by this much
    inflight: int = 0
    # chunked-prefill cursor: prompt tokens whose K/V is resident —
    # cached prefix plus every chunk dispatched so far. The engine
    # advances it only after a chunk dispatch SUCCEEDS, so a faulted
    # chunk never claims tokens it did not write. A request with
    # num_computed_tokens < len(prompt) is mid-prefill: it never joins
    # the decode batch and its page charge covers exactly its computed
    # tokens (the final chunk charges through the first decode block)
    num_computed_tokens: int = 0
    # SLO class name (observability/slo.py), or None when the request
    # opted out of SLO accounting. Validated against the engine's
    # registered classes at add_request time; the scheduler never reads
    # it — it rides along for the engine's latency observation sites
    slo_class: Optional[str] = None
    # speculative decoding accounting (ISSUE 17), filled by the engine's
    # drain: draft tokens verified / accepted, target-model passes that
    # scored this row, and tokens emitted by speculative blocks — the
    # per-request accept-rate and tokens-per-target-step the lifecycle
    # lanes and stats()["spec"] report. Zero when spec is off.
    spec_drafted: int = 0
    spec_accepted: int = 0
    spec_target_steps: int = 0
    spec_emitted: int = 0

    # metrics (perf_counter timestamps, filled by the engine)
    arrival_t: float = dataclasses.field(default_factory=time.perf_counter)
    # when the request last went back to the waiting queue (preemption);
    # queue wait counts from here, from arrival_t while it is None
    requeue_t: Optional[float] = None
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    # host-visible time of the most recent emitted token (feeds the
    # inter-token latency histogram; survives preemption so the requeue
    # gap shows up honestly)
    last_token_t: Optional[float] = None

    @property
    def num_tokens(self) -> int:
        """Tokens resident in the cache once prefilled + decoded so far."""
        return len(self.prompt) + len(self.generated)

    @property
    def next_pos(self) -> int:
        """Position the next decode token will occupy."""
        return self.num_tokens

    @property
    def prefill_done(self) -> bool:
        """Whole prompt's K/V resident — the request can decode. Only
        consulted on the chunked path; preemption folds generated tokens
        into the prompt and resets the cursor, so a requeued victim
        re-prefills from scratch either way."""
        return self.num_computed_tokens >= len(self.prompt)

    def is_done(self) -> bool:
        if len(self.generated) >= self.max_new_tokens:
            return True
        return (self.eos_token_id is not None and self.generated
                and self.generated[-1] == self.eos_token_id)


@dataclasses.dataclass
class ChunkTask:
    """One page-aligned prefill chunk of one request, scheduled into a
    mixed step: compute prompt[start : start+length] at traced offset
    `start`, attending over the request's earlier pages through its page
    table. `length` < the engine's chunk width only on the prompt's
    final chunk (the one padded spot in the whole prefill)."""

    req: Request
    start: int
    length: int

    @property
    def is_final(self) -> bool:
        return self.start + self.length >= len(self.req.prompt)


@dataclasses.dataclass
class ScheduleDecision:
    # "prefill" | "decode" | "idle" classic; "mixed" when chunked prefill
    # is on with `ragged_steps=False` — decode batch plus zero or more
    # prefill chunks chained one dispatch each; "ragged" when
    # `ragged_steps=True` and chunk work exists — the SAME rows, but the
    # engine packs them into one flat batch and dispatches a single
    # ragged executable (decode rows contribute one token each, chunks
    # their extent; `flat_tokens` is the flat token count before bucket
    # padding). A ragged scheduler still says "decode" on chunk-free
    # steps so pure decode keeps the chained-block pipeline.
    kind: str
    prefill: Optional[Request] = None
    decode: Sequence[Request] = ()
    chunks: Sequence[ChunkTask] = ()
    flat_tokens: int = 0


class Scheduler:
    def __init__(self, allocator: BlockAllocator, page_size: int,
                 max_batch_size: int, max_pages_per_seq: int,
                 prefix_cache=None, decode_horizon: int = 1,
                 drain_hook=None, obs=None, recorder=None,
                 max_waiting: Optional[int] = None,
                 max_preemptions: Optional[int] = None,
                 max_prefill_tokens: Optional[int] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 max_num_batched_tokens: Optional[int] = None,
                 ragged_steps: bool = False,
                 spec_lookahead: int = 0,
                 slot_allocator: Optional[SlotAllocator] = None):
        self.allocator = allocator
        # a model with recurrent layers: a request is admitted when a
        # state slot AND its pages are free, and gives both back together
        self.slot_allocator = slot_allocator
        self.page_size = page_size
        self.max_batch_size = max_batch_size
        self.max_pages_per_seq = max_pages_per_seq
        self.prefix_cache = prefix_cache
        self.decode_horizon = max(int(decode_horizon), 1)
        # speculative decoding (ISSUE 17): a decode block can emit up to
        # horizon × (1 + lookahead) tokens, so every page-accounting
        # site that used to charge decode_horizon charges block_tokens —
        # the WORST case, reverted down to actual acceptance by
        # revert_spec_pages after each drain. Identity when spec is off.
        self.spec_lookahead = max(int(spec_lookahead), 0)
        self.block_tokens = self.decode_horizon * (1 + self.spec_lookahead)
        # bounded waiting queue: add() past this raises EngineOverloaded
        # (backpressure to the caller); None = unbounded, as before
        self.max_waiting = max_waiting
        # preemption-storm guard: a victim preempted more than this many
        # times is parked (requeued at the BACK of the waiting queue)
        # instead of jumping the line into another preempt cycle
        self.max_preemptions = max_preemptions
        # largest prompt the engine can ever prefill (its biggest
        # bucket); _preempt refuses to fold a sequence past it with a
        # clear error instead of failing deep in _bucket_for later.
        # Chunked prefill has no bucket ceiling (any length re-prefills
        # in chunks), so the engine passes None there
        self.max_prefill_tokens = max_prefill_tokens
        # chunked prefill: None = classic prefill-XOR-decode scheduling;
        # an int C (a positive multiple of page_size, validated by the
        # engine) switches schedule() to mixed steps of decode + chunks
        self.prefill_chunk_tokens = prefill_chunk_tokens
        # per-step token budget for mixed steps: each running decoder
        # charges decode_horizon (its block's worst-case query tokens),
        # each chunk charges the full padded chunk width — the honest
        # compute cost of the fixed-shape chunk executable
        self.max_num_batched_tokens = max_num_batched_tokens
        # ragged steps: chunked-prefill steps that carry chunk work come
        # back as ONE flat kind="ragged" decision (the engine dispatches
        # a single ragged executable) instead of kind="mixed"'s
        # decode-then-chunks dispatch chain. Row selection, budget
        # charging and page reservation are IDENTICAL either way — only
        # the decision kind (and therefore the dispatch shape) changes
        self.ragged_steps = bool(ragged_steps)
        # called once per _ensure_decode_pages on pool exhaustion, before
        # any preemption: the engine drains its in-flight decode block so
        # (a) device-finished requests release their pages and (b) a
        # preemption victim's undrained tokens reach host state first
        self.drain_hook = drain_hook
        # observability hooks (the engine's ServingObs: lifecycle points
        # for enqueue/admit/preempt/finish, preemption counter, per-step
        # queue-depth + page-pool gauges). None = zero metrics work.
        self.obs = obs
        # flight recorder (observability/flight_recorder.py): terminal
        # and preemption events append to the bounded ring. None = the
        # scheduler executes no recorder code at all (raise-on-touch
        # pinned in tests/test_observability_v2.py)
        self.recorder = recorder
        self.waiting: List[Request] = []
        self.running: List[Request] = []

    # ------------------------------------------------------------ lifecycle
    def add(self, req: Request, force: bool = False) -> None:
        """Enqueue `req`. `force=True` bypasses the bounded-queue check —
        restore-time re-admission replays requests the engine ALREADY
        accepted once; bouncing them off `max_waiting` would turn a
        restart into a shedding event."""
        need = pages_for(len(req.prompt) + req.max_new_tokens,
                         self.page_size)
        if need > self.max_pages_per_seq:
            raise ValueError(
                f"request needs {need} pages > max_pages_per_seq "
                f"{self.max_pages_per_seq}; raise max_seq_len/page budget")
        if not force and self.max_waiting is not None and \
                len(self.waiting) >= self.max_waiting:
            # bounded queue = the backpressure signal: nothing was
            # registered, the caller retries later or sheds upstream
            raise EngineOverloaded(
                f"waiting queue is full ({len(self.waiting)} >= "
                f"max_waiting={self.max_waiting}); retry later")
        self.waiting.append(req)
        if self.obs is not None:
            self.obs.enqueued(req)

    def _release(self, req: Request) -> None:
        """Give back what `req` holds of the sequence state: its page
        references and, over a model with recurrent layers, its slot.
        Nothing is cleared on the device: the next owner's prefill
        writes the whole slot."""
        self.allocator.free_all(req.pages)
        req.pages = []
        if req.state_slot is not None:
            self.slot_allocator.free(req.state_slot)
            req.state_slot = None
            if self.obs is not None:
                self.obs.state_slots(self.slot_allocator)

    def finish(self, req: Request) -> None:
        """Drop a completed request's page references; a page returns to
        the pool once no other sequence (and no cached prefix) holds it."""
        req.status = "finished"
        self._release(req)
        if req in self.running:
            self.running.remove(req)
        if self.obs is not None:
            self.obs.finished(req)
        if self.recorder is not None:
            self.recorder.record("terminal", rid=req.request_id,
                                 status="finished",
                                 generated=len(req.generated))

    def finalize(self, req: Request, status: str,
                 error: Optional[str] = None) -> bool:
        """Terminal transition for the failure-side statuses (cancelled /
        expired / failed / shed): pull the request out of whichever queue
        holds it and release its pages through the refcounted path, so a
        shared prefix page only loses THIS request's reference and every
        survivor's table stays intact. Idempotent — a request already
        terminal is left alone (returns False). The engine drains any
        in-flight decode block BEFORE calling this for a running request,
        so no dispatched block still writes to the released pages."""
        if req.status in TERMINAL_STATUSES:
            return False
        if status not in TERMINAL_STATUSES or status == "finished":
            raise ValueError(f"finalize cannot set status {status!r}")
        req.status = status
        req.error = error
        req.inflight = 0
        req.finish_t = time.perf_counter()
        self._release(req)
        if req in self.running:
            self.running.remove(req)
        if req in self.waiting:
            self.waiting.remove(req)
        if self.obs is not None:
            self.obs.terminal(req, status)
        if self.recorder is not None:
            self.recorder.record("terminal", rid=req.request_id,
                                 status=status, error=error)
        return True

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    # ------------------------------------------------------------- policy
    def _admission_pages(self, req: Request) -> int:
        # prompt + the first decode BLOCK: prefill writes the prompt, and
        # the first block of `decode_horizon` fused steps writes K/V at
        # positions prompt .. prompt + min(horizon, max_new-1) - 1, so it
        # must have slots to land on without mid-block allocation. At
        # horizon 1 this reduces to the classic prompt + 1 (including the
        # exact-fill case len(prompt) % page_size == 0 where the +1 rolls
        # into a fresh page; page 0 (null) is outside the allocator, so
        # no off-by-one hides there either).
        # tests/test_serving.py::TestAdmissionPageAccounting pins this.
        # Under speculation a block emits up to block_tokens tokens, so
        # the first-block charge scales accordingly (worst case; the
        # unaccepted remainder is reverted after the drain).
        first_block = max(1, min(self.block_tokens,
                                 req.max_new_tokens - 1))
        return pages_for(len(req.prompt) + first_block, self.page_size)

    def _block_pages(self, req: Request) -> int:
        """Pages the NEXT decode block needs resident for `req`: host
        state (`num_tokens`) plus the undrained in-flight upper bound,
        advanced by one more block of writes — the block's last sampled
        token never gets K/V written inside it, hence the -1. Never
        shrinks below pages_for(num_tokens), and self-caps at the
        request's lifetime maximum because `rem` runs dry."""
        assumed = req.num_tokens + req.inflight
        rem = max(req.max_new_tokens - len(req.generated) - req.inflight,
                  0)
        want = max(assumed - 1 + min(self.block_tokens, rem),
                   req.num_tokens)
        return pages_for(want, self.page_size)

    def revert_spec_pages(self, req: Request) -> int:
        """Roll back the speculative block's WORST-CASE page charge to
        what the drain actually accepted (ISSUE 17). The block was
        admitted holding pages for `block_tokens` emits per row; after
        the drain, host state (`num_tokens`) plus any still-undrained
        in-flight bound is the truth — tail pages past it go back to
        the pool. The popped tail can never be shared prefix-cache
        pages: those cover at most `cached_tokens <= len(prompt) <=
        num_tokens` tokens, and the kept count never drops below
        pages_for(num_tokens) (nor below the chunked-prefill cursor's
        charge, which `check_consistency` audits). Returns the number
        of pages released."""
        keep = max(
            pages_for(req.num_tokens + req.inflight, self.page_size),
            pages_for(req.num_computed_tokens, self.page_size))
        freed = 0
        while len(req.pages) > keep:
            self.allocator.free(req.pages.pop())
            freed += 1
        return freed

    def _alloc_n(self, n: int) -> Optional[List[int]]:
        """All-or-nothing alloc that reclaims unreferenced prefix-cache
        pages before reporting exhaustion. An injected alloc fault
        degrades to the exhausted path — admission simply defers a step,
        which is already lossless."""
        try:
            pages = self.allocator.alloc_n(n)
            if pages is None and self.prefix_cache is not None:
                self.prefix_cache.evict(n - self.allocator.num_free)
                pages = self.allocator.alloc_n(n)
        except InjectedFault:
            return None
        return pages

    def _alloc_one(self) -> Optional[int]:
        try:
            page = self.allocator.alloc()
            if page is None and self.prefix_cache is not None \
                    and self.prefix_cache.evict(1):
                page = self.allocator.alloc()
        except InjectedFault:
            return None
        return page

    def _try_admit(self) -> Optional[Request]:
        if not self.waiting or len(self.running) >= self.max_batch_size:
            return None
        # a running request holds one slot and there are as many slots as
        # rows, so a free row is a free slot: pages alone can run out
        slots = self.slot_allocator
        req = self.waiting[0]
        cached: List[int] = []
        if self.prefix_cache is not None:
            # longest cached full-page prefix; the pool is charged only
            # for the uncached suffix (match acquires one ref per page).
            # An injected lookup fault degrades to a miss — the request
            # prefills its whole prompt, bit-identical either way
            try:
                cached = self.prefix_cache.match(req.prompt)
            except InjectedFault:
                cached = []
        pages = self._alloc_n(self._admission_pages(req) - len(cached))
        if pages is None:
            # pool exhausted. Drop the match refs FIRST — holding them
            # pins exactly the pages whose eviction could let this
            # request (or an older peer) through — then retry once
            # cache-free before reporting backpressure.
            self.allocator.free_all(cached)
            if cached:
                cached = []
                pages = self._alloc_n(self._admission_pages(req))
            if pages is None:
                if slots is not None and self.obs is not None:
                    self.obs.admission_blocked_on_pages()
                return None
        self.waiting.pop(0)
        req.pages = cached + pages
        if slots is not None:
            req.state_slot = slots.alloc()
            if self.obs is not None:
                self.obs.state_slots(slots, allocated=True)
        req.cached_tokens = len(cached) * self.page_size
        # the engine advances the cursor to len(prompt) once the (whole-
        # prompt) prefill dispatch succeeds
        req.num_computed_tokens = req.cached_tokens
        if self.prefix_cache is not None:
            self.prefix_cache.record(len(req.prompt), req.cached_tokens)
        req.status = "running"
        self.running.append(req)
        if self.obs is not None:
            self.obs.admitted(req)
        return req

    def _preempt(self, victim: Request) -> None:
        """Evict a running request and requeue it at the FRONT of the
        waiting queue with its generated tokens folded into the prompt
        (re-prefill resumes it bit-exactly — prefill and decode share the
        cache numerics). Shared prefix pages only lose the victim's
        reference; survivors and the prefix cache keep theirs.

        Two resilience guards ride here: (1) the folded prompt must stay
        prefillable — if it would exceed the engine's largest prefill
        bucket, raise a CLEAR error NOW, before any state is torn down,
        instead of failing deep in `_bucket_for` after the victim's pages
        are gone; (2) the preemption-storm guard — a victim already
        preempted more than `max_preemptions` times is PARKED: requeued
        at the BACK of the waiting queue, so it stops cycling through the
        front->admit->preempt churn and younger arrivals get a turn
        first."""
        folded = len(victim.prompt) + len(victim.generated)
        if self.max_prefill_tokens is not None \
                and folded > self.max_prefill_tokens:
            raise RuntimeError(
                f"cannot preempt request {victim.request_id}: its folded "
                f"prompt+generated length {folded} exceeds the largest "
                f"prefill bucket ({self.max_prefill_tokens} tokens) — "
                "re-prefill after requeue would be impossible. "
                "prefill_buckets must cover max_seq_len")
        self.running.remove(victim)
        # the slot goes with the pages: the re-prefill rebuilds the state
        self._release(victim)
        victim.cached_tokens = 0
        victim.num_computed_tokens = 0   # re-prefill from scratch
        victim.inflight = 0     # drain_hook ran first: nothing undrained
        victim.prompt = victim.prompt + victim.generated
        victim.max_new_tokens -= len(victim.generated)
        victim.generated = []
        victim.status = "waiting"
        victim.preemptions += 1
        if self.max_preemptions is not None \
                and victim.preemptions > self.max_preemptions:
            victim.parked = True
            self.waiting.append(victim)
            if self.obs is not None:
                self.obs.parked(victim)
        else:
            self.waiting.insert(0, victim)
        if self.obs is not None:
            self.obs.preempted(victim)
        if self.recorder is not None:
            self.recorder.record("preempt", rid=victim.request_id,
                                 parked=victim.parked,
                                 preemptions=victim.preemptions)

    def _ensure_decode_pages(self) -> None:
        """Copy-on-extend, one decode BLOCK at a time: every running
        request is topped up to its next block's worst-case page demand
        (`_block_pages`), so the fused multi-step block never allocates
        mid-flight. On pool exhaustion, first drain the engine's pending
        block once (may finish requests and free pages; also makes any
        preemption victim's host state accurate), then preempt the
        YOUNGEST running request (FCFS priority — running order is
        admission order), including the requester itself when it is the
        youngest."""
        drained = False
        for req in list(self.running):
            if req not in self.running:   # preempted by an older peer
                continue
            if self.prefill_chunk_tokens is not None \
                    and not req.prefill_done:
                # mid-prefill under chunking: the request does not decode
                # this step, and _block_pages would charge its WHOLE
                # prompt (num_tokens counts uncomputed tokens too) —
                # its pages are charged chunk-by-chunk instead
                continue
            faulted = 0
            while req in self.running and \
                    self._block_pages(req) > len(req.pages):
                page = self._alloc_one()
                if page is not None:
                    req.pages.append(page)
                    continue
                if self.allocator.num_free > 0 and faulted < 8:
                    # _alloc_one only reports None with pages still free
                    # when an injected alloc fault fired: retry (the
                    # injector advanced past the armed index) instead of
                    # mistaking the fault for real exhaustion; the bound
                    # keeps a fail_every(1) schedule from spinning
                    faulted += 1
                    continue
                if self.drain_hook is not None and not drained:
                    drained = True
                    self.drain_hook()     # may finish reqs / free pages
                    continue
                victim = self.running[-1]
                if victim is req and len(self.running) == 1:
                    # same accounting as schedule()'s too-large check:
                    # the null page is not allocatable, so report
                    # num_allocatable, not the raw pool size
                    raise RuntimeError(
                        "KV page pool too small for a single request: "
                        f"request {req.request_id} at position "
                        f"{req.next_pos} with "
                        f"{self.allocator.num_allocatable} "
                        "allocatable pages in total")
                self._preempt(victim)
                if victim is req:         # self-preempted: sit this one out
                    break

    def schedule(self) -> ScheduleDecision:
        if self.obs is not None:
            # queue-depth + page-pool gauges, sampled once per step
            self.obs.sample_queues(len(self.waiting), len(self.running),
                                   self.allocator)
        if self.prefill_chunk_tokens is not None:
            return self._schedule_chunked()
        admitted = self._try_admit()
        if admitted is not None:
            return ScheduleDecision(kind="prefill", prefill=admitted)
        if self.running:
            self._ensure_decode_pages()
            batch = self.running[:self.max_batch_size]
            return ScheduleDecision(kind="decode", decode=list(batch))
        self._check_head_fits()
        return ScheduleDecision(kind="idle")

    def _check_head_fits(self) -> None:
        """About to go idle with requests still waiting: if nothing is
        running and the head request cannot fit even in an EMPTY pool,
        no amount of waiting helps — raise now instead of idling
        forever. Otherwise the deferral is transient (an injected alloc
        fault, or pages still pinned that will be released)."""
        if self.running or not self.waiting:
            return
        req = self.waiting[0]
        need = self._admission_pages(req)
        if need > self.allocator.num_allocatable:
            raise RuntimeError(
                f"request {req.request_id} needs {need} pages but "
                f"the pool has {self.allocator.num_allocatable} "
                "allocatable in total")

    # ------------------------------------------------------ chunked prefill
    def _schedule_chunked(self) -> ScheduleDecision:
        """Mixed-step assembly under the per-step token budget
        (Sarathi-Serve stall-free batching): ALL running decoders first
        — a decode step is never skipped because prefill work exists,
        which is the head-of-line fix — then prefill chunks from the
        leftover budget: first the partially-prefilled running requests
        (oldest first), then NEW admissions for as long as batch slots
        and budget last (multi-request admission per step)."""
        budget = self.max_num_batched_tokens
        chunk = self.prefill_chunk_tokens
        decode: List[Request] = []
        if any(r.prefill_done for r in self.running):
            self._ensure_decode_pages()      # may drain and/or preempt
            decode = [r for r in self.running
                      if r.prefill_done][:self.max_batch_size]
            budget -= self.block_tokens * len(decode)
        chunks: List[ChunkTask] = []
        for req in list(self.running):
            if budget < chunk:
                break
            if req not in self.running or req.prefill_done:
                continue
            task = self._next_chunk(req)
            if task is not None:
                chunks.append(task)
                budget -= chunk
        while (budget >= chunk and self.waiting
               and len(self.running) < self.max_batch_size):
            req = self._admit_chunked()
            if req is None:
                break
            task = self._next_chunk(req)
            if task is None:      # cannot happen: admission just paid
                break             # for this chunk's pages; stay safe
            chunks.append(task)
            budget -= chunk
        # Chunk-page reservation above may have preempted a request that
        # was already picked for this step's decode batch (or had a
        # chunk queued): its pages are gone, so dispatching it now would
        # decode from freed state. Keep only entries still running; a
        # same-step re-admission is represented by its NEW chunk task
        # (the engine drops any stale task via the cursor check).
        decode = [r for r in decode
                  if r.status == "running" and r.prefill_done]
        chunks = [t for t in chunks if t.req.status == "running"]
        flat = len(decode) + sum(t.length for t in chunks)
        if self.ragged_steps:
            # one flat decision when chunk work exists; chunk-free steps
            # stay kind="decode" so pure decode keeps the chained-block
            # pipeline (and its zero-host-sync carry reuse)
            if chunks:
                return ScheduleDecision(kind="ragged", decode=decode,
                                        chunks=chunks, flat_tokens=flat)
            if decode:
                return ScheduleDecision(kind="decode", decode=decode)
        elif decode or chunks:
            return ScheduleDecision(kind="mixed", decode=decode,
                                    chunks=chunks, flat_tokens=flat)
        self._check_head_fits()
        return ScheduleDecision(kind="idle")

    def _chunk_pages_needed(self, req: Request, end: int) -> int:
        """Total pages `req` must hold once its prompt is computed up to
        `end`: the final chunk reserves through the first decode block
        (identical to unchunked `_admission_pages`, so the first decode
        block never allocates mid-flight); earlier chunks charge exactly
        their computed tokens — `end` is page-aligned there because the
        cached prefix and the chunk width both are."""
        if end >= len(req.prompt):
            return self._admission_pages(req)
        return pages_for(end, self.page_size)

    def _admit_chunked(self) -> Optional[Request]:
        """Admission under chunking: charge the pool only for the FIRST
        chunk (after the prefix-cache match), not the whole prompt — a
        long prompt no longer needs its full page demand free to start.
        Same cache-miss fallback as `_try_admit`: on exhaustion drop the
        match refs (they pin exactly the evictable pages) and retry
        cache-free once."""
        req = self.waiting[0]
        cached: List[int] = []
        if self.prefix_cache is not None:
            try:
                cached = self.prefix_cache.match(req.prompt)
            except InjectedFault:
                cached = []
        start = len(cached) * self.page_size
        need = self._chunk_pages_needed(
            req, min(start + self.prefill_chunk_tokens, len(req.prompt)))
        pages = self._alloc_n(need - len(cached))
        if pages is None:
            self.allocator.free_all(cached)
            if cached:
                cached = []
                need = self._chunk_pages_needed(
                    req, min(self.prefill_chunk_tokens, len(req.prompt)))
                pages = self._alloc_n(need)
            if pages is None:
                return None
        self.waiting.pop(0)
        req.pages = cached + pages
        req.cached_tokens = len(cached) * self.page_size
        req.num_computed_tokens = req.cached_tokens
        if self.prefix_cache is not None:
            self.prefix_cache.record(len(req.prompt), req.cached_tokens)
        req.status = "running"
        self.running.append(req)
        if self.obs is not None:
            self.obs.admitted(req)
        return req

    def _next_chunk(self, req: Request) -> Optional[ChunkTask]:
        """The next chunk of a mid-prefill request, with its pages
        reserved — or None when the pool cannot cover it this step (the
        request keeps its chunk-to-date pages and simply makes no
        progress until pages free up)."""
        start = req.num_computed_tokens
        n = min(self.prefill_chunk_tokens, len(req.prompt) - start)
        if n <= 0:
            return None
        need = self._chunk_pages_needed(req, start + n)
        if not self._reserve_chunk_pages(req, need):
            return None
        return ChunkTask(req=req, start=start, length=n)

    def _reserve_chunk_pages(self, req: Request, need: int) -> bool:
        """Top `req` up to `need` pages, mirroring _ensure_decode_pages'
        escalation: retry past injected alloc faults, drain the pending
        block once (may free pages), preempt the YOUNGEST running
        request — but never `req` itself: if req IS the youngest, it
        sits the step out so its elders progress, unless it is alone and
        over the pool's whole capacity, which no waiting can fix."""
        drained = False
        faulted = 0
        while need > len(req.pages) and req in self.running:
            pages = self._alloc_n(need - len(req.pages))
            if pages is not None:
                req.pages.extend(pages)
                return True
            if self.allocator.num_free >= need - len(req.pages) \
                    and faulted < 8:
                faulted += 1          # injected alloc fault, not real
                continue              # exhaustion: retry
            if self.drain_hook is not None and not drained:
                drained = True
                self.drain_hook()     # may finish reqs / free pages
                continue
            victim = self.running[-1]
            if victim is req:
                if len(self.running) == 1 \
                        and need > self.allocator.num_allocatable:
                    raise RuntimeError(
                        "KV page pool too small for a single request: "
                        f"request {req.request_id} needs {need} pages "
                        f"with {self.allocator.num_allocatable} "
                        "allocatable pages in total")
                return False
            self._preempt(victim)
        return req in self.running and len(req.pages) >= need

    # ----------------------------------------------------------- invariants
    def check_consistency(self) -> bool:
        """Scheduler+allocator invariant audit, run after every
        failure-isolation event: queues disjoint with statuses matching,
        every running request's pages live in the allocator (never the
        null page), waiting requests holding no pages, and the allocator
        itself sound (`BlockAllocator.check_consistency`). Raises
        RuntimeError on the first violation."""
        self.allocator.check_consistency()
        if self.slot_allocator is not None:
            self.slot_allocator.check_consistency()
            held = [r.state_slot for r in self.running]
            if None in held or len(set(held)) != len(held) \
                    or len(held) != self.slot_allocator.num_used \
                    or any(r.state_slot is not None for r in self.waiting):
                raise RuntimeError(
                    "scheduler corrupt: the running requests' state slots "
                    f"{held} are not the {self.slot_allocator.num_used} "
                    "in use, one each")
        if self.prefix_cache is not None:
            self.prefix_cache.check_consistency()
        if set(map(id, self.waiting)) & set(map(id, self.running)):
            raise RuntimeError("scheduler corrupt: request in both "
                               "waiting and running queues")
        for req in self.running:
            if req.status != "running":
                raise RuntimeError(
                    f"scheduler corrupt: request {req.request_id} in the "
                    f"running queue with status {req.status!r}")
            if self.prefill_chunk_tokens is not None:
                if req.num_computed_tokens > len(req.prompt):
                    raise RuntimeError(
                        f"scheduler corrupt: request {req.request_id} "
                        f"computed {req.num_computed_tokens} prompt "
                        f"tokens of {len(req.prompt)}")
                if pages_for(req.num_computed_tokens,
                             self.page_size) > len(req.pages):
                    raise RuntimeError(
                        f"scheduler corrupt: request {req.request_id} "
                        f"holds {len(req.pages)} pages but its "
                        f"{req.num_computed_tokens} computed tokens "
                        "need more")
            for p in req.pages:
                if p == NULL_PAGE:
                    raise RuntimeError(
                        f"scheduler corrupt: request {req.request_id} "
                        "holds the null page")
                if self.allocator.ref_count(p) < 1:
                    raise RuntimeError(
                        f"scheduler corrupt: request {req.request_id} "
                        f"holds freed page {p}")
        for req in self.waiting:
            if req.status != "waiting":
                raise RuntimeError(
                    f"scheduler corrupt: request {req.request_id} in the "
                    f"waiting queue with status {req.status!r}")
            if req.pages:
                raise RuntimeError(
                    f"scheduler corrupt: waiting request "
                    f"{req.request_id} holds pages {req.pages}")
        return True
