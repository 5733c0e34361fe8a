"""Tensor-parallel serving: Megatron-sharded executables + a partitioned
paged KV pool over a sub-mesh of local devices.

`TPContext` is the bridge between the serving engine's jitted step
families and a 1-axis `jax.sharding.Mesh` ("tp") of `tp_size` devices:

- **weight sharding** (Megatron-LM): QKV / gate / up projections are
  column-parallel (output dim sharded, each shard owns whole heads),
  O / down projections are row-parallel (input dim sharded, partial
  sums) — so each attention block and each MLP block costs exactly ONE
  `lax.psum` over the tp axis, issued inside the row-parallel Linear
  before its (replicated) bias. Embeddings, norms and the LM head stay
  replicated: the final logits are bit-identical on every shard, and
  fused sampling runs from the full distribution everywhere, keeping
  PRNG streams and emitted tokens identical to `tp_size=1`. GPT's fused
  `qkv = Linear(h, 3h)` weight is column-INTERLEAVED before placement
  (global layout (3, heads, hd) -> (tp, 3, heads/tp, hd)) so each
  shard's contiguous slice reshapes to its own (3, heads/tp, hd) block;

- **sharded paged KV pool**: the per-layer pools keep their
  (kv_heads, num_pages, page_size, head_dim) logical shape but are
  placed `P("tp", None, None, None)` — each shard owns a
  (kv_heads/tp, num_pages, page_size, head_dim) slab. Page tables, the
  null page, `BlockAllocator` accounting, prefix-cache page ids and
  scheduler admission stay shard-replicated and byte-identical to the
  single-device engine: one logical page = tp physical slabs, so no
  scheduler / recovery / cluster policy changes at all;

- **shard-local model**: the engine's model reshapes activations with
  its config's STATIC head counts, so the sharded executables trace a
  skeleton clone of the model whose attention modules count heads/tp
  (weights are rebound per call by `call_functional`, so the skeleton's
  own parameters are freed to 0-d stubs) and whose row-parallel Linears
  are retyped to `_RowParallelPsumLinear` — or, under
  `TPContext(overlap=True, overlap_chunks=K)`, to the ring-overlapped
  counterparts in serving/overlap.py, which split each all-reduce into
  K micro-row `lax.ppermute` ring chunks interleaved with the consumer
  matmuls while keeping tokens bit-identical (fixed shard-order
  accumulation, ISSUE 18);

- **execution**: `wrap_prefill_exec` / `wrap_decode_exec` wrap the
  engine's unchanged step bodies in `shard_map` over the tp axis —
  params/pools sharded per the specs above, everything else (ids, page
  tables, positions, PRNG key data, sampling knobs) replicated.

Mesh construction lives on the unified substrate
(`paddle_tpu.parallel.mesh`, shared with the ZeRO training engine):
devices are sorted by id, so any `jax.devices()` ordering produces the
same mesh — snapshot/restore and cluster sub-mesh carving stay
deterministic across processes. GQA validation requires
`kv_heads % tp == 0` (each shard owns whole KV-head groups).

Nothing in this module is imported unless `ServingEngine(tp_size>1)` —
the `tp_size=1` path runs zero TP code (pinned by tests).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

# the unified mesh substrate (ISSUE 16): device ordering and mesh
# construction are shared with the training engines in
# paddle_tpu.parallel — TP_AXIS here IS parallel.mesh.TP_AXIS
from ..core.tensor import Tensor
from ..parallel.mesh import TP_AXIS, build_mesh, device_order
from .. import nn

__all__ = ["TPContext", "validate_tp_config", "tp_device_order"]


def tp_device_order(devices=None):
    """Sorted-by-id device list — delegates to the substrate's
    `parallel.mesh.device_order`, THE canonical ordering for every mesh
    in the repo (engine sub-mesh, cluster carving, training grid), so
    snapshot/restore and cluster replica carving stay deterministic no
    matter how the caller's list was shuffled."""
    return device_order(devices)


def validate_tp_config(cfg, tp_size: int) -> None:
    """Divisibility contract for Megatron sharding of this config.
    `kv_heads % tp == 0` is the GQA rule: a KV head's pool slab lives on
    exactly one shard, and every query head of its group lives with it
    (heads % tp == 0 keeps the per-shard rep factor integral)."""
    heads = cfg.num_attention_heads
    kv = getattr(cfg, "num_key_value_heads", heads)
    inter = cfg.intermediate_size
    if tp_size < 2:
        raise ValueError(f"tp_size must be >= 2 for a TPContext "
                         f"(got {tp_size}); tp_size=1 is the plain engine")
    if heads % tp_size:
        raise ValueError(
            f"num_attention_heads ({heads}) must be divisible by "
            f"tp_size ({tp_size})")
    if kv % tp_size:
        raise ValueError(
            f"num_key_value_heads ({kv}) must be divisible by tp_size "
            f"({tp_size}) — each TP shard owns whole KV heads (GQA "
            "groups never straddle shards)")
    if inter % tp_size:
        raise ValueError(
            f"intermediate_size ({inter}) must be divisible by tp_size "
            f"({tp_size})")


class _RowParallelPsumLinear(nn.Linear):
    """Shard-local row-parallel Linear: the bound weight is the shard's
    (in/tp, out) slice, so the matmul yields a PARTIAL sum — one
    `lax.psum` over the tp axis completes it, and the (replicated) bias
    is added AFTER the reduction (a pre-psum bias would be counted tp
    times). Instances are retyped in place on the skeleton model
    (`linear.__class__ = _RowParallelPsumLinear`), so parameter names —
    what `call_functional` binds by — are untouched."""

    def forward(self, x):
        y = x.matmul(self.weight)
        y = Tensor(jax.lax.psum(y._data, TP_AXIS))
        if self.bias is not None:
            y = y + self.bias
        return y


class _RowParallelQuantPsumLinear(nn.Linear):
    """`_RowParallelPsumLinear` with the psum swapped for the EQuARX-style
    block-scaled int8 all-reduce (`quant.quantized_psum`): the partial sum
    travels as int8 blocks + fp32 scales instead of fp32, and every shard
    dequantizes/sums in fixed shard order — the result stays replicated,
    so sampling and PRNG streams remain shard-identical (just not
    bit-identical to the fp32 psum). Selected by
    `TPContext(quantized_allreduce=True)`; the quant import is deferred
    to trace time so an un-quantized TP engine never touches it."""

    def forward(self, x):
        from .quant import quantized_psum

        y = x.matmul(self.weight)
        y = Tensor(quantized_psum(y._data, TP_AXIS))
        if self.bias is not None:
            y = y + self.bias
        return y


# suffix -> PartitionSpec tables (matched against named_parameters keys);
# Linear weights are (in_features, out_features): column-parallel shards
# axis 1, row-parallel shards axis 0
_LLAMA_COL_W = (".q_proj.weight", ".k_proj.weight", ".v_proj.weight",
                ".gate_proj.weight", ".up_proj.weight")
_LLAMA_ROW_W = (".o_proj.weight", ".down_proj.weight")
_GPT_COL_W = (".attn.qkv.weight", ".ffn_in.weight")
_GPT_COL_B = (".attn.qkv.bias", ".ffn_in.bias")
_GPT_ROW_W = (".attn.out.weight", ".ffn_out.weight")
# GPT's fused qkv output dim is laid out (3, heads, hd); these params are
# interleaved to (tp, 3, heads/tp, hd) before contiguous column sharding
_GPT_QKV = (".attn.qkv.weight", ".attn.qkv.bias")


class TPContext:
    """Everything `ServingEngine(tp_size=N)` needs to run its executable
    families under `shard_map` over a tp sub-mesh: the mesh (sorted
    device ids), per-parameter PartitionSpecs, the KV pool spec, the
    shard-local skeleton model, and placement/wrapping helpers. Built
    once per engine; `jit_key` disambiguates the model-level jit cache
    per (tp degree, device subset), so cluster replicas on different
    sub-meshes never share a compiled executable."""

    def __init__(self, model, tp_size: int, devices=None,
                 quantized_allreduce: bool = False,
                 overlap: bool = False, overlap_chunks: int = 2):
        from ..models.generation import _config_of

        self.tp_size = int(tp_size)
        self.quantized_allreduce = bool(quantized_allreduce)
        # collective/compute overlap (ISSUE 18): split each row-parallel
        # all-reduce into `overlap_chunks` micro-row ring chunks
        # interleaved with the consumer matmuls. chunks=1 normalizes the
        # request OFF entirely — one chunk IS the serial schedule, so the
        # engine keeps the serial retype, the serial jit keys, and
        # literally reuses the serial executables (pinned by tests)
        self.overlap_chunks = int(overlap_chunks)
        if self.overlap_chunks < 1:
            raise ValueError(
                f"overlap_chunks must be >= 1, got {overlap_chunks}")
        self.overlap = bool(overlap) and self.overlap_chunks > 1
        self.cfg = _config_of(model)
        validate_tp_config(self.cfg, self.tp_size)
        if hasattr(model, "llama"):
            self.family = "llama"
        elif hasattr(model, "gpt"):
            self.family = "gpt"
        else:
            raise ValueError(
                "tensor-parallel serving defines Megatron sharding specs "
                "for the LLaMA/GPT decoder families; got "
                f"{type(model).__name__}")
        devs = tp_device_order(devices)
        if len(devs) < self.tp_size:
            raise ValueError(
                f"tp_size={self.tp_size} needs that many devices, got "
                f"{len(devs)}")
        self.devices: Tuple = tuple(devs[:self.tp_size])
        # byte-identical to the pre-substrate construction: the sorted
        # device prefix reshaped onto the one (tp,) axis
        self.mesh = build_mesh(((TP_AXIS, self.tp_size),), self.devices)
        self.num_layers = self.cfg.num_hidden_layers
        self.pool_spec = P(TP_AXIS, None, None, None)
        self.model = model
        self.param_specs = self._build_param_specs(model)
        self.shard_model = self._build_shard_model(model)
        # model-level jit-cache key suffix: tp degree + device identity
        # (+ a marker when the quantized all-reduce is traced in — the
        # executables differ, so the cache must never mix the two; + the
        # ring-overlap marker ONLY when overlap is effectively on, so
        # serial keys stay byte-identical to pre-overlap engines)
        self.jit_key = ("tp", self.tp_size,
                        tuple(d.id for d in self.devices)) \
            + (("qar",) if self.quantized_allreduce else ()) \
            + (("ovl", self.overlap_chunks) if self.overlap else ())
        self._probes: Dict[int, object] = {}
        # construction-time overlap probe (serial reduce+consume wall vs
        # the ring-overlapped pipeline, as a fraction of the collective
        # wall) — the documented number behind stats()["tp"]
        # ["overlap_fraction"]; None on serial engines (zero overlap
        # code runs, raise-on-touch pinned)
        self.overlap_fraction: Optional[float] = None
        if self.overlap:
            from .overlap import measure_overlap_fraction

            self.overlap_fraction = measure_overlap_fraction(
                self.mesh, self.tp_size, self.cfg.hidden_size,
                self.overlap_chunks, self.quantized_allreduce)

    # ------------------------------------------------------------ sharding
    def _spec_for(self, name: str) -> P:
        if self.family == "llama":
            if name.endswith(_LLAMA_COL_W):
                return P(None, TP_AXIS)
            if name.endswith(_LLAMA_ROW_W):
                return P(TP_AXIS, None)
        else:
            if name.endswith(_GPT_COL_W):
                return P(None, TP_AXIS)
            if name.endswith(_GPT_COL_B):
                return P(TP_AXIS)
            if name.endswith(_GPT_ROW_W):
                return P(TP_AXIS, None)
        # embeddings / norms / lm_head / row-parallel biases: replicated
        return P()

    def _build_param_specs(self, model) -> Dict[str, P]:
        from ..jit.functional import extract_state

        params, _ = extract_state(model)
        return {name: self._spec_for(name) for name in params}

    def _interleave_qkv(self, arr):
        """Reorder a fused-QKV param's output dim from (3, heads, hd) to
        (tp, 3, heads/tp, hd) so a CONTIGUOUS column shard is one
        shard's own [q|k|v] block — the shard-local
        `reshape(b, s, 3, heads/tp, hd)` then splits correctly."""
        nh = self.cfg.num_attention_heads
        hd = self.cfg.hidden_size // nh
        tp = self.tp_size
        lead = arr.shape[:-1]
        x = arr.reshape(lead + (3, tp, nh // tp, hd))
        x = jnp.moveaxis(x, -3, -4)            # (..., tp, 3, nh/tp, hd)
        return x.reshape(lead + (3 * nh * hd,))

    def shard_params(self, params: Dict[str, jnp.ndarray]
                     ) -> Dict[str, jnp.ndarray]:
        """Place the engine's full parameter dict onto the mesh per the
        Megatron specs (GPT fused-QKV params are column-interleaved
        first). Each shard materializes only its slice."""
        out = {}
        for name, arr in params.items():
            if self.family == "gpt" and name.endswith(_GPT_QKV):
                arr = self._interleave_qkv(arr)
            out[name] = jax.device_put(
                arr, NamedSharding(self.mesh, self.param_specs[name]))
        return out

    def replicate(self, tree):
        """Place a pytree fully replicated on the mesh (buffers)."""
        sh = NamedSharding(self.mesh, P())
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh),
                                      tree)

    # ------------------------------------------------------ skeleton model
    def _build_shard_model(self, model):
        """Shard-local clone of the model: same class + FULL config (so
        derived sizes like head_dim stay right), then attention head
        counts divided by tp (the static reshape constants) and the
        row-parallel Linears retyped to the psum variant. Its own
        freshly-initialized weights are immediately freed to 0-d stubs —
        `call_functional` rebinds every parameter per call, and the
        sharded executables bind the shard-local slices."""
        skel = type(model)(self.cfg)
        skel.eval()
        tp = self.tp_size
        row_cls = (_RowParallelQuantPsumLinear if self.quantized_allreduce
                   else _RowParallelPsumLinear)
        if self.family == "llama":
            for layer in skel.llama.layers:
                att = layer.self_attn
                att.num_heads //= tp
                att.num_kv_heads //= tp
                if not self.overlap:
                    att.o_proj.__class__ = row_cls
                    layer.mlp.down_proj.__class__ = row_cls
        else:
            for blk in skel.gpt.blocks:
                blk.attn.num_heads //= tp
                if not self.overlap:
                    blk.attn.out.__class__ = row_cls
                    blk.ffn_out.__class__ = row_cls
        if self.overlap:
            # ring-overlapped retype (ISSUE 18): row Linears become ring
            # partials and the decoder layers become the chunk-pipelined
            # drivers. The import stays inside the branch — serial TP
            # engines run ZERO overlap code (raise-on-touch pinned)
            from .overlap import install_overlap

            install_overlap(skel, self.family, tp, self.overlap_chunks,
                            self.quantized_allreduce)
        for _, p in skel.named_parameters():
            p._data = jnp.zeros((), p._data.dtype)
        return skel

    # ----------------------------------------------------------- wrapping
    def _pool_specs(self, pools=None):
        """Specs matching the engine's pool structure: 2-tuples (k, v)
        for plain pools, 4-tuples (k, v, k_scale, v_scale) for quantized
        ones. Every leaf — scale slabs included, they are rank-4 with
        the same leading kv-head axis — shards under the one pool spec;
        with no pools given (probe paths) assume the classic 2-tuples."""
        if pools is None:
            return [(self.pool_spec, self.pool_spec)] * self.num_layers
        return jax.tree_util.tree_map(lambda _: self.pool_spec, pools)

    @staticmethod
    def _repl_like(tree):
        return jax.tree_util.tree_map(lambda _: P(), tree)

    def wrap_prefill_exec(self, fn):
        """shard_map a prefill-family step
        `(params, buffers, ids, pools, *rest) -> (tok, key_data, pools)`
        over the tp axis: params per spec, pools kv-head-sharded,
        everything else replicated. The sampled token and key state are
        computed from the replicated logits on EVERY shard, so the
        `P()` outputs are genuinely identical across devices
        (check_vma=False: the checker can't prove replication through the
        PRNG ops, but the final psum makes it so by construction)."""
        param_specs, mesh = self.param_specs, self.mesh

        def wrapped(params, buffers, ids, pools, *rest):
            pool_specs = self._pool_specs(pools)
            return _shard_map(
                fn, mesh=mesh,
                in_specs=(param_specs, self._repl_like(buffers), P(),
                          pool_specs) + tuple(P() for _ in rest),
                out_specs=(P(), P(), pool_specs),
                check_vma=False,  # noqa: COLLECTIVE-MESH — pool outputs are per-shard by design (kv-head-sharded pages); rep checking would reject the contract
                )(params, buffers, ids, pools, *rest)
        return wrapped

    def wrap_decode_exec(self, fn):
        """shard_map the fused decode+sample block
        `(params, buffers, tokens, pools, *rest) ->
        (emitted, pools, tokens, positions, key_data, remaining)` —
        same placement contract as `wrap_prefill_exec`."""
        param_specs, mesh = self.param_specs, self.mesh

        def wrapped(params, buffers, tokens, pools, *rest):
            pool_specs = self._pool_specs(pools)
            return _shard_map(
                fn, mesh=mesh,
                in_specs=(param_specs, self._repl_like(buffers), P(),
                          pool_specs) + tuple(P() for _ in rest),
                out_specs=(P(), pool_specs, P(), P(), P(), P()),
                check_vma=False,  # noqa: COLLECTIVE-MESH — pool outputs are per-shard by design (kv-head-sharded pages); rep checking would reject the contract
                )(params, buffers, tokens, pools, *rest)
        return wrapped

    def wrap_ragged_exec(self, fn):
        """shard_map the one-dispatch ragged mixed step
        `(params, buffers, flat_ids, pools, *rest) ->
        (emitted, pools, key_out)` — same placement contract as the
        other families: the flat token buffer, page tables, row ids and
        every per-row array are replicated, the KV pools kv-head-
        sharded, and the emitted block + key state are computed from
        replicated logits on every shard."""
        param_specs, mesh = self.param_specs, self.mesh

        def wrapped(params, buffers, flat_ids, pools, *rest):
            pool_specs = self._pool_specs(pools)
            return _shard_map(
                fn, mesh=mesh,
                in_specs=(param_specs, self._repl_like(buffers), P(),
                          pool_specs) + tuple(P() for _ in rest),
                out_specs=(P(), pool_specs, P()),
                check_vma=False,  # noqa: COLLECTIVE-MESH — pool outputs are per-shard by design (kv-head-sharded pages); rep checking would reject the contract
                )(params, buffers, flat_ids, pools, *rest)
        return wrapped

    def wrap_spec_exec(self, fn):
        """shard_map the speculative decode block
        `(params, buffers, tokens, pools, *rest) ->
        (emitted, pools, tokens, positions, key_data, remaining,
        spec_stats)` — the decode contract plus the per-row accept
        counters, which like the emitted block are computed from
        replicated logits on every shard."""
        param_specs, mesh = self.param_specs, self.mesh

        def wrapped(params, buffers, tokens, pools, *rest):
            pool_specs = self._pool_specs(pools)
            return _shard_map(
                fn, mesh=mesh,
                in_specs=(param_specs, self._repl_like(buffers), P(),
                          pool_specs) + tuple(P() for _ in rest),
                out_specs=(P(), pool_specs, P(), P(), P(), P(), P()),
                check_vma=False,  # noqa: COLLECTIVE-MESH — pool outputs are per-shard by design (kv-head-sharded pages); rep checking would reject the contract
                )(params, buffers, tokens, pools, *rest)
        return wrapped

    def wrap_spec_ragged_exec(self, fn):
        """shard_map the speculative ragged mixed step
        `(params, buffers, flat_ids, pools, *rest) ->
        (emitted, pools, key_out, spec_stats)` — the ragged contract
        plus the per-row accept counters."""
        param_specs, mesh = self.param_specs, self.mesh

        def wrapped(params, buffers, flat_ids, pools, *rest):
            pool_specs = self._pool_specs(pools)
            return _shard_map(
                fn, mesh=mesh,
                in_specs=(param_specs, self._repl_like(buffers), P(),
                          pool_specs) + tuple(P() for _ in rest),
                out_specs=(P(), pool_specs, P(), P()),
                check_vma=False,  # noqa: COLLECTIVE-MESH — pool outputs are per-shard by design (kv-head-sharded pages); rep checking would reject the contract
                )(params, buffers, flat_ids, pools, *rest)
        return wrapped

    # -------------------------------------------------------- observability
    @staticmethod
    def probe_best_of(trials: Sequence[float]) -> float:
        """Aggregate one probe sample from its timing trials: the
        minimum. The floor of repeated identical dispatches IS the
        collective + steady-state dispatch; everything above it is host
        scheduling noise. Monotone non-increasing as trials are added —
        pinned by the probe-monotonicity test."""
        return min(trials)

    def collective_seconds(self, samples: int = 3, rows: int = 1,
                           best_of: int = 3) -> List[float]:
        """Measured wall seconds per all-reduce on THIS sub-mesh: a
        jitted psum of a replicated (rows, hidden) f32 buffer — the
        payload shape of one decode-step residual all-reduce (the model
        issues 2*num_layers of these per decode step). Feeds the
        `serving_tp_collective_seconds` histogram and the bench phase's
        collective-time breakdown, and is the serial baseline the
        overlap probe compares against. Includes one dispatch's host
        overhead — on CPU meshes that dominates, which is exactly the
        honest number.

        Each sample is best-of-`best_of` timed calls after TWO warm-up
        dispatches (bugfix: the first post-compile call still pays
        dispatch-queue setup; timing it reported queueing, not the
        collective)."""
        fn = self._probes.get(rows)
        if fn is None:
            mesh = self.mesh
            if self.quantized_allreduce:
                from .quant import quantized_psum

                def reduce_one(y):
                    return quantized_psum(y, TP_AXIS)
            else:
                def reduce_one(y):
                    return jax.lax.psum(y, TP_AXIS)

            def allreduce(x):
                return _shard_map(reduce_one,
                                  mesh=mesh, in_specs=P(), out_specs=P(),
                                  check_vma=False,  # noqa: COLLECTIVE-MESH — probe psum of a replicated buffer; rep tracking adds latency to the very overhead being measured
                                  )(x)
            fn = jax.jit(allreduce)
            self._probes[rows] = fn
        x = jax.device_put(
            jnp.zeros((rows, self.cfg.hidden_size), jnp.float32),
            NamedSharding(self.mesh, P()))
        fn(x).block_until_ready()              # compile + first dispatch
        fn(x).block_until_ready()              # warm-up: steady-state queue
        out = []
        for _ in range(max(int(samples), 1)):
            trials = []
            for _ in range(max(int(best_of), 1)):
                t0 = time.perf_counter()
                fn(x).block_until_ready()
                trials.append(time.perf_counter() - t0)
            out.append(self.probe_best_of(trials))
        return out

    def describe(self) -> Dict[str, object]:
        """Shape of the TP deployment for stats()/debugging: what is
        per-shard vs replicated."""
        cfg = self.cfg
        kv = getattr(cfg, "num_key_value_heads", cfg.num_attention_heads)
        return {
            "tp_size": self.tp_size,
            "quantized_allreduce": self.quantized_allreduce,
            "overlap": self.overlap,
            "overlap_chunks": self.overlap_chunks if self.overlap else 1,
            "overlap_fraction": self.overlap_fraction,
            "devices": [d.id for d in self.devices],
            "kv_heads_per_shard": kv // self.tp_size,
            "heads_per_shard": cfg.num_attention_heads // self.tp_size,
            "replicated": ["page_tables", "allocator", "scheduler",
                           "sampling", "logits", "key_state"],
        }
