"""Collective/compute overlap for TP decode — split-psum micro-row
pipelining (ISSUE 18 tentpole).

The Megatron TP engine (serving/tp.py) issues exactly ONE all-reduce per
block half, but the decode step still strictly serializes
compute -> psum -> compute: the tp-sweep tok/s curve pays the full
collective wall on every layer. T3 (arxiv 2401.16677) shows that
splitting the reduction into micro-chunks moved by a ring and
interleaving them with the consumer's matmuls hides most of that wall.
This module is that schedule, under the repo's bit-determinism
discipline:

- **the transport** is `parallel.mesh.ring_collect`: K micro-row chunks
  of each row-parallel partial ride a fixed-order `lax.ppermute` ring
  (permutation table ALWAYS built from the declared axis size —
  `ring_perm`) into a source-indexed buffer whose layout equals the
  `all_gather` the serial `ordered_psum` uses;
- **the arithmetic** is a static shard-order sum over that buffer
  (fp32), or the EXACT `block_quantize`/`block_dequant_sum` pair the
  serial `quantized_psum` is composed from (int8 qar). Same values in
  the same order as the serial reduction -> tokens stay bit-identical
  to the serial-psum engine at every tp degree, fp32 AND quantized
  (pinned across the tp x dtype x horizon x chunks matrix in
  tests/test_tp_overlap.py);
- **the overlap** is double buffering: chunk j+1's ring hops are
  emitted BEFORE chunk j's reduce+consume, so the hops carry no data
  dependency on the consumer and XLA's latency-hiding scheduler may run
  transport and matmul concurrently. Two seams per layer: the
  attention-half reduction interleaves with the MLP column matmuls
  (post-norm, gate/up or ffn_in), and layer i's final (down/ffn_out)
  reduction rides to layer i+1 as an un-reduced `_PendingTpRows` handle
  and interleaves with its input norm + QKV matmuls. The model-top
  `_resolve_tp_overlap` hook closes the last layer's pipeline before
  the final norm.

Wired as `ServingEngine(tp_overlap=True, tp_overlap_chunks=K)`:
`TPContext` retypes the skeleton's row-parallel Linears to the ring
counterparts and the decoder layers to the overlap drivers
(`install_overlap`), and suffixes its `jit_key` so the five jit-builder
families never mix serial and overlapped executables. `chunks=1` is
normalized OFF upstream (the serial executables are literally reused),
and nothing imports this module unless overlap is effectively on —
tp=1 and serial-tp engines are pinned with the raise-on-touch pattern.

`overlap_fraction` — the honest metric: a construction-time probe times
the serial reduce+consume against the ring-overlapped pipeline and
publishes the hidden fraction of the collective wall in
`stats()["tp"]["overlap_fraction"]`. On a CPU host-process mesh the
scheduler has no second execution unit, so the fraction reads ~0 — the
number documents what THIS rig hides, and real multi-chip meshes
re-measure it rather than inherit a claim.
"""
from __future__ import annotations

import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from ..models import gpt as _gpt
from ..models import llama as _llama
from ..nn import functional as F
from ..parallel.mesh import TP_AXIS, chunk_bounds, ring_collect, \
    ring_pipeline
from .. import nn

__all__ = [
    "OverlapPlan", "install_overlap", "measure_overlap_fraction",
    "overlap_probe_fn",
]


class OverlapPlan:
    """Static shape of one engine's ring-overlapped reduction: the tp
    degree (ring length), the micro-row chunk count K, and whether the
    payload rides quantized. Stamped on every retyped layer/Linear
    (plain attribute — `Layer.__setattr__` passes non-param objects
    through), so the traced schedule is a pure function of the
    skeleton, exactly like the serial retype."""

    __slots__ = ("tp_size", "chunks", "quantized")

    def __init__(self, tp_size: int, chunks: int, quantized: bool):
        self.tp_size = int(tp_size)
        self.chunks = int(chunks)
        self.quantized = bool(quantized)
        if self.tp_size < 2:
            raise ValueError(
                f"overlap needs tp_size >= 2, got {tp_size} (tp_size=1 "
                "has no collective to hide)")
        if self.chunks < 2:
            raise ValueError(
                f"overlap needs chunks >= 2, got {chunks} (chunks=1 IS "
                "the serial engine — TPContext normalizes it off)")

    # -------------------------------------------------------- transport
    def transport(self, part):
        """Issue the ring hops moving one micro-chunk's shard-local
        partial: fp32 rides raw, quantized rides the serial
        `quantized_psum`'s own `block_quantize` payload (int8 blocks +
        fp32 scales, two rings). Returns an opaque in-flight handle for
        `reduce` — the split is the overlap seam: everything here is
        independent of the previous chunk's consumer."""
        if self.quantized:
            from .quant import block_quantize

            q, scale = block_quantize(part)
            return (ring_collect(q, TP_AXIS, self.tp_size),
                    ring_collect(scale, TP_AXIS, self.tp_size),
                    part.shape[-1], part.dtype)
        return ring_collect(part, TP_AXIS, self.tp_size)

    def reduce(self, moved):
        """Finish one chunk's reduction in FIXED shard order: a static
        0..n-1 sum over the source-indexed buffer (fp32) or the serial
        `block_dequant_sum` expression (quantized) — the arithmetic the
        bit-identity contract rests on."""
        if self.quantized:
            from .quant import block_dequant_sum

            qg, sg, h, dt = moved
            return block_dequant_sum(qg, sg, h, dt)
        out = moved[0]
        for i in range(1, self.tp_size):
            out = out + moved[i]
        return out


def _chunk_bounds(chunks: int, rows: int) -> List[Tuple[int, int]]:
    """Serving alias of the shared `parallel.mesh.chunk_bounds` (the
    scheduler moved to the mesh substrate so training's bucket pipeline
    and this decode overlap share one implementation)."""
    return chunk_bounds(chunks, rows)


def _ring_pipeline(plan: OverlapPlan, partial, consume) -> None:
    """The double-buffered schedule, as a thin adapter over the shared
    `parallel.mesh.ring_pipeline`: split `partial` (rows-leading
    shard-local array) into micro-row chunks — the pipeline's items are
    the [lo, hi) bounds, transported by slicing + `plan.transport` at
    exactly the trace points the scheduler dictates — and for each
    chunk emit the NEXT chunk's ring transport before reducing and
    consuming the current one. `consume(idx, lo, hi, reduced)` runs in
    row order, so callers rebuild full outputs with one concatenate."""
    bounds = _chunk_bounds(plan.chunks, partial.shape[0])

    def transport(bound):
        lo, hi = bound
        return plan.transport(partial[lo:hi])

    def consume_idx(idx, reduced):
        lo, hi = bounds[idx]
        consume(idx, lo, hi, reduced)

    ring_pipeline(bounds, transport, plan.reduce, consume_idx)


class _TpPartial:
    """Un-reduced output of a ring-retyped row-parallel Linear: the
    shard-local partial plus the (replicated) bias the consumer must add
    AFTER the reduction, in the serial association `resid + (red + b)`
    (fp addition is not associative — the order is part of the
    bit-identity contract)."""

    __slots__ = ("partial", "bias", "plan")

    def __init__(self, partial, bias, plan: OverlapPlan):
        self.partial = partial        # raw (b, s, h) shard-local partial
        self.bias = bias              # raw (h,) replicated bias or None
        self.plan = plan


class _RingRowParallelLinear(nn.Linear):
    """Ring-overlapped counterpart of `tp._RowParallelPsumLinear`: the
    shard-local partial matmul WITHOUT the reduction — the enclosing
    overlap layer owns the ring schedule, so the Linear hands back a
    `_TpPartial` instead of psumming in place. Retyped in place
    (`linear.__class__ = ...`), parameter names untouched — the same
    shard-local weight slices bind by name via `call_functional`."""

    def forward(self, x):
        y = x.matmul(self.weight)
        b = self.bias._data if self.bias is not None else None
        return _TpPartial(y._data, b, self._ovl)


class _RingRowParallelQuantLinear(_RingRowParallelLinear):
    """Quantized variant (counterpart of `_RowParallelQuantPsumLinear`):
    the partial is identical — `OverlapPlan.quantized` routes the
    TRANSPORT through the serial `quantized_psum`'s own
    `block_quantize`/`block_dequant_sum` pair, so qar overlap engines
    stay bit-identical to qar serial engines (and, like them, only
    shard-identical vs the fp32 psum)."""


class _PendingTpRows:
    """Layer i's un-reduced final (down/ffn_out) partial, threaded to
    layer i+1 through the model's decoder loop: `residual` holds the
    post-attention rows, `partial` the shard-local MLP partial whose
    ring reduce layer i+1 interleaves with its input norm + QKV
    matmuls. `_tp_overlap_finish` closes the pipeline at the top of the
    stack (the models' `_resolve_tp_overlap` hook duck-types on it)."""

    __slots__ = ("residual", "partial", "bias", "lead", "plan")

    def __init__(self, residual, partial, bias, lead, plan: OverlapPlan):
        self.residual = residual      # (R, h) rows after the attn half
        self.partial = partial        # (R, h) shard-local partial rows
        self.bias = bias              # (h,) replicated bias or None
        self.lead = lead              # (b, s) of the layer activations
        self.plan = plan

    def _tp_overlap_finish(self):
        """Reduce the last pending partial (one shot — past the last
        layer there is no consumer left to hide hops behind) and rebuild
        the (b, s, h) activation tensor the final norm expects."""
        red = self.plan.reduce(self.plan.transport(self.partial))
        y = red if self.bias is None else red + self.bias
        x = self.residual + y
        b, s = self.lead
        return Tensor(x.reshape((b, s, x.shape[-1])))


class _OverlapLlamaDecoderLayer(_llama.LlamaDecoderLayer):
    """Retype target for `LlamaDecoderLayer` under overlap: the cache
    (serving) path re-expresses both block halves as micro-row chunk
    slices the ring can interleave with. Numerically every chunk runs
    the layer's OWN modules (norms, projections) on row slices —
    row-chunked matmul/RMSNorm equals the full-tensor op bitwise, so the
    only change vs serial is the transport, and that is order-fixed."""

    def forward(self, x, cache=None, start_pos=0):
        if cache is None:   # training path: serving never drives it
            return _llama.LlamaDecoderLayer.forward(self, x, cache,
                                                    start_pos)
        plan = self._ovl
        att = self.self_attn

        # -- seam 1: the PREVIOUS layer's down-proj reduction (if one is
        # pending) interleaves with this layer's input norm + QKV chunks
        if isinstance(x, _PendingTpRows):
            b, s = x.lead
            xs: List = []
            qs: List = []
            ks: List = []
            vs: List = []

            def consume(idx, lo, hi, red):
                y = red if x.bias is None else red + x.bias
                xc = x.residual[lo:hi] + y
                xs.append(xc)
                nrm = self.input_layernorm(Tensor(xc))
                qs.append(att.q_proj(nrm)._data)
                ks.append(att.k_proj(nrm)._data)
                vs.append(att.v_proj(nrm)._data)

            _ring_pipeline(plan, x.partial, consume)
            x2d = jnp.concatenate(xs, axis=0)
            q = Tensor(jnp.concatenate(qs, axis=0).reshape(
                (b, s, att.num_heads, att.head_dim)))
            k = Tensor(jnp.concatenate(ks, axis=0).reshape(
                (b, s, att.num_kv_heads, att.head_dim)))
            v = Tensor(jnp.concatenate(vs, axis=0).reshape(
                (b, s, att.num_kv_heads, att.head_dim)))
        else:               # first layer: nothing pending, serial entry
            b, s, _ = x.shape
            x2d = x._data.reshape((b * s, x.shape[-1]))
            xin = self.input_layernorm(x)
            q = att.q_proj(xin).reshape(
                [b, s, att.num_heads, att.head_dim])
            k = att.k_proj(xin).reshape(
                [b, s, att.num_kv_heads, att.head_dim])
            v = att.v_proj(xin).reshape(
                [b, s, att.num_kv_heads, att.head_dim])

        # -- attention proper (RoPE + paged attend): o_proj is
        # ring-retyped, so attend() hands back the un-reduced partial
        part, new_cache = att.attend(q, k, v, b, s, cache, start_pos)

        # -- seam 2: the attention-half reduction interleaves with the
        # post-norm + SwiGLU column matmul chunks; the down partial
        # stays un-reduced and rides to layer i+1
        a2d = part.partial.reshape((b * s, part.partial.shape[-1]))
        x1s: List = []
        ps: List = []

        def consume2(idx, lo, hi, red):
            y = red if part.bias is None else red + part.bias
            x1c = x2d[lo:hi] + y
            x1s.append(x1c)
            nrm = self.post_attention_layernorm(Tensor(x1c))
            mc = F.silu(self.mlp.gate_proj(nrm)) * self.mlp.up_proj(nrm)
            ps.append(self.mlp.down_proj(mc).partial)

        _ring_pipeline(plan, a2d, consume2)
        pend = _PendingTpRows(jnp.concatenate(x1s, axis=0),
                              jnp.concatenate(ps, axis=0),
                              None, (b, s), plan)
        return pend, new_cache


class _OverlapGPTBlock(_gpt.GPTBlock):
    """Retype target for `GPTBlock` under overlap — same two seams as
    the LLaMA driver, with GPT's shapes: fused QKV column matmul (its
    tp-sharded bias rides inside the module), biased row-parallel
    out/ffn_out whose replicated biases add AFTER the reduction in the
    serial association, and eval-mode dropout (identity) elided."""

    def forward(self, x, cache=None, start_pos=0):
        if cache is None:   # training path: serving never drives it
            return _gpt.GPTBlock.forward(self, x, cache, start_pos)
        plan = self._ovl
        att = self.attn
        nh, hd = att.num_heads, att.head_dim

        if isinstance(x, _PendingTpRows):
            b, s = x.lead
            xs: List = []
            qkvs: List = []

            def consume(idx, lo, hi, red):
                y = red if x.bias is None else red + x.bias
                xc = x.residual[lo:hi] + y
                xs.append(xc)
                qkvs.append(att.qkv(self.ln1(Tensor(xc)))._data)

            _ring_pipeline(plan, x.partial, consume)
            x2d = jnp.concatenate(xs, axis=0)
            t = jnp.concatenate(qkvs, axis=0).reshape((b, s, 3, nh, hd))
            t = jnp.transpose(t, (2, 0, 1, 3, 4))
            q, k, v = Tensor(t[0]), Tensor(t[1]), Tensor(t[2])
        else:
            b, s, _ = x.shape
            x2d = x._data.reshape((b * s, x.shape[-1]))
            qkv = att.qkv(self.ln1(x)).reshape([b, s, 3, nh, hd])
            qkv = qkv.transpose([2, 0, 1, 3, 4])
            q, k, v = qkv[0], qkv[1], qkv[2]

        part, new_cache = att.attend(q, k, v, b, s, cache, start_pos)

        a2d = part.partial.reshape((b * s, part.partial.shape[-1]))
        x1s: List = []
        ps: List = []
        fb: List = [None]    # ffn_out's replicated bias, same every chunk

        def consume2(idx, lo, hi, red):
            y = red if part.bias is None else red + part.bias
            x1c = x2d[lo:hi] + y
            x1s.append(x1c)
            out = self.ffn_out(F.gelu(self.ffn_in(self.ln2(Tensor(x1c)))))
            ps.append(out.partial)
            fb[0] = out.bias

        _ring_pipeline(plan, a2d, consume2)
        pend = _PendingTpRows(jnp.concatenate(x1s, axis=0),
                              jnp.concatenate(ps, axis=0),
                              fb[0], (b, s), plan)
        return pend, new_cache


def install_overlap(skel, family: str, tp_size: int, chunks: int,
                    quantized: bool) -> OverlapPlan:
    """Retype a TP skeleton model in place for the ring-overlapped
    schedule: row-parallel Linears -> `_RingRowParallel(Quant)Linear`,
    decoder layers -> the overlap drivers, with one shared `OverlapPlan`
    stamped on each. Called by `TPContext._build_shard_model` ONLY when
    overlap is effectively on (lazy import — serial/tp=1 engines never
    load this module; raise-on-touch pinned)."""
    plan = OverlapPlan(tp_size, chunks, quantized)
    row_cls = (_RingRowParallelQuantLinear if quantized
               else _RingRowParallelLinear)
    if family == "llama":
        for layer in skel.llama.layers:
            att = layer.self_attn
            att.o_proj.__class__ = row_cls
            att.o_proj._ovl = plan
            layer.mlp.down_proj.__class__ = row_cls
            layer.mlp.down_proj._ovl = plan
            layer.__class__ = _OverlapLlamaDecoderLayer
            layer._ovl = plan
    elif family == "gpt":
        for blk in skel.gpt.blocks:
            blk.attn.out.__class__ = row_cls
            blk.attn.out._ovl = plan
            blk.ffn_out.__class__ = row_cls
            blk.ffn_out._ovl = plan
            blk.__class__ = _OverlapGPTBlock
            blk._ovl = plan
    else:
        raise ValueError(f"no overlap drivers for model family {family!r}")
    return plan


# ------------------------------------------------------------------ probes
def _probe_weight(hidden: int):
    """Deterministic non-trivial consumer weight (no RNG in probes —
    construction must be reproducible): a small periodic ramp the
    algebraic simplifier cannot elide."""
    w = jnp.arange(hidden * hidden, dtype=jnp.float32) % 13.0
    return w.reshape(hidden, hidden) * 0.01


def overlap_probe_fn(mesh, hidden: int, chunks: int):
    """The ring-overlapped reduce+consume microkernel as one wrapped
    `(rows, hidden) -> (rows, hidden)` function over `mesh`: K micro-row
    ring transports interleaved with a consumer matmul — exactly the
    schedule the overlap engine traces into its decode executables. The
    `paged_decode_overlap` bench gates jit/AOT-lower this body to pin
    Mosaic lowering of the split-collective idiom."""
    tp = mesh.shape[TP_AXIS]
    plan = OverlapPlan(tp, chunks, quantized=False)
    w = _probe_weight(hidden)

    def body(x):
        outs = []

        def consume(idx, lo, hi, red):
            outs.append(red @ w)

        _ring_pipeline(plan, x, consume)
        return jnp.concatenate(outs, axis=0)

    return _shard_map(
        body, mesh=mesh, in_specs=P(), out_specs=P(),
        check_vma=False,  # noqa: COLLECTIVE-MESH — probe reduces a replicated buffer over the fixed-order ring; varying-axes tracking cannot see that the ppermute accumulation ends replicated
        )


def measure_overlap_fraction(mesh, tp_size: int, hidden: int, chunks: int,
                             quantized: bool, rows: int = 8,
                             best_of: int = 3) -> float:
    """Construction-time probe behind `stats()["tp"]["overlap_fraction"]`:
    time (a) the reduction alone, (b) serial reduce -> consumer matmul,
    (c) the ring-overlapped pipeline of the same work, each warmed and
    best-of-`best_of` (the collective_seconds probe discipline), and
    report the fraction of the collective wall the overlap hid:
    clip((b - c) / a, 0, 1). On a CPU mesh the scheduler has no second
    execution unit, so ~0 is the HONEST number — document it, don't
    synthesize a speedup; multi-chip rigs re-measure."""
    plan = OverlapPlan(tp_size, chunks, quantized)
    w = _probe_weight(hidden)
    if quantized:
        from .quant import quantized_psum

        def serial_reduce(y):
            return quantized_psum(y, TP_AXIS)
    else:
        def serial_reduce(y):
            return jax.lax.psum(y, TP_AXIS)

    def reduce_only(x):
        return serial_reduce(x)

    def serial_step(x):
        return serial_reduce(x) @ w

    def overlap_step(x):
        outs = []

        def consume(idx, lo, hi, red):
            outs.append(red @ w)

        _ring_pipeline(plan, x, consume)
        return jnp.concatenate(outs, axis=0)

    x = jax.device_put(
        jnp.ones((max(int(rows), 1), hidden), jnp.float32) * 0.5,
        NamedSharding(mesh, P()))

    def timed(body) -> float:
        fn = jax.jit(_shard_map(
            body, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False,  # noqa: COLLECTIVE-MESH — probe over a replicated buffer; rep tracking adds latency to the very wall being measured
            ))
        fn(x).block_until_ready()          # compile + first dispatch
        fn(x).block_until_ready()          # warm-up: steady-state queue
        best: Optional[float] = None
        for _ in range(max(int(best_of), 1)):
            t0 = time.perf_counter()
            fn(x).block_until_ready()
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return float(best)

    t_coll = timed(reduce_only)
    t_serial = timed(serial_step)
    t_overlap = timed(overlap_step)
    if t_coll <= 0.0:
        return 0.0
    return float(max(0.0, min(1.0, (t_serial - t_overlap) / t_coll)))
