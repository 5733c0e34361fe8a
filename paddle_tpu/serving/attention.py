"""Ragged paged attention over the flat page pool.

The op behind `attend_with_cache` when the cache is a `PagedLayerCache`:
write this step's K/V into the pool at each row's own position, then
attend each query over exactly its sequence's pages (rows sit at DIFFERENT
positions — the batch is ragged, Ragged Paged Attention's setting).

Two paths, mirroring ops/pallas_kernels.py's selection policy:
- a pure-jnp reference path (gather pages via the page table, mask by
  per-row length, reuse F.scaled_dot_product_attention) — numerically the
  twin of the static-cache `attend_with_cache`, runs everywhere;
- a Pallas decode kernel gated on backend: grid (batch, block of kv
  heads); the pools stay in HBM and the page table rides in SMEM via
  scalar prefetch. Each grid step walks ITS ROW'S OWN pages in a loop of
  `cdiv(pos + 1, block)` compute blocks of about 128 tokens (none for a
  parked row): a block's pages come into VMEM by async copies, one
  strided copy a page for all the heads of the step, double-buffered so
  the next block is in flight while this one is computed (no host-side
  gather), online softmax with fp32 maximum, sum and accumulator. Heads
  a step and pages a block follow the shapes and dtype it is handed
  (`_decode_tiling`); there is no option. The ragged kernel still has the
  older grid (token, kv_head, page), one (page_size, head_dim) tile a
  step.

Both steps stay inside ONE jitted call per decode (T3's single-dispatch
rule, arxiv 2401.16677): the write, the gather and the softmax never
bounce logits or pages to the host.

Tensor parallelism (serving.tp) needs no changes here: under shard_map
each shard traces this op with the SAME code on shard-local shapes —
kv pool slabs of num_kv_heads/tp heads, queries of num_heads/tp heads —
while page tables, positions and lengths arrive replicated. Attention is
embarrassingly parallel over heads, so the shard-local result is exact;
the block's single psum lives downstream in the row-parallel O
projection, never in the attention op itself. That stays true under
collective/compute overlap (serving.overlap): the ring-split reduction
replaces only the downstream psum — this op's output just becomes the
partial the ring chunks, transports and reduces while the next matmuls
run.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..profiler import scopes
from .kv_cache import (NULL_PAGE, LatentLayerCache, PagedLayerCache,
                       overflow_position)

__all__ = ["paged_attend", "paged_decode_attention",
           "paged_decode_available", "ragged_paged_attention",
           "ragged_attention_available", "advance_positions", "KERNEL_MODE",
           "latent_write", "latent_prefill_attention",
           "latent_decode_attention", "dsa_index_scores", "dsa_select",
           "sparse_latent_decode_attention", "sparse_prefill_mask"]

# "auto": Pallas kernel on TPU, jnp reference elsewhere; "off": always the
# reference; "interpret": run the Pallas kernel in interpret mode (hermetic
# CPU testing of the kernel itself — slow, test-only)
KERNEL_MODE = "auto"


def _on_tpu() -> bool:
    from ..ops.pallas_kernels import _on_tpu as on_tpu

    return on_tpu()


def _count_dispatch(path: str) -> None:
    """Trace-time dispatch accounting: which paged-attention path a
    jitted step compiled against (Pallas kernel / interpret / jnp
    reference / prefill variants). This runs only while a step is being
    TRACED — steady-state dispatches replay the compiled program and pay
    nothing — so the process-global observability registry ends up with
    one count per (executable, layer), a cheap cross-check that TPU runs
    really lowered the kernel path."""
    from ..observability import global_registry

    global_registry().counter(
        "serving_attention_dispatch_total",
        "trace-time paged-attention path selections",
        labels={"path": path}).inc()


def paged_decode_available(page_size: int, head_dim: int) -> bool:
    """Shape gates for the Pallas decode kernel: page rows must tile the
    8-sublane axis, head_dim anything pad-able to 128 lanes."""
    return page_size % 8 == 0 and 8 <= head_dim <= 256


def _quant_kernel_ok(page_size: int) -> bool:
    """Extra shape gate for DEQUANTIZING kernels on real TPUs: int8/fp8
    pool tiles need a 32-sublane page axis (Mosaic's narrow-dtype tile is
    (32, 128); fp32/bf16 get away with 8/16). Interpret mode skips Mosaic
    and accepts any page size."""
    return page_size % 32 == 0


def advance_positions(positions, live, max_pages: int,
                      page_size: int) -> jnp.ndarray:
    """Device-side position advance for the multi-step decode horizon:
    live rows step to the next token position; dead rows (EOS emitted,
    budget exhausted, batch padding) park at the table-overflow position,
    which `paged_attend` routes to the null page — so a fused decode
    block never needs a host decision to stop a finished row's writes.

    positions: (b,) int32 current write positions; live: (b,) bool.
    """
    park = jnp.int32(overflow_position(max_pages, page_size))
    return jnp.where(live, positions + jnp.int32(1), park)


def _positions(start_pos, b: int, s: int) -> jnp.ndarray:
    """(b, s) int32 global positions for this step's tokens. `start_pos`
    is a scalar (uniform prefill), a (b,) vector (ragged decode), or a
    (b, s) matrix that already IS the positions (flat ragged batch)."""
    start = start_pos._data if hasattr(start_pos, "_data") else start_pos
    start = jnp.asarray(start, jnp.int32)
    if start.ndim == 2:
        return start
    offs = jnp.arange(s, dtype=jnp.int32)
    if start.ndim == 0:
        return jnp.broadcast_to(start + offs, (b, s))
    return start[:, None] + offs[None, :]


@jax.jit     # traced and lowered once a shape, not once a layer
def _write_pages(pool, vals, entries, slots=None):
    """Scatter token K/V rows into the (kvh, P, ps, hd) pool: (n, kvh,
    hd) rows at (entries, slots), or, with `slots` None, (n, ps, kvh,
    hd) whole pages at `entries`. Rows mapped to the null page collide
    there harmlessly — nothing reads page 0 through a real page table.

    The write goes through the pool's (kvh * P, ps, hd) view, at index
    (h * P + entry, slot) for every kv head h: a reshape of leading
    dimensions, no bytes move. Do NOT tidy it back into
    `pool.at[:, entries, slots].set(flat)`. The TPU compiler updates a
    donated operand in place only where the indexed dimensions lead and
    the window is the minor dimensions; for the direct form (window over
    dimension 0) it relayouts the whole pool and back at every write
    (described v5e, the 1.3B pool, PR 30):

      %copy.2 = bf16[16,2049,16,128]{3,0,2,1:T(8,128)(2,1)} copy(%pool.1)
      ROOT %copy.3 = bf16[16,2049,16,128]{3,2,1,0:T(8,128)(2,1)} copy(%fusion)

    which was 71% of the device's time in GPT serving.
    `tests/test_chip_compile.py` fails if a pool-sized copy comes back.

    The scatter takes its updates one at a time, and a row of hd is a
    part of a tile: a prefill from position 0 over whole pages writes
    (ps, hd) pages instead, ps times fewer updates of whole tiles
    (`PERF.md` section 6, PR 30, has what each form read on the chip).
    """
    kvh, num_pages, ps, hd = pool.shape
    heads = jnp.arange(kvh, dtype=entries.dtype)[:, None] * num_pages
    rows = (heads + entries[None, :]).reshape(-1)    # (kvh * n,)
    index = (rows,) if slots is None else (rows, jnp.tile(slots, kvh))
    view = pool.reshape(kvh * num_pages, ps, hd)
    flat = jnp.moveaxis(vals, -2, 0)                 # (kvh, n, [ps,] hd)
    view = view.at[index].set(flat.reshape(-1, *view.shape[len(index):]))
    return view.reshape(pool.shape)


def _write_targets(page_table, pos, ps: int, row_ids=None):
    """(entries, slots), each (b, s): the physical page and the slot in
    it that the token at global position `pos[i, j]` of page-table row i
    (or, flat ragged batch, of row `row_ids[j]`) is written to."""
    max_pages = page_table.shape[1]
    page_idx = pos // ps
    if row_ids is not None:
        # flat ragged batch (b == 1, s == T): token t writes through the
        # page table ROW it belongs to, not batch row 0
        pt_rows = page_table[row_ids]                    # (T, maxP)
        entries = jnp.take_along_axis(
            pt_rows, jnp.clip(page_idx[0], 0, max_pages - 1)[:, None],
            axis=1)[:, 0][None]                          # (1, T)
    else:
        entries = jnp.take_along_axis(
            page_table, jnp.clip(page_idx, 0, max_pages - 1), axis=1)
    # padding rows whose position overflows the table (suffix prefill:
    # offset + bucket may exceed max_pages * page_size) must land in the
    # null page — clipping the index instead would alias them onto the
    # sequence's REAL last page and corrupt it
    entries = jnp.where(page_idx >= max_pages, NULL_PAGE, entries)
    return entries, pos % ps


def paged_attend(q, k, v, cache: PagedLayerCache, start_pos, rep,
                 bias=None):
    """The paged twin of `attend_with_cache`: write K/V into the pool,
    attend q over the page table. Returns (ctx Tensor, new cache view).

    q: Tensor (b, s, heads, hd); k/v: Tensor (b, s, kv_heads, hd);
    start_pos: scalar (prefill, whole batch at offset 0) or (b,) int32
    (decode, one token per row at its own position); bias: optional
    additive (1, heads, s, L) attention bias, cropped/zero-padded on its
    key axis to this step's key length.
    """
    kp, vp = cache.k_pool, cache.v_pool
    page_table = cache.page_table
    ps = cache.page_size
    b, s = q.shape[0], q.shape[1]
    raw_start = start_pos._data if hasattr(start_pos, "_data") else start_pos
    static_zero = isinstance(raw_start, int) and raw_start == 0

    with jax.named_scope(scopes.KV_WRITE):
        kd_raw = k._data if hasattr(k, "_data") else k
        vd_raw = v._data if hasattr(v, "_data") else v
        if cache.quantized:
            # quantized pools: fresh K/V is quantized ONCE here, at page-write
            # time, so every later read — decode, chunked prefill, ragged,
            # prefix-cache reuse — sees the identical bytes (lazy import: an
            # fp32/bf16 cache never reaches this branch)
            from .quant import quantize_tokens
            spec = _pool_quant_spec(kp.dtype)
            kd, k_sc = quantize_tokens(kd_raw, spec)
            vd, v_sc = quantize_tokens(vd_raw, spec)
        else:
            kd = kd_raw.astype(kp.dtype)
            vd = vd_raw.astype(vp.dtype)
        pos = _positions(start_pos, b, s)                # (b, s)
        # a prefill from position 0 over whole pages: one update a page,
        # at the page of its first token
        whole = static_zero and cache.row_ids is None and s % ps == 0
        entries, slots = _write_targets(
            page_table, pos[:, ::ps] if whole else pos, ps, cache.row_ids)
        entries = entries.reshape(-1)
        slots, n = (None, (b * s // ps, ps)) if whole else (
            slots.reshape(-1), (b * s,))

        def write(pool, vals):
            # a pool of packed heads takes a token's (kv_heads, hd) as
            # its (kv_heads / g, g * hd): the same bytes
            return _write_pages(
                pool, vals.reshape(*n, pool.shape[0], pool.shape[-1]),
                entries, slots)

        kp, vp = write(kp, kd), write(vp, vd)
        ks_pool, vs_pool = cache.k_scale, cache.v_scale
        if cache.quantized:
            # the scale slab is scattered with the SAME entries/slots as the
            # data slab — the null-page/overflow routing above covers both
            ks_pool = write(ks_pool, k_sc.reshape(b, s, -1, 1))
            vs_pool = write(vs_pool, v_sc.reshape(b, s, -1, 1))
        new_cache = PagedLayerCache(kp, vp, page_table, cache.row_ids,
                                    k_scale=ks_pool, v_scale=vs_pool,
                                    head_pack=cache.head_pack)

    # the exact prefill attends this step's own K/V block; every other
    # branch reaches K/V through the page table: pool views, pads, the
    # relayout, the kernel, the output slice
    exact_prefill = (cache.row_ids is None and s != 1 and static_zero
                     and not cache.quantized)
    with jax.named_scope(scopes.PREFILL_ATTENTION if exact_prefill
                         else scopes.PAGED_ATTENTION):
        if cache.row_ids is not None:
            ctx = ragged_paged_attention(q, new_cache, pos, rep, bias=bias)
        elif s == 1:
            ctx = paged_decode_attention(q, new_cache, pos[:, 0], rep,
                                         bias=bias)
        elif static_zero and not cache.quantized:
            ctx = _prefill_attention(q, kd, vd, rep, bias=bias)
        elif static_zero:
            # quantized pools route EVERY multi-token prefill through the
            # paged gather: the exact path would read the un-quantized fresh
            # K/V and diverge from what chunked/prefix/migration legs read
            # back from the pool — within a quantized mode, all paths must
            # see the same quantized bytes
            _count_dispatch("prefill_paged_quant")
            ctx = _prefill_attention_paged(q, new_cache, pos, rep, bias=bias)
        else:
            # prefill at a TRACED (or nonzero) offset: earlier K/V lives
            # only in the pool's pages, so attend through the page table.
            # Both offset prefills land here — a prefix-cache suffix prefill
            # AND every chunk of a chunked prefill (its offset is traced, so
            # even a first chunk at offset 0 takes this path; that is what
            # lets one chunked executable serve every chunk of every prompt)
            _count_dispatch("prefill_paged_quant" if cache.quantized
                            else "prefill_paged")
            ctx = _prefill_attention_paged(q, new_cache, pos, rep, bias=bias)
    return ctx, new_cache


def _pool_quant_spec(storage_dtype):
    """KVQuantSpec for a quantized pool's storage dtype (trace-time only,
    reached exclusively from quantized branches)."""
    from .quant import resolve_kv_dtype
    name = ("int8" if jnp.dtype(storage_dtype) == jnp.dtype(jnp.int8)
            else "fp8")
    return resolve_kv_dtype(name)


def _expand_kv(x, rep):
    return jnp.repeat(x, rep, axis=2) if rep > 1 else x


def _gathered(g, rows: int, hd: int):
    """Pages gathered through a page table, (kvh, rows, maxP, ps, width),
    as the contiguous (rows, L, kv heads, hd) the reference paths attend:
    a row of packed heads (`width` a multiple of `hd`) falls apart into
    its heads; a scale slab is gathered with `hd` 1."""
    kvh, _, mp, ps, width = g.shape
    return jnp.transpose(g, (1, 2, 3, 0, 4)).reshape(
        rows, mp * ps, kvh * width // hd, hd)


def _pack_queries(qg, pack: int):
    """(n, kvh, G, hd) grouped queries for a pool of `pack` heads a row:
    (n, kvh / pack, pack * G, pack * hd), the queries of head j of a row
    block in columns [j * hd, (j + 1) * hd) and zero elsewhere, so that
    their scores against the packed row are their own head's (0 * k of
    the neighbour adds nothing) and the kernel runs unchanged over rows
    that fill whole 128-lane tiles."""
    if pack == 1:
        return qg
    n, kvh, g, hd = qg.shape
    q5 = qg.reshape(n, kvh // pack, pack, g, hd)
    return jnp.concatenate([
        jnp.pad(q5[:, :, j], ((0, 0),) * 3
                + ((j * hd, (pack - 1 - j) * hd),)) for j in range(pack)],
        axis=2)


def _unpack_context(out, pack: int, g: int, hd: int):
    """Inverse of `_pack_queries` on the kernel's output: a query of head
    j reads its own head's columns of the packed value rows."""
    if pack == 1:
        return out
    n, kp = out.shape[:2]
    o6 = out.reshape(n, kp, pack, g, pack, hd)
    return jnp.stack([o6[:, :, j, :, j] for j in range(pack)],
                     axis=2).reshape(n, kp * pack, g, hd)


def _crop_bias(bias, length: int) -> jnp.ndarray:
    """Additive bias (1, heads, s, L) -> (1, heads, s, length): crop or
    zero-pad the key axis (the paged step's key extent is maxP*page_size,
    not the bias builder's max_len)."""
    bias_d = bias._data if hasattr(bias, "_data") else bias
    have = bias_d.shape[-1]
    if have >= length:
        return bias_d[..., :length]
    return jnp.pad(bias_d, ((0, 0),) * (bias_d.ndim - 1)
                   + ((0, length - have),))


def _prefill_attention(q, kd, vd, rep, bias=None):
    """The prefill from position 0 and nothing else: the step's own K/V
    block IS the cache and every row sits at its own index, so the mask is
    the plain causal one and the call says so: the flash kernel then skips
    the blocks above the diagonal and no (s, s) array exists. A bias (one
    head's additive term a score) cannot ride the flag: it goes in as a
    dense mask with the causal -1e9 folded in, the static-cache path's
    arithmetic."""
    from ..nn import functional as F

    kf = Tensor(_expand_kv(kd, rep))
    vf = Tensor(_expand_kv(vd, rep))
    if bias is None:
        _count_dispatch("prefill")
        return F.scaled_dot_product_attention(q, kf, vf, is_causal=True)
    _count_dispatch("prefill_masked")
    s = kd.shape[1]
    allowed = jnp.tril(jnp.ones((s, s), bool))
    mask = jnp.where(allowed, 0.0, -1e9).astype(jnp.float32)[None, None]
    mask = mask + _crop_bias(bias, s).astype(jnp.float32)
    return F.scaled_dot_product_attention(
        q, kf, vf, attn_mask=Tensor(mask), is_causal=False)


def _prefill_attention_paged(q, cache: PagedLayerCache, pos, rep,
                             bias=None):
    """Multi-token prefill at a NONZERO offset (prefix-cache hit): the
    queries' earlier keys are cached pages written by another request, so
    gather the whole sequence through the page table — the pool already
    holds this step's suffix K/V — and mask causally by global position.
    Reference path (jnp gather + sdpa), the s>1 twin of
    `_paged_decode_reference`; the Pallas kernel stays decode-only."""
    from ..nn import functional as F

    kp, vp, page_table = cache.k_pool, cache.v_pool, cache.page_table
    b = page_table.shape[0]
    ps = cache.page_size
    length = page_table.shape[1] * ps

    def gather(pool, scale=None, hd=q.shape[-1]):
        out = _gathered(pool[:, page_table], b, hd)
        if scale is None:
            return out
        # quantized pool: dequantize against the gathered scale slab
        # ((kvh, b, maxP, ps, 1) -> (b, L, kvh, 1) by the same permute)
        return out.astype(jnp.float32) * gather(scale, hd=1)

    kf = _expand_kv(gather(kp, cache.k_scale), rep)
    vf = _expand_kv(gather(vp, cache.v_scale), rep)
    # query at global pos[i, r] sees pool column j iff j <= pos[i, r];
    # pool padding (null page, beyond-length slots) masks to the same
    # -1e9 floor as the reference decode path
    allowed = (jnp.arange(length, dtype=jnp.int32)[None, None, :]
               <= pos[:, :, None])                       # (b, s, L)
    mask = jnp.where(allowed, 0.0, -1e9).astype(jnp.float32)[:, None]
    if bias is not None:
        mask = mask + _crop_bias(bias, length).astype(jnp.float32)
    return F.scaled_dot_product_attention(
        q, Tensor(kf), Tensor(vf), attn_mask=Tensor(mask), is_causal=False)


def paged_decode_attention(q, cache: PagedLayerCache, pos, rep,
                           bias=None):
    """One-token-per-row ragged attention over the page pool.

    q: Tensor (b, 1, heads, hd); pos: (b,) int32 — each row's token
    position (its key length minus one). Returns ctx Tensor (b, 1, heads,
    hd).
    """
    hd = q.shape[-1]
    use_kernel = (KERNEL_MODE != "off" and bias is None
                  and paged_decode_available(cache.page_size, hd)
                  and (not cache.quantized
                       or KERNEL_MODE == "interpret"
                       or _quant_kernel_ok(cache.page_size))
                  and (KERNEL_MODE == "interpret" or _on_tpu()))
    if use_kernel:
        tag = ("decode_pallas_interpret"
               if KERNEL_MODE == "interpret" else "decode_pallas")
        _count_dispatch(tag + "_quant" if cache.quantized else tag)
        qd = q._data if hasattr(q, "_data") else q
        out = _paged_decode_pallas(qd, cache.k_pool, cache.v_pool,
                                   cache.page_table, pos,
                                   k_scale=cache.k_scale,
                                   v_scale=cache.v_scale,
                                   pack=cache.head_pack,
                                   interpret=KERNEL_MODE == "interpret")
        return Tensor(out)
    _count_dispatch("decode_reference_quant" if cache.quantized
                    else "decode_reference")
    return _paged_decode_reference(q, cache, pos, rep, bias)


def _paged_decode_reference(q, cache, pos, rep, bias=None):
    """Gather the sequence's pages into a contiguous (b, L, kvh, hd) view
    and run the reference sdpa with a per-row length mask — bit-for-bit
    the static cache computation, with the pool's exact-zero padded
    columns masked to the same -1e9 floor."""
    from ..nn import functional as F

    kp, vp, page_table = cache.k_pool, cache.v_pool, cache.page_table
    b = page_table.shape[0]
    ps = cache.page_size
    length = page_table.shape[1] * ps
    # (kvh, b, maxP, ps, hd) -> (b, L, kvh, hd)
    def gather(pool, scale=None, hd=q.shape[-1]):
        out = _gathered(pool[:, page_table], b, hd)
        if scale is None:
            return out
        return out.astype(jnp.float32) * gather(scale, hd=1)

    kf = _expand_kv(gather(kp, cache.k_scale), rep)
    vf = _expand_kv(gather(vp, cache.v_scale), rep)
    allowed = jnp.arange(length, dtype=jnp.int32)[None, :] <= pos[:, None]
    mask = jnp.where(allowed, 0.0, -1e9).astype(
        jnp.float32)[:, None, None, :]                    # (b, 1, 1, L)
    if bias is not None:
        mask = mask + _crop_bias(bias, length).astype(jnp.float32)
    return F.scaled_dot_product_attention(
        q, Tensor(kf), Tensor(vf), attn_mask=Tensor(mask), is_causal=False)


# ------------------------------------------------------ ragged flat batch

def ragged_attention_available(page_size: int, head_dim: int) -> bool:
    """Shape gates for the Pallas ragged kernel — identical to the decode
    kernel's (same tile geometry, one more prefetched scalar array)."""
    return paged_decode_available(page_size, head_dim)


def ragged_paged_attention(q, cache: PagedLayerCache, pos, rep, bias=None):
    """Flat ragged attention: ALL rows' tokens of a mixed prefill/decode
    step ride one (1, T) sequence axis; `cache.row_ids[t]` names token
    t's page-table row and `pos[0, t]` its global position (= its kv
    length minus one). Decode rows contribute one token, prefill chunks a
    contiguous run; padding tokens park at the table-overflow position
    and attend nothing.

    q: Tensor (1, T, heads, hd); pos: (1, T) int32. Returns ctx Tensor
    (1, T, heads, hd).
    """
    hd = q.shape[-1]
    use_kernel = (KERNEL_MODE != "off" and bias is None
                  and ragged_attention_available(cache.page_size, hd)
                  and (not cache.quantized
                       or KERNEL_MODE == "interpret"
                       or _quant_kernel_ok(cache.page_size))
                  and (KERNEL_MODE == "interpret" or _on_tpu()))
    if use_kernel:
        tag = ("ragged_pallas_interpret"
               if KERNEL_MODE == "interpret" else "ragged_pallas")
        _count_dispatch(tag + "_quant" if cache.quantized else tag)
        qd = q._data if hasattr(q, "_data") else q
        out = _ragged_paged_pallas(qd, cache.k_pool, cache.v_pool,
                                   cache.page_table, pos[0],
                                   cache.row_ids,
                                   k_scale=cache.k_scale,
                                   v_scale=cache.v_scale,
                                   pack=cache.head_pack,
                                   interpret=KERNEL_MODE == "interpret")
        return Tensor(out)
    _count_dispatch("ragged_reference_quant" if cache.quantized
                    else "ragged_reference")
    return _ragged_attention_reference(q, cache, pos, rep, bias)


def _ragged_attention_reference(q, cache, pos, rep, bias=None):
    """Per-token twin of `_paged_decode_reference`: gather each TOKEN's
    page-table row into a contiguous (T, L, kvh, hd) view and run the
    reference sdpa with the same per-token position mask — so a decode
    row's token here is bit-for-bit the (b, 1) decode computation, and a
    chunk's tokens match the chunked-prefill paged gather. Padding
    tokens (position == table capacity) mask everything and produce
    garbage rows the caller never reads."""
    from ..nn import functional as F

    if bias is not None:
        raise NotImplementedError(
            "ragged flat attention does not take an attention bias")
    kp, vp, page_table = cache.k_pool, cache.v_pool, cache.page_table
    rows = cache.row_ids                              # (T,)
    ps = cache.page_size
    t = q.shape[1]
    length = page_table.shape[1] * ps
    pt = page_table[rows]                             # (T, maxP)

    def gather(pool, scale=None, hd=q.shape[-1]):
        out = _gathered(pool[:, pt], t, hd)
        if scale is None:
            return out
        return out.astype(jnp.float32) * gather(scale, hd=1)

    kf = _expand_kv(gather(kp, cache.k_scale), rep)
    vf = _expand_kv(gather(vp, cache.v_scale), rep)
    qd = q._data if hasattr(q, "_data") else q
    qt = Tensor(qd[0][:, None])                       # (T, 1, heads, hd)
    allowed = (jnp.arange(length, dtype=jnp.int32)[None, :]
               <= pos[0][:, None])                    # (T, L)
    mask = jnp.where(allowed, 0.0, -1e9).astype(
        jnp.float32)[:, None, None, :]                # (T, 1, 1, L)
    ctx = F.scaled_dot_product_attention(
        qt, Tensor(kf), Tensor(vf), attn_mask=Tensor(mask),
        is_causal=False)
    cd = ctx._data if hasattr(ctx, "_data") else ctx
    return Tensor(cd[:, 0][None])                     # (1, T, heads, hd)


# ------------------------------------------------------- Pallas decode path

def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# tokens of K/V one compute block of the decode kernel holds per head:
# large enough that a block's matmuls and its handful of copies outweigh
# the loop's fixed cost, small enough that a row's last, partly masked
# block wastes little (on the v5e, 64, 128 and 256 read within 8% of each
# other at 16 heads a step, 128 best; PERF.md section 6, PR 28)
_DECODE_BLOCK_TOKENS = 128
# VMEM the decode kernel's two-slot K/V buffers (and a quantized pool's
# scale rows) may take, well inside the 16 MiB a v5e kernel gets by default
_DECODE_VMEM_BYTES = 4 * 1024 * 1024


def _decode_tiling(kvh: int, ps: int, d_p: int, max_pages: int,
                   itemsize: int, quantized: bool) -> tuple:
    """(kv heads per grid step, pages per compute block) of the decode
    kernel, from the shapes it is handed: a block of about
    `_DECODE_BLOCK_TOKENS` tokens whose columns fill whole 128-lane
    tiles, and the most heads (a divisor of `kvh`, so 1 always does)
    whose double-buffered K and V blocks fit `_DECODE_VMEM_BYTES`. One
    strided copy brings a page for all the heads of a step, so more
    heads a step means fewer, larger copies."""
    lane = 128 // math.gcd(ps, 128)
    ppb = max(lane, _DECODE_BLOCK_TOKENS // ps // lane * lane)
    ppb = min(ppb, _round_up(max_pages, lane))
    per_head = 2 * 2 * ppb * ps * d_p * itemsize
    if quantized:
        # K and V scale rows of the whole table, fp32, one sublane of
        # eight used, double-buffered by the pipeline
        per_head += 2 * 2 * 8 * _round_up(max_pages, ppb) * ps * 4
    hb = max(h for h in range(1, kvh + 1)
             if kvh % h == 0 and (h == 1
                                  or h * per_head <= _DECODE_VMEM_BYTES))
    return hb, ppb


def _paged_decode_kernel(pt_ref, pos_ref, q_ref, k_hbm, v_hbm, *rest,
                         ps, ppb, hb, scale, n_pages, quantized=False):
    """Grid (batch, block of kv heads). The pools stay in HBM; the row's
    own pages are walked by a loop of `cdiv(pos + 1, ppb * ps)` compute
    blocks (0 for a row parked at the table's capacity, `n_pages * ps`).
    A block's `ppb` pages are brought into one of two VMEM slots by async
    copies addressed from the scalar-prefetched page table, one strided
    copy a page for all `hb` heads, and block i + 1 is in flight while
    block i is computed: online softmax with fp32 running maximum, sum
    and accumulator (flash structure), batched over the heads of the step.

    Quantized pools: Mosaic cannot slice an HBM ref whose minor dimension
    is 1, so the (ps, 1) scale slabs cannot be copied page by page; the
    row's scales arrive gathered, a (hb, 1, L) fp32 block with the tokens
    on the lanes, and each tile is dequantized against its slab where the
    tokens are on the lanes too: K's scale multiplies the score columns,
    V's the probabilities, both in fp32 (q . (k * ks) == (q . k) * ks)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if quantized:
        ks_ref, vs_ref, o_ref, k_buf, v_buf, sems = rest
    else:
        o_ref, k_buf, v_buf, sems = rest

    b_ = pl.program_id(0)
    h0 = pl.program_id(1) * hb
    pos = pos_ref[b_]
    bk = ppb * ps
    g_p, d_p = q_ref.shape[2], q_ref.shape[3]
    # a parked row (finished, or batch padding) sits AT the capacity and
    # walks nothing: nobody reads its output. Whole blocks need no mask;
    # the row's last block does unless the row ends exactly on its edge
    length = jnp.where(pos < n_pages * ps, pos + 1, 0)
    n_blocks = (length + bk - 1) // bk
    n_full = length // bk

    def block_copies(i, slot):
        """The copies of compute block `i` into `slot`. Waiting needs a
        descriptor's shapes and semaphore only, so `i=None` (the waits)
        addresses page 0 and reads no table entry."""
        out = []
        for j in range(ppb):
            page = 0 if i is None else pt_ref[b_, i * ppb + j]
            for n, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                out.append(pltpu.make_async_copy(
                    hbm.at[pl.ds(h0, hb), page], buf.at[slot, :, j],
                    sems.at[n, slot]))
        return out

    def start(i, slot):
        for c in block_copies(i, slot):
            c.start()

    def block(i, carry, last):
        """One compute block of the flash update. Only a row's `last`,
        partly filled block has columns past `pos`: there the scores are
        masked and the K/V behind them (the page's stale slots, null
        pages, whatever they hold: parked rows leave NaN in the null
        page) is kept out of the sums."""
        m_prev, l_prev, acc = carry
        slot = jax.lax.rem(i, 2)

        if not last:
            @pl.when(i + 1 < n_blocks)
            def _prefetch():
                start(i + 1, 1 - slot)

        for c in block_copies(None, slot):
            c.wait()
        qblk = q_ref[0]
        kblk = k_buf[slot].reshape(hb, bk, d_p)
        vblk = v_buf[slot].reshape(hb, bk, d_p)
        if last:
            rows = i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk, 1), 1)
            vblk = jnp.where(rows <= pos, vblk, jnp.zeros_like(vblk))
        if quantized:
            qblk = qblk.astype(jnp.float32)
            kblk = kblk.astype(jnp.float32)
            vblk = vblk.astype(jnp.float32)
        # (hb, G, bk) scores: the q groups ride the MXU in the input dtype
        s = jax.lax.dot_general(
            qblk, kblk, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT) * scale
        if quantized:
            col0 = pl.multiple_of(i * bk, 128)
            s = s * ks_ref[0, :, :, pl.ds(col0, bk)]
        if last:
            cols = i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bk), 2)
            s = jnp.where(cols <= pos, s, -jnp.inf)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        # no jnp.isfinite (its primitive has no Mosaic lowering on some
        # jax versions): m_safe only needs the all-masked guard, and
        # exp(-inf - finite) is already an exact 0 for masked columns
        # and never-seen rows alike
        m_safe = jnp.where(m_cur == -jnp.inf, 0.0, m_cur)
        p = jnp.exp(s - m_safe)
        alpha = jnp.exp(m_prev - m_safe)
        l_cur = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        if quantized:
            vs = vs_ref[0, :, :, pl.ds(col0, bk)]
            p = p * (jnp.where(cols <= pos, vs, 0.0) if last else vs)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(vblk.dtype), vblk, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        return m_cur, l_cur, acc

    @pl.when(n_blocks > 0)
    def _first():
        start(0, 0)

    carry = jax.lax.fori_loop(
        0, n_full, functools.partial(block, last=False),
        (jnp.full((hb, g_p, 1), -jnp.inf, jnp.float32),
         jnp.zeros((hb, g_p, 1), jnp.float32),
         jnp.zeros((hb, g_p, d_p), jnp.float32)))
    _, l_fin, acc = jax.lax.cond(
        n_full < n_blocks,
        lambda c: block(n_full, c, last=True), lambda c: c, carry)
    o_ref[0] = (acc / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)


# jitted so that a step with many layers traces the kernel and lowers it
# to Mosaic once, not once a layer: the layers' calls share one function
# of the module (a decode executable's set-up time, PERF.md section 6)
@functools.partial(jax.jit, static_argnames=("pack", "interpret"))
def _paged_decode_pallas(q, k_pool, v_pool, page_table, pos,
                         k_scale=None, v_scale=None, pack=1,
                         interpret=False):
    """q: (b, 1, heads, hd); pools: (kvh, P, ps, hd), or (kvh / pack, P,
    ps, pack * hd) with `pack` heads a row (the view's `head_pack`);
    page_table: (b, maxP) i32; pos: (b,) i32; k_scale/v_scale: optional
    (kvh, P, ps, 1) fp32 scale slabs (quantized pools). Returns (b, 1,
    heads, hd)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, _, heads, hd = q.shape
    kvh, _, ps, width = k_pool.shape
    # a pool of packed heads: `kvh` row blocks of `pack` heads each
    rep = heads // (kvh * pack)
    max_pages = page_table.shape[1]
    scale = 1.0 / (hd ** 0.5)
    quantized = k_scale is not None

    d_p = _round_up(width, 128)
    g_p = _round_up(pack * rep, 8)
    # (b, kvh, G, hd): q head h*rep + g attends kv head h — matches the
    # repeat(axis=2) expansion of the reference path
    qg = _pack_queries(q.reshape(b, kvh * pack, rep, hd), pack)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_p - pack * rep),
                      (0, d_p - width)))
    # no-ops for rows of whole tiles (heads of 128, packed heads of 64):
    # any other width is padded here, the whole pool at every call
    kp = jnp.pad(k_pool, ((0, 0), (0, 0), (0, 0), (0, d_p - width)))
    vp = jnp.pad(v_pool, ((0, 0), (0, 0), (0, 0), (0, d_p - width)))
    hb, ppb = _decode_tiling(kvh, ps, d_p, max_pages, kp.dtype.itemsize,
                             quantized)
    # whole blocks: the last may reach past the table where ppb does not
    # divide it, through null pages whose columns are masked
    table = jnp.pad(page_table.astype(jnp.int32),
                    ((0, 0), (0, _round_up(max_pages, ppb) - max_pages)),
                    constant_values=NULL_PAGE)

    q_spec = pl.BlockSpec((1, hb, g_p, d_p),
                          lambda b_, h_, pt, ps_: (b_, h_, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [q_spec, in_hbm, in_hbm]
    operands = [qg, kp, vp]
    if quantized:
        length = table.shape[1] * ps

        def row_scales(pool):
            # (kvh, P, ps, 1) -> the rows' own scales, tokens on the lanes
            g = pool[..., 0][:, table]                   # (kvh, b, maxP, ps)
            return jnp.transpose(g, (1, 0, 2, 3)).reshape(b, kvh, 1, length)

        sc_spec = pl.BlockSpec((1, hb, 1, length),
                               lambda b_, h_, pt, ps_: (b_, h_, 0, 0))
        in_specs += [sc_spec, sc_spec]
        operands += [row_scales(k_scale), row_scales(v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh // hb),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, hb, ppb, ps, d_p), kp.dtype),
            pltpu.VMEM((2, hb, ppb, ps, d_p), vp.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),     # (K or V, slot)
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, ps=ps, ppb=ppb, hb=hb,
                          scale=scale, n_pages=max_pages,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g_p, d_p), q.dtype),
        interpret=interpret,
        name=scopes.PAGED_DECODE_KERNEL,
    )(table, pos.astype(jnp.int32), *operands)
    return _unpack_context(out[:, :, :pack * rep, :width], pack, rep,
                           hd).reshape(b, 1, heads, hd)


# ------------------------------------------------------------ latent pools
#
# The twin of `paged_attend` for a model with latent attention (MLA,
# models/mla_moe.py), in three pieces because the model does work
# between them: it writes one row a token ([normed latent; rotated rope
# key], `latent_write`), prefills over the step's own expanded K/V
# (`latent_prefill_attention`), and decodes in the absorbed form, the
# scores taken against the cached rows themselves and the latent summed
# under the softmax (`latent_decode_attention`, the `mla_decode` kernel).

def latent_write(rows, cache: LatentLayerCache, start_pos, index_keys=None):
    """Write (b, s, width) rows into the latent pool at each token's own
    position, zero-padded to the pool's whole tiles, and, over a pool
    that holds them, the tokens' (b, s, index width) index keys into the
    same pages and slots. Returns (new cache view, (b, s) positions)."""
    b, s, width = rows.shape
    if (index_keys is None) != (cache.index_pool is None):
        raise ValueError("a latent pool with index keys is written with "
                         "them, one without is not")
    with jax.named_scope(scopes.KV_WRITE):
        pos = _positions(start_pos, b, s)
        entries, slots = _write_targets(cache.page_table, pos,
                                        cache.page_size)
        at = (entries.reshape(-1), slots.reshape(-1))
        rows = jnp.pad(rows.reshape(b * s, width).astype(cache.pool.dtype),
                       ((0, 0), (0, cache.pool.shape[-1] - width)))
        pool = cache.pool.at[at].set(rows)
        index_pool = cache.index_pool
        if index_keys is not None:
            index_pool = index_pool.at[at].set(index_keys.reshape(
                b * s, index_keys.shape[-1]).astype(index_pool.dtype))
    return LatentLayerCache(pool, cache.page_table, index_pool), pos


def latent_prefill_attention(q, k, v, scale: float, live=None):
    """Exact causal attention of a prefill that starts at position 0 over
    the step's own expanded K/V. q, k: (b, s, heads, dk); v: (b, s, heads,
    dv) with dv <= dk. The flash helper takes one width, so V rides padded
    to dk and the output is cut back; `scale` must be dk ** -0.5, which is
    what the helper applies. `live` (a traced int32, or None for all):
    how many of the s positions are the prompt; given, the kernel walks
    the prompt's own blocks alone (`pallas_kernels.flash_prefill`) and
    the rows past it come out zero, on every path."""
    from ..nn import functional as F
    from ..ops import pallas_kernels

    dk, dv = q.shape[-1], v.shape[-1]
    if abs(scale * math.sqrt(dk) - 1.0) > 1e-6:
        raise ValueError("the flash helper scales by head width ** -0.5")
    with jax.named_scope(scopes.PREFILL_ATTENTION):
        _count_dispatch("mla_prefill")
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dk - dv)))
        if live is not None and pallas_kernels.flash_attention_available(
                q, k, vp):
            return pallas_kernels.flash_prefill(q, k, vp, live)[..., :dv]
        ctx = F.scaled_dot_product_attention(
            Tensor(q), Tensor(k), Tensor(vp), is_causal=True)._data
        if live is not None:
            there = jnp.arange(q.shape[1])[None, :, None, None] < live
            ctx = jnp.where(there, ctx, jnp.zeros_like(ctx))
        return ctx[..., :dv]


def _latent_kernel(name: str, page_size: int):
    """Which path a latent pool's kernel `name` takes, counted under it
    in `serving_attention_dispatch_total`: None for the jnp path
    (`<name>_reference`), else the kernel's `interpret` flag."""
    interpret = KERNEL_MODE == "interpret"
    if KERNEL_MODE == "off" or page_size % 8 or not (interpret or _on_tpu()):
        _count_dispatch(name + "_reference")
        return None
    _count_dispatch(name + ("_pallas_interpret" if interpret else "_pallas"))
    return interpret


def latent_decode_attention(q, cache: LatentLayerCache, pos, scale: float,
                            latent: int):
    """One query a row over the row's cached latent rows, absorbed form.

    q: (b, heads, width), a head being [q_nope absorbed into the latent's
    space (`latent` wide); rotated q_rope]; pos: (b,) int32, each row's
    token position. Scores are q . row over the whole width, the output
    the softmax-weighted sum of the rows' first `latent` columns:
    (b, heads, latent), for the model to take through W_uv."""
    with jax.named_scope(scopes.PAGED_ATTENTION):
        interpret = _latent_kernel("mla_decode", cache.page_size)
        if interpret is None:
            return _mla_decode_reference(q, cache, pos, scale, latent)
        return _mla_decode_pallas(
            q, cache.pool, cache.page_table, pos, scale=float(scale),
            latent=latent, interpret=interpret)


def _mla_decode_reference(q, cache, pos, scale, latent):
    """Gather each row's pages into a contiguous (b, L, width) view and
    take the softmax in float32: the kernel's test reference and the
    `KERNEL_MODE="off"` path. Columns past a row's position are masked
    and their rows kept out of the sum, whatever they hold."""
    pool, page_table = cache.pool, cache.page_table
    b, length = page_table.shape[0], page_table.shape[1] * cache.page_size
    rows = pool[page_table].reshape(
        b, length, pool.shape[-1])[..., :q.shape[-1]]
    allowed = jnp.arange(length, dtype=jnp.int32)[None, :] <= pos[:, None]
    rows = jnp.where(allowed[..., None], rows, jnp.zeros_like(rows))
    s = jnp.einsum("bhw,blw->bhl", q, rows,
                   preferred_element_type=jnp.float32) * scale
    # -1e30, not -inf: a parked row sees no column, and its output, which
    # nobody reads, stays finite (a mean of zeroed rows)
    p = jax.nn.softmax(jnp.where(allowed[:, None, :], s, -1e30), -1)
    return jnp.einsum("bhl,blc->bhc", p.astype(rows.dtype),
                      rows[..., :latent],
                      preferred_element_type=jnp.float32).astype(q.dtype)


# tokens of cached rows one compute block of the latent decode kernel
# holds: a row is 1,280 B where a K/V token of 16 heads was 8 KB, and the
# rows run to thousands of tokens, so a block is larger than the K/V
# kernel's: 64 pages of 16 by one copy each, 1.3 MB a slot (on the v5e,
# 32 rows of 2,100-16,800 tokens, whose bytes need 326 us: blocks of 256
# tokens 825 us, 512 655, 1,024 602; PERF.md section 6, PR 29)
_MLA_BLOCK_TOKENS = 1024


def _mla_decode_kernel(pt_ref, pos_ref, q_ref, pool_hbm, o_ref, buf, sems,
                       *, ps, ppb, scale, n_pages, latent):
    """Grid (batch,): built as `_paged_decode_kernel` is. The pool stays
    in HBM; a row's own pages are walked by a loop of `cdiv(pos + 1, ppb *
    ps)` compute blocks (0 for a parked row), a block's `ppb` pages
    brought into one of two VMEM slots by one copy each, block i + 1 in
    flight while block i is computed. All heads share the one cached row
    a token, so they ride the MXU together: scores (heads, block) =
    q . rows over the whole width, then the online softmax's sum of the
    rows' first `latent` columns, fp32 maximum, sum and accumulator."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b_ = pl.program_id(0)
    pos = pos_ref[b_]
    bk = ppb * ps
    g_p, width = q_ref.shape[1], q_ref.shape[2]
    length = jnp.where(pos < n_pages * ps, pos + 1, 0)
    n_blocks = (length + bk - 1) // bk
    n_full = length // bk

    def block_copies(i, slot):
        """The copies of block `i` into `slot`; `i=None` (the waits) needs
        shapes and semaphore only and reads no table entry."""
        return [pltpu.make_async_copy(
            pool_hbm.at[0 if i is None else pt_ref[b_, i * ppb + j]],
            buf.at[slot, j], sems.at[slot]) for j in range(ppb)]

    def start(i, slot):
        for c in block_copies(i, slot):
            c.start()

    def block(i, carry, last):
        m_prev, l_prev, acc = carry
        slot = jax.lax.rem(i, 2)

        if not last:
            @pl.when(i + 1 < n_blocks)
            def _prefetch():
                start(i + 1, 1 - slot)

        for c in block_copies(None, slot):
            c.wait()
        rows = buf[slot].reshape(bk, width)
        if last:
            # past the row's position: stale slots and null pages, kept
            # out of scores and sums whatever they hold
            at = i * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
            rows = jnp.where(at <= pos, rows, jnp.zeros_like(rows))
        s = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT) * scale
        if last:
            cols = i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            s = jnp.where(cols <= pos, s, -jnp.inf)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_safe = jnp.where(m_cur == -jnp.inf, 0.0, m_cur)
        p = jnp.exp(s - m_safe)
        alpha = jnp.exp(m_prev - m_safe)
        l_cur = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :latent],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        return m_cur, l_cur, acc

    @pl.when(n_blocks > 0)
    def _first():
        start(0, 0)

    carry = jax.lax.fori_loop(
        0, n_full, functools.partial(block, last=False),
        (jnp.full((g_p, 1), -jnp.inf, jnp.float32),
         jnp.zeros((g_p, 1), jnp.float32),
         jnp.zeros((g_p, latent), jnp.float32)))
    _, l_fin, acc = jax.lax.cond(
        n_full < n_blocks,
        lambda c: block(n_full, c, last=True), lambda c: c, carry)
    o_ref[0] = (acc / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)


# jitted for the reason `_paged_decode_pallas` is: the layers of a step
# share one traced and lowered kernel
@functools.partial(jax.jit, static_argnames=("scale", "latent", "interpret"))
def _mla_decode_pallas(q, pool, page_table, pos, *, scale, latent,
                       interpret=False):
    """q: (b, heads, row width); pool: (P, ps, width), the row width in
    whole tiles; page_table: (b, maxP) i32; pos: (b,) i32. Returns (b,
    heads, latent)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, _ = q.shape
    _, ps, width = pool.shape
    max_pages = page_table.shape[1]
    g_p = _round_up(heads, 8)
    ppb = min(max(1, _MLA_BLOCK_TOKENS // ps), max_pages)
    # the pool's columns past the row are zero, and so are q's
    qg = jnp.pad(q, ((0, 0), (0, g_p - heads), (0, width - q.shape[2])))
    table = jnp.pad(page_table.astype(jnp.int32),
                    ((0, 0), (0, _round_up(max_pages, ppb) - max_pages)),
                    constant_values=NULL_PAGE)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, g_p, width), lambda b_, pt, ps_: (b_, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, g_p, latent),
                               lambda b_, pt, ps_: (b_, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, ppb, ps, width), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    out = pl.pallas_call(
        functools.partial(_mla_decode_kernel, ps=ps, ppb=ppb, scale=scale,
                          n_pages=max_pages, latent=latent),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g_p, latent), q.dtype),
        interpret=interpret,
        name=scopes.MLA_DECODE_KERNEL,
    )(table, pos.astype(jnp.int32), qg, pool)
    return out[:, :heads]


# ------------------------------------------- indexed (sparse) latent pools
#
# A latent pool whose layers also hold one index key a token
# (`LatentLayerCache.index_pool`, DeepSeek sparse attention): a query
# attends the `topk` cached positions its indexer scores highest, and no
# others. A decode step scores a row's whole context of index keys in
# place (`dsa_index`, 256 B a cached token), chooses, and gathers the
# chosen latent rows alone by token index (`mla_sparse_decode`, 1,280 B a
# chosen token): the row's whole context of latent rows is never read,
# copied or gathered. A prefill from position 0 has every key in the
# step: it scores, chooses and masks a block of queries at a time.

def dsa_index_scores(q, w, cache: LatentLayerCache, pos):
    """Index scores of one query a row against the row's cached index
    keys: I[b, s] = sum_j w[b, j] * relu(q[b, j] . key[b, s]) for s <=
    pos[b], -inf past it, in float32.

    q: (b, heads, width) rotated index queries; w: (b, heads) float32
    head weights, already scaled; pos: (b,) int32. Returns (b, L) float32
    with L at least the table's capacity in tokens."""
    with jax.named_scope(scopes.DSA_INDEX):
        interpret = _latent_kernel("dsa_index", cache.page_size)
        if interpret is None:
            return _dsa_index_reference(q, w, cache, pos)
        return _dsa_index_pallas(q, w, cache.index_pool, cache.page_table,
                                 pos, interpret=interpret)


def _dsa_index_reference(q, w, cache, pos):
    """Gather each row's pages of index keys into a contiguous view: the
    kernel's test reference and the `KERNEL_MODE="off"` path."""
    pool, page_table = cache.index_pool, cache.page_table
    b, length = page_table.shape[0], page_table.shape[1] * cache.page_size
    keys = pool[page_table].reshape(b, length, pool.shape[-1])
    s = jnp.einsum("bhw,blw->bhl", q, keys,
                   preferred_element_type=jnp.float32)
    s = jnp.sum(jnp.maximum(s, 0.0) * w.astype(jnp.float32)[..., None], 1)
    allowed = jnp.arange(length, dtype=jnp.int32)[None, :] <= pos[:, None]
    return jnp.where(allowed, s, -jnp.inf)


# positions a block of the choice's compaction holds: a 128-lane row
_SELECT_BLOCK = 128


def dsa_select(scores, pos, topk: int):
    """The positions a decode row attends: the `topk` highest scores, the
    lower position first among equals. Returns (b, topk) int32 positions
    in ascending order, of which the first n[b] = min(pos[b] + 1, topk)
    are the choice (a parked row chooses none), and n, (b,) int32.

    Neither a sort nor a scatter, both of which the chip does slowly (a
    `jax.lax.top_k` of 2,048 from 40,960 is a full variadic sort, 3.8 ms
    for 32 rows inside the decode block; PERF.md section 6, PR 35): the
    chosen set is a threshold (`_highest`), and its members' positions
    are read off running counts. Within a block of 128 positions the
    running count is a product with a triangle of ones; the block that
    holds the m-th member is the number of blocks that end before it;
    that block's counts come to the member by a one-hot product (exact:
    every value is an integer under 256 in bf16, sums in float32), and
    its place in the block is how many counts lie under its rank."""
    with jax.named_scope(scopes.DSA_SELECT):
        b, length = scores.shape
        k = min(topk, length)
        live = pos < length
        n = jnp.where(live, jnp.minimum(pos + 1, k), 0).astype(jnp.int32)
        blk = _SELECT_BLOCK
        padded = -(-length // blk) * blk
        scores = jnp.pad(scores, ((0, 0), (0, padded - length)),
                         constant_values=-jnp.inf)
        cols = jnp.arange(padded, dtype=jnp.int32)[None]
        chosen = _highest(scores, k) & (cols <= pos[:, None])
        nb = padded // blk
        triangle = (jnp.arange(blk)[:, None] <= jnp.arange(blk)[None, :]
                    ).astype(jnp.bfloat16)
        # (b, nb, blk): members among a block's positions 0 .. j
        within = jnp.einsum(
            "bnp,pq->bnq", chosen.reshape(b, nb, blk).astype(jnp.bfloat16),
            triangle, preferred_element_type=jnp.float32)
        counts = within[..., -1]
        ends = jnp.cumsum(counts, axis=1)                       # (b, nb)
        rank = jnp.arange(k, dtype=jnp.float32)[None, :, None]  # m
        before = ends[:, None, :] <= rank                       # (b, k, nb)
        block = jnp.sum(before, -1).astype(jnp.int32)   # nb: past the set
        start = jnp.sum(jnp.where(before, counts[:, None, :], 0.0), -1)
        onehot = (block[..., None] == jnp.arange(nb, dtype=jnp.int32)
                  ).astype(jnp.bfloat16)
        mine = jnp.einsum("bkn,bnq->bkq", onehot,
                          within.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
        place = jnp.sum(mine < (rank[..., 0] - start + 1.0)[..., None],
                        -1).astype(jnp.int32)
        where = jnp.minimum(block * blk + place, length - 1)
        return where.astype(jnp.int32), n


def _page_lookup(page_table, index):
    """page_table[b, index[b, k]] without a gather (of scalars the chip
    brings one at a time: 2.1 ms for 65,536 inside the decode block):
    the table in rows of 64, a row chosen by a one-hot product, an entry
    of it by a comparison. A page id rides as two bytes, each exact in
    bf16, sums in float32: exact for ids under 65,536, which is all an
    indexed pool may hold (`kv_cache.INDEXED_POOL_MAX_PAGES`)."""
    b, max_pages = page_table.shape
    row = 64
    rows = -(-max_pages // row)
    table = jnp.pad(page_table.astype(jnp.int32),
                    ((0, 0), (0, rows * row - max_pages))).reshape(b, rows,
                                                                   row)
    index = jnp.clip(index, 0, max_pages - 1)
    onehot = (index[..., None] // row == jnp.arange(rows, dtype=jnp.int32)
              ).astype(jnp.bfloat16)
    both = jnp.concatenate([table >> 8, table & 255], -1).astype(
        jnp.bfloat16)                                   # (b, rows, 2 * row)
    mine = jnp.einsum("bkr,brc->bkc", onehot, both,
                      preferred_element_type=jnp.float32)
    entry = (index[..., None] % row == jnp.arange(row, dtype=jnp.int32))
    high = jnp.sum(jnp.where(entry, mine[..., :row], 0.0), -1)
    low = jnp.sum(jnp.where(entry, mine[..., row:], 0.0), -1)
    return (high * 256.0 + low).astype(jnp.int32)


def sparse_latent_decode_attention(q, cache: LatentLayerCache, chosen, n,
                                   scale: float, latent: int):
    """`latent_decode_attention` over the chosen positions alone.

    q: (b, heads, width) absorbed queries; chosen: (b, k) int32 token
    positions of which the first n[b] count. Returns (b, heads, latent)."""
    with jax.named_scope(scopes.DSA_ATTEND):
        interpret = _latent_kernel("mla_sparse_decode", cache.page_size)
        if interpret is None:
            return _mla_sparse_decode_reference(q, cache, chosen, n, scale,
                                                latent)
        return _mla_sparse_decode_pallas(
            q, cache.pool, cache.page_table, chosen, n, scale=float(scale),
            latent=latent, interpret=interpret)


def _mla_sparse_decode_reference(q, cache, chosen, n, scale, latent):
    """Gather the chosen rows through the page table and take the softmax
    in float32: the kernel's test reference."""
    pool, page_table, ps = cache.pool, cache.page_table, cache.page_size
    pages = jnp.take_along_axis(
        page_table, jnp.clip(chosen // ps, 0, page_table.shape[1] - 1), 1)
    rows = pool[pages, chosen % ps][..., :q.shape[-1]]      # (b, k, width)
    allowed = jnp.arange(chosen.shape[1], dtype=jnp.int32)[None] < n[:, None]
    rows = jnp.where(allowed[..., None], rows, jnp.zeros_like(rows))
    s = jnp.einsum("bhw,bkw->bhk", q, rows,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(allowed[:, None, :], s, -1e30), -1)
    return jnp.einsum("bhk,bkc->bhc", p.astype(rows.dtype),
                      rows[..., :latent],
                      preferred_element_type=jnp.float32).astype(q.dtype)


# keys a prefill's index scores are taken against at a time: the scores
# of a block of queries are (queries, heads, keys) float32 before the
# heads are summed, 128 MB at 512 x 64 x 1,024
_DSA_PREFILL_KEY_BLOCK = 1024


def sparse_prefill_mask(q, w, keys, first, topk: int):
    """The additive mask of a block of a prefill's queries: 0 where query
    t attends key s, -inf elsewhere. Query t attends the min(topk, t + 1)
    keys s <= t of largest I[t, s] = sum_j w[t, j] relu(q[t, j] . key[s]),
    the lower position first among equals; scores, ReLU, sum and choice
    in float32.

    q: (bq, heads, width) rotated index queries at positions first ..
    first + bq - 1; w: (bq, heads) float32; keys: (L, width), every key
    of the step. Returns (bq, L) float32."""
    bq, length = q.shape[0], keys.shape[0]
    kb = min(_DSA_PREFILL_KEY_BLOCK, length)
    # whole blocks of keys: those added lie past every query
    padded = -(-length // kb) * kb
    keys = jnp.pad(keys, ((0, padded - length), (0, 0)))
    with jax.named_scope(scopes.DSA_INDEX):
        def scores(block):
            s = jnp.einsum("qhw,kw->qhk", q, block,
                           preferred_element_type=jnp.float32)
            return jnp.sum(jnp.maximum(s, 0.0)
                           * w.astype(jnp.float32)[..., None], 1)

        index = jax.lax.map(scores, keys.reshape(padded // kb, kb, -1))
        index = jnp.moveaxis(index, 0, 1).reshape(bq, padded)[:, :length]
        at = first + jnp.arange(bq, dtype=jnp.int32)[:, None]
        cols = jnp.arange(length, dtype=jnp.int32)[None, :]
        index = jnp.where(cols <= at, index, -jnp.inf)
    with jax.named_scope(scopes.DSA_SELECT):
        chosen = _highest(index, min(topk, length)) & (cols <= at)
        return jnp.where(chosen, 0.0, -jnp.inf).astype(jnp.float32)


def _highest(scores, k: int):
    """(rows, n) bool: each row's k highest scores, the lower position
    first among equals. Without a sort, which the chip does slowly: the
    k-th highest value of a row is found a bit at a time, from the top
    bit down, each a comparison and a count over the row (32 passes of
    elementwise work; a sort of n is some log2(n)^2 / 2 passes of
    exchanges); everything above it is in, and of those that equal it the
    first few in position order, which takes a running count only where
    some row has equals at the edge."""
    # float32 to integers of the same order; -0.0 counts as 0.0
    scores = jnp.where(scores == 0, 0.0, scores.astype(jnp.float32))
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def refine(i, least):
        trial = least | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
            jnp.uint32)))
        enough = jnp.sum(keys >= trial, -1, keepdims=True) >= k
        return jnp.where(enough, trial, least)

    least = jax.lax.fori_loop(
        0, 32, refine, jnp.zeros((scores.shape[0], 1), jnp.uint32))
    above = keys > least
    spare = k - jnp.sum(above, -1, keepdims=True)    # >= 1 equals are in

    def by_position(_):
        equal = keys == least
        return above | (equal & (jnp.cumsum(equal, -1) <= spare))

    exact = jnp.all(jnp.sum(keys >= least, -1, keepdims=True) == k)
    return jax.lax.cond(exact, lambda _: keys >= least, by_position, None)


# tokens of index keys one compute block of `dsa_index` holds: a key is
# 256 B and a page of 16 one 4 KB copy, so a block is 128 pages, 512 KB a
# slot
_DSA_INDEX_BLOCK_TOKENS = 2048


def _dsa_index_kernel(pt_ref, pos_ref, q_ref, w_ref, keys_hbm, o_ref, buf,
                      sems, *, ps, ppb, n_pages):
    """Grid (batch,): built as `_mla_decode_kernel` is. The key pool stays
    in HBM; a row's own pages are walked in `cdiv(pos + 1, ppb * ps)`
    blocks, block i + 1 in flight while block i is scored. All heads
    share the one key a token: scores (heads, block) on the MXU, then
    ReLU, the heads' weights and their sum in float32. Blocks past the
    row's position are never read and stay -inf."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b_ = pl.program_id(0)
    pos = pos_ref[b_]
    bk = ppb * ps
    width = q_ref.shape[2]
    length = jnp.where(pos < n_pages * ps, pos + 1, 0)
    n_blocks = (length + bk - 1) // bk

    def block_copies(i, slot):
        return [pltpu.make_async_copy(
            keys_hbm.at[0 if i is None else pt_ref[b_, i * ppb + j]],
            buf.at[slot, j], sems.at[slot]) for j in range(ppb)]

    def start(i, slot):
        for c in block_copies(i, slot):
            c.start()

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    @pl.when(n_blocks > 0)
    def _first():
        start(0, 0)

    def block(i, carry):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            start(i + 1, 1 - slot)

        for c in block_copies(None, slot):
            c.wait()
        keys = buf[slot].reshape(bk, width)
        s = jax.lax.dot_general(
            q_ref[0], keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        s = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0], axis=0, keepdims=True)
        cols = i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        # past the row's position: stale slots and null pages, whatever
        # they hold
        o_ref[0, pl.ds(i, 1), :] = jnp.where(cols <= pos, s, -jnp.inf)
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _dsa_index_pallas(q, w, keys, page_table, pos, *, interpret=False):
    """q: (b, heads, width); w: (b, heads) f32; keys: (P, ps, width);
    page_table: (b, maxP) i32; pos: (b,) i32. Returns (b, L) f32, L the
    table's capacity in whole blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, width = q.shape
    _, ps, _ = keys.shape
    max_pages = page_table.shape[1]
    g_p = _round_up(heads, 8)
    ppb = min(max(1, _DSA_INDEX_BLOCK_TOKENS // ps), max_pages)
    n_blk = -(-max_pages // ppb)
    qg = jnp.pad(q, ((0, 0), (0, g_p - heads), (0, 0)))
    wg = jnp.pad(w.astype(jnp.float32), ((0, 0), (0, g_p - heads)))[..., None]
    table = jnp.pad(page_table.astype(jnp.int32),
                    ((0, 0), (0, n_blk * ppb - max_pages)),
                    constant_values=NULL_PAGE)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, g_p, width), lambda b_, pt, ps_: (b_, 0, 0)),
                  pl.BlockSpec((1, g_p, 1), lambda b_, pt, ps_: (b_, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, n_blk, ppb * ps),
                               lambda b_, pt, ps_: (b_, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, ppb, ps, width), keys.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    out = pl.pallas_call(
        functools.partial(_dsa_index_kernel, ps=ps, ppb=ppb,
                          n_pages=max_pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_blk, ppb * ps), jnp.float32),
        interpret=interpret,
        name=scopes.DSA_INDEX_KERNEL,
    )(table, pos.astype(jnp.int32), qg, wg, keys)
    return out.reshape(b, n_blk * ppb * ps)


# chosen rows one grid step of `mla_sparse_decode` attends: 640 KB a
# block of 1,280 B rows
_DSA_ATTEND_TOKENS = 512


def _gather_chosen(pool, page_table, chosen):
    """The chosen tokens' cached rows, (b, k, width), through the page
    table. The pool's tiles are 8 tokens deep and a kernel's copy cannot
    start inside one (Mosaic: "slice shape must be aligned to tiling"),
    so the rows are brought by XLA's gather, which can."""
    ps = pool.shape[1]
    return pool[_page_lookup(page_table, chosen // ps), chosen % ps]


def _mla_sparse_decode_kernel(n_ref, q_ref, rows_ref, o_ref, m_ref, l_ref,
                              acc_ref, *, bk, scale, latent):
    """Grid (batch, blocks of chosen rows), the blocks innermost. As
    `_mla_decode_kernel` over the gathered rows: scores over the row's
    whole width for all heads at once, online softmax in float32, the sum
    of the rows' first `latent` columns. A block past the row's count is
    skipped; in the last one the rows past it are kept out of scores and
    sums whatever they hold."""
    from jax.experimental import pallas as pl

    b_, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[b_]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, m_ref.dtype)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bk < n)
    def _block():
        at = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        rows = rows_ref[0]
        rows = jnp.where(at < n, rows, jnp.zeros_like(rows))
        s = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT) * scale
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(cols < n, s, -jnp.inf)
        # the block holds a chosen row, so its maximum is finite
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :latent],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)
        m_ref[...] = m_cur

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "latent", "interpret"))
def _mla_sparse_decode_pallas(q, pool, page_table, chosen, n, *, scale,
                              latent, interpret=False):
    """q: (b, heads, row width); pool: (P, ps, width); page_table: (b,
    maxP) i32; chosen: (b, k) i32 positions; n: (b,) i32. Returns (b,
    heads, latent)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, _ = q.shape
    width = pool.shape[2]
    g_p = _round_up(heads, 8)
    bk = min(_DSA_ATTEND_TOKENS, _round_up(chosen.shape[1], 16))
    k = _round_up(chosen.shape[1], bk)
    chosen = jnp.pad(chosen, ((0, 0), (0, k - chosen.shape[1])))
    rows = _gather_chosen(pool, page_table, chosen)
    # the pool's columns past the row are zero, and so are q's
    qg = jnp.pad(q, ((0, 0), (0, g_p - heads), (0, width - q.shape[2])))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, k // bk),
        in_specs=[pl.BlockSpec((1, g_p, width), lambda b_, j, n_: (b_, 0, 0)),
                  pl.BlockSpec((1, bk, width), lambda b_, j, n_: (b_, j, 0))],
        out_specs=pl.BlockSpec((1, g_p, latent),
                               lambda b_, j, n_: (b_, 0, 0)),
        scratch_shapes=[pltpu.VMEM((g_p, 1), jnp.float32),
                        pltpu.VMEM((g_p, 1), jnp.float32),
                        pltpu.VMEM((g_p, latent), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_mla_sparse_decode_kernel, bk=bk, scale=scale,
                          latent=latent),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g_p, latent), q.dtype),
        interpret=interpret,
        name=scopes.MLA_SPARSE_DECODE_KERNEL,
    )(n.astype(jnp.int32), qg, rows)
    return out[:, :heads]


# ------------------------------------------------------- Pallas ragged path

def _ragged_attend_kernel(pt_ref, pos_ref, row_ref, q_ref, k_ref, v_ref,
                          *rest, ps, scale, n_pages, quantized=False):
    """Grid (token, kv_head, page) over a flat TOKEN axis: one (page_size,
    head_dim) K/V tile a grid step, the BlockSpec index map gathering
    page `pi` of token t's OWN page-table row (row_ref, scalar-prefetched
    alongside the table); online softmax in fp32 VMEM scratch (flash
    structure). Pages wholly past the token's position are skipped
    splash-style, and tokens parked at the table capacity (flat-batch
    padding) skip every page and emit zeros. Quantized pools dequantize
    each K/V tile in-register against its (page_size, 1) scale tile.
    The page axis of the grid is the table's capacity, not the token's
    length: the grid the decode kernel had until it walked a row's own
    pages in blocks (PERF.md section 7)."""
    from jax.experimental import pallas as pl

    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest

    t_ = pl.program_id(0)
    pi = pl.program_id(2)
    pos = pos_ref[t_]

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute():
        if quantized:
            qblk = q_ref[0, 0].astype(jnp.float32)
            kblk = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]
        else:
            qblk = q_ref[0, 0]
            kblk = k_ref[0, 0]
        s = jax.lax.dot_general(
            qblk, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT) * scale
        cols = pi * ps + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(cols <= pos, s, -jnp.inf)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # same all-masked guard as the decode kernel: no jnp.isfinite
        # (no Mosaic lowering on some jax versions)
        m_safe = jnp.where(m_cur == -jnp.inf, 0.0, m_cur)
        p = jnp.exp(s - m_safe)
        alpha = jnp.exp(m_prev - m_safe)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_cur
        vblk = v_ref[0, 0]
        if quantized:
            vblk = vblk.astype(jnp.float32) * vs_ref[0, 0]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT)

    # padding tokens sit exactly AT the table capacity (n_pages * ps), so
    # the second clause skips all their pages; real tokens always sit
    # below it
    pl.when((pi * ps <= pos) & (pos < n_pages * ps))(_compute)

    @pl.when(pi == n_pages - 1)
    def _done():
        l_fin = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l_fin).astype(o_ref.dtype)


def _ragged_paged_pallas(q, k_pool, v_pool, page_table, pos, row_ids,
                         k_scale=None, v_scale=None, pack=1,
                         interpret=False):
    """q: (1, T, heads, hd); pools: (kvh, P, ps, hd), `pack` heads a
    row as the decode kernel's; page_table: (B, maxP) i32; pos/row_ids:
    (T,) i32; k_scale/v_scale: optional (kvh, P, ps, 1) fp32 scale
    slabs. Returns (1, T, heads, hd)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, t, heads, hd = q.shape
    kvh, _, ps, width = k_pool.shape
    rep = heads // (kvh * pack)
    max_pages = page_table.shape[1]
    scale = 1.0 / (hd ** 0.5)
    quantized = k_scale is not None

    d_p = _round_up(width, 128)
    g_p = _round_up(pack * rep, 8)
    # (T, kvh, G, hd): q head h*rep + g attends kv head h, exactly the
    # decode kernel's grouping with tokens in place of batch rows
    qg = _pack_queries(q.reshape(t, kvh * pack, rep, hd), pack)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_p - pack * rep),
                      (0, d_p - width)))
    kp = jnp.pad(k_pool, ((0, 0), (0, 0), (0, 0), (0, d_p - width)))
    vp = jnp.pad(v_pool, ((0, 0), (0, 0), (0, 0), (0, d_p - width)))

    q_spec = pl.BlockSpec((1, 1, g_p, d_p),
                          lambda t_, h_, pi, pt, ps_, rw: (t_, h_, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, ps, d_p),
        lambda t_, h_, pi, pt, ps_, rw: (h_, pt[rw[t_], pi], 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qg, kp, vp]
    if quantized:
        sc_spec = pl.BlockSpec(
            (1, 1, ps, 1),
            lambda t_, h_, pi, pt, ps_, rw: (h_, pt[rw[t_], pi], 0, 0))
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(t, kvh, max_pages),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((g_p, d_p), jnp.float32),
            pltpu.VMEM((g_p, 1), jnp.float32),
            pltpu.VMEM((g_p, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_attend_kernel, ps=ps, scale=scale,
                          n_pages=max_pages, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, kvh, g_p, d_p), q.dtype),
        interpret=interpret,
        name=scopes.PAGED_RAGGED_KERNEL,
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32),
      row_ids.astype(jnp.int32), *operands)
    return _unpack_context(out[:, :, :pack * rep, :width], pack, rep,
                           hd).reshape(1, t, heads, hd)
