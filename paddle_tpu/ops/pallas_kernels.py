"""Pallas TPU kernels — the PHI `fusion/` + flash-attention analog (ref:
paddle/phi/kernels/gpu/flash_attn_kernel.cu over the external flashattn lib,
upstream layout, unverified — mount empty).

Selection policy: the functional layer calls *_available() first; on non-TPU
backends we fall back to the jnp reference op and let XLA fuse. The kernels
follow the pallas_guide.md playbook: grid over (batch, heads, q-blocks,
k-blocks), K/V tiles resident in VMEM, online-softmax accumulation in fp32,
inner grid dimension = the accumulated one (TPU grids iterate the last
dimension fastest).

Round-2 widening (the round-1 kernel demanded d%128==0 and seq%512==0, so the
flagship head_dim-64 models never hit it, and it had NO backward — jax.vjp
through pallas_call raises, so the training bench could never use it):
- any head_dim 8..256: zero-padded to a 128-lane multiple (exact: zero
  d-lanes contribute nothing to q·k nor to the sliced output);
- any seq length: padded to the block size; padded K columns masked to -inf,
  padded Q rows sliced off (their gradients are zero, see _flash_bwd);
- additive float attn_mask (paddle semantics), broadcastable over heads;
- full flash BACKWARD (recompute-based: dq kernel accumulating over k-blocks,
  dk/dv kernel accumulating over q-blocks, logsumexp residual from forward)
  wired through jax.custom_vjp so Tensor.backward()/jax.grad work;
- interpret=True runs the same kernels on CPU for hermetic CI.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_BLOCK_Q = 512
_BLOCK_K = 512


def _on_tpu() -> bool:
    # a backend that fails to start raises: the kernels never give way
    # to their references because the device could not be reached
    return jax.devices()[0].platform == "tpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def flash_attention_available(q, k, v, attn_mask=None) -> bool:
    if not _on_tpu():
        return False
    qd = q._data if hasattr(q, "_data") else q
    if qd.ndim != 4:
        return False
    d = qd.shape[3]
    if attn_mask is not None:
        md = attn_mask._data if hasattr(attn_mask, "_data") else attn_mask
        if md.ndim != 4 or not jnp.issubdtype(md.dtype, jnp.floating):
            return False  # boolean masks go through the XLA reference path
    return 8 <= d <= 256


def _pick_block(s: int, cap: int) -> int:
    """Largest 128-multiple <= cap covering s without excessive padding."""
    return min(cap, _round_up(s, 128))


def _keep_mask(pltpu, seed_ref, b_, h_, qi, ki, shape, dropout_p,
               interpret):
    """Per-(batch, head, q-block, k-block) dropout keep mask. Seeding with
    the same 5-tuple in forward and both backward kernels reproduces the
    identical mask — the recompute-based backward never materializes it.
    Real TPU uses the on-chip PRNG; interpret mode (no Mosaic prng lowering
    on CPU) emulates with threefry fold-ins — each path is internally
    consistent fwd/bwd, which is the contract that matters."""
    if interpret:
        key = jax.random.key(seed_ref[0].astype(jnp.uint32))
        for t in (b_, h_, qi, ki):
            key = jax.random.fold_in(key, t)
        bits = jax.random.bits(key, shape, jnp.uint32)
    else:
        # Mosaic's prng_set_seed_32 takes at most 2 seed words; fold the
        # 4 block coordinates into one i32 with odd-constant mixing
        # (wrapping int32 arithmetic decorrelates neighboring blocks)
        mixed = (b_ * jnp.int32(-1640531527)) ^ (h_ * jnp.int32(97) +
                 qi * jnp.int32(1000003)) ^ (ki * jnp.int32(13176917))
        pltpu.prng_seed(seed_ref[0], mixed)
        # prng_random_bits returns SIGNED int32 (jax 0.9 abstract eval) —
        # compare in uint32 or half the bits sit below any uint threshold
        bits = pltpu.prng_random_bits(shape).astype(jnp.uint32)
    thresh = np.uint32(min(int(dropout_p * (2.0 ** 32)), 2 ** 32 - 1))
    return bits >= thresh


def _sds(shape, dtype, vma):
    """ShapeDtypeStruct with varying-manual-axes when running inside a
    shard_map region (check_vma=True requires pallas outputs to declare
    which mesh axes they vary over)."""
    if vma is not None:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------- forward

def _causal_block_live(qi, ki, block_q, block_k, offs=None):
    """Whether block (qi, ki) holds a score the causal mask lets through:
    its last row against its first column, by the in-block mask's own
    `rows >= cols` rule. A block that is not live is all -inf: it leaves
    m, l and the accumulators as they were, so the three kernels run
    their bodies under this predicate alone. `offs` holds a ring step's
    global (q, k) offsets (the SMEM ref) or is None; Python integers work
    as well as grid ids."""
    q_off, k_off = (0, 0) if offs is None else (offs[0], offs[1])
    return q_off + (qi + 1) * block_q - 1 >= k_off + ki * block_k


def flash_block_steps(sq_p, sk_p, block_q, block_k, is_causal, live=None):
    """(computed, total) grid steps a (batch, head) of one flash call over
    padded lengths `sq_p` x `sk_p`: what the kernels' predicate keeps, for
    the tests, the serving counters and PERF.md's reckoning. With `live`
    (causal only: `flash_prefill`) the q blocks past the first `live`
    rows compute nothing either."""
    n_q, n_k = sq_p // block_q, sk_p // block_k
    if not is_causal:
        return n_q * n_k, n_q * n_k
    rows = n_q if live is None else min(-(-live // block_q), n_q)
    steps = sum(bool(_causal_block_live(qi, ki, block_q, block_k))
                for qi in range(rows) for ki in range(n_k))
    return steps, n_q * n_k


def _qkv_layout(qt, kt, *, heads, block_q, block_k, kv_major, vma,
                clamp_causal=False):
    """Shared layout selection for the three flash kernels.

    Returns (b, h, sq_p, sk_p, d_p, blk, q_spec, k_spec, sds_like,
    coords) where `blk` slices a grid block out of a q/k/v/do ref,
    `q_spec`/`k_spec` are the BlockSpecs for row/col operands,
    `sds_like(rows_p, dt)` builds an output ShapeDtypeStruct in the active
    layout, and `coords(i2, i3)` gives the (qi, ki) of the blocks a grid
    step brings, for the specs the callers build themselves. `kv_major`
    flips the grid's (qi, ki) order to (ki, qi) — the dkv kernel
    accumulates over q, so its k index comes third.

    With `clamp_causal` (the static causal mask: no ring offsets) a step
    that `_causal_block_live` rules out names the nearest live block of
    its row instead of its own — the one already in VMEM — so the
    pipeline brings nothing for a step that computes nothing: the k side
    is clamped from above, or with `kv_major` the q side from below."""
    from jax.experimental import pallas as pl

    packed = heads is not None
    if packed:
        b, sq_p, hd = qt.shape
        h = heads
        d_p = hd // h
        sk_p = kt.shape[1]
    else:
        b, h, sq_p, d_p = qt.shape
        sk_p = kt.shape[2]

    def spec(block, pick):
        # pick selects this operand's row coordinate from (third, fourth)
        # grid ids; the other two grid ids are always (b, h)
        if packed:
            return pl.BlockSpec(
                (1, block, d_p),
                lambda b_, h_, i2, i3: (b_, pick(i2, i3), h_))
        return pl.BlockSpec(
            (1, 1, block, d_p),
            lambda b_, h_, i2, i3: (b_, h_, pick(i2, i3), 0))

    n_q = sq_p // block_q

    def coords(i2, i3):
        # grid (b, h, ki, qi) when kv_major, else (b, h, qi, ki)
        qi, ki = (i3, i2) if kv_major else (i2, i3)
        if clamp_causal and kv_major:
            # first live q block of this k column; a column past every
            # row (sk > sq) has none and stays on the last block
            qi = jnp.minimum(jnp.maximum(qi, ki * block_k // block_q),
                             n_q - 1)
        elif clamp_causal:
            ki = jnp.minimum(ki, ((qi + 1) * block_q - 1) // block_k)
        return qi, ki

    q_spec = spec(block_q, lambda i2, i3: coords(i2, i3)[0])
    k_spec = spec(block_k, lambda i2, i3: coords(i2, i3)[1])

    def sds_like(rows_p, dtype):
        if packed:
            return _sds((b, rows_p, h * d_p), dtype, vma)
        return _sds((b, h, rows_p, d_p), dtype, vma)

    blk = (lambda ref: ref[0]) if packed else (lambda ref: ref[0, 0])
    return b, h, sq_p, sk_p, d_p, blk, q_spec, k_spec, sds_like, coords


def _blk_store(packed, ref, value):
    if packed:
        ref[0] = value
    else:
        ref[0, 0] = value


def _mask_spec(coords, block_q, block_k, b_is_one, h_is_one, q_is_one):
    """BlockSpec of the additive mask: broadcast (size-1) dims stay on
    block 0, the others follow the step's (qi, ki) of `_qkv_layout`."""
    from jax.experimental import pallas as pl

    def index(b_, h_, i2, i3):
        qi, ki = coords(i2, i3)
        return (0 if b_is_one else b_, 0 if h_is_one else h_,
                0 if q_is_one else qi, ki)

    return pl.BlockSpec((1, 1, 1 if q_is_one else block_q, block_k), index)


def _online_softmax(s, m_ref, l_ref):
    """One k block's step of the forward's running softmax: folds the
    block's (block_q, block_k) scores `s` into the row maxima `m_ref`
    and sums `l_ref`. Returns the block's unnormalized weights and the
    factor the accumulator is rescaled by."""
    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # fully-masked rows keep m=-inf; clamp so exp(-inf--inf) != nan.
    # In-kernel values are finite or -inf by construction, and the
    # is_finite primitive has no Mosaic lowering on this jax — the
    # != -inf test is the same guard and compiles
    m_safe = jnp.where(m_cur != -jnp.inf, m_cur, 0.0)
    p = jnp.exp(jnp.where(s != -jnp.inf, s - m_safe, -jnp.inf))
    alpha = jnp.where(m_prev != -jnp.inf,
                      jnp.exp(m_prev - m_safe), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1,
                                              keepdims=True)
    m_ref[...] = m_cur
    return p, alpha


def _fwd_call(qt, kt, vt, mask, seed, *, scale, sk, is_causal, has_mask,
              mask_b_is_one, mask_h_is_one, mask_q_is_one, block_q, block_k,
              dropout_p, interpret, offs=None, keep_neg_inf_lse=False,
              vma=None, heads=None):
    """qt/kt/vt: padded (b, h, S, D) — or, with `heads=h`, the PACKED
    layout (b, S, h*D): the per-head slab is addressed by the BlockSpec
    index map's h coordinate instead of a transposed axis, so the caller
    never materializes a bshd->bhsd transpose (r5 trace: ~5 ms/step of
    relayout at ERNIE-base). Returns (out_padded, logsumexp).

    `offs` (i32[2] in SMEM: global q-row / k-col offsets) generalizes causal
    masking to ring attention, where the q and k shards sit at different
    global sequence positions per step. With `keep_neg_inf_lse`, fully
    masked rows report lse=-inf (so a ring merge weighs them at zero)
    instead of the 0.0 clamp the single-call path uses."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    packed = heads is not None
    dyn_offsets = offs is not None
    (b, h, sq_p, sk_p, d_p, blk, q_spec, k_spec, sds_like,
     coords) = _qkv_layout(
        qt, kt, heads=heads, block_q=block_q, block_k=block_k,
        kv_major=False, vma=vma,
        clamp_causal=is_causal and not dyn_offsets)
    n_q, n_k = sq_p // block_q, sk_p // block_k
    need_k_mask = sk_p != sk
    has_dropout = dropout_p > 0.0

    def kernel(*refs):
        refs = list(refs)
        q_ref, k_ref, v_ref = refs[:3]
        refs = refs[3:]
        m_in_ref = refs.pop(0) if has_mask else None
        seed_ref = refs.pop(0) if has_dropout else None
        offs_ref = refs.pop(0) if dyn_offsets else None
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        # grid ids are read here: the body below may run under pl.when
        b_, h_ = pl.program_id(0), pl.program_id(1)
        qi = pl.program_id(2)
        ki = pl.program_id(3)

        @pl.when(ki == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)

        def _compute():
            # qk matmul stays in the INPUT dtype (bf16 rides the MXU
            # natively; f32 upcast triples the passes) w/ f32 accumulation.
            # precision is pinned on every kernel dot: a global
            # jax_default_matmul_precision="highest" would otherwise force
            # an fp32 contract on bf16 vectors, which Mosaic rejects
            # ("Bad lhs type" — caught by tests/test_chip_compile.py)
            s = jax.lax.dot_general(
                blk(q_ref), blk(k_ref), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT) * scale
            if has_mask:
                s = s + m_in_ref[0, 0].astype(jnp.float32)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            if is_causal:
                rows = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                if dyn_offsets:
                    s = jnp.where(rows + offs_ref[0] >= cols + offs_ref[1],
                                  s, -jnp.inf)
                else:
                    s = jnp.where(rows >= cols, s, -jnp.inf)
            if need_k_mask:
                s = jnp.where(cols < sk, s, -jnp.inf)
            p, alpha = _online_softmax(s, m_ref, l_ref)
            vblk = blk(v_ref)
            # attention dropout (upscale_in_train): drop unnormalized
            # weights in the value accumulation; the softmax denominator l
            # uses UNdropped p
            p_acc = p
            if has_dropout:
                keep = _keep_mask(pltpu, seed_ref, b_, h_, qi, ki,
                                  (block_q, block_k), dropout_p, interpret)
                p_acc = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
            # p cast to V's dtype: bf16 inputs keep the PV matmul on the
            # MXU's native path (f32 accumulation)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p_acc.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)

        if is_causal:
            # splash-style whole-block skip: a k block that lies entirely
            # in the future of its q block contributes nothing — skip its
            # work (the uniform grid still visits the step, so a ring's
            # SPMD program stays identical on every rank)
            pl.when(_causal_block_live(qi, ki, block_q, block_k,
                                       offs_ref))(_compute)
        else:
            _compute()

        @pl.when(ki == n_k - 1)
        def _done():
            l_fin = jnp.maximum(l_ref[...], 1e-30)
            _blk_store(packed, o_ref,
                       (acc_ref[...] / l_fin).astype(o_ref.dtype))
            lse = m_ref[...][:, 0] + jnp.log(l_fin[:, 0])
            if not keep_neg_inf_lse:
                lse = jnp.where(lse != -jnp.inf, lse, 0.0)
            # lse rows live in a (8, block_q) tile (sublane-broadcast) —
            # Mosaic requires the last two block dims be (8,128)-aligned,
            # so a flat (1,1,block_q) row block is not lowerable
            lse_ref[0, 0] = jnp.broadcast_to(lse[None, :], (8, block_q))

    in_specs = [q_spec, k_spec, k_spec]
    operands = [qt, kt, vt]
    if has_mask:
        in_specs.append(_mask_spec(coords, block_q, block_k, mask_b_is_one,
                                   mask_h_is_one, mask_q_is_one))
        operands.append(mask)
    if dropout_p > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(seed)
    if dyn_offsets:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(offs)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_k),
        in_specs=in_specs,
        out_specs=[
            q_spec,
            pl.BlockSpec((1, 1, 8, block_q),
                         lambda b_, h_, qi, ki: (b_, h_, 0, qi)),
        ],
        out_shape=[
            sds_like(sq_p, qt.dtype),
            _sds((b, h, 8, sq_p), jnp.float32, vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d_p), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return out, lse


def _live_grid(live, n_q, n_k, block_q, block_k):
    """The flattened grid of a causal forward over a prompt of `live`
    (traced int32) rows: the causal steps (qi, ki) of the first
    ceil(live / block_q) q blocks, q-major, then one step a q block past
    the prompt. Returns the step count and three (steps,) int32 tables
    sized for the whole bucket: the q block a step writes, and the q and
    k blocks it reads. A step past the prompt reads the blocks of the
    last computed step, which the pipeline holds, so it brings nothing."""
    kept = [[ki for ki in range(n_k)
             if _causal_block_live(qi, ki, block_q, block_k)]
            for qi in range(n_q)]
    q_all = jnp.asarray(np.repeat(np.arange(n_q), [len(r) for r in kept]),
                        jnp.int32)
    k_all = jnp.asarray(np.concatenate(kept), jnp.int32)
    first = jnp.asarray(np.cumsum([0] + [len(r) for r in kept]), jnp.int32)
    rows = jnp.clip((live + block_q - 1) // block_q, 0, n_q)
    computed = first[rows]
    t = jnp.arange(q_all.shape[0], dtype=jnp.int32)
    past = t >= computed
    last = jnp.maximum(computed - 1, 0)
    q_out = jnp.where(past, jnp.minimum(rows + t - computed, n_q - 1), q_all)
    q_in = jnp.where(past, q_all[last], q_all)
    k_in = jnp.where(past, k_all[last], k_all)
    return computed + n_q - rows, q_out, q_in, k_in


def _fwd_live_call(qt, kt, vt, live, *, scale, block_q, block_k, interpret,
                   heads=None):
    """The causal forward of a prefill whose prompt is the first `live`
    rows of a longer bucket (qt/kt/vt as `_fwd_call`'s, padded alike;
    `live` an int32 (1,) array). Its grid is (b, h, steps) with `steps`
    traced (`_live_grid`): the prompt's causal blocks alone, then one
    step a q block past the prompt that writes zeros. Rows at or past
    `live` come out zero with a logsumexp of 0, whatever the padding
    holds; keys past it are zeroed before P.V so that 0 x NaN cannot
    reach a row of the prompt. No mask, dropout or ring offsets: the
    exact prefill from position 0 needs none. Returns (out_padded,
    logsumexp)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    packed = heads is not None
    (b, h, sq_p, sk_p, d_p, blk, _, _, sds_like,
     _) = _qkv_layout(qt, kt, heads=heads, block_q=block_q, block_k=block_k,
                      kv_major=False, vma=None)
    n_q, n_k = sq_p // block_q, sk_p // block_k
    steps, q_out, q_in, k_in = _live_grid(live[0], n_q, n_k, block_q,
                                          block_k)

    def kernel(q_out_ref, q_in_ref, k_in_ref, live_ref, q_ref, k_ref, v_ref,
               o_ref, lse_ref, acc_ref, m_ref, l_ref):
        t = pl.program_id(2)
        qi, ki, n = q_out_ref[t], k_in_ref[t], live_ref[0]
        computes = qi * block_q < n           # a row of the block is there

        @pl.when(ki == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)

        @pl.when(computes)
        def _compute():
            s = jax.lax.dot_general(
                blk(q_ref), blk(k_ref), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT) * scale
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            p, alpha = _online_softmax(jnp.where(rows >= cols, s, -jnp.inf),
                                       m_ref, l_ref)
            vblk = blk(v_ref)
            keys = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, vblk.shape, 0)
            vblk = jnp.where(keys < n, vblk, jnp.zeros_like(vblk))
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)

        last_k = jnp.minimum(((qi + 1) * block_q - 1) // block_k, n_k - 1)

        @pl.when(jnp.logical_and(computes, ki == last_k))
        def _done():
            there = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0) < n
            l_fin = jnp.maximum(l_ref[...], 1e-30)
            _blk_store(packed, o_ref, jnp.where(
                there, acc_ref[...] / l_fin, 0.0).astype(o_ref.dtype))
            lse = jnp.where(there, m_ref[...] + jnp.log(l_fin), 0.0)[:, 0]
            lse_ref[0, 0] = jnp.broadcast_to(lse[None, :], (8, block_q))

        @pl.when(jnp.logical_not(computes))
        def _zeros():
            _blk_store(packed, o_ref, jnp.zeros((block_q, d_p), o_ref.dtype))
            lse_ref[0, 0] = jnp.zeros((8, block_q), jnp.float32)

    def spec(block, pick):
        if packed:
            return pl.BlockSpec((1, block, d_p),
                                lambda b_, h_, t, *tabs: (b_, pick(t, *tabs),
                                                          h_))
        return pl.BlockSpec((1, 1, block, d_p),
                            lambda b_, h_, t, *tabs: (b_, h_,
                                                      pick(t, *tabs), 0))

    k_spec = spec(block_k, lambda t, q_out, q_in, k_in, n: k_in[t])
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, h, steps),
            in_specs=[spec(block_q, lambda t, q_out, q_in, k_in, n: q_in[t]),
                      k_spec, k_spec],
            out_specs=[
                spec(block_q, lambda t, q_out, q_in, k_in, n: q_out[t]),
                pl.BlockSpec((1, 1, 8, block_q),
                             lambda b_, h_, t, q_out, q_in, k_in, n:
                             (b_, h_, 0, q_out[t])),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d_p), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ]),
        out_shape=[sds_like(sq_p, qt.dtype),
                   _sds((b, h, 8, sq_p), jnp.float32, None)],
        interpret=interpret,
    )(q_out, q_in, k_in, live, qt, kt, vt)
    return out, lse


# --------------------------------------------------------------- backward

def _recompute_p_ds(q_ref, k_ref, m_in_ref, lse_blk, qi, ki, *, scale, sk,
                    is_causal, has_mask, need_k_mask, block_q, block_k,
                    offs_ref=None, blk=None):
    """Shared backward recompute: p = exp(s - lse), masked like forward.
    `offs_ref` carries the ring step's global (q, k) position offsets.
    `blk` slices a grid block out of a ref ([0] packed, [0, 0] bhsd)."""
    blk = blk or (lambda ref: ref[0, 0])
    s = jax.lax.dot_general(blk(q_ref), blk(k_ref),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT) * scale
    if has_mask:
        s = s + m_in_ref[0, 0].astype(jnp.float32)
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    if is_causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        if offs_ref is not None:
            s = jnp.where(rows + offs_ref[0] >= cols + offs_ref[1],
                          s, -jnp.inf)
        else:
            s = jnp.where(rows >= cols, s, -jnp.inf)
    if need_k_mask:
        s = jnp.where(cols < sk, s, -jnp.inf)
    p = jnp.exp(jnp.where(s != -jnp.inf, s - lse_blk, -jnp.inf))
    return p


def _bwd_dq_call(qt, kt, vt, mask, seed, dot, lse, delta, *, scale, sk,
                 is_causal, has_mask, mask_b_is_one, mask_h_is_one,
                 mask_q_is_one, block_q, block_k, dropout_p, want_dmask,
                 interpret, offs=None, vma=None, heads=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    packed = heads is not None
    dyn_offsets = offs is not None
    (b, h, sq_p, sk_p, d_p, blk, q_spec, k_spec, sds_like,
     coords) = _qkv_layout(
        qt, kt, heads=heads, block_q=block_q, block_k=block_k,
        kv_major=False, vma=vma,
        clamp_causal=is_causal and not dyn_offsets)
    n_q, n_k = sq_p // block_q, sk_p // block_k
    need_k_mask = sk_p != sk
    has_dropout = dropout_p > 0.0

    def kernel(*refs):
        refs = list(refs)
        q_ref, k_ref, v_ref = refs[:3]
        refs = refs[3:]
        m_in_ref = refs.pop(0) if has_mask else None
        seed_ref = refs.pop(0) if has_dropout else None
        offs_ref = refs.pop(0) if dyn_offsets else None
        do_ref, lse_ref, delta_ref = refs[:3]
        outs = refs[3:]
        if want_dmask:
            dq_ref, dmask_ref, acc_ref = outs
        else:
            dq_ref, acc_ref = outs
        # grid ids are read here: the body below may run under pl.when
        b_, h_ = pl.program_id(0), pl.program_id(1)
        qi = pl.program_id(2)
        ki = pl.program_id(3)

        @pl.when(ki == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def _compute():
            lse_blk = lse_ref[0, 0, 0][:, None]
            p = _recompute_p_ds(q_ref, k_ref, m_in_ref, lse_blk, qi, ki,
                                scale=scale, sk=sk, is_causal=is_causal,
                                has_mask=has_mask, need_k_mask=need_k_mask,
                                block_q=block_q, block_k=block_k,
                                offs_ref=offs_ref, blk=blk)
            dp = jax.lax.dot_general(blk(do_ref), blk(v_ref),
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            if has_dropout:
                # dP = M/(1-r) ∘ dP_dropped — same mask as fwd (same seeds)
                keep = _keep_mask(pltpu, seed_ref, b_, h_, qi, ki,
                                  (block_q, block_k), dropout_p, interpret)
                dp = jnp.where(keep, dp / (1.0 - dropout_p), 0.0)
            ds = p * (dp - delta_ref[0, 0, 0][:, None])
            if want_dmask:
                # s = scale*q·k + mask ⇒ d(mask) = ds, unscaled; per-
                # (h,qi,ki) blocks are each visited exactly once so a plain
                # store is safe
                dmask_ref[0, 0] = ds
            kblk = blk(k_ref)
            acc_ref[...] += jax.lax.dot_general(
                ds.astype(kblk.dtype), kblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT) * scale

        if is_causal:
            live = _causal_block_live(qi, ki, block_q, block_k, offs_ref)
            pl.when(live)(_compute)
            if want_dmask:
                # every (qi, ki) block of d(mask) is its own output
                # block: one the mask rules out has ds = 0
                @pl.when(jnp.logical_not(live))
                def _no_dmask():
                    dmask_ref[0, 0] = jnp.zeros_like(dmask_ref[0, 0])
        else:
            _compute()

        @pl.when(ki == n_k - 1)
        def _done():
            _blk_store(packed, dq_ref, acc_ref[...].astype(dq_ref.dtype))

    row_spec = pl.BlockSpec((1, 1, 8, block_q),
                            lambda b_, h_, qi, ki: (b_, h_, 0, qi))
    score_spec = pl.BlockSpec((1, 1, block_q, block_k),
                              lambda b_, h_, qi, ki: (b_, h_, qi, ki))
    in_specs = [q_spec, k_spec, k_spec]
    operands = [qt, kt, vt]
    if has_mask:
        in_specs.append(_mask_spec(coords, block_q, block_k, mask_b_is_one,
                                   mask_h_is_one, mask_q_is_one))
        operands.append(mask)
    if has_dropout:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(seed)
    if dyn_offsets:
        assert not want_dmask, "ring offsets and mask grads don't combine"
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(offs)
    in_specs += [q_spec, row_spec, row_spec]
    operands += [dot, lse, delta]

    out_specs = [q_spec]
    out_shape = [sds_like(sq_p, qt.dtype)]
    if want_dmask:
        out_specs.append(score_spec)
        out_shape.append(_sds((b, h, sq_p, sk_p), jnp.float32, vma))

    result = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_k),
        in_specs=in_specs,
        out_specs=out_specs if want_dmask else out_specs[0],
        out_shape=out_shape if want_dmask else out_shape[0],
        scratch_shapes=[pltpu.VMEM((block_q, d_p), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return result if want_dmask else (result, None)


def _bwd_dkv_call(qt, kt, vt, mask, seed, dot, lse, delta, *, scale, sk,
                  is_causal, has_mask, mask_b_is_one, mask_h_is_one,
                  mask_q_is_one, block_q, block_k, dropout_p, interpret,
                  offs=None, vma=None, heads=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    packed = heads is not None
    dyn_offsets = offs is not None
    (b, h, sq_p, sk_p, d_p, blk, q_spec, k_spec, sds_like,
     coords) = _qkv_layout(
        qt, kt, heads=heads, block_q=block_q, block_k=block_k,
        kv_major=True, vma=vma,
        clamp_causal=is_causal and not dyn_offsets)
    n_q, n_k = sq_p // block_q, sk_p // block_k
    need_k_mask = sk_p != sk
    has_dropout = dropout_p > 0.0

    def kernel(*refs):
        refs = list(refs)
        q_ref, k_ref, v_ref = refs[:3]
        refs = refs[3:]
        m_in_ref = refs.pop(0) if has_mask else None
        seed_ref = refs.pop(0) if has_dropout else None
        offs_ref = refs.pop(0) if dyn_offsets else None
        do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs
        b_, h_ = pl.program_id(0), pl.program_id(1)
        ki = pl.program_id(2)
        qi = pl.program_id(3)   # q innermost: it is the accumulated dim here

        @pl.when(qi == 0)
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        def _compute():
            lse_blk = lse_ref[0, 0, 0][:, None]
            p = _recompute_p_ds(q_ref, k_ref, m_in_ref, lse_blk, qi, ki,
                                scale=scale, sk=sk, is_causal=is_causal,
                                has_mask=has_mask, need_k_mask=need_k_mask,
                                block_q=block_q, block_k=block_k,
                                offs_ref=offs_ref, blk=blk)
            doblk = blk(do_ref)
            if has_dropout:
                # seed args in (b, h, qi, ki) order — identical to fwd/dq
                # even though this kernel's grid iterates (ki, qi)
                keep = _keep_mask(pltpu, seed_ref, b_, h_, qi, ki,
                                  (block_q, block_k), dropout_p, interpret)
                p_d = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
            else:
                p_d = p
            dv_acc[...] += jax.lax.dot_general(
                p_d.astype(doblk.dtype), doblk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)      # P_dropped^T @ dO
            dp = jax.lax.dot_general(doblk, blk(v_ref),
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
            if has_dropout:
                dp = jnp.where(keep, dp / (1.0 - dropout_p), 0.0)
            ds = p * (dp - delta_ref[0, 0, 0][:, None])
            qblk = blk(q_ref)
            dk_acc[...] += jax.lax.dot_general(
                ds.astype(qblk.dtype), qblk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT) * scale  # ds^T @ Q

        if is_causal:
            pl.when(_causal_block_live(qi, ki, block_q, block_k,
                                       offs_ref))(_compute)
        else:
            _compute()

        @pl.when(qi == n_q - 1)
        def _done():
            _blk_store(packed, dk_ref, dk_acc[...].astype(dk_ref.dtype))
            _blk_store(packed, dv_ref, dv_acc[...].astype(dv_ref.dtype))

    row_spec = pl.BlockSpec(
        (1, 1, 8, block_q),
        lambda b_, h_, i2, i3: (b_, h_, 0, coords(i2, i3)[0]))
    in_specs = [q_spec, k_spec, k_spec]
    operands = [qt, kt, vt]
    if has_mask:
        in_specs.append(_mask_spec(coords, block_q, block_k, mask_b_is_one,
                                   mask_h_is_one, mask_q_is_one))
        operands.append(mask)
    if has_dropout:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(seed)
    if dyn_offsets:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(offs)
    in_specs += [q_spec, row_spec, row_spec]
    operands += [dot, lse, delta]

    dk, dv = pl.pallas_call(
        kernel,
        grid=(b, h, n_k, n_q),
        in_specs=in_specs,
        out_specs=[k_spec, k_spec],
        out_shape=[sds_like(sk_p, kt.dtype), sds_like(sk_p, vt.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d_p), jnp.float32),
                        pltpu.VMEM((block_k, d_p), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return dk, dv


# --------------------------------------------------------- custom-vjp glue

@functools.lru_cache(maxsize=None)
def _flash_vjp(is_causal: bool, has_mask: bool, mask_b_is_one: bool,
               mask_h_is_one: bool, mask_q_is_one: bool, sk: int,
               real_d: int, mask_needs_grad: bool, dropout_p: float,
               interpret: bool, vma=None, heads=None):
    """custom_vjp'd padded-layout flash attention, specialized per config.
    `real_d` is the unpadded head dim — it sets the softmax scale. When
    `mask_needs_grad`, the dq kernel additionally emits d(mask)=ds blocks
    (O(s^2) fp32 — only materialized for trainable masks, e.g. learned
    position biases); otherwise the mask cotangent is zeros. With
    `dropout_p` > 0 a scalar `seed` rides along (SMEM) and the on-chip PRNG
    regenerates the identical keep mask in forward and backward. With
    `heads`, qt/kt/vt are in the PACKED (b, S, h*D) layout (see
    _fwd_call)."""
    scale = 1.0 / math.sqrt(real_d)
    s_axis = 1 if heads is not None else 2

    def _kw(qt, kt):
        return dict(scale=scale, sk=sk, is_causal=is_causal,
                    has_mask=has_mask, mask_b_is_one=mask_b_is_one,
                    mask_h_is_one=mask_h_is_one, mask_q_is_one=mask_q_is_one,
                    block_q=min(_BLOCK_Q, qt.shape[s_axis]),
                    block_k=min(_BLOCK_K, kt.shape[s_axis]),
                    dropout_p=dropout_p,
                    interpret=interpret, vma=vma, heads=heads)

    @jax.custom_vjp
    def f(qt, kt, vt, mask, seed):
        out, _ = _fwd_call(qt, kt, vt, mask, seed, **_kw(qt, kt))
        return out

    def fwd(qt, kt, vt, mask, seed):
        out, lse = _fwd_call(qt, kt, vt, mask, seed, **_kw(qt, kt))
        return out, (qt, kt, vt, mask, seed, out, lse)

    def bwd(res, dout):
        qt, kt, vt, mask, seed, out, lse = res
        if heads is not None:
            # packed (b, S, h*d): per-head delta then to (b, h, S)
            b_, s_, hd_ = out.shape
            delta = jnp.sum(
                dout.astype(jnp.float32).reshape(b_, s_, heads, -1)
                * out.astype(jnp.float32).reshape(b_, s_, heads, -1),
                axis=-1).transpose(0, 2, 1)                   # [b,h,S]
        else:
            delta = jnp.sum(dout.astype(jnp.float32)
                            * out.astype(jnp.float32), axis=-1)  # [b,h,S]
        # match lse's sublane-broadcast (b,h,8,S) layout (see _fwd_call)
        delta = jnp.broadcast_to(delta[:, :, None, :],
                                 (*delta.shape[:2], 8, delta.shape[-1]))
        kw = _kw(qt, kt)
        dq, dmask_full = _bwd_dq_call(
            qt, kt, vt, mask, seed, dout, lse, delta,
            want_dmask=has_mask and mask_needs_grad, **kw)
        dk, dv = _bwd_dkv_call(qt, kt, vt, mask, seed, dout, lse, delta,
                               **kw)
        if dmask_full is not None:
            # collapse broadcast dims back to the primal mask's shape;
            # padded rows/cols carry ds=0 (dO=0 / p=0), matching jnp.pad's vjp
            dmask = dmask_full
            if mask_b_is_one:
                dmask = dmask.sum(axis=0, keepdims=True)
            if mask_h_is_one:
                dmask = dmask.sum(axis=1, keepdims=True)
            if mask_q_is_one:
                dmask = dmask.sum(axis=2, keepdims=True)
        else:
            dmask = jnp.zeros_like(mask)
        # integer seed: cotangent type is float0 per the custom_vjp contract
        dseed = np.zeros(np.shape(seed), dtype=jax.dtypes.float0)
        return dq, dk, dv, dmask.astype(mask.dtype), dseed

    f.defvjp(fwd, bwd)
    return f


def _flash_layout(q, k, v):
    """The kernels' operands of (b, s, h, d) q, k, v: blocks picked,
    lengths and head width padded, heads packed or transposed. Returns
    (qt, kt, vt, heads, block_q, block_k, sq_p, sk_p, back), `heads` the
    kernels' `heads=` and `back` the map of their output to (b, sq, h,
    d)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_q = _pick_block(sq, _BLOCK_Q)
    block_k = _pick_block(sk, _BLOCK_K)
    sq_p = _round_up(sq, block_q)
    sk_p = _round_up(sk, block_k)
    # head_dim 64 lowers natively (Mosaic tiles a 64-lane block into a
    # half-used vreg); padding it to 128 doubled the q/k/v HBM traffic
    # and cost ~7 ms/step of pad+slice ops at ERNIE-base (r5 trace)
    d_p = d if d in (64, 128, 256) else _round_up(d, 128)

    # 128-multiple head dims take the PACKED (b, S, h*d) layout: a pure
    # reshape (free) instead of a materialized bshd->bhsd transpose; the
    # kernels address the head slab through the BlockSpec index map.
    # Mosaic requires a block's lane dim be 128-divisible or equal to the
    # array dim, so d=64 heads (block (1, bq, 64) over (b, S, h*64))
    # cannot ride this path — they keep the transpose with d_p=d (no pad)
    packed = d == d_p and d % 128 == 0 and h > 1

    if packed:
        def prep(x, s_target):
            x = x.reshape(x.shape[0], x.shape[1], h * d)
            return jnp.pad(x, ((0, 0), (0, s_target - x.shape[1]), (0, 0)))

        def back(out):
            return out[:, :sq, :].reshape(b, sq, h, d)
    else:
        def prep(x, s_target):
            x = jnp.einsum("bshd->bhsd", x)
            return jnp.pad(x, ((0, 0), (0, 0), (0, s_target - x.shape[2]),
                               (0, d_p - d)))

        def back(out):
            return jnp.einsum("bhsd->bshd", out[:, :, :sq, :d])

    return (prep(q, sq_p), prep(k, sk_p), prep(v, sk_p),
            h if packed else None, block_q, block_k, sq_p, sk_p, back)


@functools.partial(
    jax.jit,
    static_argnames=("is_causal", "has_mask", "mask_needs_grad",
                     "dropout_p", "interpret"))
def _flash_attention_data(q, k, v, mask=None, seed=None, is_causal=False,
                          has_mask=False, mask_needs_grad=False,
                          dropout_p=0.0, interpret=False):
    sq, d = q.shape[1], q.shape[3]
    sk = k.shape[1]
    qt, kt, vt, heads, _, _, sq_p, sk_p, back = _flash_layout(q, k, v)
    mask_b_is_one = mask_h_is_one = mask_q_is_one = True
    if has_mask:
        # keep broadcast (size-1) batch/head/q dims at 1 — the BlockSpec
        # index maps pin them to block 0, so a (b,1,1,sk) padding mask never
        # materializes the O(s^2) buffer flash attention exists to avoid
        mask_b_is_one = mask.shape[0] == 1
        mask_h_is_one = mask.shape[1] == 1
        mask_q_is_one = mask.shape[2] == 1
        q_dim = 1 if mask_q_is_one else sq
        mask = jnp.broadcast_to(
            mask, (mask.shape[0], mask.shape[1], q_dim, sk)
        ).astype(jnp.float32)
        mask = jnp.pad(mask, ((0, 0), (0, 0),
                              (0, 0 if mask_q_is_one else sq_p - sq),
                              (0, sk_p - sk)))
    else:
        mask = jnp.zeros((1, 1, 1, 1), jnp.float32)  # unused placeholder
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)            # unused placeholder

    f = _flash_vjp(is_causal, has_mask, mask_b_is_one, mask_h_is_one,
                   mask_q_is_one, sk, d, mask_needs_grad, float(dropout_p),
                   interpret, heads=heads)
    return back(f(qt, kt, vt, mask, seed.astype(jnp.int32).reshape((1,))))


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_prefill(q, k, v, live, interpret=False):
    """Causal self-attention of a prefill from position 0 whose prompt is
    the first `live` (traced int32) of the s rows of q, k, v (b, s, h,
    d): the forward alone, over the prompt's own causal blocks
    (`_fwd_live_call`). Rows at or past `live` come out zero."""
    qt, kt, vt, heads, block_q, block_k, _, _, back = _flash_layout(q, k, v)
    out, _ = _fwd_live_call(
        qt, kt, vt, jnp.asarray(live, jnp.int32).reshape((1,)),
        scale=1.0 / math.sqrt(q.shape[3]), block_q=block_q, block_k=block_k,
        interpret=interpret, heads=heads)
    return back(out)


@functools.lru_cache(maxsize=None)
def flash_prefill_steps(s, live):
    """(computed, the whole bucket's causal) grid steps a (batch, head) of
    `flash_prefill` over `s` rows of which the first `live` are the
    prompt's."""
    block = _pick_block(s, _BLOCK_Q)
    s_p = _round_up(s, block)
    return (flash_block_steps(s_p, s_p, block, block, True, live)[0],
            flash_block_steps(s_p, s_p, block, block, True)[0])


def flash_attention(q, k, v, attn_mask=None, is_causal=False,
                    dropout_p=0.0, rng_key=None, interpret=False):
    """Tensor-level wrapper used by nn.functional (differentiable).

    With `dropout_p` > 0 a scalar seed is derived from `rng_key` (or the
    framework's default generator) — attention-probs dropout then runs
    INSIDE the kernel (upscale_in_train), so training reaches the flash
    path instead of falling back to the materialized-softmax reference."""
    from ..core.dispatch import apply_callable

    seed = None
    if dropout_p > 0.0:
        if rng_key is None:
            from ..core.rng import default_generator

            rng_key = default_generator().next_key()
        seed = jax.random.randint(rng_key, (1,), 0, 2 ** 31 - 1,
                                  dtype=jnp.int32)

    if attn_mask is None:
        def fn(qd, kd, vd):
            return _flash_attention_data(qd, kd, vd, seed=seed,
                                         is_causal=is_causal,
                                         dropout_p=dropout_p,
                                         interpret=interpret)

        return apply_callable("flash_attention", fn, q, k, v)

    needs_grad = (hasattr(attn_mask, "stop_gradient")
                  and not attn_mask.stop_gradient)

    def fn(qd, kd, vd, md):
        return _flash_attention_data(qd, kd, vd, md, seed=seed,
                                     is_causal=is_causal,
                                     has_mask=True,
                                     mask_needs_grad=needs_grad,
                                     dropout_p=dropout_p,
                                     interpret=interpret)

    return apply_callable("flash_attention", fn, q, k, v, attn_mask)


# ==================================================================== norms
#
# Fused RMSNorm / LayerNorm (SURVEY §7's "fused LN" in the designed Pallas
# fusion set alongside flash attention). One HBM pass for the forward
# (reduction + normalize + affine fused in VMEM), one for dx; dw/db are a
# plain XLA reduction over rows (a matmul-shaped sum XLA handles well).
# f32 compute inside the kernel regardless of input dtype (bf16-safe).

_NORM_BLOCK_ROWS = 256
_NORM_MAX_HIDDEN = 16384


def fused_norm_available(x) -> bool:
    """Fused path: TPU, float dtype, last dim 128-aligned (no pad-mask
    logic in-kernel; every transformer hidden size qualifies)."""
    xd = x._data if hasattr(x, "_data") else x
    if not _on_tpu():
        return False
    if xd.ndim < 2 or xd.shape[-1] % 128 != 0:
        return False
    if xd.shape[-1] > _NORM_MAX_HIDDEN:
        return False
    return jnp.issubdtype(xd.dtype, jnp.floating)


def _norm_fwd_call(x2, w, b, *, eps, subtract_mean, block_r, interpret):
    """x2: (rows_p, h). Returns (y, mu, rstd) with mu/rstd (rows_p, 128)
    sublane-broadcast (Mosaic block rule: last two dims (8,128)-tiled)."""
    from jax.experimental import pallas as pl

    rows_p, h = x2.shape
    n_r = rows_p // block_r
    has_b = b is not None

    def kernel(*refs):
        x_ref, w_ref = refs[0], refs[1]
        b_ref = refs[2] if has_b else None
        y_ref, mu_ref, rstd_ref = refs[-3:]
        xb = x_ref[...].astype(jnp.float32)
        if subtract_mean:
            mu = jnp.mean(xb, axis=1, keepdims=True)
            xc = xb - mu
        else:
            mu = jnp.zeros((block_r, 1), jnp.float32)
            xc = xb
        rstd = jax.lax.rsqrt(jnp.mean(xc * xc, axis=1, keepdims=True) + eps)
        y = xc * rstd * w_ref[...].astype(jnp.float32)
        if has_b:
            y = y + b_ref[...].astype(jnp.float32)
        y_ref[...] = y.astype(y_ref.dtype)
        mu_ref[...] = jnp.broadcast_to(mu, (block_r, 128))
        rstd_ref[...] = jnp.broadcast_to(rstd, (block_r, 128))

    in_specs = [
        pl.BlockSpec((block_r, h), lambda r: (r, 0)),
        pl.BlockSpec((1, h), lambda r: (0, 0)),
    ]
    operands = [x2, w.reshape(1, h)]
    if has_b:
        in_specs.append(pl.BlockSpec((1, h), lambda r: (0, 0)))
        operands.append(b.reshape(1, h))
    return pl.pallas_call(
        kernel,
        grid=(n_r,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((block_r, h), lambda r: (r, 0)),
                   pl.BlockSpec((block_r, 128), lambda r: (r, 0)),
                   pl.BlockSpec((block_r, 128), lambda r: (r, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows_p, h), x2.dtype),
                   jax.ShapeDtypeStruct((rows_p, 128), jnp.float32),
                   jax.ShapeDtypeStruct((rows_p, 128), jnp.float32)],
        interpret=interpret,
    )(*operands)


def _norm_bwd_call(x2, w, dy2, mu, rstd, *, subtract_mean, block_r,
                   interpret):
    """dx in one fused pass; (rows_p, h) blocks."""
    from jax.experimental import pallas as pl

    rows_p, h = x2.shape
    n_r = rows_p // block_r

    def kernel(x_ref, w_ref, dy_ref, mu_ref, rstd_ref, dx_ref):
        xb = x_ref[...].astype(jnp.float32)
        dy = dy_ref[...].astype(jnp.float32)
        wv = w_ref[...].astype(jnp.float32)
        mu = mu_ref[..., :1]
        rstd = rstd_ref[..., :1]
        xc = (xb - mu) if subtract_mean else xb
        xhat = xc * rstd
        dyw = dy * wv
        c1 = jnp.mean(dyw * xhat, axis=1, keepdims=True)
        dx = dyw - xhat * c1
        if subtract_mean:
            dx = dx - jnp.mean(dyw, axis=1, keepdims=True)
        dx_ref[...] = (dx * rstd).astype(dx_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid=(n_r,),
        in_specs=[pl.BlockSpec((block_r, h), lambda r: (r, 0)),
                  pl.BlockSpec((1, h), lambda r: (0, 0)),
                  pl.BlockSpec((block_r, h), lambda r: (r, 0)),
                  pl.BlockSpec((block_r, 128), lambda r: (r, 0)),
                  pl.BlockSpec((block_r, 128), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((block_r, h), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, h), x2.dtype),
        interpret=interpret,
    )(x2, w.reshape(1, h), dy2, mu, rstd)


def _fused_norm_data(x, weight, bias=None, eps=1e-6, subtract_mean=False,
                     interpret=False):
    """Differentiable fused norm over the last axis. subtract_mean=False →
    RMSNorm, True → LayerNorm."""
    shape = x.shape
    h = shape[-1]
    rows = int(np.prod(shape[:-1]))
    # VMEM budget: the kernel holds ~4 f32 (block_r, h) tiles (x, y/dx, dy,
    # temporaries); cap the row block so 16*block_r*h bytes stays ~4 MB
    vmem_cap = max(8, (4 * 1024 * 1024 // (16 * h)) // 8 * 8)
    block_r = min(_NORM_BLOCK_ROWS, vmem_cap, _round_up(rows, 8))
    rows_p = _round_up(rows, block_r)
    has_b = bias is not None

    @jax.custom_vjp
    def run(x, w, b):
        return _fwd(x, w, b)[0]

    def _fwd(x, w, b):
        x2 = x.reshape(rows, h)
        if rows_p != rows:  # padded rows: zeros → rstd=rsqrt(eps), no nan
            x2 = jnp.pad(x2, ((0, rows_p - rows), (0, 0)))
        y, mu, rstd = _norm_fwd_call(x2, w, b, eps=eps,
                                     subtract_mean=subtract_mean,
                                     block_r=block_r, interpret=interpret)
        out = y[:rows].reshape(shape)
        return out, (x2, w, mu, rstd)

    def _bwd(res, dy):
        x2, w, mu, rstd = res
        dy2 = dy.reshape(rows, h)
        if rows_p != rows:
            dy2 = jnp.pad(dy2, ((0, rows_p - rows), (0, 0)))
        dx = _norm_bwd_call(x2, w, dy2, mu, rstd,
                            subtract_mean=subtract_mean, block_r=block_r,
                            interpret=interpret)
        # dw/db: row reductions — XLA's territory (fuses into one pass)
        xc = x2.astype(jnp.float32)
        if subtract_mean:
            xc = xc - mu[:, :1]
        xhat = xc * rstd[:, :1]
        dyf = dy2.astype(jnp.float32)
        dw = jnp.sum(dyf * xhat, axis=0).astype(w.dtype)
        db = jnp.sum(dyf, axis=0).astype(w.dtype) if has_b else None
        return (dx[:rows].reshape(shape), dw, db)

    run.defvjp(lambda x, w, b: _fwd(x, w, b), _bwd)
    b_arg = bias if has_b else None
    return run(x, weight, b_arg)


def rms_norm_fused(x, weight, eps=1e-6, interpret=False):
    return _fused_norm_data(x, weight, None, eps, subtract_mean=False,
                            interpret=interpret)


def layer_norm_fused(x, weight, bias=None, eps=1e-5, interpret=False):
    return _fused_norm_data(x, weight, bias, eps, subtract_mean=True,
                            interpret=interpret)


# ============================================================ ring attention
#
# Pallas ring flash attention (SURVEY §5 long-context bullet: "ring attention
# as a Pallas splash/flash kernel with ppermute"). Inside shard_map over the
# sep axis each rank holds a sequence shard of Q,K,V; per ring step the LOCAL
# flash kernel above runs on (q_local, k_block, v_block) with the step's
# global position offsets driving the causal mask IN-KERNEL (never a
# materialized score or mask buffer), and the normalized partial outputs are
# merged with elementwise log-sum-exp weights. Communication is one ppermute
# of the KV pair per step (ICI neighbor exchange); causal steps whose block
# lies entirely in the future skip their MXU work via pl.when (splash-style)
# while keeping the SPMD program uniform across ranks.
#
# Backward rotates (k, v, dk_acc, dv_acc) a full loop: each rank folds its
# local contribution into the passing block's gradient accumulators using the
# recompute-based dq/dkv kernels with the SAME global lse/delta residuals,
# so after n shifts every rank holds exactly its own dk/dv.


def _ring_merge(o_acc, lse_acc, o_s, lse_s):
    """Fold one normalized flash partial (o_s, lse_s) into the accumulator.
    Elementwise over (b,h,s)+(b,h,s,d) — no O(s^2) buffer anywhere."""
    new_lse = jnp.logaddexp(lse_acc, lse_s)
    safe = jnp.where(jnp.isfinite(new_lse), new_lse, 0.0)
    w_acc = jnp.where(jnp.isfinite(lse_acc), jnp.exp(lse_acc - safe), 0.0)
    w_s = jnp.where(jnp.isfinite(lse_s), jnp.exp(lse_s - safe), 0.0)
    o = o_acc * w_acc[..., None] + o_s.astype(jnp.float32) * w_s[..., None]
    return o, new_lse


@functools.lru_cache(maxsize=None)
def _ring_vjp(axis_name: str, n: int, causal: bool, scale: float, sk: int,
              block_q: int, block_k: int, interpret: bool):
    """custom_vjp'd ring flash attention over `axis_name` (n ranks), one
    (b, h, S_pad, D_pad) shard per rank; `sk` is the real (unpadded) local
    sequence length."""
    kw = dict(scale=scale, sk=sk, is_causal=causal, has_mask=False,
              mask_b_is_one=True, mask_h_is_one=True, mask_q_is_one=True,
              block_q=block_q, block_k=block_k, dropout_p=0.0,
              interpret=interpret, vma=(axis_name,))
    perm = tuple((i, (i + 1) % n) for i in range(n))

    def _placeholders():
        return (jnp.zeros((1, 1, 1, 1), jnp.float32),
                jnp.zeros((1,), jnp.int32))

    def _offs_for(my, step):
        if not causal:
            return None
        src = (my - step) % n       # whose KV block this rank now holds
        return jnp.stack([my * sk, src * sk]).astype(jnp.int32)

    def _fwd_impl(qt, kt, vt):
        mask, seed = _placeholders()
        my = jax.lax.axis_index(axis_name)
        b, h, S, D = qt.shape
        o = jnp.zeros((b, h, S, D), jnp.float32)
        lse = jnp.full((b, h, S), -jnp.inf, jnp.float32)
        kv = (kt, vt)
        for step in range(n):
            o_s, lse_s = _fwd_call(qt, kv[0], kv[1], mask, seed,
                                   offs=_offs_for(my, step),
                                   keep_neg_inf_lse=True, **kw)
            o, lse = _ring_merge(o, lse, o_s, lse_s[:, :, 0, :])
            if step != n - 1:
                kv = jax.lax.ppermute(kv, axis_name, perm)
        return o.astype(qt.dtype), lse

    @jax.custom_vjp
    def f(qt, kt, vt):
        return _fwd_impl(qt, kt, vt)[0]

    def fwd(qt, kt, vt):
        out, lse = _fwd_impl(qt, kt, vt)
        return out, (qt, kt, vt, out, lse)

    def bwd(res, do):
        qt, kt, vt, out, lse = res
        b, h, S, D = qt.shape
        mask, seed = _placeholders()
        my = jax.lax.axis_index(axis_name)
        # global residuals: p = exp(s - lse_global) inside the per-step
        # kernels IS the globally-normalized attention weight, so the flash
        # backward decomposition holds blockwise across the ring
        lse_b = jnp.broadcast_to(
            jnp.where(jnp.isfinite(lse), lse, 0.0)[:, :, None, :],
            (b, h, 8, S))
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)
        delta_b = jnp.broadcast_to(delta[:, :, None, :], (b, h, 8, S))
        dq = jnp.zeros((b, h, S, D), jnp.float32)
        ring = (kt, vt, jnp.zeros((b, h, S, D), jnp.float32),
                jnp.zeros((b, h, S, D), jnp.float32))
        for step in range(n):
            kb, vb, dka, dva = ring
            offs = _offs_for(my, step)
            dq_s, _ = _bwd_dq_call(qt, kb, vb, mask, seed, do, lse_b,
                                   delta_b, want_dmask=False, offs=offs,
                                   **kw)
            dk_s, dv_s = _bwd_dkv_call(qt, kb, vb, mask, seed, do, lse_b,
                                       delta_b, offs=offs, **kw)
            dka = dka + dk_s.astype(jnp.float32)
            dva = dva + dv_s.astype(jnp.float32)
            # shift EVERY step: after n shifts each block's gradient
            # accumulator is back home with all n contributions. The last
            # shift carries only the accumulators — k/v are dead weight
            # once no further step will read them
            if step != n - 1:
                ring = jax.lax.ppermute((kb, vb, dka, dva), axis_name, perm)
            else:
                dka, dva = jax.lax.ppermute((dka, dva), axis_name, perm)
            dq = dq + dq_s.astype(jnp.float32)
        return (dq.astype(qt.dtype), dka.astype(kt.dtype),
                dva.astype(vt.dtype))

    f.defvjp(fwd, bwd)
    return f


def ring_flash_attention_pallas(q, k, v, axis_name: str, causal=False,
                                scale=None, interpret=False):
    """Ring flash attention on raw (b, h, s_local, d) shards inside
    shard_map over `axis_name`. Differentiable (custom vjp rotating the
    gradient accumulators around the same ring)."""
    n = int(jax.lax.axis_size(axis_name))
    b, h, s, d = q.shape
    if scale is None:
        scale = d ** -0.5
    block_q = _pick_block(s, _BLOCK_Q)
    block_k = _pick_block(s, _BLOCK_K)
    block = max(block_q, block_k)
    S = _round_up(s, block)
    # 64/128/256 head dims lower natively (same Mosaic rule as the
    # flash entry point) — no pad-to-128 HBM traffic
    d_p = d if d in (64, 128, 256) else _round_up(d, 128)

    def padp(x):
        return jnp.pad(x, ((0, 0), (0, 0), (0, S - s), (0, d_p - d)))

    f = _ring_vjp(axis_name, n, bool(causal), float(scale), s,
                  block_q, block_k, bool(interpret))
    out = f(padp(q), padp(k), padp(v))
    return out[:, :, :s, :d]


def _fwd_flash_for_ulysses(q, k, v, scale, causal, axis_name, interpret):
    """Full-sequence flash for the Ulysses head slice: inputs already in
    the kernel's (b, h, s, d) layout inside shard_map over `axis_name`.
    Differentiable (the standard flash custom vjp); only the default
    1/sqrt(d) scale is expressible — callers with a custom scale use the
    XLA reference path."""
    b, h, s, d = q.shape
    if abs(float(scale) - d ** -0.5) > 1e-12:
        raise ValueError("pallas ulysses path supports the default scale")
    block = max(_pick_block(s, _BLOCK_Q), _pick_block(s, _BLOCK_K))
    S = _round_up(s, block)
    # 64/128/256 head dims lower natively (same Mosaic rule as the
    # flash entry point) — no pad-to-128 HBM traffic
    d_p = d if d in (64, 128, 256) else _round_up(d, 128)

    def padp(x):
        return jnp.pad(x, ((0, 0), (0, 0), (0, S - s), (0, d_p - d)))

    f = _flash_vjp(bool(causal), False, True, True, True, s, d, False,
                   0.0, bool(interpret), vma=(axis_name,))
    out = f(padp(q), padp(k), padp(v), jnp.zeros((1, 1, 1, 1), jnp.float32),
            jnp.zeros((1,), jnp.int32))
    return out[:, :, :s, :d]


# ============================================================ MoE dispatch
#
# Fused MoE dispatch (SURVEY §7's Pallas fusion set; the global_scatter/
# global_gather analog, ref paddle/fluid/operators/collective/
# global_scatter_op.* — upstream layout, unverified). The XLA reference
# path dispatches with a [T, E, C] one-hot einsum: O(T*E*C*d) mostly-zero
# MXU work plus a materialized [T, E, C] mask. The fused form is a row
# GATHER: expert_in[e, c] = x[token_of_slot[e, c]] — one DMA per routed
# row, no dead FLOPs. The same kernel serves the combine stage
# (out[t, k] = expert_out[slot_of_token[t, k]]), so `gather_rows` is the
# single primitive:
#
#   gather_rows(src [N, d], idx [M] int32) -> [M, d]
#     out[m] = src[idx[m]]  (idx < 0 -> zero row: over-capacity slots)
#
# Forward: Pallas kernel — idx rides in SMEM via scalar prefetch, each
# output row is an async HBM->VMEM copy. Backward: the transpose of a
# gather is scatter-add, which XLA lowers well — jnp .at[].add, no
# hand-written kernel needed (documented asymmetry).

_GATHER_BLOCK_M = 256


def _gather_rows_kernel(idx_ref, src_ref, out_ref, sem, *, block_m, d_pad):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    base = pl.program_id(0) * block_m
    m_total = idx_ref.shape[0]

    def body(j, _):
        row = idx_ref[jnp.minimum(base + j, m_total - 1)]
        # clamped gather; empty slots (idx < 0) copy row 0 and are zeroed
        # OUTSIDE the kernel (an in-kernel masked store at a dynamic row
        # is not sublane-aligned; Mosaic rejects it — AOT tier finding).
        # src/out ride FLAT (1-D): a row slice of a (8,128)-tiled 2-D
        # memref can't start at an arbitrary dynamic row, but a 1-D slice
        # of length d_pad at offset row*d_pad is provably 128-aligned.
        safe = jnp.maximum(row, 0)
        copy = pltpu.make_async_copy(
            src_ref.at[pl.ds(safe * d_pad, d_pad)],
            out_ref.at[pl.ds(j * d_pad, d_pad)], sem)
        copy.start()
        copy.wait()
        return 0

    jax.lax.fori_loop(0, block_m, body, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def gather_rows(src, idx, n_src=None, interpret=False):
    """out[m] = src[idx[m]] (zero row where idx < 0). Differentiable: the
    vjp scatter-adds cotangent rows back into src."""
    return _gather_rows_fwd_impl(src, idx, interpret)


def _gather_rows_fwd_impl(src, idx, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m = idx.shape[0]
    n, d = src.shape
    block_m = min(_GATHER_BLOCK_M, _round_up(m, 8))
    m_pad = _round_up(m, block_m)
    # flat 1-D memrefs tile at 1024 elements (8 sublanes x 128 lanes); row
    # slices must start and span on that boundary
    d_pad = _round_up(d, 1024)
    srcp = jnp.pad(src, ((0, 0), (0, d_pad - d)))
    idxp = jnp.pad(idx.astype(jnp.int32), (0, m_pad - m),
                   constant_values=-1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m_pad // block_m,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block_m * d_pad,),
                               lambda i, idx_ref: (i,)),
        scratch_shapes=[pltpu.SemaphoreType.DMA],
    )
    out = pl.pallas_call(
        functools.partial(_gather_rows_kernel, block_m=block_m,
                          d_pad=d_pad),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad * d_pad,), src.dtype),
        interpret=interpret,
    )(idxp, srcp.reshape(-1))
    out = out.reshape(m_pad, d_pad)
    out = jnp.where((idxp >= 0)[:, None], out, 0)   # empty slots -> zero
    return out[:m, :d]


def _gather_rows_bwd_fwd(src, idx, n_src, interpret):
    return _gather_rows_fwd_impl(src, idx, interpret), (idx, src.shape[0])


def _gather_rows_bwd(n_src, interpret, res, g):
    idx, n = res
    safe = jnp.maximum(idx, 0)
    g = jnp.where((idx >= 0)[:, None], g, 0)
    dsrc = jnp.zeros((n, g.shape[1]), g.dtype).at[safe].add(g)
    return dsrc, None


gather_rows.defvjp(_gather_rows_bwd_fwd, _gather_rows_bwd)


def moe_dispatch_available(x) -> bool:
    xd = x._data if hasattr(x, "_data") else x
    return _on_tpu() and xd.ndim == 2


def moe_dispatch_indices(topi, pos, keep, num_experts, capacity):
    """Routing metadata -> gather indices, pure jnp (cheap).

    topi/pos/keep: [T, k] expert id, in-expert position, capacity mask.
    Returns (slot_token [E*C] int32: which token fills each expert slot,
    tok_slot [T, k] int32: which flat slot serves each (token, k) — both
    -1 where unrouted/empty)."""
    t, k = topi.shape
    flat_slot = topi * capacity + jnp.clip(pos, 0, capacity - 1)
    routed = keep > 0
    tok_slot = jnp.where(routed, flat_slot, -1).astype(jnp.int32)
    token_ids = jnp.broadcast_to(jnp.arange(t)[:, None], (t, k))
    slot_token = jnp.full((num_experts * capacity,), -1, jnp.int32)
    slot_token = slot_token.at[jnp.where(routed, flat_slot,
                                         num_experts * capacity)].set(
        token_ids.astype(jnp.int32), mode="drop")
    return slot_token, tok_slot
