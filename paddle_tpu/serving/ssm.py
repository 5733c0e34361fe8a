"""The recurrent (Mamba-2) layers' two device paths over the state pools.

A model with state-space layers keeps, beside the K/V pages of its
attention layers, one fixed-size slot a live request a recurrent layer
(`kv_cache.StateLayerCache`): the float32 SSM state S[h] of shape (P, N)
for each of H heads, and the last `conv_width - 1` rows of the causal
conv's input. With a_t = dt_t * A (per head, A < 0):

    S_t[h] = exp(a_t[h]) * S_{t-1}[h] + dt_t[h] * x_t[h] (outer) B_t
    y_t[h] = S_t[h] C_t + D[h] * x_t[h]

- **decode** (`state_update`): one token a row. The Pallas kernel
  `ssm_decode` walks a grid (row, block of heads); the row's slot comes
  from a scalar-prefetched table, the pool is aliased input to output,
  and each step brings its block of the slot's state into VMEM, computes
  the update and the read in float32, and writes the block back to the
  same slot: the donated pool is updated in place and only the live
  rows' states move. A row whose slot is `NULL_SLOT` (padding, or parked
  inside a block) reads and writes that slot, which nobody owns.
- **prefill** (`chunk_scan`): the chunked (SSD) form. Within a chunk of
  Q tokens the output is the masked product ((C B^T) . L) (dt x) with
  L[t, u] = exp(cum_t - cum_u) from the cumulative a; a chunk's end state
  is a second product; a scan over the chunks carries the state, whose
  contribution to a token is exp(cum_t) * C_t S. Cumulative sums, exp
  and the carried state are float32; the products take their operands in
  the model's type with float32 accumulation. Plain `jax.numpy`: the
  products are matmuls the compiler places on the MXU. A position whose
  dt is 0 (padding of a bucket) leaves the state as it stands.

The state of head h is stored as its transpose (N, P), `g = 128 // P`
heads side by side in a 128-lane row (`kv_cache.StateSpec.ssm_shape`):
then the token's x and decay are rows, its B and C columns, the read a
sum over sublanes, and the kernel needs no transpose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..profiler import scopes
from . import attention as _att
from .kv_cache import NULL_SLOT, StateLayerCache

__all__ = ["state_update", "chunk_scan", "conv_step", "conv_prefill",
           "write_state", "parked_slots", "pack_state", "unpack_state"]

# bytes of a slot's state one grid step of the decode kernel brings in
# (and writes back): the pipeline holds the block four times (in and out,
# double-buffered) beside the kernel's own temporaries, inside the 16 MiB
# a v5e kernel gets by default. The readings that chose it are in
# PERF.md section 6 (PR 33).
_SSM_BLOCK_BYTES = 1024 * 1024


def pack_state(state, pack: int):
    """(..., H, P, N) states to the pool's (..., H / g, N, g * P)."""
    *lead, h, p, n = state.shape
    s = state.reshape(*lead, h // pack, pack, p, n)
    s = jnp.moveaxis(s, -1, -3)                  # (..., H/g, N, g, P)
    return s.reshape(*lead, h // pack, n, pack * p)


def unpack_state(stored, pack: int):
    """Inverse of `pack_state`: (..., H / g, N, g * P) to (..., H, P, N)."""
    *lead, hk, n, lanes = stored.shape
    s = stored.reshape(*lead, hk, n, pack, lanes // pack)
    s = jnp.moveaxis(s, -3, -1)                  # (..., H/g, g, P, N)
    return s.reshape(*lead, hk * pack, lanes // pack, n)


def _decode_tiling(hk: int, n: int, lanes: int) -> int:
    """Row blocks of the stored state (of `g` heads each) one grid step
    of the decode kernel takes: the most that divide `hk`, keep a block
    within `_SSM_BLOCK_BYTES` and are a multiple of 8 (the token's x
    rides as a (rows, lanes) tile) or all of them."""
    fits = [h for h in range(1, hk + 1)
            if hk % h == 0 and (h % 8 == 0 or h == hk)
            and h * n * lanes * 4 <= _SSM_BLOCK_BYTES]
    return max(fits) if fits else (8 if hk % 8 == 0 else hk)


def _ssm_decode_kernel(slot_ref, xd_ref, dec_ref, b_ref, c_ref, s_ref,
                       y_ref, so_ref, *, hb):
    """Grid (row, block of `hb` row blocks of the state). `s_ref` is the
    row's slot, picked by the index map from the prefetched table, and
    `so_ref` the same block of the same buffer."""
    del slot_ref
    bcol, ccol = b_ref[0], c_ref[0]              # (N, 1) each
    for k in range(hb):
        new = (s_ref[0, k] * dec_ref[0, k:k + 1, :]
               + bcol * xd_ref[0, k:k + 1, :])    # (N, lanes)
        so_ref[0, k] = new
        y_ref[0, k:k + 1, :] = jnp.sum(new * ccol, axis=0, keepdims=True)


# jitted for the reason `attention._paged_decode_pallas` is: the layers
# of a step share one traced and lowered kernel
@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_decode_pallas(xd, dec, bcol, ccol, pool, slots, interpret=False):
    """xd, dec: (b, H / g, lanes) float32, dt * x and exp(dt * A) as the
    state's rows; bcol, ccol: (b, N, 1) float32; pool: (slots + 1, H / g,
    N, lanes) float32; slots: (b,) int32. Returns (y (b, H / g, lanes),
    the pool updated in place)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hk, lanes = xd.shape
    n = pool.shape[2]
    hb = _decode_tiling(hk, n, lanes)
    row = pl.BlockSpec((1, hb, lanes), lambda r, h, sl: (r, h, 0))
    col = pl.BlockSpec((1, n, 1), lambda r, h, sl: (r, 0, 0))
    state = pl.BlockSpec((1, hb, n, lanes),
                         lambda r, h, sl: (sl[r], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b, hk // hb),
        in_specs=[row, row, col, col, state], out_specs=[row, state])
    y, pool = pl.pallas_call(
        functools.partial(_ssm_decode_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hk, lanes), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 5 (after the prefetched table) is the pool: output 1
        input_output_aliases={5: 1},
        interpret=interpret,
        name=scopes.SSM_DECODE_KERNEL,
    )(slots.astype(jnp.int32), xd, dec, bcol, ccol, pool)
    return y, pool


def _ssm_decode_reference(xd, dec, bcol, ccol, pool, slots):
    """The kernel's arithmetic in plain `jax.numpy`: what runs off the
    TPU and what the kernel is tested against."""
    s = pool[slots]                                   # (b, H/g, N, lanes)
    new = s * dec[:, :, None, :] + bcol[:, None] * xd[:, :, None, :]
    y = jnp.sum(new * ccol[:, None], axis=2)
    return y, pool.at[slots].set(new)


def state_update(x, dt, a, b_t, c_t, d, cache: StateLayerCache, slots):
    """One token a row through the recurrence, the state read from and
    written to the row's slot.

    x: (b, H, P); dt: (b, H) float32, after the softplus; a: (H,)
    float32, negative; b_t, c_t: (b, N); d: (H,); slots: (b,) int32, the
    slot each row reads and writes (`NULL_SLOT` for a row that is not
    there). Returns (y (b, H, P) float32, the new cache view)."""
    bsz, h, p = x.shape
    hk, _, lanes = cache.ssm_pool.shape[1:]
    x32, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    xd = (x32 * dt[..., None]).reshape(bsz, hk, lanes)
    dec = jnp.broadcast_to(jnp.exp(dt * a)[..., None],
                           (bsz, h, p)).reshape(bsz, hk, lanes)
    bcol = b_t.astype(jnp.float32)[..., None]
    ccol = c_t.astype(jnp.float32)[..., None]
    mode = _att.KERNEL_MODE
    if mode != "off" and (mode == "interpret" or _att._on_tpu()):
        _att._count_dispatch("ssm_decode_pallas_interpret"
                             if mode == "interpret" else "ssm_decode_pallas")
        y, pool = _ssm_decode_pallas(xd, dec, bcol, ccol, cache.ssm_pool,
                                     slots, interpret=mode == "interpret")
    else:
        _att._count_dispatch("ssm_decode_reference")
        y, pool = _ssm_decode_reference(xd, dec, bcol, ccol,
                                        cache.ssm_pool, slots)
    y = y.reshape(bsz, h, p) + d.astype(jnp.float32)[None, :, None] * x32
    return y, StateLayerCache(pool, cache.conv_pool, cache.slots)


def chunk_scan(x, dt, a, b_t, c_t, d, chunk: int):
    """A whole sequence from a zero state, in chunks of `chunk` tokens.

    x: (b, s, H, P); dt: (b, s, H) float32 after the softplus, 0 at the
    positions that are padding; a: (H,) float32; b_t, c_t: (b, s, N);
    d: (H,). Returns (y (b, s, H, P) float32, the state after the last
    position (b, H, P, N) float32)."""
    _att._count_dispatch("ssm_chunk_scan")
    bsz, s, h, p = x.shape
    n = b_t.shape[-1]
    op = x.dtype                                  # the products' operands
    q = min(int(chunk), s)
    pad = -s % q
    if pad:
        x, b_t, c_t = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (
            v.ndim - 2)) for v in (x, b_t, c_t))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    c = (s + pad) // q
    dt = dt.astype(jnp.float32).reshape(bsz, c, q, h)
    xc = x.reshape(bsz, c, q, h, p)
    bc, cc = b_t.reshape(bsz, c, q, n), c_t.reshape(bsz, c, q, n)
    cum = jnp.cumsum(dt * a, axis=2)              # (b, c, q, H), <= 0
    xdt = xc.astype(jnp.float32) * dt[..., None]  # (b, c, q, H, P)

    # within a chunk: ((C B^T) . L) (dt x), L[t, u] = exp(cum_t - cum_u)
    # for u <= t; C B^T is shared by the heads (one group)
    scores = jnp.einsum("bctn,bcun->bctu", cc, bc,
                        preferred_element_type=jnp.float32)
    cum_h = jnp.moveaxis(cum, 3, 2)               # (b, c, H, q)
    diff = cum_h[..., :, None] - cum_h[..., None, :]
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    y = jnp.einsum("bchtu,bcuhp->bcthp",
                   (scores[:, :, None] * decay).astype(op), xdt.astype(op),
                   preferred_element_type=jnp.float32)

    # a chunk's own end state and its decay over the whole chunk
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)     # (b, c, q, H)
    ends = jnp.einsum("bcuhp,bcun->bchpn",
                      (xdt * to_end[..., None]).astype(op), bc,
                      preferred_element_type=jnp.float32)
    whole = jnp.exp(cum[:, :, -1, :])             # (b, c, H)

    def carry_on(state, inp):
        end, dec = inp
        return dec[..., None, None] * state + end, state

    final, before = jax.lax.scan(
        carry_on, jnp.zeros((bsz, h, p, n), jnp.float32),
        (jnp.moveaxis(ends, 1, 0), jnp.moveaxis(whole, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)           # (b, c, H, P, N)
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bctn,bchpn->bcthp", cc, before.astype(op),
        preferred_element_type=jnp.float32)
    y = y + d.astype(jnp.float32)[:, None] * xc.astype(jnp.float32)
    return y.reshape(bsz, c * q, h, p)[:, :s], final


def conv_step(xbc, weight, bias, cache: StateLayerCache, slots):
    """The causal depthwise conv at one new token a row: the slot's tail
    and the token make the window; the tail shifts by one. xbc: (b, C);
    weight: (C, W). Returns (out (b, C) float32, the new cache view)."""
    bsz, ch = xbc.shape
    tail = cache.conv_pool[slots].reshape(bsz, -1, ch)
    window = jnp.concatenate(
        [tail, xbc[:, None].astype(tail.dtype)], axis=1)     # (b, W, C)
    taps = jnp.moveaxis(weight.astype(jnp.float32), 0, 1)    # (W, C)
    out = jax.nn.silu(jnp.sum(window.astype(jnp.float32) * taps, axis=1)
                      + bias.astype(jnp.float32))
    pool = cache.conv_pool.at[slots].set(window[:, 1:].reshape(bsz, -1))
    return out, StateLayerCache(cache.ssm_pool, pool, cache.slots)


def conv_prefill(xbc, weight, bias, length):
    """The conv over a whole sequence from an empty tail, as `W` shifted
    products. xbc: (b, s, C); `length` (traced) is the number of real
    positions. Returns (out (b, s, C) float32, the tail after position
    `length - 1`: (b, W - 1, C), zeros where the sequence is shorter)."""
    s, taps = xbc.shape[1], weight.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    acc = bias.astype(jnp.float32) + sum(
        w[:, j] * padded[:, j:j + s].astype(jnp.float32)
        for j in range(taps))
    tail = jax.lax.dynamic_slice_in_dim(padded, length, taps - 1, axis=1)
    return jax.nn.silu(acc), tail


def write_state(cache: StateLayerCache, state, tail) -> StateLayerCache:
    """A prefill's result into its rows' slots: state (b, H, P, N)
    float32 and tail (b, W - 1, C)."""
    lanes = cache.ssm_pool.shape[-1]
    pack = lanes // state.shape[2]
    ssm = cache.ssm_pool.at[cache.slots].set(
        pack_state(state.astype(jnp.float32), pack))
    conv = cache.conv_pool.at[cache.slots].set(
        tail.reshape(tail.shape[0], -1).astype(cache.conv_pool.dtype))
    return StateLayerCache(ssm, conv, cache.slots)


def parked_slots(slots, live):
    """The slots a decode step's rows use: a row that is not `live`
    (parked inside a block, or padding) takes the null slot."""
    return jnp.where(live, slots, jnp.int32(NULL_SLOT))
