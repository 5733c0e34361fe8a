"""Ring attention + Ulysses — first-class long-context primitives (sep axis).

Ref: the reference exposes flash-attn kernels, the sep HCG axis, and
batch_isend_irecv ring primitives, with ring/Ulysses loops composed in the
ecosystem (SURVEY §2.3 "Ring attention"); here both are in-core as the prompt
requires.

* ring_flash_attention: inside shard_map over the sep axis each rank holds a
  sequence shard of Q,K,V; KV blocks rotate around the ring via ppermute
  while the online-softmax accumulator (m, l, o) folds in one block per step
  — flash attention's numerics, ICI-bandwidth communication, O(s/n) memory.
* ulysses_attention: all_to_all reshards sequence<->heads so every rank runs
  full-sequence attention on its head slice, then reshards back (the
  DeepSpeed-Ulysses layout swap).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ....core.tensor import Tensor

__all__ = ["ring_flash_attention", "ulysses_attention", "RingFlashAttention"]


def _unwrap(x):
    return x._data if isinstance(x, Tensor) else x


def _axis_size(name):
    return int(jax.lax.axis_size(name))


def ring_flash_attention(q, k, v, group=None, causal: bool = False,
                         axis_name: Optional[str] = None,
                         scale: Optional[float] = None,
                         impl: Optional[str] = None,
                         interpret: bool = False):
    """Ring attention over a sequence-sharded axis.

    Args are [batch, heads, s_local, head_dim] shards inside shard_map over
    `axis_name` (or group.axis_name). Returns the local attention output
    shard. Outside a named axis, falls back to plain attention.

    impl: None (auto: Pallas on TPU, XLA einsum elsewhere) | "pallas" |
    "xla". The Pallas path runs the flash kernel per ring step — bf16 MXU
    matmuls, in-kernel causal offsets, no materialized score block
    (SURVEY §5's "ring attention as a Pallas splash/flash kernel").
    """
    qd, kd, vd = _unwrap(q), _unwrap(k), _unwrap(v)
    name = axis_name or (group.axis_name if group is not None else "sep")
    scale = scale if scale is not None else qd.shape[-1] ** -0.5

    try:
        n = _axis_size(name)
    except (NameError, KeyError, TypeError, ValueError):
        # no live sep axis (eager / outside shard_map) -> local-only.
        # Deliberately NOT broad: an AttributeError from jax API
        # drift in _axis_size must propagate, not silently shrink
        # the ring to the local shard (the PR 5 wrong-result bug).
        n = 1
    if n == 1:
        out = _flash_block(qd, kd, vd, scale, causal, 0, 0, None)
        return Tensor(out.astype(qd.dtype)) if isinstance(q, Tensor) else out

    from ....ops import pallas_kernels as _pk

    use_pallas = impl == "pallas" or (
        impl is None and _pk._on_tpu() and qd.ndim == 4
        and 8 <= qd.shape[-1] <= 256)
    if use_pallas:
        out = _pk.ring_flash_attention_pallas(
            qd, kd, vd, name, causal=causal, scale=scale,
            interpret=interpret)
        return Tensor(out) if isinstance(q, Tensor) else out

    my = jax.lax.axis_index(name)
    s_local = qd.shape[2]

    # online softmax accumulators
    o = jnp.zeros_like(qd, dtype=jnp.float32)
    m = jnp.full(qd.shape[:3], -jnp.inf, dtype=jnp.float32)   # b,h,s
    l = jnp.zeros(qd.shape[:3], dtype=jnp.float32)

    kv = (kd, vd)
    perm = [(i, (i + 1) % n) for i in range(n)]
    for step in range(n):
        src = (my - step) % n     # whose KV block we now hold
        kb, vb = kv
        o, m, l = _online_update(qd, kb, vb, o, m, l, scale, causal,
                                 my, src, s_local)
        if step != n - 1:
            kv = jax.lax.ppermute(kv, name, perm)
    out = (o / l[..., None]).astype(qd.dtype)
    if isinstance(q, Tensor):
        return Tensor(out)
    return out


def _online_update(qd, kb, vb, o, m, l, scale, causal, my_idx, src_idx,
                   s_local):
    """Fold one KV block into the (o, m, l) accumulator (flash attention's
    streaming softmax)."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", qd.astype(jnp.float32),
                        kb.astype(jnp.float32)) * scale
    if causal:
        q_pos = my_idx * s_local + jnp.arange(s_local)[:, None]
        k_pos = src_idx * s_local + jnp.arange(kb.shape[2])[None, :]
        mask = q_pos >= k_pos
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    block_max = jnp.max(scores, axis=-1)
    new_m = jnp.maximum(m, block_max)
    # guard fully-masked rows (new_m = -inf)
    safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
    p = jnp.exp(scores - safe_m[..., None])
    p = jnp.where(jnp.isfinite(scores), p, 0.0)
    correction = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
    new_l = l * correction + jnp.sum(p, axis=-1)
    new_o = o * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, vb.astype(jnp.float32))
    return new_o, new_m, new_l


def _flash_block(qd, kd, vd, scale, causal, my, src, _):
    scores = jnp.einsum("bhqd,bhkd->bhqk", qd, kd) * scale
    if causal:
        s_q, s_k = qd.shape[2], kd.shape[2]
        mask = jnp.arange(s_q)[:, None] >= jnp.arange(s_k)[None, :]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vd)


def ulysses_attention(q, k, v, group=None, causal: bool = False,
                      axis_name: Optional[str] = None,
                      scale: Optional[float] = None,
                      impl: Optional[str] = None,
                      interpret: bool = False):
    """Ulysses: all_to_all seq<->heads, full-sequence attention, reshard back.

    Inputs [b, h, s_local, d] sharded on seq inside shard_map; heads must be
    divisible by the axis size. The full-sequence attention on each head
    slice runs the Pallas flash kernel on TPU (impl="pallas" to force,
    "xla" for the materialized reference).
    """
    qd, kd, vd = _unwrap(q), _unwrap(k), _unwrap(v)
    name = axis_name or (group.axis_name if group is not None else "sep")
    try:
        n = _axis_size(name)
    except (NameError, KeyError, TypeError, ValueError):
        # no live sep axis (eager / outside shard_map) -> local-only.
        # Deliberately NOT broad: an AttributeError from jax API
        # drift in _axis_size must propagate, not silently shrink
        # the ring to the local shard (the PR 5 wrong-result bug).
        n = 1
    scale = scale if scale is not None else qd.shape[-1] ** -0.5
    if n == 1:
        out = _flash_block(qd, kd, vd, scale, causal, 0, 0, None)
        return Tensor(out) if isinstance(q, Tensor) else out

    assert qd.shape[1] % n == 0, "heads must divide the sep axis size"

    def seq_to_heads(x):
        # [b, h, s/n, d] -> all_to_all over heads -> [b, h/n, s, d]
        return jax.lax.all_to_all(x, name, split_axis=1, concat_axis=2,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, name, split_axis=2, concat_axis=1,
                                  tiled=True)

    qh, kh, vh = seq_to_heads(qd), seq_to_heads(kd), seq_to_heads(vd)
    from ....ops import pallas_kernels as _pk

    default_scale = abs(scale - qd.shape[-1] ** -0.5) < 1e-12
    if impl == "pallas" and not default_scale:
        raise ValueError(
            "ulysses impl='pallas' supports the default 1/sqrt(d) scale "
            "only; drop the custom scale or use impl='xla'")
    use_pallas = default_scale and (impl == "pallas" or (
        impl is None and _pk._on_tpu() and 8 <= qd.shape[-1] <= 256))
    if use_pallas:
        # full-sequence flash on the head slice: (b,h,s,d) matches the
        # kernel's padded layout directly; vma declared for shard_map
        out = _pk._fwd_flash_for_ulysses(qh, kh, vh, scale, causal, name,
                                         interpret)
    else:
        out = _flash_block(qh, kh, vh, scale, causal, 0, 0, None)
    out = heads_to_seq(out.astype(qd.dtype))
    return Tensor(out) if isinstance(q, Tensor) else out


class RingFlashAttention:
    """Layer-ish wrapper (callable) selecting ring vs ulysses."""

    def __init__(self, mode: str = "ring", group=None, causal: bool = True):
        assert mode in ("ring", "ulysses")
        self.mode = mode
        self.group = group
        self.causal = causal

    def __call__(self, q, k, v):
        fn = (ring_flash_attention if self.mode == "ring"
              else ulysses_attention)
        return fn(q, k, v, group=self.group, causal=self.causal)
