"""Hybrid decoder of Mamba-2 and attention layers: the Granite 4.0-H
family's layer as `granite-4.0-h-micro` publishes it
(https://huggingface.co/ibm-granite/granite-4.0-h-micro, `model_type`
`granitemoehybrid`, dense: `num_local_experts` 0).

Pre-norm residual blocks, RMSNorm, `layer_types` naming each layer's
mixer. With `h` the stream:

- embedding `h = E[ids] * embedding_multiplier`; every sub-layer's output
  enters the stream times `residual_multiplier`; the head is the
  embedding's transpose over the final norm, divided by `logits_scaling`.
- MLP (every layer): `[a, b] = split(x W_in)`, `(silu(a) * b) W_out`.
- attention: grouped-query, no bias, **no positional encoding**
  (`position_embedding_type` "nope"), scores times `attention_multiplier`
  in the place of 1/sqrt(head width). The kernels the repo has scale by
  1/sqrt(width) themselves, so q is scaled by the quotient (0.125 for
  the published sizes, exact in any float) in front of them.
- Mamba-2 mixer (H heads of P, state N, one group, conv width W):
  `[z, xBC, dt] = split(x W_in)`; `xBC` through a causal depthwise conv
  and silu; `[x, B, C] = split(xBC)`; `dt = softplus(dt + dt_bias)`,
  `A = -exp(A_log)`; `S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h]
  x_t[h] (outer) B_t`, `y_t[h] = S_t[h] C_t + D[h] x_t[h]`;
  `y = RMS(y * silu(z); g)` over all H * P columns (one group, the gate
  first); `out = y W_out`. `A_log`, `dt_bias` and `D` are float32
  whatever the model's type, as the published code holds them.

Serving (`serving.ServingEngine`): the cache path takes one view a layer,
a `PagedLayerCache` for an attention layer and a `StateLayerCache` for a
Mamba layer (`config.state_cache_spec` tells the cache manager which is
which). A prefill runs the chunked scan of `serving.ssm` and writes the
state after the last real token, and the conv's last real rows, to the
row's slot; a decode step updates the slot in place. The protocol is
`models/mla_moe.py`'s: `logits_at` makes a prefill return one position's
logits and marks the positions past it as padding.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Parameter, Tensor
from ..nn import initializer as I
from ..profiler import scopes

__all__ = ["HybridSsmConfig", "HybridSsmModel", "HybridSsmForCausalLM"]

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclasses.dataclass
class HybridSsmConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    shared_intermediate_size: int = 8192
    layer_types: Tuple[str, ...] = _PERIOD * 4
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    attention_bias: bool = False
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    position_embedding_type: str = "nope"
    num_local_experts: int = 0
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    # as `MlaMoeConfig`'s: the type parameters are created in, and
    # parameters as shapes only until a loader assigns their `_data`
    dtype: str = "float32"
    deferred_weights: bool = False

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        unknown = set(self.layer_types) - {"mamba", "attention"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if "attention" not in self.layer_types:
            raise ValueError(
                "a decoder of Mamba layers alone is not written: a parked "
                "row is told by its position in an attention layer's "
                "page table")
        unwritten = [name for name, off in (
            ("mamba_n_groups", self.mamba_n_groups == 1),
            ("mamba_proj_bias", not self.mamba_proj_bias),
            ("mamba_conv_bias", self.mamba_conv_bias),
            ("attention_bias", not self.attention_bias),
            ("num_local_experts", self.num_local_experts == 0),
            ("tie_word_embeddings", self.tie_word_embeddings),
            ("position_embedding_type",
             self.position_embedding_type == "nope")) if not off]
        if unwritten:
            raise ValueError(
                "HybridSsmConfig: only the published values of "
                + ", ".join(unwritten) + " are written")
        if self.mamba_n_heads * self.mamba_d_head != \
                self.mamba_expand * self.hidden_size:
            raise ValueError("mamba_n_heads * mamba_d_head must be "
                             "mamba_expand * hidden_size")

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def state_cache_spec(self):
        """Which layers keep a state slot instead of K/V pages, and its
        sizes; its presence is what tells `serving.kv_cache` to build
        state pools beside the K/V pools."""
        from ..serving.kv_cache import StateSpec
        return StateSpec(
            state_layers=tuple(t == "mamba" for t in self.layer_types),
            heads=self.mamba_n_heads, head_dim=self.mamba_d_head,
            state_dim=self.mamba_d_state, conv_dim=self.conv_dim,
            conv_width=self.mamba_d_conv)

    @classmethod
    def granite_4_0_h_micro(cls):
        return cls()

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=512, hidden_size=128, num_hidden_layers=4,
                    num_attention_heads=4, num_key_value_heads=2,
                    shared_intermediate_size=256,
                    layer_types=("mamba", "attention", "mamba", "mamba"),
                    mamba_n_heads=4, mamba_d_head=64, mamba_d_state=16,
                    mamba_chunk_size=8, attention_multiplier=1.0 / 32,
                    # at this width and depth the published multipliers
                    # (12 and 0.22) leave the logits to the embedding of
                    # the last token alone: nothing a layer does would
                    # show in a token
                    embedding_multiplier=1.0, residual_multiplier=1.0,
                    max_position_embeddings=512)
        base.update(kw)
        return cls(**base)


def _param(layer: nn.Layer, cfg: HybridSsmConfig, shape,
           kind: str = "weight", dtype: Optional[str] = None):
    """A parameter of `kind` weight (N(0, initializer_range)), gain (1),
    bias (0) or one of the Mamba-2 draws, in the configuration's type
    (or `dtype`), or its shape alone."""
    dtype = dtype or cfg.dtype
    if cfg.deferred_weights:
        p = Parameter(jnp.zeros((), dtype))
        p._data = jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
        return p
    init = {"weight": I.Normal(0.0, cfg.initializer_range),
            "gain": I.Constant(1.0), "bias": I.Constant(0.0),
            # Mamba-2's own: A in [1, 16], dt in [1e-3, 1e-1] through the
            # softplus's inverse; the midpoints here, a loader or a test
            # draws them
            "a_log": I.Constant(math.log(4.0)),
            "dt_bias": I.Constant(math.log(math.expm1(1e-2)))}[kind]
    return layer.create_parameter(list(shape), dtype=dtype,
                                  default_initializer=init)


class _Weight(nn.Layer):
    """A bias-free projection or a norm's gain, held as `weight`."""

    def __init__(self, cfg: HybridSsmConfig, shape, kind: str = "weight"):
        super().__init__()
        self.weight = _param(self, cfg, shape, kind)


def _rms(x, gain, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


class _Conv(nn.Layer):
    """The depthwise causal conv's taps (channels, width) and bias."""

    def __init__(self, cfg: HybridSsmConfig):
        super().__init__()
        self.weight = _param(self, cfg, (cfg.conv_dim, cfg.mamba_d_conv))
        self.bias = _param(self, cfg, (cfg.conv_dim,), "bias")


class Mamba2Mixer(nn.Layer):
    def __init__(self, cfg: HybridSsmConfig):
        super().__init__()
        self.cfg = cfg
        h, heads = cfg.hidden_size, cfg.mamba_n_heads
        self.in_proj = _Weight(
            cfg, (h, cfg.d_inner + cfg.conv_dim + heads))
        self.conv1d = _Conv(cfg)
        self.A_log = _param(self, cfg, (heads,), "a_log", "float32")
        self.D = _param(self, cfg, (heads,), "gain", "float32")
        self.dt_bias = _param(self, cfg, (heads,), "dt_bias", "float32")
        self.norm = _Weight(cfg, (cfg.d_inner,), "gain")
        self.out_proj = _Weight(cfg, (cfg.d_inner, h))

    def forward(self, x, gain, valid, cache=None, live=None):
        """x: (b, s, hidden) raw array, the stream before the first norm;
        gain: that norm's; valid: (b, s) bool (prefill: False at padding);
        live: (b,) bool, given by a decode step alone (False for a parked
        or absent row). Returns the mixer's output before the residual
        and the new cache view."""
        from ..serving import ssm

        cfg = self.cfg
        decode = live is not None
        b, s, _ = x.shape
        heads, p, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
        d_in = cfg.d_inner
        with jax.named_scope(scopes.ATTN_QKV):
            with jax.named_scope(scopes.SSM_IN_PROJ):
                proj = _rms(x, gain, cfg.rms_norm_eps) \
                    @ self.in_proj.weight._data
                z = proj[..., :d_in]
                xbc = proj[..., d_in:d_in + cfg.conv_dim]
                dt = proj[..., d_in + cfg.conv_dim:]
            with jax.named_scope(scopes.SSM_CONV):
                w, bias = self.conv1d.weight._data, self.conv1d.bias._data
                if decode:
                    slots = ssm.parked_slots(cache.slots, live)
                    conv, cache = ssm.conv_step(xbc[:, 0], w, bias, cache,
                                                slots)
                    conv = conv[:, None]
                else:
                    length = jnp.sum(valid[0].astype(jnp.int32))
                    conv, tail = ssm.conv_prefill(xbc, w, bias, length)
                conv = conv.astype(x.dtype)
        xs = conv[..., :d_in].reshape(b, s, heads, p)
        b_t, c_t = conv[..., d_in:d_in + n], conv[..., d_in + n:]
        outer = (scopes.PAGED_ATTENTION if decode
                 else scopes.PREFILL_ATTENTION)
        inner = (scopes.SSM_STATE_UPDATE if decode
                 else scopes.SSM_CHUNK_SCAN)
        with jax.named_scope(outer), jax.named_scope(inner):
            a = -jnp.exp(self.A_log._data.astype(jnp.float32))
            dt = jax.nn.softplus(dt.astype(jnp.float32)
                                 + self.dt_bias._data.astype(jnp.float32))
            if decode:
                y, cache = ssm.state_update(
                    xs[:, 0], dt[:, 0], a, b_t[:, 0], c_t[:, 0],
                    self.D._data, cache, slots)
                y = y[:, None]
            else:
                # padding: decay 1, input 0, the state stands
                dt = jnp.where(valid[..., None], dt, 0.0)
                y, state = ssm.chunk_scan(xs, dt, a, b_t, c_t, self.D._data,
                                          cfg.mamba_chunk_size)
                if cache is not None:
                    cache = ssm.write_state(cache, state, tail)
        with jax.named_scope(scopes.ATTN_OUT), \
                jax.named_scope(scopes.SSM_GATE_OUT):
            gated = y.reshape(b, s, d_in) * jax.nn.silu(
                z.astype(jnp.float32))
            out = _rms(gated, self.norm.weight._data,
                       cfg.rms_norm_eps).astype(x.dtype) \
                @ self.out_proj.weight._data
        return out, cache


class HybridAttention(nn.Layer):
    def __init__(self, cfg: HybridSsmConfig):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        hd = h // cfg.num_attention_heads
        self.q_proj = _Weight(cfg, (h, cfg.num_attention_heads * hd))
        self.k_proj = _Weight(cfg, (h, cfg.num_key_value_heads * hd))
        self.v_proj = _Weight(cfg, (h, cfg.num_key_value_heads * hd))
        self.o_proj = _Weight(cfg, (cfg.num_attention_heads * hd, h))

    def forward(self, x, gain, cache=None, start_pos=0):
        from ..nn import functional as F
        from ..serving.attention import paged_attend

        cfg = self.cfg
        b, s, _ = x.shape
        heads, kvh = cfg.num_attention_heads, cfg.num_key_value_heads
        hd = cfg.hidden_size // heads
        with jax.named_scope(scopes.ATTN_QKV):
            normed = _rms(x, gain, cfg.rms_norm_eps)
            # the kernels scale by hd ** -0.5: q carries the rest
            q = (normed @ self.q_proj.weight._data) * jnp.asarray(
                cfg.attention_multiplier * math.sqrt(hd), x.dtype)
            q = q.reshape(b, s, heads, hd)
            k = (normed @ self.k_proj.weight._data).reshape(b, s, kvh, hd)
            v = (normed @ self.v_proj.weight._data).reshape(b, s, kvh, hd)
        if cache is not None:
            ctx, cache = paged_attend(Tensor(q), Tensor(k), Tensor(v),
                                      cache, start_pos, heads // kvh)
        else:
            with jax.named_scope(scopes.PREFILL_ATTENTION):
                rep = heads // kvh
                ctx = F.scaled_dot_product_attention(
                    Tensor(q), Tensor(jnp.repeat(k, rep, axis=2)),
                    Tensor(jnp.repeat(v, rep, axis=2)), is_causal=True)
        with jax.named_scope(scopes.ATTN_OUT):
            out = ctx._data.reshape(b, s, heads * hd) \
                @ self.o_proj.weight._data
        return out, cache


class HybridMLP(nn.Layer):
    """SwiGLU with the gate and the up projection in one matrix."""

    def __init__(self, cfg: HybridSsmConfig):
        super().__init__()
        h, f = cfg.hidden_size, cfg.shared_intermediate_size
        self.input_linear = _Weight(cfg, (h, 2 * f))
        self.output_linear = _Weight(cfg, (f, h))

    def forward(self, x):
        both = x @ self.input_linear.weight._data
        f = both.shape[-1] // 2
        return (jax.nn.silu(both[..., :f]) * both[..., f:]) \
            @ self.output_linear.weight._data


class HybridDecoderLayer(nn.Layer):
    def __init__(self, cfg: HybridSsmConfig, index: int):
        super().__init__()
        self.cfg = cfg
        self.is_mamba = cfg.layer_types[index] == "mamba"
        self.input_layernorm = _Weight(cfg, (cfg.hidden_size,), "gain")
        if self.is_mamba:
            self.mamba = Mamba2Mixer(cfg)
        else:
            self.self_attn = HybridAttention(cfg)
        self.post_attention_layernorm = _Weight(cfg, (cfg.hidden_size,),
                                                "gain")
        self.shared_mlp = HybridMLP(cfg)

    def forward(self, x, valid, cache=None, start_pos=0, live=None):
        cfg = self.cfg
        res = jnp.asarray(cfg.residual_multiplier, x.dtype)
        gain = self.input_layernorm.weight._data
        if self.is_mamba:
            out, cache = self.mamba(x, gain, valid, cache, live)
            with jax.named_scope(scopes.ATTN_OUT), \
                    jax.named_scope(scopes.SSM_GATE_OUT):
                x = x + out * res
        else:
            out, cache = self.self_attn(x, gain, cache, start_pos)
            with jax.named_scope(scopes.ATTN_OUT):
                x = x + out * res
        with jax.named_scope(scopes.MLP):
            normed = _rms(x, self.post_attention_layernorm.weight._data,
                          cfg.rms_norm_eps)
            x = x + self.shared_mlp(normed) * res
        return x, cache


class HybridSsmModel(nn.Layer):
    def __init__(self, cfg: Optional[HybridSsmConfig] = None):
        super().__init__()
        self.config = cfg or HybridSsmConfig()
        cfg = self.config
        self.embed_tokens = _Weight(cfg, (cfg.vocab_size, cfg.hidden_size))
        self.layers = nn.LayerList([HybridDecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = _Weight(cfg, (cfg.hidden_size,), "gain")

    def forward(self, ids, valid, caches=None, start_pos=0, live=None):
        cfg = self.config
        with jax.named_scope(scopes.EMBED):
            x = self.embed_tokens.weight._data[ids]
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        if caches is not None and len(caches) != len(self.layers):
            raise ValueError(f"got {len(caches)} caches for "
                             f"{len(self.layers)} decoder layers")
        new_caches = []
        for i, layer in enumerate(self.layers):
            x, nc = layer(x, valid, None if caches is None else caches[i],
                          start_pos, live)
            new_caches.append(nc)
        return x, (None if caches is None else new_caches)


class HybridSsmForCausalLM(nn.Layer):
    # what `serving.ServingEngine` asks a model before it builds its
    # executables: forward takes `logits_at`
    serving_logits_at = True

    def __init__(self, cfg: Optional[HybridSsmConfig] = None):
        super().__init__()
        self.model = HybridSsmModel(cfg)
        self.config = self.model.config

    def forward(self, input_ids, caches=None, start_pos=0, logits_at=None):
        """Without caches: logits of every position, one causal forward
        from an empty state. With the engine's per-layer views: (logits,
        new views); a prefill (`start_pos` the integer 0) given
        `logits_at` returns the logits of that position alone, (b, 1,
        vocab), and treats the positions past it as padding: the state it
        leaves in the slot is the state after that position."""
        ids = input_ids._data if hasattr(input_ids, "_data") else input_ids
        b, s = ids.shape
        cfg = self.config
        prefill = caches is None or (isinstance(start_pos, int)
                                     and start_pos == 0)
        live = None
        if prefill:
            last = s - 1 if logits_at is None else logits_at
            valid = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None] <= last, (b, s))
        elif s == 1:
            # a decode row parked at its table's capacity is not there
            from ..serving.kv_cache import overflow_position
            sp = jnp.asarray(start_pos._data if hasattr(start_pos, "_data")
                             else start_pos, jnp.int32)
            paged = next(c for c in caches if hasattr(c, "page_table"))
            park = overflow_position(paged.page_table.shape[1],
                                     paged.page_size)
            live = sp.reshape(b) < park
            valid = live[:, None]
        else:
            raise NotImplementedError(
                "HybridSsmForCausalLM over state slots prefills from "
                "position 0 and decodes one token a row: a prefill at an "
                "offset (prefix cache, chunked prefill, speculative "
                "verify) would need the state at that offset, which is "
                "not kept")
        x, new_caches = self.model(ids, valid, caches, start_pos, live)
        with jax.named_scope(scopes.LM_HEAD):
            if logits_at is not None:
                x = jax.lax.dynamic_slice_in_dim(x, logits_at, 1, axis=1)
            normed = _rms(x, self.model.norm.weight._data, cfg.rms_norm_eps)
            logits = (normed @ self.model.embed_tokens.weight._data.T) \
                / jnp.asarray(cfg.logits_scaling, x.dtype)
        if caches is None:
            return Tensor(logits)
        return Tensor(logits), new_caches
