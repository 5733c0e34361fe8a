"""Decoder with latent attention (MLA) and a dropless mixture of experts:
the DeepSeek-V3 family's layer as JoyAI-LLM-Flash publishes it
(https://huggingface.co/jdopensource/JoyAI-LLM-Flash, `model_type`
`joyai_llm_flash`).

Pre-norm residual blocks, RMSNorm, no biases.

- Attention: queries through a low-rank pair (`q_a_proj`, norm,
  `q_b_proj`), a head being [q_nope; q_rope]; keys and values through one
  down-projection to [latent; rope key] (`kv_a_proj`), the latent normed,
  the one rope key shared by all heads, and an up-projection
  (`kv_b_proj`) to each head's [k_nope; v]. RoPE rotates the pairs
  (2i, 2i+1) of the rope part in place (`rope_interleave`).
- What is cached is the row [normed latent; rotated rope key] a token
  (`serving.kv_cache.LatentLayerCache`), not K and V. Prefill attends the
  step's own expanded K/V; decode takes the **absorbed** form: q_nope is
  carried into the latent's space through W_uk, scores are taken against
  the cached rows themselves, the latent is summed under the softmax and
  taken through W_uv afterwards (`serving.attention`, kernel `mla_decode`).
- The first `first_k_dense_replace` layers have a SwiGLU MLP; the others a
  dropless expert layer: sigmoid scores in float32, the top `k` of score +
  bias, weights scale * score / sum, (token, expert) pairs sorted by
  expert, one grouped matmul a projection over the ragged groups, the
  weighted combine, and a shared expert added. No capacity, no dropped
  token, empty groups allowed.

The multi-token-prediction layer of the published checkpoint is not
built: the main model's logits do not depend on it.

Serving protocol beyond `forward(input_ids, caches=, start_pos=)`:
`logits_at` (a traced index) makes a prefill return the logits of that
one position instead of a (bucket, vocab) array nobody reads, and the
cache path returns a third value, `{"moe_expert_tokens": (expert layers,
experts) int32}`, the tokens each expert got (padding and parked rows
left out), which the engine's counters read.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Parameter, Tensor
from ..nn import initializer as I
from ..profiler import scopes

__all__ = ["MlaMoeConfig", "MlaMoeModel", "MlaMoeForCausalLM",
           "dropless_moe", "GROUPED_MATMUL"]


@dataclasses.dataclass
class MlaMoeConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.5
    rope_theta: float = 32000000.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    # the type parameters are created in: a 5.5B-parameter cut made in
    # float32 and cast afterwards would not fit the chip it is cast on
    dtype: str = "float32"
    # parameters are shapes only until a loader assigns their `_data`
    # (`jax.ShapeDtypeStruct` placeholders): nothing is allocated or
    # drawn for weights that a checkpoint is about to replace
    deferred_weights: bool = False

    @property
    def latent_cache_dim(self) -> int:
        """Width of the row a token leaves in the cache; its presence is
        what tells `serving.kv_cache` to build a latent pool."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @classmethod
    def joyai_llm_flash(cls):
        return cls()

    @classmethod
    def tiny(cls):
        return cls(vocab_size=512, hidden_size=64, num_hidden_layers=3,
                   num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
                   qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
                   intermediate_size=128, moe_intermediate_size=32,
                   n_routed_experts=16, num_experts_per_tok=4,
                   max_position_embeddings=256)


def _param(layer: nn.Layer, cfg: MlaMoeConfig, shape, kind: str = "weight"):
    """A parameter of `kind` weight (N(0, initializer_range)), gain (1) or
    bias (0) in the configuration's type, or its shape alone."""
    if cfg.deferred_weights:
        p = Parameter(jnp.zeros((), cfg.dtype))
        p._data = jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(cfg.dtype))
        return p
    init = {"weight": I.Normal(0.0, cfg.initializer_range),
            "gain": I.Constant(1.0), "bias": I.Constant(0.0)}[kind]
    return layer.create_parameter(list(shape), dtype=cfg.dtype,
                                  default_initializer=init)


class _Weight(nn.Layer):
    """A bias-free projection or a norm's gain, held as `weight`."""

    def __init__(self, cfg: MlaMoeConfig, shape, kind: str = "weight"):
        super().__init__()
        self.weight = _param(self, cfg, shape, kind)


def _rms(x, gain, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta: float):
    """x: (b, s, ..., rope); positions: (b, s). Rotates the pairs
    (2i, 2i+1) by p * theta^(-2i/rope) in float32, in place."""
    rope = x.shape[-1]
    freq = theta ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    angle = positions.astype(jnp.float32)[..., None] * freq
    angle = angle.reshape(positions.shape + (1,) * (x.ndim - 3)
                          + (rope // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(x.shape).astype(x.dtype)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


class MlaAttention(nn.Layer):
    def __init__(self, cfg: MlaMoeConfig):
        super().__init__()
        h, heads = cfg.hidden_size, cfg.num_attention_heads
        self.cfg = cfg
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        self.q_a_proj = _Weight(cfg, (h, cfg.q_lora_rank))
        self.q_a_layernorm = _Weight(cfg, (cfg.q_lora_rank,), "gain")
        self.q_b_proj = _Weight(cfg, (cfg.q_lora_rank, heads * qk))
        self.kv_a_proj = _Weight(
            cfg, (h, cfg.kv_lora_rank + cfg.qk_rope_head_dim))
        self.kv_a_layernorm = _Weight(cfg, (cfg.kv_lora_rank,), "gain")
        self.kv_b_proj = _Weight(
            cfg, (cfg.kv_lora_rank,
                  heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)))
        self.o_proj = _Weight(cfg, (heads * cfg.v_head_dim, h))

    def forward(self, x, cache=None, start_pos=0):
        """x: (b, s, hidden) raw array, already normed. Returns the
        attention output before the residual and the new cache view."""
        from ..serving import attention as att

        cfg = self.cfg
        b, s, _ = x.shape
        heads, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
        rope, vd, kvr = (cfg.qk_rope_head_dim, cfg.v_head_dim,
                         cfg.kv_lora_rank)
        eps, scale = cfg.rms_norm_eps, (nope + rope) ** -0.5
        prefill = isinstance(start_pos, int) and start_pos == 0
        decode = cache is not None and not prefill and s == 1
        if cache is not None and not (prefill or decode):
            raise NotImplementedError(
                "MlaAttention over a latent pool prefills from position 0 "
                "and decodes one token a row: a prefill at an offset "
                "(prefix cache, chunked prefill, speculative verify) is "
                "not written")
        w_kv_b = self.kv_b_proj.weight._data.reshape(kvr, heads, nope + vd)
        with jax.named_scope(scopes.ATTN_QKV):
            pos = att._positions(start_pos, b, s)
            cq = _rms(x @ self.q_a_proj.weight._data,
                      self.q_a_layernorm.weight._data, eps)
            q = (cq @ self.q_b_proj.weight._data).reshape(
                b, s, heads, nope + rope)
            q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos,
                                                  cfg.rope_theta)
            kva = x @ self.kv_a_proj.weight._data
            latent = _rms(kva[..., :kvr], self.kv_a_layernorm.weight._data,
                          eps)
            k_rope = _rope(kva[..., kvr:], pos, cfg.rope_theta)
            if not decode:
                kv = jnp.einsum("bsc,chd->bshd", latent, w_kv_b)
                k = jnp.concatenate(
                    [kv[..., :nope], jnp.broadcast_to(
                        k_rope[:, :, None], (b, s, heads, rope))], -1)
                v = kv[..., nope:]
                q = jnp.concatenate([q_nope, q_rope], -1)
        new_cache = None
        if cache is not None:
            new_cache, pos = att.latent_write(
                jnp.concatenate([latent, k_rope], -1), cache, start_pos)
        if decode:
            with jax.named_scope(scopes.PAGED_ATTENTION):
                with jax.named_scope(scopes.MLA_ABSORB):
                    q_lat = jnp.concatenate(
                        [jnp.einsum("bhn,chn->bhc", q_nope[:, 0],
                                    w_kv_b[..., :nope]), q_rope[:, 0]], -1)
            u = att.latent_decode_attention(q_lat, new_cache, pos[:, 0],
                                            scale, kvr)
            with jax.named_scope(scopes.PAGED_ATTENTION):
                ctx = jnp.einsum("bhc,chv->bhv", u, w_kv_b[..., nope:])
        else:
            ctx = att.latent_prefill_attention(q, k, v, scale)
        with jax.named_scope(scopes.ATTN_OUT):
            out = ctx.reshape(b, s, heads * vd) @ self.o_proj.weight._data
        return out, new_cache


class MlaMoeMLP(nn.Layer):
    """SwiGLU: down(silu(gate(x)) * up(x)); the dense layers' MLP and the
    shared expert."""

    def __init__(self, cfg: MlaMoeConfig, width: int):
        super().__init__()
        h = cfg.hidden_size
        self.gate_proj = _Weight(cfg, (h, width))
        self.up_proj = _Weight(cfg, (h, width))
        self.down_proj = _Weight(cfg, (width, h))

    def forward(self, x):
        return _swiglu(x, self.gate_proj.weight._data,
                       self.up_proj.weight._data,
                       self.down_proj.weight._data)


# ----------------------------------------------------------- expert layer

# "auto": the megablox grouped-matmul kernel of the installed JAX on the
# TPU (on the v5e it beat `jax.lax.ragged_dot`, which XLA lowers to a
# kernel of its own, 1.3 to 2.3 ms at a decode step's 256 pairs and 4.0
# to 6.2 ms at a prefill chunk's 65,536), `jax.lax.ragged_dot` elsewhere;
# "ragged_dot" and "gmm_interpret" force one (tests)
GROUPED_MATMUL = "auto"
# rows of the sorted pairs one tile of the grouped matmul holds: a tile
# that straddles two experts is computed once for each, so a decode
# step's few pairs take small tiles and a prefill's many take large ones
# (on the v5e, 256 pairs over 162 experts: 32 rows 1.30 ms a projection,
# 128 rows 1.42; 65,536 pairs: 32 rows 10.8 ms, 128 rows 4.5, 256 rows
# 4.0 but 4.7 for 3.5 on the down projection; PERF.md section 6, PR 29)
_GMM_TILE_ROWS_SMALL, _GMM_TILE_ROWS, _GMM_SMALL_PAIRS = 32, 128, 2048
# tokens of a prefill the expert layer takes at a time: the sorted pairs,
# their three projections and the unsorted outputs are 8 x the tokens
# each, 2 GB at 16k tokens; at 8k an expert still gets 256 rows
_MOE_CHUNK_TOKENS = 8192


def _grouped_matmul(xs, w, group_sizes):
    """xs[rows of group e] @ w[e] for the ragged groups of `group_sizes`;
    rows past the last group hold nothing a caller may read."""
    mode = GROUPED_MATMUL
    if mode == "auto":
        from ..ops.pallas_kernels import _on_tpu
        mode = "gmm" if _on_tpu() else "ragged_dot"
    if mode == "ragged_dot":
        return jax.lax.ragged_dot(xs, w, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    # whole tiles of rows (16 is a bf16 tile's sublanes); the rows added
    # lie past the last group
    m, k = xs.shape
    tm = min(_GMM_TILE_ROWS if m > _GMM_SMALL_PAIRS
             else _GMM_TILE_ROWS_SMALL, -(-m // 16) * 16)
    padded = -(-m // tm) * tm
    # the kernel's dot names no precision of its own, and Mosaic takes
    # bf16 operands at the MXU's own only: keep a process-wide default
    # (the tests' "highest") away from it
    with jax.default_matmul_precision("default"):
        out = gmm(jnp.pad(xs, ((0, padded - m), (0, 0))), w, group_sizes,
                  preferred_element_type=xs.dtype,
                  tiling=(tm, min(k, 1024), min(w.shape[-1], 1024)),
                  interpret=mode == "gmm_interpret")
    return out[:m]


def dropless_moe(x, valid, router, bias, gate, up, down, *, top_k: int,
                 scale: float):
    """The routed experts of one layer over a flat batch of tokens.

    x: (T, h); valid: (T,) bool, False for padding and parked rows, which
    get no expert; router: (h, E); bias: (E,), added to the scores for
    the choice alone; gate, up: (E, h, f); down: (E, f, h). Returns the
    weighted sum over each token's experts, (T, h), and the tokens each
    expert got, (E,) int32."""
    t, h = x.shape
    e = router.shape[1]
    with jax.named_scope(scopes.MOE_ROUTER):
        sc = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(sc + bias.astype(jnp.float32), top_k)
        picked = jnp.take_along_axis(sc, chosen, 1)
        weight = scale * picked / jnp.sum(picked, -1, keepdims=True)
    with jax.named_scope(scopes.MOE_DISPATCH):
        # pairs sorted by expert; those of tokens that are not there sort
        # behind the last group and belong to none
        ids = jnp.where(valid[:, None], chosen, e).reshape(-1)
        order = jnp.argsort(ids, stable=True)
        sizes = jnp.diff(jnp.searchsorted(
            ids[order], jnp.arange(e + 1, dtype=ids.dtype))
            ).astype(jnp.int32)
        xs = x[order // top_k]
    with jax.named_scope(scopes.MOE_EXPERTS):
        mid = (jax.nn.silu(_grouped_matmul(xs, gate, sizes))
               * _grouped_matmul(xs, up, sizes))
        ys = _grouped_matmul(mid, down, sizes)
    with jax.named_scope(scopes.MOE_COMBINE):
        inverse = jnp.argsort(order)    # a sort: the chip scatters slowly
        y = ys[inverse].reshape(t, top_k, h).astype(jnp.float32)
        # a pair of no group was never computed: keep what lies there out
        y = jnp.where(valid[:, None, None], y, 0.0)
        out = jnp.sum(y * weight[..., None], axis=1).astype(x.dtype)
    return out, sizes


class MlaMoeRouter(nn.Layer):
    def __init__(self, cfg: MlaMoeConfig):
        super().__init__()
        self.weight = _param(self, cfg, (cfg.hidden_size,
                                         cfg.n_routed_experts))
        self.e_score_correction_bias = _param(
            self, cfg, (cfg.n_routed_experts,), "bias")


class MlaMoeExperts(nn.Layer):
    """The routed experts' weights, stacked over experts."""

    def __init__(self, cfg: MlaMoeConfig):
        super().__init__()
        e, h, f = (cfg.n_routed_experts, cfg.hidden_size,
                   cfg.moe_intermediate_size)
        self.gate_proj = _param(self, cfg, (e, h, f))
        self.up_proj = _param(self, cfg, (e, h, f))
        self.down_proj = _param(self, cfg, (e, f, h))


class MlaMoeExpertLayer(nn.Layer):
    def __init__(self, cfg: MlaMoeConfig):
        super().__init__()
        self.cfg = cfg
        self.gate = MlaMoeRouter(cfg)
        self.experts = MlaMoeExperts(cfg)
        self.shared_experts = MlaMoeMLP(
            cfg, cfg.n_shared_experts * cfg.moe_intermediate_size)

    def forward(self, x, valid):
        """x: (b, s, h); valid: (b, s) bool. Returns (y, (E,) tokens an
        expert)."""
        cfg = self.cfg
        b, s, h = x.shape
        flat, ok = x.reshape(b * s, h), valid.reshape(b * s)

        def routed(args):
            return dropless_moe(
                *args, self.gate.weight._data,
                self.gate.e_score_correction_bias._data,
                self.experts.gate_proj._data, self.experts.up_proj._data,
                self.experts.down_proj._data,
                top_k=cfg.num_experts_per_tok,
                scale=cfg.routed_scaling_factor)

        t, chunk = b * s, _MOE_CHUNK_TOKENS
        if t <= chunk:
            y, sizes = routed((flat, ok))
        else:
            n = -(-t // chunk)
            pad = n * chunk - t
            ys, sizes = jax.lax.map(routed, (
                jnp.pad(flat, ((0, pad), (0, 0))).reshape(n, chunk, h),
                jnp.pad(ok, (0, pad)).reshape(n, chunk)))
            y, sizes = ys.reshape(n * chunk, h)[:t], jnp.sum(sizes, 0)
        with jax.named_scope(scopes.MOE_SHARED):
            y = y + self.shared_experts(flat)
        return y.reshape(b, s, h), sizes


class MlaMoeDecoderLayer(nn.Layer):
    def __init__(self, cfg: MlaMoeConfig, index: int):
        super().__init__()
        self.cfg = cfg
        self.input_layernorm = _Weight(cfg, (cfg.hidden_size,), "gain")
        self.self_attn = MlaAttention(cfg)
        self.post_attention_layernorm = _Weight(cfg, (cfg.hidden_size,),
                                                "gain")
        self.is_moe = index >= cfg.first_k_dense_replace
        self.mlp = (MlaMoeExpertLayer(cfg) if self.is_moe
                    else MlaMoeMLP(cfg, cfg.intermediate_size))

    def forward(self, x, valid, cache=None, start_pos=0):
        eps = self.cfg.rms_norm_eps
        with jax.named_scope(scopes.ATTN_QKV):
            normed = _rms(x, self.input_layernorm.weight._data, eps)
        attn, new_cache = self.self_attn(normed, cache, start_pos)
        with jax.named_scope(scopes.ATTN_OUT):
            x = x + attn
        with jax.named_scope(scopes.MLP):
            normed = _rms(x, self.post_attention_layernorm.weight._data,
                          eps)
            if self.is_moe:
                y, sizes = self.mlp(normed, valid)
            else:
                y, sizes = self.mlp(normed), None
            x = x + y
        return x, new_cache, sizes


class MlaMoeModel(nn.Layer):
    def __init__(self, cfg: Optional[MlaMoeConfig] = None):
        super().__init__()
        self.config = cfg or MlaMoeConfig()
        cfg = self.config
        self.embed_tokens = _Weight(cfg, (cfg.vocab_size, cfg.hidden_size))
        self.layers = nn.LayerList([MlaMoeDecoderLayer(cfg, i)
                                    for i in range(cfg.num_hidden_layers)])
        self.norm = _Weight(cfg, (cfg.hidden_size,), "gain")

    def forward(self, ids, valid, caches=None, start_pos=0):
        """ids: (b, s) int array. Returns the last layer's output before
        the final norm, the new cache views (None without caches) and the
        (expert layers, E) tokens an expert."""
        with jax.named_scope(scopes.EMBED):
            x = self.embed_tokens.weight._data[ids]
        if caches is not None and len(caches) != len(self.layers):
            raise ValueError(f"got {len(caches)} caches for "
                             f"{len(self.layers)} decoder layers")
        new_caches, sizes = [], []
        for i, layer in enumerate(self.layers):
            x, nc, n = layer(x, valid,
                             None if caches is None else caches[i],
                             start_pos)
            new_caches.append(nc)
            if n is not None:
                sizes.append(n)
        return x, (None if caches is None else new_caches), jnp.stack(sizes)


class MlaMoeForCausalLM(nn.Layer):
    # what `serving.ServingEngine` asks a model before it builds its
    # executables: forward takes `logits_at`, and the cache path returns
    # (logits, caches, aux)
    serving_logits_at = True
    serving_aux = True

    def __init__(self, cfg: Optional[MlaMoeConfig] = None):
        super().__init__()
        self.model = MlaMoeModel(cfg)
        self.config = cfg = self.model.config
        self.lm_head = _Weight(cfg, (cfg.hidden_size, cfg.vocab_size))

    def forward(self, input_ids, caches=None, start_pos=0, logits_at=None):
        """Without caches: logits of every position, one causal forward.
        With the engine's latent cache views: (logits, new views, aux); a
        prefill (`start_pos` the integer 0) given `logits_at` returns the
        logits of that position alone, (b, 1, vocab), and treats the
        positions past it as padding."""
        ids = input_ids._data if hasattr(input_ids, "_data") else input_ids
        b, s = ids.shape
        cfg = self.config
        if caches is None or (isinstance(start_pos, int) and start_pos == 0):
            last = s - 1 if logits_at is None else logits_at
            valid = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None] <= last, (b, s))
        else:
            # a decode row parked at its table's capacity is not there
            from ..serving.kv_cache import overflow_position
            sp = jnp.asarray(start_pos._data if hasattr(start_pos, "_data")
                             else start_pos, jnp.int32)
            park = overflow_position(caches[0].page_table.shape[1],
                                     caches[0].page_size)
            valid = jnp.broadcast_to((sp < park).reshape(b, -1), (b, s))
        x, new_caches, sizes = self.model(ids, valid, caches, start_pos)
        with jax.named_scope(scopes.LM_HEAD):
            if logits_at is not None:
                x = jax.lax.dynamic_slice_in_dim(x, logits_at, 1, axis=1)
            logits = (_rms(x, self.model.norm.weight._data, cfg.rms_norm_eps)
                      @ self.lm_head.weight._data)
        if caches is None:
            return Tensor(logits)
        return Tensor(logits), new_caches, {"moe_expert_tokens": sizes}
