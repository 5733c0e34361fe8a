"""Decoder with latent attention (MLA) and a dropless mixture of experts:
the DeepSeek-V3 family's layer as JoyAI-LLM-Flash publishes it
(https://huggingface.co/jdopensource/JoyAI-LLM-Flash, `model_type`
`joyai_llm_flash`) and, with the keys that default to off, as
DeepSeek-V3.2 does (https://huggingface.co/deepseek-ai/DeepSeek-V3.2,
`model_type` `deepseek_v32`): sparse attention chosen by a lightning
indexer, group-limited routing, YaRN, and an expert layer that holds a
share of the experts.

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

- `index_topk` (DeepSeek sparse attention): each attention layer has an
  indexer, `index_n_heads` query heads of `index_head_dim` from MLA's own
  normed query latent, one LayerNormed key a token, and a weight a head;
  I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s]), and query t attends the
  `index_topk` positions s <= t of largest I[t, s] alone (all of them up
  to that many), the lower position first among equals. The index key is
  cached beside the latent row; a decode step scores the row's cached
  keys (kernel `dsa_index`), chooses, and attends the chosen rows gathered
  by token index (kernel `mla_sparse_decode`); a prefill scores, chooses
  and masks a block of its queries at a time. Scores, ReLU, sum and
  choice are float32.
- `n_group`, `topk_group` (`noaux_tc`): the experts are `n_group` groups,
  a group's score the sum of its two highest score + bias, and the choice
  is among the experts of the `topk_group` best groups.
- `rope_scaling` (YaRN): the rope frequencies are interpolated between
  their own and their `factor`-th, and the softmax scale carries
  `mscale_all_dim`'s square.
- `router_experts`, `expert_offset` (one chip's share of an
  expert-parallel deployment): the router is `router_experts` wide, the
  layer holds experts `expert_offset .. expert_offset + n_routed_experts`
  of them and computes their part of the result; a pair routed to an
  expert held elsewhere adds nothing here, and nothing stands in for it.

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
import math
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
    # sparse attention: None, or how many positions a query attends
    index_topk: Optional[int] = None
    index_n_heads: int = 64
    index_head_dim: int = 128
    # group-limited routing; 1 and 1: none
    n_group: int = 1
    topk_group: int = 1
    # None, or the published YaRN group: factor,
    # original_max_position_embeddings, beta_fast, beta_slow,
    # mscale, mscale_all_dim
    rope_scaling: Optional[dict] = None
    # the router's width where this chip holds a share of the experts
    # (`n_routed_experts` of them, from `expert_offset` on); None: all
    router_experts: Optional[int] = None
    expert_offset: int = 0
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

    @property
    def index_cache_dim(self) -> Optional[int]:
        """Width of the index key a token leaves beside its row, where
        attention is sparse: tells `serving.kv_cache` to hold it."""
        return None if self.index_topk is None else self.index_head_dim

    @property
    def router_width(self) -> int:
        return self.router_experts or self.n_routed_experts

    def __post_init__(self):
        if self.router_width % self.n_group or not (
                1 <= self.topk_group <= self.n_group):
            raise ValueError(
                f"n_group={self.n_group} must divide the router's "
                f"{self.router_width} experts and hold topk_group="
                f"{self.topk_group}")
        if self.expert_offset + self.n_routed_experts > self.router_width:
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset + self.n_routed_experts}"
                f" are not among the router's {self.router_width}")
        if self.index_topk is not None \
                and self.index_head_dim < self.qk_rope_head_dim:
            raise ValueError(
                f"index_head_dim={self.index_head_dim}: the indexer rotates "
                f"its first {self.qk_rope_head_dim} columns")
        if self.rope_scaling is not None \
                and self.rope_scaling.get("type", "yarn") != "yarn":
            raise NotImplementedError(
                f"rope_scaling type {self.rope_scaling['type']!r}: only "
                "yarn is written")

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

    @classmethod
    def tiny_sparse(cls, **over):
        """`tiny` with every DeepSeek-V3.2 mechanism on: 8 of 40
        positions attended, 4 groups of which 2 stay, YaRN."""
        return cls(**{**dataclasses.asdict(cls.tiny()), **dict(
            index_topk=8, index_n_heads=4, index_head_dim=32,
            n_group=4, topk_group=2,
            rope_scaling={"type": "yarn", "factor": 4.0,
                          "original_max_position_embeddings": 32,
                          "beta_fast": 32, "beta_slow": 1, "mscale": 1.0,
                          "mscale_all_dim": 1.0}), **over})


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


# elements of the (tokens, hidden) stream up to which a decoder layer
# takes its MLP half, and a norm its input, whole: 67 M, twice a
# 16,384-token prefill at 2,048
_LAYER_CHUNK_ELEMENTS = 1 << 26


def _rms(x, gain, eps: float):
    if x.size > _LAYER_CHUNK_ELEMENTS and x.dtype == jnp.bfloat16:
        # a long prefill's whole stream: the squares summed by a dot of
        # the bf16 values into float32 (each product is exact there), so
        # that no float32 copy of the stream, twice its size, is held
        # for the sum and the scaling both
        ms = jnp.einsum("...h,...h->...", x, x,
                        preferred_element_type=jnp.float32) / x.shape[-1]
        scale = jax.lax.rsqrt(ms + eps)[..., None]
        return (x.astype(jnp.float32) * scale
                * gain.astype(jnp.float32)).astype(x.dtype)
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def _rope_freq(rope: int, theta: float, scaling: Optional[dict]):
    """The rope's `rope / 2` frequencies: theta^(-2i/rope), or, under
    YaRN, each interpolated towards its `factor`-th by a ramp that rises
    from 0 at the dimension that turns `beta_fast` times over the
    original context to 1 at the one that turns `beta_slow` times."""
    freq = theta ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    if scaling is None:
        return freq
    original = scaling["original_max_position_embeddings"]

    def dim_of(turns):
        return (rope * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(dim_of(scaling.get("beta_slow", 1))), rope // 2 - 1)
    ramp = jnp.clip((jnp.arange(rope // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freq * (1.0 - ramp) + freq / scaling["factor"] * ramp


def _softmax_mscale(scaling: Optional[dict]) -> float:
    """What YaRN multiplies the softmax scale by: the square of 0.1 *
    mscale_all_dim * ln(factor) + 1 (with `mscale` equal to
    `mscale_all_dim`, as published, cos and sin carry no factor)."""
    if scaling is None or not scaling.get("mscale_all_dim") \
            or scaling["factor"] <= 1:
        return 1.0
    return (0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"])
            + 1.0) ** 2


def _rope_angles(x, positions, freq):
    angle = positions.astype(jnp.float32)[..., None] * freq
    angle = angle.reshape(positions.shape + (1,) * (x.ndim - 3)
                          + (freq.shape[0],))
    return jnp.cos(angle), jnp.sin(angle)


def _rope(x, positions, freq):
    """x: (b, s, ..., rope); positions: (b, s). Rotates the pairs
    (2i, 2i+1) by p * freq[i] in float32, in place."""
    cos, sin = _rope_angles(x, positions, freq)
    x32 = x.astype(jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(x.shape).astype(x.dtype)


def _rope_halves(x, positions, freq):
    """As `_rope` over the first 2 * len(freq) columns of x, pairing
    column i with column i + len(freq) (the indexer's layout); the
    columns past them are left as they are."""
    half = freq.shape[0]
    cos, sin = _rope_angles(x, positions, freq)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x32[..., 2 * half:]], -1).astype(x.dtype)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


class MlaIndexer(nn.Layer):
    """The lightning indexer's weights: query heads from MLA's normed
    query latent, one key a token through a LayerNorm with gain and
    shift, one weight a head."""

    def __init__(self, cfg: MlaMoeConfig):
        super().__init__()
        h, heads, d = cfg.hidden_size, cfg.index_n_heads, cfg.index_head_dim
        self.wq_b = _Weight(cfg, (cfg.q_lora_rank, heads * d))
        self.wk = _Weight(cfg, (h, d))
        self.k_norm = _Weight(cfg, (d,), "gain")
        self.k_norm.bias = _param(self.k_norm, cfg, (d,), "bias")
        self.weights_proj = _Weight(cfg, (h, heads))


# queries a block of a sparse prefill: the block's index scores, its mask
# and its expanded queries are what the step holds beside every key
_SPARSE_PREFILL_QUERIES = 512
# heads a call of a sparse prefill's flash: the step's keys and values
# are expanded for these alone, 2 x 135 MB at 32,768 keys as the flash
# helper pads them
_SPARSE_PREFILL_HEADS = 8
# groups a sparse prefill's blocks of queries come in, by position: a
# group's blocks attend the keys up to the group's end
_SPARSE_PREFILL_GROUPS = 4
_INDEX_NORM_EPS = 1e-6


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
        if cfg.index_topk is not None:
            self.indexer = MlaIndexer(cfg)

    def _queries(self, cq, pos, freq):
        """(q_nope, rotated q_rope) of the normed query latent `cq`."""
        cfg = self.cfg
        nope = cfg.qk_nope_head_dim
        q = (cq @ self.q_b_proj.weight._data).reshape(
            cq.shape[:2] + (cfg.num_attention_heads,
                            nope + cfg.qk_rope_head_dim))
        return q[..., :nope], _rope(q[..., nope:], pos, freq)

    def _index_queries(self, cq, pos, freq):
        """The indexer's rotated query heads."""
        cfg = self.cfg
        return _rope_halves((cq @ self.indexer.wq_b.weight._data).reshape(
            cq.shape[:2] + (cfg.index_n_heads, cfg.index_head_dim)),
            pos, freq)

    def _index_weights(self, x):
        """The float32 weight of each of the indexer's heads."""
        cfg = self.cfg
        w = jnp.matmul(x, self.indexer.weights_proj.weight._data,
                       preferred_element_type=jnp.float32)
        return w * (cfg.index_n_heads ** -0.5 * cfg.index_head_dim ** -0.5)

    def _index_keys(self, x, pos, freq):
        """One LayerNormed, rotated index key a token."""
        ix = self.indexer
        k = (x @ ix.wk.weight._data).astype(jnp.float32)
        mu = jnp.mean(k, -1, keepdims=True)
        k = (k - mu) * jax.lax.rsqrt(
            jnp.mean((k - mu) ** 2, -1, keepdims=True) + _INDEX_NORM_EPS)
        k = (k * ix.k_norm.weight._data.astype(jnp.float32)
             + ix.k_norm.bias._data.astype(jnp.float32)).astype(x.dtype)
        return _rope_halves(k, pos, freq)

    def forward(self, x, cache=None, start_pos=0, live=None):
        """x: (b, s, hidden) raw array, already normed; `live`: how many
        of the s positions are there (the others are padding), a traced
        integer or None for all. Returns the attention output before the
        residual and the new cache view."""
        from ..serving import attention as att

        cfg = self.cfg
        b, s, _ = x.shape
        heads, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
        rope, vd, kvr = (cfg.qk_rope_head_dim, cfg.v_head_dim,
                         cfg.kv_lora_rank)
        eps = cfg.rms_norm_eps
        scale = (nope + rope) ** -0.5 * _softmax_mscale(cfg.rope_scaling)
        sparse = cfg.index_topk is not None
        prefill = isinstance(start_pos, int) and start_pos == 0
        decode = cache is not None and not prefill and s == 1
        if cache is not None and not (prefill or decode):
            raise NotImplementedError(
                "MlaAttention over a latent pool (a row of [latent; rope "
                "key] a token and, where attention is sparse, its index "
                "key) prefills from position 0 and decodes one token a "
                "row: a prefill at an offset (prefix cache, chunked "
                "prefill, speculative verify) is not written")
        w_kv_b = self.kv_b_proj.weight._data.reshape(kvr, heads, nope + vd)
        freq = _rope_freq(rope, cfg.rope_theta, cfg.rope_scaling)
        index_keys = None
        with jax.named_scope(scopes.ATTN_QKV):
            pos = att._positions(start_pos, b, s)
            cq = _rms(x @ self.q_a_proj.weight._data,
                      self.q_a_layernorm.weight._data, eps)
            kva = x @ self.kv_a_proj.weight._data
            latent = _rms(kva[..., :kvr], self.kv_a_layernorm.weight._data,
                          eps)
            k_rope = _rope(kva[..., kvr:], pos, freq)
            if sparse:
                with jax.named_scope(scopes.DSA_INDEX):
                    index_keys = self._index_keys(x, pos, freq)
                    index_w = self._index_weights(x)
                    if decode:
                        index_q = self._index_queries(cq, pos, freq)
            if decode or not sparse:
                q_nope, q_rope = self._queries(cq, pos, freq)
            if not (decode or sparse):
                kv = jnp.einsum("bsc,chd->bshd", latent, w_kv_b)
                k = jnp.concatenate(
                    [kv[..., :nope], jnp.broadcast_to(
                        k_rope[:, :, None], (b, s, heads, rope))], -1)
                v = kv[..., nope:]
                q = jnp.concatenate([q_nope, q_rope], -1)
        new_cache = None
        if cache is not None:
            new_cache, pos = att.latent_write(
                jnp.concatenate([latent, k_rope], -1), cache, start_pos,
                index_keys)
        if decode:
            with jax.named_scope(scopes.PAGED_ATTENTION):
                with jax.named_scope(scopes.MLA_ABSORB):
                    q_lat = jnp.concatenate(
                        [jnp.einsum("bhn,chn->bhc", q_nope[:, 0],
                                    w_kv_b[..., :nope]), q_rope[:, 0]], -1)
                if sparse:
                    index = att.dsa_index_scores(
                        index_q[:, 0], index_w[:, 0], new_cache, pos[:, 0])
                    chosen, n = att.dsa_select(index, pos[:, 0],
                                               cfg.index_topk)
                    u = att.sparse_latent_decode_attention(
                        q_lat, new_cache, chosen, n, scale, kvr)
            if not sparse:
                u = att.latent_decode_attention(q_lat, new_cache, pos[:, 0],
                                                scale, kvr)
            with jax.named_scope(scopes.PAGED_ATTENTION):
                ctx = jnp.einsum("bhc,chv->bhv", u, w_kv_b[..., nope:])
        elif sparse:
            # the output projection too, a block of queries at a time
            return jnp.stack([self._sparse_prefill(
                cq[i], index_w[i], latent[i], k_rope[i], index_keys[i],
                freq, scale, s if live is None else live)
                for i in range(b)]), new_cache
        else:
            ctx = att.latent_prefill_attention(q, k, v, scale, live)
        with jax.named_scope(scopes.ATTN_OUT):
            out = ctx.reshape(b, s, heads * vd) @ self.o_proj.weight._data
        return out, new_cache

    def _sparse_prefill(self, cq, index_w, latent, k_rope, index_keys, freq,
                        scale, live):
        """One sequence's attention from position 0 where each query
        attends the positions its indexer chooses: masked dense flash
        over the step's own expanded K/V. Every key of the step is held
        (latent, rope key and index key, 1.4 KB a token); queries go a
        block at a time through their projections, index scores, choice,
        attention and output projection, and within a block the keys and
        values are expanded for a few heads at a time, so that nothing
        of queries x keys or of keys x heads x width is ever whole.

        What the mask throws away is not all computed: the blocks past
        the `live` positions are not run (a loop of as many turns as
        there are live blocks), and the blocks come in up to
        `_SPARSE_PREFILL_GROUPS` groups by position, each against the
        keys up to its own end, a static extent (five eighths of the
        square in four groups).

        cq, index_w, latent, k_rope, index_keys: (s, ...) of one sequence.
        Returns (s, hidden); rows past `live` are zero."""
        from ..nn import functional as F
        from ..serving import attention as att

        cfg = self.cfg
        s = cq.shape[0]
        heads, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
        rope, vd, kvr = (cfg.qk_rope_head_dim, cfg.v_head_dim,
                         cfg.kv_lora_rank)
        bq = min(_SPARSE_PREFILL_QUERIES, s)
        hc = min(_SPARSE_PREFILL_HEADS, heads)
        if heads % hc:
            raise ValueError(f"{heads} heads are not whole calls of {hc}")
        n = -(-s // bq)
        pad = n * bq - s
        groups = max(g for g in range(1, _SPARSE_PREFILL_GROUPS + 1)
                     if n % g == 0)
        # the flash helper takes one width for queries, keys and values,
        # pads it to whole 128-lane tiles (through a copy, unless it is
        # handed them whole) and scales by its ** -0.5: heads ride zero-
        # padded to `wide`, and the rest of the softmax scale on the
        # queries
        wide = -(-(nope + rope) // 128) * 128
        extra = scale * wide ** 0.5
        w_kv = jnp.moveaxis(self.kv_b_proj.weight._data.reshape(
            kvr, heads // hc, hc, nope + vd), 1, 0)
        o_proj = self.o_proj.weight._data
        cq = jnp.pad(cq, ((0, pad), (0, 0)))
        index_w = jnp.pad(index_w, ((0, pad), (0, 0)))

        def block(first, keys: int):
            """The output rows of the queries at first .. first + bq - 1
            against the step's first `keys` keys."""
            cqb = jax.lax.dynamic_slice_in_dim(cq, first, bq)
            iw = jax.lax.dynamic_slice_in_dim(index_w, first, bq)
            pos = (first + jnp.arange(bq, dtype=jnp.int32))[None]
            with jax.named_scope(scopes.ATTN_QKV):
                q_nope, q_rope = self._queries(cqb[None], pos, freq)
                q = jnp.concatenate([q_nope, q_rope], -1)[0]
                q = jnp.pad((q.astype(jnp.float32) * extra).astype(q.dtype),
                            ((0, 0), (0, 0), (0, wide - nope - rope)))
                with jax.named_scope(scopes.DSA_INDEX):
                    iq = self._index_queries(cqb[None], pos, freq)[0]
            with jax.named_scope(scopes.PREFILL_ATTENTION):
                mask = att.sparse_prefill_mask(iq, iw, index_keys[:keys],
                                               first, cfg.index_topk)

                def some_heads(args):
                    qh, w = args            # (bq, hc, wide)
                    kv = jnp.einsum("sc,chd->shd", latent[:keys], w)
                    k = jnp.concatenate(
                        [kv[..., :nope],
                         jnp.broadcast_to(k_rope[:keys, None],
                                          (keys, hc, rope)),
                         jnp.zeros((keys, hc, wide - nope - rope),
                                   kv.dtype)], -1)
                    v = jnp.pad(kv[..., nope:],
                                ((0, 0), (0, 0), (0, wide - vd)))
                    return F.scaled_dot_product_attention(
                        Tensor(qh[None]), Tensor(k[None]), Tensor(v[None]),
                        attn_mask=Tensor(mask[None, None]))._data[0, ..., :vd]

                with jax.named_scope(scopes.DSA_ATTEND):
                    att._count_dispatch("mla_sparse_prefill")
                    ctx = jax.lax.map(some_heads, (jnp.moveaxis(q.reshape(
                        bq, heads // hc, hc, wide), 1, 0), w_kv))
            with jax.named_scope(scopes.ATTN_OUT):
                return jnp.moveaxis(ctx, 0, 1).reshape(
                    bq, heads * vd) @ o_proj

        live_blocks = (jnp.asarray(live, jnp.int32) + bq - 1) // bq
        out = jnp.zeros((n * bq, o_proj.shape[1]), cq.dtype)
        for g in range(groups):
            lo, hi = g * n // groups, (g + 1) * n // groups
            keys = min(s, hi * bq)

            def turn(i, out, lo=lo, keys=keys):
                first = (lo + i) * bq
                return jax.lax.dynamic_update_slice_in_dim(
                    out, block(first, keys), first, 0)

            out = jax.lax.fori_loop(
                0, jnp.clip(live_blocks - lo, 0, hi - lo), turn, out)
        return out[:s]


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
# each, 2 GB at 16k tokens of 2,048; at 8k an expert still gets 256 rows.
# A wider model takes 1,024: at 7,168 the pairs' float32 outputs before
# the combine are 235 MB
_MOE_CHUNK_TOKENS = 8192
_MOE_CHUNK_HIDDEN = 2048
_MOE_CHUNK_TOKENS_WIDE = 1024


def _chunk_tokens(hidden: int) -> int:
    return (_MOE_CHUNK_TOKENS if hidden <= _MOE_CHUNK_HIDDEN
            else _MOE_CHUNK_TOKENS_WIDE)


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


def group_limited_scores(choice, n_group: int, topk_group: int):
    """`noaux_tc`'s group limit on the (T, E) scores the choice is made
    by: the experts are `n_group` groups of neighbours, a group's score
    is the sum of its two highest, and every expert outside the
    `topk_group` best groups (the lower group first among equals) is put
    out of the choice, at -inf."""
    t, e = choice.shape
    grouped = choice.reshape(t, n_group, e // n_group)
    best = jnp.sum(jax.lax.top_k(grouped, min(2, e // n_group))[0], -1)
    _, kept = jax.lax.top_k(best, topk_group)
    keep = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None], 1)
    return jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(t, e)


# tokens up to which a layer that holds a share of the experts takes
# every held expert over every token. Read on the chip at 8 experts of
# 7,168 x 2,048 (PERF.md section 6, PR 35): at 256 rows the dense form
# takes 1.18 ms and the grouped one 1.29 (7 of 8 experts touched), at 512
# rows 2.19 and 1.42; up to 128 rows the dense form is the weights'
# 0.70 GB at 80-88% of the memory's pace, 0.98-1.08 ms
_DENSE_SHARE_TOKENS = 256


def _dense_share(x, chosen, weight, here, gate, up, down):
    """A decode step of a layer that holds a few of the router's experts:
    every held expert over every token, each token's output weighted by
    what the router gave that expert (nought where it was not chosen).
    The same sum as the grouped matmuls'.

    It reads what the deployment's step reads. The chips that share the
    layer send this one their tokens' pairs, so at load every held
    expert is touched at every step, by as many pairs as the step has
    rows (32 chips x 32 rows x 8 / 256 experts), and the step's time is
    the held experts' weights crossing HBM once. Alone, with nothing
    standing in for the absent chips, a step's few pairs touch the
    experts they happen to: the grouped matmuls then read fewer weights
    (0.60 ms against 0.99 at 32 rows, 4 of 8 experts touched), a time
    no deployment sees and one that moves with the seed's router (2.6%
    of spread over five windows, 0.19% with this form: PERF.md section
    6, PR 35). A prefill's chunk, over `_DENSE_SHARE_TOKENS` rows, takes
    the grouped matmuls.

    chosen: (T, k) held-expert indices, meaningful where `here`; weight:
    (T, k) float32. Returns as `dropless_moe`."""
    e = gate.shape[0]
    with jax.named_scope(scopes.MOE_DISPATCH):
        pairs = (chosen[..., None] == jnp.arange(e, dtype=chosen.dtype)
                 ) & here[..., None]                        # (T, k, E)
        share = jnp.sum(jnp.where(pairs, weight[..., None], 0.0), 1)
        sizes = jnp.sum(pairs, (0, 1)).astype(jnp.int32)
    with jax.named_scope(scopes.MOE_EXPERTS):
        mid = (jax.nn.silu(jnp.einsum("th,ehf->etf", x, gate))
               * jnp.einsum("th,ehf->etf", x, up))
        ys = jnp.einsum("etf,efh->eth", mid, down)
    with jax.named_scope(scopes.MOE_COMBINE):
        out = jnp.einsum("eth,te->th", ys.astype(jnp.float32),
                         share).astype(x.dtype)
    return out, sizes


def dropless_moe(x, valid, router, bias, gate, up, down, *, top_k: int,
                 scale: float, n_group: int = 1, topk_group: int = 1,
                 expert_offset: int = 0):
    """The routed experts of one layer over a flat batch of tokens.

    x: (T, h); valid: (T,) bool, False for padding and parked rows, which
    get no expert; router: (h, R); bias: (R,), added to the scores for
    the choice alone; gate, up: (E, h, f); down: (E, f, h), the experts
    `expert_offset .. expert_offset + E` of the router's R (all of them
    where E == R). Returns the weighted sum over those of each token's
    experts that are held, (T, h), and the tokens each held expert got,
    (E,) int32."""
    t, h = x.shape
    e = gate.shape[0]
    everyone = e == router.shape[1]
    with jax.named_scope(scopes.MOE_ROUTER):
        sc = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        choice = sc + bias.astype(jnp.float32)
        if n_group > 1:
            choice = group_limited_scores(choice, n_group, topk_group)
        _, chosen = jax.lax.top_k(choice, top_k)
        picked = jnp.take_along_axis(sc, chosen, 1)
        weight = scale * picked / jnp.sum(picked, -1, keepdims=True)
    here = valid[:, None]
    if not everyone:
        chosen = chosen - expert_offset
        here = here & (chosen >= 0) & (chosen < e)
        if t <= _DENSE_SHARE_TOKENS:
            return _dense_share(x, chosen, weight, here, gate, up, down)
    with jax.named_scope(scopes.MOE_DISPATCH):
        # pairs sorted by expert; those of tokens that are not there, and
        # those of experts held elsewhere, sort behind the last group and
        # belong to none
        ids = jnp.where(here, chosen, e).reshape(-1)
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
        y = jnp.where(here[..., None], y, 0.0)
        out = jnp.sum(y * weight[..., None], axis=1).astype(x.dtype)
    return out, sizes


class MlaMoeRouter(nn.Layer):
    def __init__(self, cfg: MlaMoeConfig):
        super().__init__()
        self.weight = _param(self, cfg, (cfg.hidden_size, cfg.router_width))
        self.e_score_correction_bias = _param(
            self, cfg, (cfg.router_width,), "bias")


class MlaMoeExperts(nn.Layer):
    """The routed experts' weights, stacked over the experts held."""

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
        """x: (b, s, h); valid: (b, s) bool. Returns (y, (E,) tokens a
        held expert)."""
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
                scale=cfg.routed_scaling_factor, n_group=cfg.n_group,
                topk_group=cfg.topk_group,
                expert_offset=cfg.expert_offset)

        t, chunk = b * s, _chunk_tokens(h)
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
        live = None
        if cache is not None and isinstance(start_pos, int):
            # a prefill's padding lies behind its prompt
            live = jnp.max(jnp.sum(valid, -1)).astype(jnp.int32)
        attn, new_cache = self.self_attn(normed, cache, start_pos, live)
        with jax.named_scope(scopes.ATTN_OUT):
            x = x + attn
        def mlp_half(x, valid):
            normed = _rms(x, self.post_attention_layernorm.weight._data,
                          eps)
            if self.is_moe:
                y, sizes = self.mlp(normed, valid)
            else:
                y, sizes = self.mlp(normed), None
            return x + y, sizes

        with jax.named_scope(scopes.MLP):
            b, s, h = x.shape
            if b * s * h <= _LAYER_CHUNK_ELEMENTS:
                x, sizes = mlp_half(x, valid)
            else:
                # a long prefill of a wide model: the norm, the MLP or
                # the experts and the residual a chunk of tokens at a
                # time, so that the step holds the stream twice, not the
                # normed input, both outputs and their sum beside it at a
                # time; and only the chunks that hold a token that is
                # there (a prompt's padding lies behind it)
                t, chunk = b * s, _chunk_tokens(h)
                n = -(-t // chunk)
                pad = n * chunk - t
                flat = jnp.pad(x.reshape(t, h), ((0, pad), (0, 0)))
                ok = jnp.pad(valid.reshape(t), (0, pad))
                last = jnp.max(jnp.where(ok, jnp.arange(n * chunk), -1))

                def turn(i, carry):
                    out, sizes = carry
                    at = i * chunk
                    y, got = mlp_half(
                        jax.lax.dynamic_slice_in_dim(flat, at, chunk)[None],
                        jax.lax.dynamic_slice_in_dim(ok, at, chunk)[None])
                    out = jax.lax.dynamic_update_slice_in_dim(
                        out, y[0], at, 0)
                    return out, (None if got is None else sizes + got)

                sizes = (jnp.zeros((self.cfg.n_routed_experts,), jnp.int32)
                         if self.is_moe else None)
                out, sizes = jax.lax.fori_loop(
                    0, last // chunk + 1, turn, (flat, sizes))
                # the barrier keeps the next norm's float32 copy of the
                # stream out of the loop's own output (the compiler
                # otherwise has the loop write it in float32: 0.9 GB)
                x = jax.lax.optimization_barrier(
                    out[:t].reshape(b, s, h))
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

    @property
    def serving_prefill_live(self):
        """Whether a prefill's flash walks the prompt's own blocks alone
        (`latent_prefill_attention` given the prompt's length): the dense
        latent kind; the sparse kind runs its own masked blocks."""
        return self.config.index_topk is None

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
