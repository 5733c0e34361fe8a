"""GPT-family decoder for the hybrid-parallel benchmark (BASELINE.json config
#4: GPT-3 1.3B TP+PP; upstream model lives in the PaddleNLP ecosystem).

Pre-LN causal transformer. Attention uses the framework's
scaled_dot_product_attention op so the Pallas flash path (ops/pallas_kernels)
kicks in on TPU.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from .. import nn
from ..nn import functional as F
from ..profiler import scopes


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    layer_norm_eps: float = 1e-5
    # LM head via fused_linear_cross_entropy when labels ride into
    # forward: the (b*s, vocab) f32 logits never materialize
    fused_lm_loss: bool = False

    @classmethod
    def gpt3_1p3b(cls):
        return cls(hidden_size=2048, num_hidden_layers=24,
                   num_attention_heads=16, intermediate_size=8192,
                   max_position_embeddings=2048)

    @classmethod
    def tiny(cls):
        return cls(vocab_size=1024, hidden_size=128, num_hidden_layers=2,
                   num_attention_heads=4, intermediate_size=256,
                   max_position_embeddings=128)


class GPTAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig, tensor_parallel: bool = False):
        super().__init__()
        self.num_heads = cfg.num_attention_heads
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        if tensor_parallel:
            from ..distributed.fleet.meta_parallel import (
                ColumnParallelLinear, RowParallelLinear,
            )

            self.qkv = ColumnParallelLinear(cfg.hidden_size,
                                            3 * cfg.hidden_size,
                                            gather_output=True)
            self.out = RowParallelLinear(cfg.hidden_size, cfg.hidden_size)
        else:
            self.qkv = nn.Linear(cfg.hidden_size, 3 * cfg.hidden_size)
            self.out = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.dropout_p = cfg.attention_probs_dropout_prob

    def forward(self, x, cache=None, start_pos=0):
        b, s, h = x.shape
        # scaled_dot_product_attention's layout contract is (b, s, heads, hd)
        with jax.named_scope(scopes.ATTN_QKV):
            qkv = self.qkv(x).reshape(
                [b, s, 3, self.num_heads, self.head_dim])
            qkv = qkv.transpose([2, 0, 1, 3, 4])  # 3,b,s,nh,hd
            q, k, v = qkv[0], qkv[1], qkv[2]
        if cache is not None:  # KV-cache decode (inference only)
            return self.attend(q, k, v, b, s, cache, start_pos)
        ctx = F.scaled_dot_product_attention(
            q, k, v, is_causal=True,
            dropout_p=self.dropout_p if self.training else 0.0)
        ctx = ctx.reshape([b, s, self.num_heads * self.head_dim])
        return self.out(ctx)

    def attend(self, q, k, v, b, s, cache, start_pos):
        """Cache-path tail of the block, factored so the TP ring-overlap
        driver (serving/overlap.py) can feed q/k/v assembled from
        micro-row chunk matmuls: cache/paged attention, then the output
        projection — which under TP retyping returns either the reduced
        tensor (serial psum) or an un-reduced ring partial. The serial
        forward calls it with identical inputs (pure code motion)."""
        from .generation import attend_with_cache
        ctx, new_cache = attend_with_cache(q, k, v, cache, start_pos, 1)
        # num_heads*head_dim, not cfg.hidden_size: under tensor
        # parallelism this module runs with num_heads/tp local heads,
        # so ctx is narrower than the input (and b may be a symbolic
        # -1 under to_static, ruling out a -1 here)
        with jax.named_scope(scopes.ATTN_OUT):
            out = self.out(
                ctx.reshape([b, s, self.num_heads * self.head_dim]))
        return out, new_cache


def _resolve_tp_overlap(x):
    """Finish a pending tensor-parallel ring reduction: the serving
    overlap driver (serving/overlap.py) threads an un-reduced handle
    through the decoder loop so block i's output all-reduce can overlap
    block i+1's QKV matmuls, and the handle past the LAST block is
    closed here, before the final norm. Plain tensors pass through
    untouched — the overlap-off path stays zero-cost (duck-typed: no
    serving import)."""
    fin = getattr(x, "_tp_overlap_finish", None)
    return x if fin is None else fin()


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig, tensor_parallel: bool = False):
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.attn = GPTAttention(cfg, tensor_parallel)
        self.ln2 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        if tensor_parallel:
            from ..distributed.fleet.meta_parallel import (
                ColumnParallelLinear, RowParallelLinear,
            )

            self.ffn_in = ColumnParallelLinear(cfg.hidden_size,
                                               cfg.intermediate_size,
                                               gather_output=False)
            self.ffn_out = RowParallelLinear(cfg.intermediate_size,
                                             cfg.hidden_size,
                                             input_is_parallel=True)
        else:
            self.ffn_in = nn.Linear(cfg.hidden_size, cfg.intermediate_size)
            self.ffn_out = nn.Linear(cfg.intermediate_size, cfg.hidden_size)
        self.dropout = nn.Dropout(cfg.hidden_dropout_prob)

    def forward(self, x, cache=None, start_pos=0):
        if cache is None:
            x = x + self.dropout(self.attn(self.ln1(x)))
            x = x + self.dropout(
                self.ffn_out(F.gelu(self.ffn_in(self.ln2(x)))))
            return x
        with jax.named_scope(scopes.ATTN_QKV):
            normed = self.ln1(x)
        attn, new_cache = self.attn(normed, cache, start_pos)
        with jax.named_scope(scopes.ATTN_OUT):
            x = x + self.dropout(attn)
        with jax.named_scope(scopes.MLP):
            x = x + self.dropout(
                self.ffn_out(F.gelu(self.ffn_in(self.ln2(x)))))
        return x, new_cache


class GPTModel(nn.Layer):
    """tensor_parallel=True builds Megatron TP blocks (fleet mp_layers) whose
    param marks drive GSPMD sharding under a jitted step (bench config #4's
    mp dimension)."""

    def __init__(self, cfg: Optional[GPTConfig] = None,
                 tensor_parallel: bool = False):
        super().__init__()
        self.config = cfg or GPTConfig()
        cfg = self.config
        if tensor_parallel:
            from ..distributed.fleet.meta_parallel import (
                VocabParallelEmbedding,
            )

            self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size)
        else:
            self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = nn.Embedding(cfg.max_position_embeddings, cfg.hidden_size)
        self.dropout = nn.Dropout(cfg.hidden_dropout_prob)
        self.blocks = nn.LayerList([GPTBlock(cfg, tensor_parallel)
                                    for _ in range(cfg.num_hidden_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        from .ernie import _init_transformer_weights

        _init_transformer_weights(self, 0.02)

    def forward(self, input_ids, position_ids=None, caches=None,
                start_pos=0):
        from ..core.tensor import Tensor
        from ..tensor.creation import arange
        import jax.numpy as jnp

        b, s = input_ids.shape
        if position_ids is None:
            if caches is None:
                position_ids = arange(s, dtype="int64").unsqueeze(0)
            else:  # decode offset may be traced: static arange + add
                sp = jnp.asarray(
                    start_pos._data if hasattr(start_pos, "_data")
                    else start_pos, jnp.int32)
                if sp.ndim == 2:  # flat ragged batch: (b, s) positions
                    position_ids = Tensor(sp)
                elif sp.ndim == 1:  # ragged serving batch: per-row offsets
                    position_ids = Tensor(
                        sp[:, None] + jnp.arange(s, dtype=jnp.int32)[None])
                else:
                    position_ids = Tensor(
                        (jnp.arange(s, dtype=jnp.int32) + sp)[None])
        with jax.named_scope(scopes.EMBED):
            x = self.dropout(self.wte(input_ids) + self.wpe(position_ids))
        if caches is None:
            for blk in self.blocks:
                x = blk(x)
            return self.ln_f(x)
        if len(caches) != len(self.blocks):
            raise ValueError(f"got {len(caches)} caches for "
                             f"{len(self.blocks)} blocks")
        new_caches = []
        for blk, cache in zip(self.blocks, caches):
            x, nc = blk(x, cache, start_pos)
            new_caches.append(nc)
        with jax.named_scope(scopes.LM_HEAD):
            return self.ln_f(_resolve_tp_overlap(x)), new_caches


class GPTEmbeddingPipe(nn.Layer):
    """First pipeline stage: token + position embeddings."""

    def __init__(self, cfg: GPTConfig, tensor_parallel: bool = False):
        super().__init__()
        if tensor_parallel:
            from ..distributed.fleet.meta_parallel import (
                VocabParallelEmbedding,
            )

            self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size)
        else:
            self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = nn.Embedding(cfg.max_position_embeddings, cfg.hidden_size)
        self.dropout = nn.Dropout(cfg.hidden_dropout_prob)

    def forward(self, input_ids):
        from ..tensor.creation import arange

        s = input_ids.shape[1]
        pos = arange(s, dtype="int64").unsqueeze(0)
        return self.dropout(self.wte(input_ids) + self.wpe(pos))


class GPTHeadPipe(nn.Layer):
    """Last pipeline stage: final norm + (untied) LM head."""

    def __init__(self, cfg: GPTConfig, tensor_parallel: bool = False):
        super().__init__()
        self.ln_f = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        if tensor_parallel:
            from ..distributed.fleet.meta_parallel import ColumnParallelLinear

            self.head = ColumnParallelLinear(cfg.hidden_size, cfg.vocab_size,
                                             has_bias=False)
        else:
            self.head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                  bias_attr=False)

    def forward(self, x):
        return self.head(self.ln_f(x))


def gpt_pipe_layers(cfg: GPTConfig, tensor_parallel: bool = False):
    """LayerDesc list for PipelineLayer (the GPTForCausalLMPipe shape used by
    the fleet static TP+PP benchmark, config #4)."""
    from ..distributed.fleet.meta_parallel import LayerDesc

    descs = [LayerDesc(GPTEmbeddingPipe, cfg, tensor_parallel)]
    descs += [LayerDesc(GPTBlock, cfg, tensor_parallel)
              for _ in range(cfg.num_hidden_layers)]
    descs.append(LayerDesc(GPTHeadPipe, cfg, tensor_parallel))
    return descs


class GPTPretrainingCriterion(nn.Layer):
    """Shifted causal-LM cross entropy for the pipe head output."""

    def forward(self, logits, labels):
        vocab = logits.shape[-1]
        return F.cross_entropy(
            logits[:, :-1].reshape([-1, vocab]),
            labels[:, 1:].reshape([-1]))


class GPTForCausalLM(nn.Layer):
    def __init__(self, cfg: Optional[GPTConfig] = None):
        super().__init__()
        self.gpt = GPTModel(cfg)

    def forward(self, input_ids, position_ids=None, caches=None,
                start_pos=0, labels=None):
        if caches is None:
            h = self.gpt(input_ids, position_ids)
            if labels is not None and self.gpt.config.fused_lm_loss:
                # shifted causal CE fused with the tied head projection
                from .. import incubate

                hidden = h.shape[-1]
                return incubate.nn.functional.fused_linear_cross_entropy(
                    h[:, :-1].reshape([-1, hidden]), self.gpt.wte.weight,
                    None, labels[:, 1:].reshape([-1]), transpose_y=True)
            # tied LM head: one [h, vocab] matmul
            logits = h.matmul(self.gpt.wte.weight, transpose_y=True)
            if labels is not None:
                return self.loss(logits, labels)
            return logits
        h, new_caches = self.gpt(input_ids, position_ids, caches, start_pos)
        with jax.named_scope(scopes.LM_HEAD):
            logits = h.matmul(self.gpt.wte.weight, transpose_y=True)
        return logits, new_caches

    def generate(self, input_ids, **kwargs):
        from .generation import generate
        return generate(self, input_ids, **kwargs)

    def loss(self, logits, labels):
        vocab = logits.shape[-1]
        return F.cross_entropy(
            logits[:, :-1].reshape([-1, vocab]),
            labels[:, 1:].reshape([-1]))


def gpt_spmd_pipeline_fn(model: "GPTModel", mesh, *, num_stages: int,
                         num_micro: int, axis_name: str = "pp",
                         data_axis: str = "dp"):
    """Multi-host pipeline-parallel forward for a REAL GPT stack.

    Builds the SPMD collective pipeline (fleet.meta_parallel.spmd_pipeline
    — GPipe over ppermute, the engine that crosses process boundaries)
    from `model`'s own weights: the homogeneous decoder blocks are
    STACKED per stage (leading dims (num_stages, blocks_per_stage)),
    embeddings and the tied LM head run replicated outside the pipelined
    region (exactly how gpt_pipe_layers segments for the 1F1B engine).

    Returns (fn, stacked_params) with fn(stacked_params, embed_params,
    input_ids) -> logits, jit-able over `mesh`; grads flow through both
    param trees. Ref: fleet/meta_parallel/pipeline_parallel.py +
    pp_utils/p2p_communication.py (upstream layout, unverified).
    """
    import jax
    import jax.numpy as jnp

    from ..distributed.fleet.meta_parallel.spmd_pipeline import (
        make_spmd_pipeline_fn,
    )
    from ..jit.functional import call_functional, extract_state

    cfg = model.config
    n_layers = cfg.num_hidden_layers
    if n_layers % num_stages:
        raise ValueError(f"{n_layers} blocks do not split over "
                         f"{num_stages} stages")
    per_stage = n_layers // num_stages

    block0 = model.blocks[0]
    block_param_trees = []
    for blk in model.blocks:
        p, _ = extract_state(blk)
        block_param_trees.append(p)
    # leaves -> (num_stages, per_stage, *leaf_shape)
    stacked = {
        k: jnp.stack([jnp.stack(
            [block_param_trees[s * per_stage + i][k]
             for i in range(per_stage)])
            for s in range(num_stages)])
        for k in block_param_trees[0]
    }

    def stage_fn(stage_params, x):
        # stage_params leaves: (per_stage, ...) — scan the stage's blocks
        def one_block(h, leaf_slice):
            out, _ = call_functional(block0, leaf_slice, {}, (h,),
                                     training=False)
            return out, None

        h, _ = jax.lax.scan(one_block, x, stage_params)
        return h

    pipe = make_spmd_pipeline_fn(stage_fn, mesh, num_stages=num_stages,
                                 num_micro=num_micro, axis_name=axis_name,
                                 data_axis=data_axis)

    def embed_params_of(m):
        """Replicated (non-pipelined) params: embeddings + final norm."""
        return {"wte": m.wte.weight._data, "wpe": m.wpe.weight._data,
                "g": m.ln_f.weight._data, "b": m.ln_f.bias._data}

    def fn(stacked_params, embed_params, input_ids):
        b, s = input_ids.shape
        pos = jnp.arange(s)[None, :]
        h = (embed_params["wte"][input_ids]
             + embed_params["wpe"][pos])
        h = pipe(stacked_params, h)
        # final norm + tied-head projection (replicated)
        mu = h.mean(-1, keepdims=True)
        var = ((h - mu) ** 2).mean(-1, keepdims=True)
        h = ((h - mu) / jnp.sqrt(var + cfg.layer_norm_eps)
             * embed_params["g"] + embed_params["b"])
        return h @ embed_params["wte"].T

    return fn, stacked, embed_params_of(model)
