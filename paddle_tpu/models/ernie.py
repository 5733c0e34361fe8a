"""ERNIE/BERT-family encoder: the model of `chipbench`'s pretraining cell.

Capability target: ERNIE-1.0 pretraining (BASELINE.json config #3; upstream
model lives in the PaddleNLP ecosystem, not core Paddle). Architecture is the
standard pre/post-LN transformer encoder with MLM + NSP heads, written with
framework nn layers so the whole stack (Layer, initializers, functional ops,
AMP, jit, fleet sharding) is exercised end-to-end.

TPU notes: weights are kept layout-neutral ([hidden, 3*hidden] fused QKV so
the MXU sees one big matmul; MLM head ties input embeddings, projecting with
a single [hidden, vocab] matmul).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from .. import nn
from ..core.tensor import Tensor
from ..nn import functional as F
from ..profiler import scopes


@dataclasses.dataclass
class ErnieConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    layer_norm_eps: float = 1e-12
    initializer_range: float = 0.02
    # activation checkpointing: rerun each encoder layer's forward in the
    # backward instead of keeping its activations (jax.remat via
    # fleet.recompute) — trades ~1/3 more FLOPs for O(layers) less HBM
    # (the `chipbench` train cell leaves it off)
    recompute: bool = False
    # MLM head via fused_linear_cross_entropy: forward(…, masked_lm_labels=)
    # returns the loss without materializing (b*s, vocab) f32 logits
    # (the `chipbench` train cell sets it; tests/test_chip_compile.py
    # holds the compiled step to it)
    fused_mlm_loss: bool = False

    @classmethod
    def ernie_base(cls):
        return cls(vocab_size=18000)

    @classmethod
    def bert_base(cls):
        return cls(vocab_size=30522)

    @classmethod
    def tiny(cls):
        return cls(vocab_size=1024, hidden_size=128, num_hidden_layers=2,
                   num_attention_heads=4, intermediate_size=256,
                   max_position_embeddings=128)


class ErnieSelfAttention(nn.Layer):
    def __init__(self, cfg: ErnieConfig):
        super().__init__()
        self.num_heads = cfg.num_attention_heads
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        self.qkv = nn.Linear(cfg.hidden_size, 3 * cfg.hidden_size)
        self.out = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.dropout_p = cfg.attention_probs_dropout_prob

    def forward(self, x, attn_mask=None):
        b, s, h = x.shape
        # sdpa's layout contract is (b, s, heads, hd); the fused path
        # (Pallas flash on TPU) handles the additive float mask in-kernel
        with jax.named_scope(scopes.ATTENTION):
            qkv = self.qkv(x).reshape(
                [b, s, 3, self.num_heads, self.head_dim])
            qkv = qkv.transpose([2, 0, 1, 3, 4])  # 3,b,s,heads,hd
            q, k, v = qkv[0], qkv[1], qkv[2]
        # under no scope: a flash kernel's events are named after the
        # path in front of its pallas_call, transformations and all
        # (`jvp(jit(_flash_attention_data))`), and a scope around the
        # call moves the `jvp(...)` onto the scope. The kernels get a
        # name= of their own, and this call its scope, in one step with
        # the metric that reads the present name (ROADMAP.md)
        ctx = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask,
            dropout_p=self.dropout_p if self.training else 0.0)
        with jax.named_scope(scopes.ATTENTION):
            return self.out(ctx.reshape([b, s, h]))


class ErnieLayer(nn.Layer):
    def __init__(self, cfg: ErnieConfig):
        super().__init__()
        self.attention = ErnieSelfAttention(cfg)
        self.attn_norm = nn.LayerNorm(cfg.hidden_size,
                                      epsilon=cfg.layer_norm_eps)
        self.ffn_in = nn.Linear(cfg.hidden_size, cfg.intermediate_size)
        self.ffn_out = nn.Linear(cfg.intermediate_size, cfg.hidden_size)
        self.ffn_norm = nn.LayerNorm(cfg.hidden_size,
                                     epsilon=cfg.layer_norm_eps)
        self.dropout = nn.Dropout(cfg.hidden_dropout_prob)

    def forward(self, x, attn_mask=None):
        # post-LN (BERT convention)
        a = self.attention(x, attn_mask)
        with jax.named_scope(scopes.ATTENTION):
            x = self.attn_norm(x + self.dropout(a))
        with jax.named_scope(scopes.FFN):
            f = self.ffn_out(F.gelu(self.ffn_in(x)))
            return self.ffn_norm(x + self.dropout(f))


class ErnieEmbeddings(nn.Layer):
    def __init__(self, cfg: ErnieConfig):
        super().__init__()
        self.word_embeddings = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.position_embeddings = nn.Embedding(cfg.max_position_embeddings,
                                                cfg.hidden_size)
        self.token_type_embeddings = nn.Embedding(cfg.type_vocab_size,
                                                  cfg.hidden_size)
        self.norm = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.dropout = nn.Dropout(cfg.hidden_dropout_prob)

    def forward(self, input_ids, token_type_ids=None, position_ids=None):
        from ..tensor.creation import arange, zeros_like

        b, s = input_ids.shape
        if position_ids is None:
            position_ids = arange(s, dtype="int64").unsqueeze(0)
        if token_type_ids is None:
            token_type_ids = zeros_like(input_ids)
        emb = (self.word_embeddings(input_ids)
               + self.position_embeddings(position_ids)
               + self.token_type_embeddings(token_type_ids))
        return self.dropout(self.norm(emb))


def _init_transformer_weights(root: nn.Layer, std: float):
    """BERT-style init: N(0, std) for Linear/Embedding weights (incl. their
    tensor-parallel variants), zeros for biases; LayerNorm params untouched
    (ones/zeros). Rebinds _data only, preserving dist_spec marks."""
    from ..nn.initializer import Normal
    from ..distributed.fleet.meta_parallel import (
        ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    )

    init = Normal(mean=0.0, std=std)
    types = (nn.Linear, nn.Embedding, ColumnParallelLinear,
             RowParallelLinear, VocabParallelEmbedding)
    for sub in root.sublayers(include_self=True):
        if isinstance(sub, types):
            w = sub.weight
            w._data = init(w.shape, w._data.dtype)


class ErnieModel(nn.Layer):
    """Encoder stack; returns (sequence_output, pooled_output)."""

    def __init__(self, cfg: Optional[ErnieConfig] = None):
        super().__init__()
        self.config = cfg or ErnieConfig.ernie_base()
        cfg = self.config
        self.embeddings = ErnieEmbeddings(cfg)
        self.layers = nn.LayerList([ErnieLayer(cfg)
                                    for _ in range(cfg.num_hidden_layers)])
        self.pooler = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        _init_transformer_weights(self, cfg.initializer_range)

    def forward(self, input_ids, token_type_ids=None, position_ids=None,
                attention_mask=None):
        if attention_mask is not None:
            # [b, s] 1/0 mask -> additive [b,1,1,s]
            attention_mask = ((1.0 - attention_mask.astype("float32"))
                              * -1e4).unsqueeze(1).unsqueeze(1)
        with jax.named_scope(scopes.EMBED):
            x = self.embeddings(input_ids, token_type_ids, position_ids)
        if self.config.recompute and self.training:
            from ..distributed.fleet.recompute import recompute

            for layer in self.layers:
                x = recompute(layer, x, attention_mask)
        else:
            for layer in self.layers:
                x = layer(x, attention_mask)
        pooled = F.tanh(self.pooler(x[:, 0]))
        return x, pooled


class ErnieForPretraining(nn.Layer):
    """MLM + NSP heads; forward returns (prediction_logits, seq_rel_logits).

    The MLM projection ties the word-embedding matrix (one [h, vocab] matmul
    on the MXU)."""

    def __init__(self, cfg: Optional[ErnieConfig] = None):
        super().__init__()
        self.ernie = ErnieModel(cfg)
        cfg = self.ernie.config
        self.config = cfg
        self.transform = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.mlm_norm = nn.LayerNorm(cfg.hidden_size,
                                     epsilon=cfg.layer_norm_eps)
        self.mlm_bias = self.create_parameter(
            [cfg.vocab_size], is_bias=True)
        self.nsp = nn.Linear(cfg.hidden_size, 2)

    def forward(self, input_ids, token_type_ids=None, position_ids=None,
                attention_mask=None, masked_lm_labels=None):
        seq, pooled = self.ernie(input_ids, token_type_ids, position_ids,
                                 attention_mask)
        with jax.named_scope(scopes.MLM_HEAD_LOSS):
            out = self._mlm_head(seq, masked_lm_labels)
        return out, self.nsp(pooled)

    def _mlm_head(self, seq, masked_lm_labels):
        """The MLM transform and the tied vocabulary head: the loss where
        labels are given, else the logits."""
        h = self.mlm_norm(F.gelu(self.transform(seq)))
        word_emb = self.ernie.embeddings.word_embeddings.weight
        if masked_lm_labels is None:
            return h.matmul(word_emb, transpose_y=True) + self.mlm_bias
        if self.config.fused_mlm_loss:
            # tied-weight LM head + CE in one chunked pass — the f32
            # (b*s, vocab) logits tensor never exists
            from .. import incubate

            return incubate.nn.functional.fused_linear_cross_entropy(
                h.reshape([-1, self.config.hidden_size]), word_emb,
                self.mlm_bias, masked_lm_labels.reshape([-1]),
                ignore_index=-100, transpose_y=True)
        logits = h.matmul(word_emb, transpose_y=True) + self.mlm_bias
        return F.cross_entropy(
            logits.reshape([-1, self.config.vocab_size]),
            masked_lm_labels.reshape([-1]), ignore_index=-100)

    def loss(self, logits, nsp_logits, mlm_labels, nsp_labels=None,
             ignore_index=-100):
        """Pretraining loss: masked-LM CE (+ NSP CE when labels given)."""
        vocab = logits.shape[-1]
        mlm = F.cross_entropy(
            logits.reshape([-1, vocab]), mlm_labels.reshape([-1]),
            ignore_index=ignore_index)
        if nsp_labels is not None:
            nsp = F.cross_entropy(nsp_logits, nsp_labels.reshape([-1]))
            return mlm + nsp
        return mlm
