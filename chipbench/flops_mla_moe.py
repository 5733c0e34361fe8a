"""Operations and bytes that a decoder with latent attention and routed
experts needs, from shapes and from the program's own counters. As
`flops.py`: nothing the implementation adds is counted, a multiply-add
is two operations.

`serve_flops` counts the **published** (expanded) form of the attention:
the absorbed form that a server decodes in does more arithmetic a cached
token (below), and a share of the peak must not rise by it.
"""
from __future__ import annotations


def _attention_projection_flops(cfg: dict) -> int:
    """One token through one layer's five attention projections."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    qr, kvr, vd = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["v_head_dim"]
    return 2 * (h * qr + qr * heads * (nope + rope) + h * (kvr + rope)
                + kvr * heads * (nope + vd) + heads * vd * h)


def _expert_flops(cfg: dict) -> int:
    """One token through one expert: three matrices of hidden x width."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward operations to serve one request: every processed token
    through the projections, its routed and shared experts and the router
    (the dense MLP in the leading layers), causal attention over its
    context in the expanded form (2 * heads * (qk width + v width) a
    position attended a layer, the query's own included), and the head
    once a row of logits needed. The last emitted token is never fed
    back."""
    if new_tokens <= 0:
        return 0.0
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    dense = cfg["first_k_dense_replace"]
    processed = prompt_len + new_tokens - 1
    attended = processed * (processed + 1) // 2
    expert_layer = ((cfg["num_experts_per_tok"] + cfg["n_shared_experts"])
                    * _expert_flops(cfg) + 2 * h * cfg["n_routed_experts"])
    dense_layer = 2 * 3 * h * cfg["intermediate_size"]
    per_position = 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    return float(
        processed * (layers * _attention_projection_flops(cfg)
                     + dense * dense_layer + (layers - dense) * expert_layer)
        + layers * per_position * attended
        + new_tokens * 2 * h * cfg["vocab_size"])


def _row_width(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def mla_decode_bytes(cfg: dict, context_tokens: int, rows: int,
                     itemsize: int = 2) -> float:
    """Bytes the absorbed decode attention must move over all layers: the
    cached row of every context token once, the absorbed query in and the
    summed latent out once a decoded token."""
    heads = cfg["num_attention_heads"]
    return float(cfg["num_hidden_layers"] * itemsize * (
        context_tokens * _row_width(cfg)
        + rows * heads * (_row_width(cfg) + cfg["kv_lora_rank"])))


def mla_decode_flops(cfg: dict, context_tokens: int) -> float:
    """Scores over the row's whole width and the sum of its latent part,
    for every head, over all layers."""
    return float(cfg["num_hidden_layers"] * context_tokens * 2
                 * cfg["num_attention_heads"]
                 * (_row_width(cfg) + cfg["kv_lora_rank"]))


def moe_experts_work(cfg: dict, pairs: float, experts_touched: float,
                     itemsize: int = 2) -> dict:
    """The routed experts' grouped matmuls, from the program's counters:
    each expert touched in a layer dispatch brings its three matrices
    once, each (token, expert) pair its row in and its row out."""
    weights = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return {"flops": float(pairs * 2 * weights),
            "bytes": float(itemsize * (experts_touched * weights
                                       + pairs * 2 * cfg["hidden_size"]))}
