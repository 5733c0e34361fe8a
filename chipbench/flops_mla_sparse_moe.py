"""Operations and bytes that a decoder with sparse latent attention (a
lightning indexer beside MLA) and a held share of routed experts needs,
from shapes and from the program's own counters. As `flops_mla_moe.py`:
nothing the implementation adds is counted, a multiply-add is two
operations.

`serve_flops` counts the **published algorithm**: index scores over
every causal pair, attention over min(context, index_topk) keys in the
expanded form, and of a token's routed experts only the pairs that the
experts held here got (the program's counter). So neither a prefill that
masks a dense attention nor a decode that attends in the absorbed form
can raise the share of the peak.
"""
from __future__ import annotations


def _attention_projection_flops(cfg: dict) -> int:
    """One token through one layer's five attention projections and the
    indexer's three."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    qr, kvr, vd = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["v_head_dim"]
    ih, iw = cfg["index_n_heads"], cfg["index_head_dim"]
    return 2 * (h * qr + qr * heads * (nope + rope) + h * (kvr + rope)
                + kvr * heads * (nope + vd) + heads * vd * h
                + qr * ih * iw + h * iw + h * ih)


def expert_flops(cfg: dict) -> int:
    """One token through one expert: three matrices of hidden x width."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _attended(first: int, count: int, topk: int) -> int:
    """Sum of min(c, topk) over the contexts c = first .. first + count
    - 1."""
    under = min(max(topk - first, 0), count)
    return (under * first + under * (under - 1) // 2
            + (count - under) * topk)


def serve_flops(cfg: dict, context: int, new_tokens: int,
                prefilled: bool) -> float:
    """Forward operations of `new_tokens` steps of one session whose
    first step has `context` keys in its context (its own included), all
    but the routed experts (`moe_experts_work` has those, from the
    counter): projections, indexer, router, shared expert or dense MLP,
    index scores against every key of the context, attention over
    min(context, index_topk) keys in the expanded form, and the head once
    a token. With `prefilled` False the `context` - 1 tokens before the
    first step are counted too (a prefill inside the window)."""
    if new_tokens <= 0:
        return 0.0
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    dense, topk = cfg["first_k_dense_replace"], cfg["index_topk"]
    index_pair = 2 * cfg["index_n_heads"] * cfg["index_head_dim"]
    attend_pair = 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    first, count = (context, new_tokens) if prefilled else (
        1, context - 1 + new_tokens)
    in_context = count * first + count * (count - 1) // 2
    expert_layer = (cfg["n_shared_experts"] * expert_flops(cfg)
                    + 2 * h * cfg["router_experts"])
    dense_layer = 2 * 3 * h * cfg["intermediate_size"]
    return float(
        count * (layers * _attention_projection_flops(cfg)
                 + dense * dense_layer + (layers - dense) * expert_layer)
        + layers * (index_pair * in_context
                    + attend_pair * _attended(first, count, topk))
        + new_tokens * 2 * h * cfg["vocab_size"])


def dsa_index_work(cfg: dict, keys_in_context: float,
                   itemsize: int = 2) -> dict:
    """`dsa_index`, from the program's counter of cached keys in a decode
    row's context (summed over rows, layers and steps): each key is
    brought once, 256 B, and scored by every head, 16,384 operations."""
    return {"flops": float(keys_in_context * 2 * cfg["index_n_heads"]
                           * cfg["index_head_dim"]),
            "bytes": float(keys_in_context * itemsize
                           * cfg["index_head_dim"])}


def mla_sparse_decode_work(cfg: dict, keys_selected: float,
                           itemsize: int = 2) -> dict:
    """`mla_sparse_decode`, from the program's counter of keys attended:
    each chosen row is brought once, 1,280 B as the pool holds it, and
    scored over its 576 columns and summed over its 512 by every head in
    the absorbed form, 278,528 operations."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    held = -(-row // 128) * 128
    return {"flops": float(keys_selected * 2 * cfg["num_attention_heads"]
                           * (row + cfg["kv_lora_rank"])),
            "bytes": float(keys_selected * itemsize * held)}


def moe_experts_work(cfg: dict, pairs: float, experts_touched: float,
                     itemsize: int = 2) -> dict:
    """The held experts' grouped matmuls, from the program's counters:
    each expert touched in a layer dispatch brings its three matrices
    once, each (token, held expert) pair its row in and its row out."""
    weights = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return {"flops": float(pairs * 2 * weights),
            "bytes": float(itemsize * (experts_touched * weights
                                       + pairs * 2 * cfg["hidden_size"]))}
