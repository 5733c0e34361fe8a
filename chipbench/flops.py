"""Operations and bytes the algorithm needs, from shapes alone.

Nothing the implementation adds is counted: no embedding look-ups, no
recomputation, no vocabulary head at positions whose logits nobody
reads. A multiply-add is two operations.
"""
from __future__ import annotations


def _layer_matmul_flops(cfg: dict) -> int:
    """Forward operations of one token through one layer's four
    projections (4 h^2) and its feed-forward pair (2 h ffn)."""
    h, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    return 2 * (4 * h * h + 2 * h * ffn)


def ernie_train_flops_per_token(cfg: dict, seq: int,
                                labelled_share: float) -> float:
    """Forward + backward (3 x forward) of ERNIE pretraining per input
    token: the layers, full (bidirectional) attention over `seq`
    positions, and the MLM transform and tied vocabulary head at the
    labelled positions only."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    body = layers * (_layer_matmul_flops(cfg) + 4 * seq * h)
    head = labelled_share * (2 * h * h + 2 * h * cfg["vocab_size"])
    return 3.0 * (body + head)


def gpt_serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward operations to serve one request: every processed token
    through the layers, causal attention over its context (4 L h per
    position attended, the query's own included), and the vocabulary
    head once for the prefill and once per decoded token. The last
    emitted token is never fed back, so prompt + new - 1 tokens are
    processed and `new_tokens` rows of logits are needed."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    processed = prompt_len + new_tokens - 1
    if new_tokens <= 0:
        return 0.0
    attended = processed * (processed + 1) // 2
    return float(processed * layers * _layer_matmul_flops(cfg)
                 + 4 * layers * h * attended
                 + new_tokens * 2 * h * cfg["vocab_size"])


def flash_train_call(batch: int, heads: int, seq: int, head_dim: int,
                     causal: bool = False) -> dict:
    """One layer's attention in a training step, forward and backward
    together: operations (QK^T and PV forward; dV, dP, dQ, dK backward,
    with no recomputed QK^T counted) and the bytes of q, k, v, o read or
    written once forward and q, k, v, o, do, dq, dk, dv once backward,
    in bf16."""
    share = 0.5 if causal else 1.0
    pair = 2 * batch * heads * seq * seq * head_dim * share
    tensor = batch * heads * seq * head_dim * 2
    return {"flops": (2 + 4) * pair, "bytes": (4 + 8) * tensor}


def paged_decode_bytes(cfg: dict, context_tokens: int, rows: int,
                       kv_bytes: int = 2) -> float:
    """Bytes one decode step's paged attention must move over all
    layers: K and V of every context token once, q in and out once."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    return float(layers * (2 * context_tokens * h * kv_bytes
                           + 2 * rows * h * 2))


def paged_decode_flops(cfg: dict, context_tokens: int) -> float:
    """QK^T and PV of one decode step over all layers."""
    return float(4 * cfg["num_hidden_layers"] * cfg["hidden_size"]
                 * context_tokens)
