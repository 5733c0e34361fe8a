"""Operations and bytes that a hybrid decoder of Mamba-2 and attention
layers needs, from shapes alone. As `flops.py`: nothing the
implementation adds is counted, a multiply-add is two operations.

`serve_flops` counts the recurrence in its **linear-time** form, 5
operations an element of the state a token (the decay, the outer
product's two, the read's two). The chunked form a prefill runs does
more arithmetic a token, and a padded bucket more still: a share of the
peak must not rise by either.
"""
from __future__ import annotations


def _kinds(cfg: dict) -> tuple:
    mamba = sum(t == "mamba" for t in cfg["layer_types"])
    return mamba, len(cfg["layer_types"]) - mamba


def _state_elems(cfg: dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]


def _token_matmul_params(cfg: dict) -> int:
    """Matrix elements one token meets on its way through the layers:
    every layer's MLP, a Mamba layer's two projections, an attention
    layer's four."""
    h, f = cfg["hidden_size"], cfg["shared_intermediate_size"]
    heads = cfg["mamba_n_heads"]
    d_inner = heads * cfg["mamba_d_head"]
    conv_dim = d_inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    kv = (cfg["num_key_value_heads"] * h) // cfg["num_attention_heads"]
    mamba, attention = _kinds(cfg)
    return ((mamba + attention) * 3 * h * f
            + mamba * (h * (d_inner + conv_dim + heads) + d_inner * h)
            + attention * (2 * h * h + 2 * h * kv))


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward operations to serve one request: every processed token
    through the matrices, the recurrence's 5 operations a state element
    a Mamba layer, causal attention over its context in the attention
    layers (4 h a position attended, the query's own included), and the
    head once a row of logits needed. The last emitted token is never
    fed back. The conv's 8 operations a channel are left out: a
    thousandth of the rest."""
    if new_tokens <= 0:
        return 0.0
    mamba, attention = _kinds(cfg)
    processed = prompt_len + new_tokens - 1
    attended = processed * (processed + 1) // 2
    return float(
        processed * (2 * _token_matmul_params(cfg)
                     + mamba * 5 * _state_elems(cfg))
        + attention * 4 * cfg["hidden_size"] * attended
        + new_tokens * 2 * cfg["hidden_size"] * cfg["vocab_size"])


def ssm_decode_bytes(cfg: dict, decoded: int) -> float:
    """Bytes the one-token state update must move over the Mamba layers:
    the float32 state read and written once a decoded token, and the
    token's x, B, C, dt in and y out, in float32 as the kernel takes
    them."""
    mamba, _ = _kinds(cfg)
    heads, n = cfg["mamba_n_heads"], cfg["mamba_d_state"]
    x = heads * cfg["mamba_d_head"]
    token = 4 * (2 * x + 2 * n * cfg["mamba_n_groups"] + heads)
    return float(decoded * mamba * (2 * 4 * _state_elems(cfg) + token))


def ssm_decode_flops(cfg: dict, decoded: int) -> float:
    """The recurrence's 5 operations a state element a decoded token."""
    mamba, _ = _kinds(cfg)
    return float(decoded * mamba * 5 * _state_elems(cfg))


def paged_decode_bytes(cfg: dict, context_tokens: int, rows: int,
                       kv_bytes: int = 2) -> float:
    """Bytes one decode step's paged attention must move over the
    attention layers: K and V of every context token once at their own
    width (kv heads x head width, unpadded), q in and out once."""
    _, attention = _kinds(cfg)
    h = cfg["hidden_size"]
    kv = (cfg["num_key_value_heads"] * h) // cfg["num_attention_heads"]
    return float(attention * (2 * context_tokens * kv * kv_bytes
                              + 2 * rows * h * 2))


def paged_decode_flops(cfg: dict, context_tokens: int) -> float:
    """QK^T and PV of one decode step over the attention layers."""
    _, attention = _kinds(cfg)
    return float(4 * attention * cfg["hidden_size"] * context_tokens)
