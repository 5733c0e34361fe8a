"""The names the device trace is read by, in one place.

Every unit of device work that a metric or an operator reads is named
where the program creates it: a Pallas kernel by its `name=`, a layer
boundary of the two hot steps by a `jax.named_scope` of one of the
strings below, a jitted executable by its `__name__`. A scope changes
an operation's `op_name` metadata and nothing else: the compiled
program is the same, the trace says where its time went.
`PERF.md` section 3 quotes these lists; `chipbench/scopes.py` keeps
the benchmark's own copy (a test holds the two together).
"""
from __future__ import annotations

# serving: models/gpt.py cache path, serving/attention.py, serving/engine.py
EMBED = "embed"
ATTN_QKV = "attn_qkv"
KV_WRITE = "kv_write"
PAGED_ATTENTION = "paged_attention"
PREFILL_ATTENTION = "prefill_attention"
ATTN_OUT = "attn_out"
MLP = "mlp"
LM_HEAD = "lm_head"
SAMPLING = "sampling"

# training: models/ernie.py and the fused ops it calls, parallel/zero.py
ATTENTION = "attention"
FFN = "ffn"
MLM_HEAD_LOSS = "mlm_head_loss"
GRAD_REDUCE = "grad_reduce"
OPTIMIZER_UPDATE = "optimizer_update"

SERVE_SCOPES = (EMBED, ATTN_QKV, KV_WRITE, PAGED_ATTENTION,
                PREFILL_ATTENTION, ATTN_OUT, MLP, LM_HEAD, SAMPLING)

# sub-scopes, nested inside a serving scope: models/mla_moe.py. Under
# `mlp`, the parts of a dropless expert layer: the router (float32
# scores, top-k, weights), the dispatch (sort of the (token, expert)
# pairs, group sizes, gather), the grouped matmuls, the weighted combine
# and the shared expert. Under `paged_attention`, the absorption of a
# latent-attention decode query into the cached latent's space
MOE_ROUTER = "moe_router"
MOE_DISPATCH = "moe_dispatch"
MOE_EXPERTS = "moe_experts"
MOE_COMBINE = "moe_combine"
MOE_SHARED = "moe_shared"
MLA_ABSORB = "mla_absorb"
# models/hybrid_ssm.py, the parts of a Mamba-2 mixer: the first norm and
# the input projection, and the causal conv (under `attn_qkv`); the
# one-token state update of a decode step (under `paged_attention`) and
# the chunked scan of a prefill with its write of the slot (under
# `prefill_attention`); the gate, the gated norm, the output projection
# and the residual (under `attn_out`)
SSM_IN_PROJ = "ssm_in_proj"
SSM_CONV = "ssm_conv"
SSM_STATE_UPDATE = "ssm_state_update"
SSM_CHUNK_SCAN = "ssm_chunk_scan"
SSM_GATE_OUT = "ssm_gate_out"
# serving/attention.py and models/mla_moe.py, the parts of sparse latent
# attention (a lightning indexer beside MLA): the indexer's projections
# (under `attn_qkv`) and its scores of a query against the cached or the
# step's index keys; the choice of the `index_topk` highest; and the
# attention over the chosen rows alone (a decode step's gather and
# absorbed attention, a prefill's masked flash). The last three under
# `paged_attention` in a decode step, `prefill_attention` in a prefill
DSA_INDEX = "dsa_index"
DSA_SELECT = "dsa_select"
DSA_ATTEND = "dsa_attend"
SERVE_SUBSCOPES = (MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS, MOE_COMBINE,
                   MOE_SHARED, MLA_ABSORB, SSM_IN_PROJ, SSM_CONV,
                   SSM_STATE_UPDATE, SSM_CHUNK_SCAN, SSM_GATE_OUT,
                   DSA_INDEX, DSA_SELECT, DSA_ATTEND)
TRAIN_SCOPES = (EMBED, ATTENTION, FFN, MLM_HEAD_LOSS, GRAD_REDUCE,
                OPTIMIZER_UPDATE)
# sub-scopes, nested inside a training scope: models/ernie.py. Under
# `mlm_head_loss`, the head over every position of the batch, which runs
# only where a batch has more labels than the gathered rows hold
MLM_HEAD_ALL_ROWS = "mlm_head_all_rows"
TRAIN_SUBSCOPES = (MLM_HEAD_ALL_ROWS,)

# Pallas kernels of serving/attention.py: the trace names their events
# `%paged_decode.N` / `%paged_ragged.N` whatever encloses the call
PAGED_DECODE_KERNEL = "paged_decode"
PAGED_RAGGED_KERNEL = "paged_ragged"
# the absorbed-form decode kernel over a latent pool, `%mla_decode.N`
MLA_DECODE_KERNEL = "mla_decode"
# the in-place state update of serving/ssm.py, `%ssm_decode.N`
SSM_DECODE_KERNEL = "ssm_decode"
# sparse latent attention's two: a row's index query against its own
# pages of index keys, `%dsa_index.N`, and the absorbed attention over
# the chosen rows, gathered by token index, `%mla_sparse_decode.N`
DSA_INDEX_KERNEL = "dsa_index"
MLA_SPARSE_DECODE_KERNEL = "mla_sparse_decode"

# jitted executables: the trace's `XLA Modules` line reads
# `jit_<name>`; a family is its first word
EXECUTABLES = ("prefill", "prefill_offset", "prefill_chunk", "decode_block",
               "ragged_block", "spec_decode_block", "spec_ragged_block",
               "zero_train_step")


def named(fn, name: str):
    """`fn` under the name its jitted executable is to carry, one of
    `EXECUTABLES`."""
    if name not in EXECUTABLES:
        raise ValueError(f"{name!r} is not in scopes.EXECUTABLES: add it "
                         "there, where PERF.md and the tests read the list")
    fn.__name__ = fn.__qualname__ = name
    return fn
