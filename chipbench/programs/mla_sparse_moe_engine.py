"""The program under test of a serving configuration with sparse latent
attention and a held share of routed experts: a `ServingEngine` over
`MlaMoeForCausalLM` in the configuration's type (bf16) with the indexer, the group limit, YaRN and
the share switched on by the configuration's own keys, holding the
benchmark's weights, with the options the configuration names and every
other at the program's default. The model is built with its parameters
as shapes only (`deferred_weights`)."""
from __future__ import annotations

import dataclasses

# at import, so that a program without the model fails before the
# driver has made a single weight
from paddle_tpu.models.mla_moe import MlaMoeConfig, MlaMoeForCausalLM

_NEEDS = ("index_topk", "index_n_heads", "index_head_dim", "n_group",
          "topk_group", "rope_scaling", "router_experts", "expert_offset")
_FIELDS = {f.name for f in dataclasses.fields(MlaMoeConfig)}
if not _FIELDS.issuperset(_NEEDS):
    # a program from before the indexer: at import, as above
    raise ImportError(
        "this program's MlaMoeConfig has no "
        + ", ".join(k for k in _NEEDS if k not in _FIELDS)
        + ": it cannot run a configuration with sparse latent attention")


def build(cfg: dict, program: dict, leaves: dict):
    from paddle_tpu.serving import ServingEngine

    model = MlaMoeForCausalLM(MlaMoeConfig(
        **{k: v for k, v in cfg.items() if k in _FIELDS},
        dtype=cfg["precision"], deferred_weights=True))
    model.eval()
    params = dict(model.named_parameters())
    if set(params) != set(leaves):
        raise ValueError("the program's parameters and the reference's "
                         "differ: " + ", ".join(sorted(
                             set(params) ^ set(leaves))[:6]))
    for name, p in params.items():
        if tuple(p.shape) != tuple(leaves[name].shape):
            raise ValueError(f"{name}: {p.shape} != {leaves[name].shape}")
        p._data = leaves[name]
    return ServingEngine(model, **program["engine"])
