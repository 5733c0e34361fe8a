"""The program under test of a latent-attention, routed-expert serving
configuration: a `ServingEngine` over `MlaMoeForCausalLM` in bf16,
holding the benchmark's weights, with the options the configuration
names and every other at the program's default. The model is built with
its parameters as shapes only (`deferred_weights`): a copy of its own to
be replaced would not fit the chip beside the benchmark's."""
from __future__ import annotations

import dataclasses

# at import, so that a program without the model fails before the
# driver has made a single weight
from paddle_tpu.models.mla_moe import MlaMoeConfig, MlaMoeForCausalLM


def build(cfg: dict, program: dict, leaves: dict):
    from paddle_tpu.serving import ServingEngine

    fields = {f.name for f in dataclasses.fields(MlaMoeConfig)}
    model = MlaMoeForCausalLM(MlaMoeConfig(
        **{k: v for k, v in cfg.items() if k in fields},
        dtype="bfloat16", deferred_weights=True))
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
