"""The program under test of a hybrid (Mamba-2 + attention) serving
configuration: a `ServingEngine` over `HybridSsmForCausalLM` in bf16,
holding the benchmark's weights, with the options the configuration
names and every other at the program's default. The model is built with
its parameters as shapes only (`deferred_weights`): nothing is drawn for
weights the benchmark's are about to replace."""
from __future__ import annotations

import dataclasses

# at import, so that a program without the model fails before the
# driver has made a single weight
from paddle_tpu.models.hybrid_ssm import (HybridSsmConfig,
                                          HybridSsmForCausalLM)


def build(cfg: dict, program: dict, leaves: dict):
    from paddle_tpu.serving import ServingEngine

    fields = {f.name for f in dataclasses.fields(HybridSsmConfig)}
    model = HybridSsmForCausalLM(HybridSsmConfig(
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
