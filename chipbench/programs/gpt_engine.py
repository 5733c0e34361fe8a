"""The program under test of a GPT serving configuration: a
`ServingEngine` over the GPT decoder in bf16, holding the benchmark's
weights, with the options the configuration names and every other at
the program's default."""
from __future__ import annotations

import dataclasses


def build(cfg: dict, program: dict, leaves: dict):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServingEngine

    fields = {f.name for f in dataclasses.fields(GPTConfig)}
    model = GPTForCausalLM(GPTConfig(
        **{k: v for k, v in cfg.items() if k in fields})).bfloat16()
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
