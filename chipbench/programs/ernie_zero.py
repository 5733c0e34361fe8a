"""The program under test of an ERNIE pretraining configuration:
ERNIE's fused masked-LM loss behind `ZeroTrainStep`, as `chip_smoke.py`
drives it."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def build(cfg: dict, program: dict):
    import paddle_tpu as paddle
    from paddle_tpu.jit.functional import call_functional, extract_state
    from paddle_tpu.models import ErnieConfig, ErnieForPretraining
    from paddle_tpu.parallel import ZeroTrainStep

    if cfg["dropout"]["residual"] or cfg["dropout"]["attention"]:
        raise ValueError("the plain reference cannot follow a step with "
                         "dropout: its masks are drawn inside the kernels")
    fields = {f.name for f in dataclasses.fields(ErnieConfig)}
    model = ErnieForPretraining(ErnieConfig(
        **{k: v for k, v in cfg.items() if k in fields},
        hidden_dropout_prob=cfg["dropout"]["residual"],
        attention_probs_dropout_prob=cfg["dropout"]["attention"],
        fused_mlm_loss=bool(program.get("fused_mlm_loss", True))))
    model.train()
    _, buffers = extract_state(model)
    key = jax.random.key(0)     # no dropout is drawn: both rates are 0

    def loss_fn(params, ids, labels):
        (loss, _nsp), _ = call_functional(
            model, params, buffers, (ids, None, None, None, labels),
            rng_key=key, training=True)
        return loss.astype(jnp.float32)

    opt = paddle.optimizer.Adam(
        learning_rate=program["optimizer"]["learning_rate"],
        parameters=model.parameters())
    return ZeroTrainStep(model, opt, loss_fn, **program["trainer"])
