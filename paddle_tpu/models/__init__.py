"""paddle_tpu.models — the models `chipbench`'s cells train and serve.

Upstream these live in the PaddleNLP ecosystem (ERNIE/GPT/LLaMA on top of
paddle.nn); here they are first-class so the framework ships runnable
benchmark models (BASELINE.json configs #3-#5).
"""
from .ernie import ErnieConfig, ErnieModel, ErnieForPretraining  # noqa: F401
from .gpt import (  # noqa: F401
    GPTConfig, GPTEmbeddingPipe, GPTForCausalLM, GPTHeadPipe, GPTModel,
    GPTPretrainingCriterion, gpt_pipe_layers,
)
from .llama import (  # noqa: F401
    LlamaConfig, LlamaForCausalLM, LlamaModel,
)
from .t5 import (  # noqa: F401
    T5Config, T5ForConditionalGeneration, T5Model,
)


_LAZY = {"MlaMoeConfig": "mla_moe", "MlaMoeModel": "mla_moe",
         "MlaMoeForCausalLM": "mla_moe",
         "HybridSsmConfig": "hybrid_ssm", "HybridSsmModel": "hybrid_ssm",
         "HybridSsmForCausalLM": "hybrid_ssm"}


def __getattr__(name):
    # imported when first asked for: a process that serves another model
    # does not pay for this one
    if name in _LAZY:
        import importlib
        return getattr(importlib.import_module(
            f"{__name__}.{_LAZY[name]}"), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
