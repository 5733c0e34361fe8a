"""The lower precisions the controls compute in: the step below the one
a configuration states, the one that would tempt a later PR. Each is a
matmul to put in the place of `x @ w` in a plain reference: operands
rounded on the way in, forward and backward, products and sums exact."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _scaled(x, dtype, top: float):
    """Round through `dtype` with one scale per tensor: the largest
    magnitude lands on `top`, the format's largest number."""
    scale = jnp.max(jnp.abs(x)) / top + 1e-30
    return (x / scale).astype(dtype).astype(x.dtype) * scale


def _matmul(round_forward, round_backward):
    @jax.custom_vjp
    def matmul(x, w):
        return round_forward(x) @ round_forward(w)

    def forward(x, w):
        xq, wq = round_forward(x), round_forward(w)
        return xq @ wq, (xq, wq)

    def backward(kept, g):
        xq, wq = kept
        gq = round_backward(g)
        k = xq.shape[-1]
        return gq @ wq.T, xq.reshape(-1, k).T @ gq.reshape(-1, gq.shape[-1])

    matmul.defvjp(forward, backward)
    return matmul


# float8 as fp8 training uses it: e4m3 for activations and weights, e5m2
# for the gradients that flow back. The step below bfloat16.
fp8 = _matmul(lambda x: _scaled(x, jnp.float8_e4m3fn, 448.0),
              lambda g: _scaled(g, jnp.float8_e5m2, 57344.0))

BELOW = {"bfloat16": fp8}
