"""Plain reference of the GPT-3 decoder (Brown et al. 2020,
arXiv:2005.14165: pre-LN causal transformer, learned positions, output
head tied to the token embedding), one full forward over a whole
sequence in `jax.numpy` and float32 at matmul precision `highest`. No
cache, no paging, no kernels, no bf16 arithmetic. It imports nothing of
the program. Layers run one at a time, each leaf widened to float32 as
it is used, so that a 1.3B model fits beside whatever the process still
holds.

`matmul` is the control's hook, put in the place of every projection,
feed-forward and vocabulary matmul (`chipbench/lowprec.py`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def shapes(cfg: dict) -> dict:
    h, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    out = {
        "gpt.wte.weight": ((cfg["vocab_size"], h), "weight"),
        "gpt.wpe.weight": ((cfg["max_position_embeddings"], h), "weight"),
        "gpt.ln_f.weight": ((h,), "gain"),
        "gpt.ln_f.bias": ((h,), "bias"),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"gpt.blocks.{i}."
        out.update({
            p + "ln1.weight": ((h,), "gain"), p + "ln1.bias": ((h,), "bias"),
            p + "attn.qkv.weight": ((h, 3 * h), "weight"),
            p + "attn.qkv.bias": ((3 * h,), "bias"),
            p + "attn.out.weight": ((h, h), "weight"),
            p + "attn.out.bias": ((h,), "bias"),
            p + "ln2.weight": ((h,), "gain"), p + "ln2.bias": ((h,), "bias"),
            p + "ffn_in.weight": ((h, ffn), "weight"),
            p + "ffn_in.bias": ((ffn,), "bias"),
            p + "ffn_out.weight": ((ffn, h), "weight"),
            p + "ffn_out.bias": ((h,), "bias"),
        })
    return out


def _norm(x, gain, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def _mm(x, w, matmul):
    return x @ w if matmul is None else matmul(x, w)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "matmul"))
def _block(x, w, *, heads, eps, matmul):
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    seq, h = x.shape
    hd = h // heads
    y = _norm(x, w["ln1.weight"], w["ln1.bias"], eps)
    qkv = (_mm(y, w["attn.qkv.weight"], matmul)
           + w["attn.qkv.bias"]).reshape(seq, 3, heads, hd)
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    scores = jnp.einsum("qnd,knd->nqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    ctx = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, -1), v)
    x = x + _mm(ctx.reshape(seq, h), w["attn.out.weight"], matmul) \
        + w["attn.out.bias"]
    y = _norm(x, w["ln2.weight"], w["ln2.bias"], eps)
    f = jax.nn.gelu(_mm(y, w["ffn_in.weight"], matmul) + w["ffn_in.bias"],
                    approximate=False)
    return x + _mm(f, w["ffn_out.weight"], matmul) + w["ffn_out.bias"]


@functools.partial(jax.jit, static_argnames=("eps", "matmul"))
def _head(x, gain, bias, wte, rows, *, eps, matmul):
    y = _norm(x[rows], gain.astype(jnp.float32), bias.astype(jnp.float32),
              eps)
    return _mm(y, wte.astype(jnp.float32).T, matmul)


def logits(params: dict, ids, rows, cfg: dict, matmul=None):
    """Float32 logits at positions `rows` of the sequence `ids` (1-D,
    padded on the right as the caller likes: under causal attention the
    padding cannot reach an earlier position)."""
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = (params["gpt.wte.weight"][ids].astype(jnp.float32)
             + params["gpt.wpe.weight"][:ids.shape[0]].astype(jnp.float32))
        for i in range(cfg["num_hidden_layers"]):
            p = f"gpt.blocks.{i}."
            layer = {k[len(p):]: v for k, v in params.items()
                     if k.startswith(p)}
            x = _block(x, layer, heads=cfg["num_attention_heads"],
                       eps=cfg["layer_norm_eps"], matmul=matmul)
        return _head(x, params["gpt.ln_f.weight"], params["gpt.ln_f.bias"],
                     params["gpt.wte.weight"], jnp.asarray(rows, jnp.int32),
                     eps=cfg["layer_norm_eps"], matmul=matmul)
