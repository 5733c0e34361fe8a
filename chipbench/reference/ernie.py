"""Plain reference of ERNIE 1.0 pretraining (arXiv:1904.09223; the
BERT-base encoder, post-LN, with the masked-LM head tied to the word
embeddings), its loss, gradients and Adam, in straightforward
`jax.numpy` and float32 at matmul precision `highest`. No kernels, no
fused loss, no bf16. It imports nothing of the program.

Departures from the paper, as the cell states them: no next-sentence
loss (the cell trains the masked-LM loss alone), no dropout (the
configuration file sets both rates to 0 and says why), token type 0
everywhere.

`matmul` is the control's hook: a function put in the place of every
projection, feed-forward and vocabulary matmul (`chipbench/lowprec.py`),
so that the same code computed in a lower precision can stand in the
program's place.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BETA1, BETA2, EPS = 0.9, 0.999, 1e-8


def shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, kind). The names are the published layer
    names as PaddleNLP spells them; a configuration file may map them
    onto other names of the program (`param_names`)."""
    h, ffn, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    out = {
        "mlm_bias": ((v,), "bias"),
        "ernie.embeddings.word_embeddings.weight": ((v, h), "weight"),
        "ernie.embeddings.position_embeddings.weight": (
            (cfg["max_position_embeddings"], h), "weight"),
        "ernie.embeddings.token_type_embeddings.weight": (
            (cfg["type_vocab_size"], h), "weight"),
        "ernie.embeddings.norm.weight": ((h,), "gain"),
        "ernie.embeddings.norm.bias": ((h,), "bias"),
        "ernie.pooler.weight": ((h, h), "weight"),
        "ernie.pooler.bias": ((h,), "bias"),
        "transform.weight": ((h, h), "weight"),
        "transform.bias": ((h,), "bias"),
        "mlm_norm.weight": ((h,), "gain"),
        "mlm_norm.bias": ((h,), "bias"),
        "nsp.weight": ((h, 2), "weight"),
        "nsp.bias": ((2,), "bias"),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"ernie.layers.{i}."
        out.update({
            p + "attention.qkv.weight": ((h, 3 * h), "weight"),
            p + "attention.qkv.bias": ((3 * h,), "bias"),
            p + "attention.out.weight": ((h, h), "weight"),
            p + "attention.out.bias": ((h,), "bias"),
            p + "attn_norm.weight": ((h,), "gain"),
            p + "attn_norm.bias": ((h,), "bias"),
            p + "ffn_in.weight": ((h, ffn), "weight"),
            p + "ffn_in.bias": ((ffn,), "bias"),
            p + "ffn_out.weight": ((ffn, h), "weight"),
            p + "ffn_out.bias": ((h,), "bias"),
            p + "ffn_norm.weight": ((h,), "gain"),
            p + "ffn_norm.bias": ((h,), "bias"),
        })
    return out


def _norm(x, gain, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def _mm(x, w, matmul):
    return x @ w if matmul is None else matmul(x, w)


def loss_sum(params, ids, labels, cfg: dict, matmul=None):
    """Sum of the masked-LM cross entropy over the labelled positions of
    `ids` (rows x seq), and their count."""
    p = params
    heads = cfg["num_attention_heads"]
    h = cfg["hidden_size"]
    hd = h // heads
    eps = cfg["layer_norm_eps"]
    rows, seq = ids.shape
    word = p["ernie.embeddings.word_embeddings.weight"]
    x = (word[ids]
         + p["ernie.embeddings.position_embeddings.weight"][:seq][None]
         + p["ernie.embeddings.token_type_embeddings.weight"][0])
    x = _norm(x, p["ernie.embeddings.norm.weight"],
              p["ernie.embeddings.norm.bias"], eps)
    for i in range(cfg["num_hidden_layers"]):
        n = f"ernie.layers.{i}."
        qkv = _mm(x, p[n + "attention.qkv.weight"], matmul) \
            + p[n + "attention.qkv.bias"]
        qkv = qkv.reshape(rows, seq, 3, heads, hd)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(
            jnp.float32(hd))
        ctx = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, -1), v)
        a = _mm(ctx.reshape(rows, seq, h), p[n + "attention.out.weight"],
                matmul) + p[n + "attention.out.bias"]
        x = _norm(x + a, p[n + "attn_norm.weight"], p[n + "attn_norm.bias"],
                  eps)
        f = jax.nn.gelu(_mm(x, p[n + "ffn_in.weight"], matmul)
                        + p[n + "ffn_in.bias"], approximate=False)
        f = _mm(f, p[n + "ffn_out.weight"], matmul) + p[n + "ffn_out.bias"]
        x = _norm(x + f, p[n + "ffn_norm.weight"], p[n + "ffn_norm.bias"],
                  eps)
    t = jax.nn.gelu(_mm(x, p["transform.weight"], matmul)
                    + p["transform.bias"], approximate=False)
    t = _norm(t, p["mlm_norm.weight"], p["mlm_norm.bias"], eps)
    logits = _mm(t, word.T, matmul) + p["mlm_bias"]
    mask = labels >= 0
    logp = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(
        logp, jnp.where(mask, labels, 0)[..., None], -1)[..., 0]
    return -jnp.sum(jnp.where(mask, picked, 0.0)), jnp.sum(mask)


def loss_and_grads(params, ids, labels, cfg: dict, block_rows: int,
                   matmul=None):
    """Mean loss over the labelled positions of the whole batch and its
    gradient, accumulated over blocks of `block_rows` rows so that the
    float32 activations fit beside the program's own peak."""
    rows, seq = ids.shape
    if rows % block_rows:
        raise ValueError(f"{rows} rows do not split into blocks of "
                         f"{block_rows}")
    blocks = rows // block_rows
    count = jnp.sum(labels >= 0).astype(jnp.float32)

    def block_loss(p, ids_b, labels_b):
        return loss_sum(p, ids_b, labels_b, cfg, matmul)[0]

    def body(carry, batch):
        total, acc = carry
        value, g = jax.value_and_grad(block_loss)(params, *batch)
        return (total + value,
                jax.tree_util.tree_map(jnp.add, acc, g)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    (total, acc), _ = jax.lax.scan(
        body, (jnp.float32(0.0), zero),
        (ids.reshape(blocks, block_rows, seq),
         labels.reshape(blocks, block_rows, seq)))
    return total / count, jax.tree_util.tree_map(lambda g: g / count, acc)


def adam(params, grads, m, v, lr, t):
    """Adam (Kingma & Ba) with bias correction, as Paddle's `Adam`."""
    t = jnp.float32(t)
    m = jax.tree_util.tree_map(
        lambda a, g: BETA1 * a + (1 - BETA1) * g, m, grads)
    v = jax.tree_util.tree_map(
        lambda a, g: BETA2 * a + (1 - BETA2) * g * g, v, grads)
    c1, c2 = 1 - BETA1 ** t, 1 - BETA2 ** t
    new = jax.tree_util.tree_map(
        lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + EPS),
        params, m, v)
    return new, m, v


def pieces(tree: dict) -> dict:
    """The leaves as the comparison sees them: the paper's separate
    query, key and value projections, which this file (like the
    program) stores fused. The key's bias cannot move the softmax, so
    its gradient is nought, and only as a leaf of its own can the rule
    on noughts leave it out."""
    out = {}
    for k, x in tree.items():
        if ".attention.qkv." in k:
            for part, third in zip(("query", "key", "value"),
                                   jnp.split(x, 3, axis=-1)):
                out[k.replace(".qkv.", f".{part}.")] = third
        else:
            out[k] = x
    return out


def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for k, x in pieces(tree).items()}


def follow(params, batches, cfg: dict, lr: float, block_rows: int,
           matmul=None) -> dict:
    """Train from `params` over `batches` (a list of (ids, labels)) and
    return what `correct` compares: each step's loss, the first gradient
    and the norm of every leaf of it, and the norm of every leaf's change
    after the last step."""
    with jax.default_matmul_precision("highest"):
        @jax.jit
        def step(p, m, v, ids, labels, t):
            loss, g = loss_and_grads(p, ids, labels, cfg, block_rows, matmul)
            new, m, v = adam(p, g, m, v, lr, t)
            return loss, g, new, m, v

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        p, m, v = params, zeros, zeros
        losses, first = [], None
        for t, (ids, labels) in enumerate(batches, start=1):
            loss, g, p, m, v = step(p, m, v, jnp.asarray(ids),
                                    jnp.asarray(labels), t)
            losses.append(float(loss))
            if first is None:
                first = g
        change = difference_norms(p, params)
    return {"losses": losses, "first_gradient": first,
            "grad_norms": {k: float(x) for k, x in
                           jax.jit(leaf_norms)(first).items()},
            "change_norms": change}


@jax.jit
def _difference_norms(a, b):
    return leaf_norms({k: a[k].astype(jnp.float32) - b[k] for k in b})


def difference_norms(a: dict, b: dict) -> dict:
    """Norm of a - b, piece by piece, as floats."""
    return {k: float(x) for k, x in _difference_norms(a, b).items()}
