"""Plain reference of a decoder with latent attention (MLA) and a
dropless mixture of experts, as JoyAI-LLM-Flash and the DeepSeek-V3
family publish it: one full forward over a whole sequence in
`jax.numpy` and float32 at matmul precision `highest`. No cache, no
paging, no kernels, no absorbed weights, no grouped matmul, no bf16
arithmetic. It imports nothing of the program.

The equations (pre-norm residual blocks; N is RMSNorm without a shift):

- attention, the **expanded** form: cq = N(x Wqa); q = cq Wqb, a head
  being [q_nope; q_rope]; [ckv; kr] = x Wkva; c = N(ckv); one rope key
  kr for all heads; [k_nope; v] of each head = c Wkvb; scores
  (q_nope . k_nope + RoPE(q_rope) . RoPE(kr)) / sqrt(nope + rope),
  causal softmax, out = [o_1 .. o_H] Wo. No biases.
- RoPE rotates the pairs (2i, 2i+1) of the rope part by
  p * theta^(-2i/rope) (`rope_interleave`) and leaves them in place.
- the first `first_k_dense_replace` layers have a SwiGLU MLP; the
  others a router sc = sigmoid(x Wg), chosen = the top `k` of sc + b
  (the lower index first among equals), g = scale * sc[chosen] /
  sum(sc[chosen]), y = sum g_e E_e(x) + E_shared(x), E = SwiGLU.
- final N, untied head.

Departures from a run of the published checkpoint, each on purpose:
the router is float32 under every `matmul` (the configuration states
float32 routing; the control lowers the precision of projections,
experts and head, and a router in fp8 would route elsewhere, which no
precision of the program does); the multi-token-prediction layer is
not held (the main model's logits do not depend on it); `n_group` is 1,
so there is no group-limited selection to write.

Layers run one at a time and every leaf is widened to float32 as it is
used: an expert's three matrices only when a token chose it (a plain
loop over the experts that got tokens, on concrete indices), attention
in blocks of query rows, so that 17k tokens of a 5.5B-parameter cut fit
beside whatever the process still holds. `matmul` is the control's
hook (`chipbench/lowprec.py`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK = 128      # query rows a block of the attention
_SEQ_MULTIPLE = 1024    # a sequence is padded to whole kilotokens: few shapes
_MIN_EXPERT_ROWS = 16   # an expert's rows are padded to a power of two


def _dims(cfg: dict) -> dict:
    return {
        "h": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "vd": cfg["v_head_dim"], "qr": cfg["q_lora_rank"],
        "kvr": cfg["kv_lora_rank"], "experts": cfg["n_routed_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "ffn": cfg["intermediate_size"],
        "moe_ffn": cfg["moe_intermediate_size"],
        "shared": cfg["n_shared_experts"],
        "dense": cfg["first_k_dense_replace"],
    }


def shapes(cfg: dict) -> dict:
    d = _dims(cfg)
    h, heads = d["h"], d["heads"]
    out = {
        "model.embed_tokens.weight": ((cfg["vocab_size"], h), "weight"),
        "model.norm.weight": ((h,), "gain"),
        "lm_head.weight": ((h, cfg["vocab_size"]), "weight"),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        out.update({
            p + "input_layernorm.weight": ((h,), "gain"),
            a + "q_a_proj.weight": ((h, d["qr"]), "weight"),
            a + "q_a_layernorm.weight": ((d["qr"],), "gain"),
            a + "q_b_proj.weight": (
                (d["qr"], heads * (d["nope"] + d["rope"])), "weight"),
            a + "kv_a_proj.weight": ((h, d["kvr"] + d["rope"]), "weight"),
            a + "kv_a_layernorm.weight": ((d["kvr"],), "gain"),
            a + "kv_b_proj.weight": (
                (d["kvr"], heads * (d["nope"] + d["vd"])), "weight"),
            a + "o_proj.weight": ((heads * d["vd"], h), "weight"),
            p + "post_attention_layernorm.weight": ((h,), "gain"),
        })
        m = p + "mlp."
        if i < d["dense"]:
            out.update({
                m + "gate_proj.weight": ((h, d["ffn"]), "weight"),
                m + "up_proj.weight": ((h, d["ffn"]), "weight"),
                m + "down_proj.weight": ((d["ffn"], h), "weight"),
            })
            continue
        e, f, s = d["experts"], d["moe_ffn"], d["shared"] * d["moe_ffn"]
        out.update({
            m + "gate.weight": ((h, e), "weight"),
            m + "gate.e_score_correction_bias": ((e,), "bias"),
            m + "experts.gate_proj": ((e, h, f), "weight"),
            m + "experts.up_proj": ((e, h, f), "weight"),
            m + "experts.down_proj": ((e, f, h), "weight"),
            m + "shared_experts.gate_proj.weight": ((h, s), "weight"),
            m + "shared_experts.up_proj.weight": ((h, s), "weight"),
            m + "shared_experts.down_proj.weight": ((s, h), "weight"),
        })
    return out


def _norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _mm(x, w, matmul):
    return x @ w if matmul is None else matmul(x, w)


def _rope(x, positions, theta):
    """x: (seq, ..., rope); rotates the pairs (2i, 2i+1) in place."""
    rope = x.shape[-1]
    freq = theta ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (rope // 2,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(x.shape)


def _swiglu(y, gate, up, down, matmul):
    return _mm(jax.nn.silu(_mm(y, gate, matmul)) * _mm(y, up, matmul),
               down, matmul)


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "vd", "eps", "theta", "matmul"))
def _attention(x, w, *, heads, nope, rope, vd, eps, theta, matmul):
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    seq = x.shape[0]
    pos = jnp.arange(seq)
    y = _norm(x, w["input_layernorm.weight"], eps)
    cq = _norm(_mm(y, w["q_a_proj.weight"], matmul),
               w["q_a_layernorm.weight"], eps)
    q = _mm(cq, w["q_b_proj.weight"], matmul).reshape(
        seq, heads, nope + rope)
    kva = _mm(y, w["kv_a_proj.weight"], matmul)
    kvr = kva.shape[1] - rope
    c = _norm(kva[:, :kvr], w["kv_a_layernorm.weight"], eps)
    kr = _rope(kva[:, kvr:], pos, theta)                    # (seq, rope)
    kv = _mm(c, w["kv_b_proj.weight"], matmul).reshape(
        seq, heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(kr[:, None], (seq, heads, rope))],
        -1)
    v = kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, theta)], -1)
    scale = 1.0 / jnp.sqrt(jnp.float32(nope + rope))

    def rows(args):
        qb, qpos = args                                     # (bq, heads, d)
        s = jnp.einsum("qnd,knd->nqk", qb, k) * scale
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(s, -1), v)

    bq = _QUERY_BLOCK
    ctx = jax.lax.map(rows, (q.reshape(seq // bq, bq, heads, nope + rope),
                             pos.reshape(seq // bq, bq)))
    return x + _mm(ctx.reshape(seq, heads * vd), w["o_proj.weight"], matmul)


@functools.partial(jax.jit, static_argnames=("eps", "matmul"))
def _dense_mlp(x, w, *, eps, matmul):
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    y = _norm(x, w["post_attention_layernorm.weight"], eps)
    return x + _swiglu(y, w["mlp.gate_proj.weight"], w["mlp.up_proj.weight"],
                       w["mlp.down_proj.weight"], matmul)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "scale",
                                             "matmul"))
def _route_and_share(x, w, *, eps, top_k, scale, matmul):
    """The normed input, the chosen experts and their weights, the margin
    between the last chosen and the first left out, and x + the shared
    expert."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    y = _norm(x, w["post_attention_layernorm.weight"], eps)
    sc = jax.nn.sigmoid(y @ w["mlp.gate.weight"])           # float32, always
    ranked, order = jax.lax.top_k(
        sc + w["mlp.gate.e_score_correction_bias"], top_k + 1)
    chosen = order[:, :top_k]
    picked = jnp.take_along_axis(sc, chosen, 1)
    g = scale * picked / jnp.sum(picked, -1, keepdims=True)
    shared = _swiglu(y, w["mlp.shared_experts.gate_proj.weight"],
                     w["mlp.shared_experts.up_proj.weight"],
                     w["mlp.shared_experts.down_proj.weight"], matmul)
    return y, chosen, g, ranked[:, top_k - 1] - ranked[:, top_k], x + shared


@functools.partial(jax.jit, static_argnames=("matmul",), donate_argnums=(0,))
def _expert(out, y, tokens, g, e, gate, up, down, *, matmul):
    """out[tokens] += g * E_e(y[tokens]) for expert `e` of the stacked
    leaves; padded entries carry g = 0. The expert's three matrices are
    widened here, as they are used."""
    def one(w):
        return jax.lax.dynamic_index_in_dim(w, e, keepdims=False).astype(
            jnp.float32)

    return out.at[tokens].add(
        g[:, None] * _swiglu(y[tokens], one(gate), one(up), one(down),
                             matmul))


def _experts(out, y, chosen, g, layer, matmul):
    """A plain loop over the experts that got tokens, on concrete
    indices; an expert's rows are padded to a power of two so that a few
    shapes serve them all."""
    chosen, g = np.asarray(chosen), np.asarray(g)
    for e in np.unique(chosen):
        tokens, slot = np.nonzero(chosen == e)
        n = max(_MIN_EXPERT_ROWS, 1 << int(len(tokens) - 1).bit_length())
        idx = np.zeros((n,), np.int32)
        weight = np.zeros((n,), np.float32)
        idx[:len(tokens)] = tokens
        weight[:len(tokens)] = g[tokens, slot]
        out = _expert(out, y, idx, weight, np.int32(e),
                      layer["mlp.experts.gate_proj"],
                      layer["mlp.experts.up_proj"],
                      layer["mlp.experts.down_proj"], matmul=matmul)
    return out


@functools.partial(jax.jit, static_argnames=("eps", "matmul"))
def _head(x, gain, head, rows, *, eps, matmul):
    y = _norm(x[rows], gain.astype(jnp.float32), eps)
    return _mm(y, head.astype(jnp.float32), matmul)


def logits(params: dict, ids, rows, cfg: dict, matmul=None,
           with_margin: bool = False):
    """Float32 logits at positions `rows` of the sequence `ids` (1-D,
    padded on the right as the caller likes: under causal attention the
    padding cannot reach an earlier position). With `with_margin` also
    the least margin, over the expert layers, between the score of the
    last expert chosen at each of `rows` and the first left out: where
    it is all but nought, another precision may route elsewhere."""
    d = _dims(cfg)
    ids = np.asarray(ids, np.int32)
    rows = jnp.asarray(rows, jnp.int32)
    multiple = _SEQ_MULTIPLE if len(ids) > _SEQ_MULTIPLE else _QUERY_BLOCK
    seq = -(-len(ids) // multiple) * multiple
    ids = jnp.asarray(np.pad(ids, (0, seq - len(ids))))
    eps = cfg["rms_norm_eps"]
    margin = jnp.full((seq,), jnp.inf, jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = params["model.embed_tokens.weight"][ids].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            p = f"model.layers.{i}."
            layer = {k[len(p):]: v for k, v in params.items()
                     if k.startswith(p)}
            attn = {k.replace("self_attn.", ""): v for k, v in layer.items()
                    if not k.startswith(("mlp.", "post_"))}
            x = _attention(x, attn, heads=d["heads"], nope=d["nope"],
                           rope=d["rope"], vd=d["vd"], eps=eps,
                           theta=float(cfg["rope_theta"]), matmul=matmul)
            if i < d["dense"]:
                x = _dense_mlp(
                    x, {k: v for k, v in layer.items()
                        if k.startswith(("mlp.", "post_"))},
                    eps=eps, matmul=matmul)
                continue
            small = {k: v for k, v in layer.items()
                     if k.startswith(("mlp.gate.", "mlp.shared", "post_"))}
            y, chosen, g, m, x = _route_and_share(
                x, small, eps=eps, top_k=d["top_k"],
                scale=float(cfg["routed_scaling_factor"]), matmul=matmul)
            margin = jnp.minimum(margin, m)
            x = _experts(x, y, chosen, g, layer, matmul)
        out = _head(x, params["model.norm.weight"], params["lm_head.weight"],
                    rows, eps=eps, matmul=matmul)
    return (out, margin[rows]) if with_margin else out
