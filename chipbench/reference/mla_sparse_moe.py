"""Plain reference of a decoder with latent attention (MLA) whose queries
attend the keys a lightning indexer chooses, and a group-limited mixture
of experts of which one chip's share is held: DeepSeek-V3.2
(https://huggingface.co/deepseek-ai/DeepSeek-V3.2, `model_type`
`deepseek_v32`). One full forward over a whole sequence in `jax.numpy`
and float32 at matmul precision `highest`. No cache, no paging, no
kernels, no absorbed weights, no grouped matmul, no gather of chosen
rows, no bf16 arithmetic. It imports nothing of the program.

The equations (pre-norm residual blocks; N is RMSNorm without a shift;
no biases but the index key's LayerNorm):

- attention, the **expanded** form, as `reference/mla_moe.py` has it:
  cq = N(x Wqa); q = cq Wqb, a head being [q_nope; q_rope]; [ckv; kr] =
  x Wkva; c = N(ckv); one rope key kr for all heads; [k_nope; v] of each
  head = c Wkvb; scores (q_nope . k_nope + RoPE(q_rope) . RoPE(kr)) *
  (nope + rope)^(-1/2) * m^2, softmax **over the set S_t alone**, out =
  [o_1 .. o_H] Wo.
- YaRN: f_i = theta^(-2i/rope); d(r) = rope ln(original / (2 pi r)) /
  (2 ln theta); low = floor(d(beta_fast)), high = ceil(d(beta_slow));
  ramp_i = clip((i - low) / (high - low), 0, 1); f'_i = f_i (1 - ramp_i)
  + (f_i / factor) ramp_i. With mscale = mscale_all_dim cos and sin carry
  no factor, and m = 0.1 mscale_all_dim ln(factor) + 1.
- RoPE of MLA rotates the pairs (2i, 2i+1) of the rope part by p f'_i
  and leaves them in place; the indexer's rotates the halves (i, i + 32)
  of the first 64 columns of each query head and of the key.
- indexer: qI = cq W_Iq, `index_n_heads` heads of `index_head_dim`; kI =
  LN(x W_Ik) with gain, shift and eps 1e-6; w = (x W_Iw) heads^(-1/2)
  width^(-1/2); I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) for s <=
  t; S_t = the min(index_topk, t + 1) positions s <= t of largest
  I[t, s], the lower position first among equals. With `index_topk`
  None in the configuration handed in, S_t is every s <= t.
- routing (`noaux_tc`): sc = sigmoid(x Wg) over the router's
  `router_experts`; c = sc + b; the experts are `n_group` groups of
  neighbours, a group's score the sum of its two largest c, the
  `topk_group` best groups stay (the lower first among equals); chosen =
  the `num_experts_per_tok` largest c among their experts; g = scale *
  sc[chosen] / sum sc[chosen]; y = sum over the chosen experts **held
  here** (`expert_offset` .. `expert_offset` + `n_routed_experts`) of
  g_e E_e(x) + E_shared(x), E = SwiGLU. What an expert held elsewhere
  would add is left out, and that partial result goes on.
- the first `first_k_dense_replace` layers have a SwiGLU MLP.
- final N, untied head over the held rows of the vocabulary.

Departures from a run of the published checkpoint, each on purpose: the
router, the index scores and both choices are float32 under every
`matmul`; the index queries and keys are not rotated by a Hadamard
matrix nor rounded to fp8 (the rotation is orthonormal and changes no
score; fp8 is the configuration's `assumed`); the
multi-token-prediction layer is not held.

A configuration handed in with `index_operand_mantissa_bits` (7 is
bfloat16's) is a stand-in, not the reference: the rotated index queries
and keys are rounded to that many bits of mantissa before the scores are
taken, and nothing else is. It reads how far the choice alone moves
under the precision a program caches its index keys in, and which share
of the exact choice it keeps (`selection_kept`).

Layers run one at a time, queries in blocks, heads a few at a time, and
every leaf is widened to float32 as it is used, so that 39k tokens at
hidden 7,168 fit beside the bf16 leaves. `matmul` is the control's hook
(`chipbench/lowprec.py`): projections, experts and head.

Only what the asked positions' logits depend on is computed, in shapes
that do not follow a sequence's length: a block of queries is taken
against the keys up to the next `_KEY_MULTIPLE` past its own end (a
later key is masked anyway), no block past the last asked position is
run, the last layer runs the blocks that hold an asked position alone,
and a long sequence is padded to whole `_SEQ_MULTIPLE`s, so that one
run's compiled functions serve the next run's sequences too.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# query rows a block of the attention: its keys and values are expanded
# once a block, so a larger block repeats less of that
_QUERY_BLOCK = 2048
# heads whose keys and values, and index heads whose scores, are held at
# once: (heads, queries, keys) float32, 0.67 GB at 2 x 2,048 x 40,960
_HEAD_BLOCK = 2
_KEY_MULTIPLE = 8192    # a block's keys end at a whole multiple of this
_MLP_ROWS = 4096        # rows a block of the dense MLP
_SEQ_MULTIPLE = 8192    # a long sequence is padded to these: few shapes
_ROWS_MULTIPLE = 512    # and the asked positions to these, for the head
_MIN_EXPERT_ROWS = 16   # an expert's rows are padded to a power of two
_INDEX_NORM_EPS = 1e-6


def _dims(cfg: dict) -> dict:
    return {
        "h": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "vd": cfg["v_head_dim"], "qr": cfg["q_lora_rank"],
        "kvr": cfg["kv_lora_rank"], "held": cfg["n_routed_experts"],
        "router": cfg.get("router_experts") or cfg["n_routed_experts"],
        "offset": cfg.get("expert_offset", 0),
        "top_k": cfg["num_experts_per_tok"],
        "n_group": cfg.get("n_group", 1),
        "topk_group": cfg.get("topk_group", 1),
        "ffn": cfg["intermediate_size"],
        "moe_ffn": cfg["moe_intermediate_size"],
        "shared": cfg["n_shared_experts"],
        "dense": cfg["first_k_dense_replace"],
        "ih": cfg["index_n_heads"], "id": cfg["index_head_dim"],
        "topk": cfg.get("index_topk"),
        "ibits": cfg.get("index_operand_mantissa_bits"),
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
            a + "indexer.wq_b.weight": ((d["qr"], d["ih"] * d["id"]),
                                        "weight"),
            a + "indexer.wk.weight": ((h, d["id"]), "weight"),
            a + "indexer.k_norm.weight": ((d["id"],), "gain"),
            a + "indexer.k_norm.bias": ((d["id"],), "bias"),
            a + "indexer.weights_proj.weight": ((h, d["ih"]), "weight"),
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
        e, f, s = d["held"], d["moe_ffn"], d["shared"] * d["moe_ffn"]
        out.update({
            m + "gate.weight": ((h, d["router"]), "weight"),
            m + "gate.e_score_correction_bias": ((d["router"],), "bias"),
            m + "experts.gate_proj": ((e, h, f), "weight"),
            m + "experts.up_proj": ((e, h, f), "weight"),
            m + "experts.down_proj": ((e, f, h), "weight"),
            m + "shared_experts.gate_proj.weight": ((h, s), "weight"),
            m + "shared_experts.up_proj.weight": ((h, s), "weight"),
            m + "shared_experts.down_proj.weight": ((s, h), "weight"),
        })
    return out


def rope_frequencies(rope: int, theta: float, scaling) -> np.ndarray:
    """f' of the docstring, (rope / 2,) float32."""
    i = np.arange(rope // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / rope)
    if scaling is None:
        return f.astype(np.float32)

    def d(turns):
        return (rope * math.log(scaling["original_max_position_embeddings"]
                                / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    low = max(math.floor(d(scaling["beta_fast"])), 0)
    high = min(math.ceil(d(scaling["beta_slow"])), rope // 2 - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f * (1 - ramp) + f / scaling["factor"] * ramp).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    d = _dims(cfg)
    scale = (d["nope"] + d["rope"]) ** -0.5
    scaling = cfg.get("rope_scaling")
    if scaling and scaling.get("mscale_all_dim") and scaling["factor"] > 1:
        scale *= (0.1 * scaling["mscale_all_dim"]
                  * math.log(scaling["factor"]) + 1.0) ** 2
    return scale


def _norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _mm(x, w, matmul):
    return x @ w if matmul is None else matmul(x, w)


def _angles(x, positions, freq):
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (freq.shape[0],)
    return jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)


def _rope_pairs(x, positions, freq):
    """x: (seq, ..., rope); rotates the pairs (2i, 2i+1) in place."""
    cos, sin = _angles(x, positions, freq)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(x.shape)


def _rope_halves(x, positions, freq):
    """x: (seq, ..., width); rotates column i with column i + len(freq)
    of the first 2 len(freq) columns and leaves the others."""
    half = freq.shape[0]
    cos, sin = _angles(x, positions, freq)
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., 2 * half:]], -1)


def _swiglu(y, gate, up, down, matmul):
    return _mm(jax.nn.silu(_mm(y, gate, matmul)) * _mm(y, up, matmul),
               down, matmul)


@functools.partial(jax.jit, static_argnames=("rope", "ih", "eps", "matmul"))
def _keys(x, w, freq, *, rope, ih, eps, matmul):
    """What every token leaves for the queries after it, and the two
    small things its own query needs of its normed input: the normed
    query latent cq, the indexer's head weights wi, the normed latent c,
    the rotated rope key kr and the rotated index key kI."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    pos = jnp.arange(x.shape[0])
    y = _norm(x, w["input_layernorm.weight"], eps)
    cq = _norm(_mm(y, w["q_a_proj.weight"], matmul),
               w["q_a_layernorm.weight"], eps)
    kva = _mm(y, w["kv_a_proj.weight"], matmul)
    kvr = kva.shape[1] - rope
    c = _norm(kva[:, :kvr], w["kv_a_layernorm.weight"], eps)
    kr = _rope_pairs(kva[:, kvr:], pos, freq)
    ki = _mm(y, w["indexer.wk.weight"], matmul)
    mu = jnp.mean(ki, -1, keepdims=True)
    ki = ((ki - mu) / jnp.sqrt(jnp.mean((ki - mu) ** 2, -1, keepdims=True)
                               + _INDEX_NORM_EPS)
          * w["indexer.k_norm.weight"] + w["indexer.k_norm.bias"])
    wi = _mm(y, w["indexer.weights_proj.weight"], matmul) * (
        ih ** -0.5 * ki.shape[-1] ** -0.5)
    return cq, wi, c, kr, _rope_halves(ki, pos, freq)


def _highest(index, topk: int):
    """Of each row of scores the `topk` highest, the lower position first
    among equals, as a mask; and the score of the first left out beside
    that of the last chosen, (rows, 2), or None where a row has no more
    than `topk` scores and all are chosen."""
    seq = index.shape[-1]
    if topk >= seq:
        return None, jnp.ones(index.shape, bool)
    # the topk-th highest and the next: one sort of the values alone
    edge = jnp.sort(index, axis=-1)[:, seq - topk - 1:seq - topk + 1]
    last = edge[:, 1:]
    above, equal = index > last, index == last
    # of the equals at the edge the lower positions, as many as the
    # others leave room for
    room = topk - jnp.sum(above, -1, keepdims=True)
    return edge, above | (equal & (jnp.cumsum(equal, -1) <= room))


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "vd", "ih", "topk", "ibits", "scale", "matmul"))
def _attend(first, cq, wi, c, kr, ki, w, freq, *, heads, nope, rope, vd,
            ih, topk, ibits, scale, matmul):
    """The attention output, before the residual, of the block of queries
    at positions first .. first + len(cq) - 1 over the keys handed in,
    the sequence's first len(c) (its shapes are a block's and a key
    length's, whatever the sequence's); the block's selection margin: I
    of the last position chosen less I of the first left out (inf where
    every position is chosen); and, of a stand-in whose index operands
    are rounded to `ibits` bits of mantissa, the share of the exact
    choice that its own choice keeps (1 for the reference itself)."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    bq, seq = cq.shape[0], c.shape[0]
    qpos = first + jnp.arange(bq)
    kpos = jnp.arange(seq)
    causal = kpos[None, :] <= qpos[:, None]
    margin = jnp.full((bq,), jnp.inf, jnp.float32)
    kept = jnp.ones((bq,), jnp.float32)
    allowed = causal
    if topk is not None:
        qi = _rope_halves(_mm(cq, w["indexer.wq_b.weight"], matmul).reshape(
            bq, ih, -1), qpos, freq)
        hb = min(_HEAD_BLOCK, ih)

        def choice(qi, ki):
            def some_index_heads(total, args):
                qh, wh = args                   # (bq, hb, d), (bq, hb)
                return total + jnp.einsum("qj,qjk->qk", wh, jax.nn.relu(
                    jnp.einsum("qjd,kd->qjk", qh, ki))), None

            index, _ = jax.lax.scan(
                some_index_heads, jnp.zeros((bq, seq), jnp.float32),
                (jnp.moveaxis(qi.reshape(bq, ih // hb, hb, -1), 1, 0),
                 jnp.moveaxis(wi.reshape(bq, ih // hb, hb), 1, 0)))
            edge, chosen = _highest(jnp.where(causal, index, -jnp.inf), topk)
            return edge, causal & chosen

        edge, allowed = choice(qi, ki)
        if ibits is not None:
            # `astype` rounds nothing on the TPU, which keeps the excess
            # precision: `reduce_precision` does
            exact = allowed
            edge, allowed = choice(
                jax.lax.reduce_precision(qi, 8, ibits),
                jax.lax.reduce_precision(ki, 8, ibits))
            kept = (jnp.sum(exact & allowed, -1)
                    / jnp.sum(exact, -1)).astype(jnp.float32)
        if edge is not None:
            margin = jnp.where(qpos + 1 > topk, edge[:, 1] - edge[:, 0],
                               jnp.inf)
    q = _mm(cq, w["q_b_proj.weight"], matmul).reshape(bq, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope],
                         _rope_pairs(q[..., nope:], qpos, freq)], -1)
    hb = min(_HEAD_BLOCK, heads)
    w_kv = w["kv_b_proj.weight"].reshape(-1, heads // hb, hb, nope + vd)

    def some_heads(args):
        qh, wh = args                                   # (bq, hb, d)
        kv = _mm(c, wh.reshape(wh.shape[0], -1), matmul).reshape(
            seq, hb, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(kr[:, None], (seq, hb, rope))],
            -1)
        s = jnp.einsum("qnd,knd->nqk", qh, k) * scale
        s = jnp.where(allowed[None], s, -jnp.inf)
        return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(s, -1),
                          kv[..., nope:])

    ctx = jax.lax.map(some_heads, (
        jnp.moveaxis(q.reshape(bq, heads // hb, hb, nope + rope), 1, 0),
        jnp.moveaxis(w_kv, 1, 0)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(bq, heads * vd)
    return _mm(ctx, w["o_proj.weight"], matmul), margin, kept


@functools.partial(jax.jit, static_argnames=("rows",))
def _rows(x, first, *, rows):
    return jax.lax.dynamic_slice_in_dim(x, first, rows)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add_rows(x, first, rows):
    return jax.lax.dynamic_update_slice_in_dim(
        x, jax.lax.dynamic_slice_in_dim(x, first, rows.shape[0]) + rows,
        first, 0)


def _attention(x, w, cfg, d, freq, matmul, start, stop):
    """x + attention(x) for the blocks of queries that hold a position
    of start .. stop - 1, a block at a time into x itself (the other rows
    are left as they are: nothing asked for reads them), and every
    position's selection margin and share of the exact choice kept (inf
    and 1 where the block was not run)."""
    cq, wi, c, kr, ki = _keys(x, w, freq, rope=d["rope"], ih=d["ih"],
                              eps=cfg["rms_norm_eps"], matmul=matmul)
    small = {k: v for k, v in w.items() if k in (
        "q_b_proj.weight", "kv_b_proj.weight", "o_proj.weight",
        "indexer.wq_b.weight")}
    seq = x.shape[0]
    bq = min(_QUERY_BLOCK, seq)
    upto = {}       # the keys up to an end, cut once
    blocks = []
    for first in range(start // bq * bq, min(stop, seq), bq):
        # a block that overhangs the end is the shorter: `dynamic_slice`
        # would move it back over rows already done
        rows = min(bq, seq - first)
        end = min(seq, -(-(first + rows) // _KEY_MULTIPLE) * _KEY_MULTIPLE)
        if end not in upto:
            upto[end] = (c[:end], kr[:end], ki[:end])
        out, margin, share = _attend(
            np.int32(first), _rows(cq, np.int32(first), rows=rows),
            _rows(wi, np.int32(first), rows=rows), *upto[end], small, freq,
            heads=d["heads"], nope=d["nope"], rope=d["rope"], vd=d["vd"],
            ih=d["ih"], topk=d["topk"], ibits=d["ibits"],
            scale=softmax_scale(cfg), matmul=matmul)
        x = _add_rows(x, np.int32(first), out)
        blocks.append((first, margin, share))
    margins = np.full((seq,), np.inf, np.float32)
    kept = np.ones((seq,), np.float32)
    for first, margin, share in blocks:
        margins[first:first + len(margin)] = np.asarray(margin)
        kept[first:first + len(share)] = np.asarray(share)
    return x, margins, kept


@functools.partial(jax.jit, static_argnames=("rows", "eps", "matmul"),
                   donate_argnums=(0,))
def _dense_mlp_rows(x, first, w, *, rows, eps, matmul):
    """x[first : first + rows] += MLP(N(x[first : first + rows]))."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    block = jax.lax.dynamic_slice_in_dim(x, first, rows)
    y = _norm(block, w["post_attention_layernorm.weight"], eps)
    return jax.lax.dynamic_update_slice_in_dim(
        x, block + _swiglu(y, w["mlp.gate_proj.weight"],
                           w["mlp.up_proj.weight"],
                           w["mlp.down_proj.weight"], matmul), first, 0)


def _dense_mlp(x, w, *, eps, matmul):
    """x + MLP(N(x)), a block of rows at a time: the two projections'
    outputs are 2.7 GB each over 36k tokens."""
    # whole blocks: a block that overhung the end would be moved back
    # over rows already done (`dynamic_slice` clamps its start)
    rows = math.gcd(x.shape[0], _MLP_ROWS)
    for first in range(0, x.shape[0], rows):
        x = _dense_mlp_rows(x, np.int32(first), w, rows=rows, eps=eps,
                            matmul=matmul)
    return x


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "n_group", "topk_group", "scale", "offset", "held",
    "matmul"))
def _route_and_share(x, w, *, eps, top_k, n_group, topk_group, scale,
                     offset, held, matmul):
    """The normed input, the chosen experts (of the router's width) and
    their weights, the margin of the choices that reach the experts held
    here, and x + the shared expert.

    The margin: for each held expert, how far its score + bias lies from
    where it would change sides (chosen: above the first expert left out;
    not chosen: under the last one chosen), and for each group that holds
    one of them, how far the group's score lies from where the group
    would (staying: above the first group put out; put out: under the
    last that stays); the least of them. A choice among experts held
    elsewhere moves nothing here but the weights' common divisor, by the
    difference of two scores that all but tie."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    y = _norm(x, w["post_attention_layernorm.weight"], eps)
    sc = jax.nn.sigmoid(y @ w["mlp.gate.weight"])           # float32, always
    c = sc + w["mlp.gate.e_score_correction_bias"]
    here = slice(offset, offset + held)
    margin = jnp.full((x.shape[0],), jnp.inf, jnp.float32)
    if n_group > 1:
        size = c.shape[1] // n_group
        grouped = c.reshape(c.shape[0], n_group, size)
        best = jnp.sum(jax.lax.top_k(grouped, 2)[0], -1)    # (seq, groups)
        ranked, kept = jax.lax.top_k(best, min(topk_group + 1, n_group))
        stays = jnp.any(kept[:, :topk_group, None]
                        == jnp.arange(n_group)[None, None, :], 1)
        if topk_group < n_group:
            for g in sorted({e // size for e in range(offset,
                                                      offset + held)}):
                margin = jnp.minimum(margin, jnp.where(
                    stays[:, g], best[:, g] - ranked[:, topk_group],
                    ranked[:, topk_group - 1] - best[:, g]))
        c = jnp.where(stays[:, :, None], grouped, -jnp.inf).reshape(c.shape)
    ranked, order = jax.lax.top_k(c, top_k + 1)
    chosen = order[:, :top_k]
    last_in, first_out = ranked[:, top_k - 1:top_k], ranked[:, top_k:]
    mine = c[:, here]
    margin = jnp.minimum(margin, jnp.min(jnp.where(
        mine >= last_in, mine - first_out, last_in - mine), -1))
    picked = jnp.take_along_axis(sc, chosen, 1)
    g = scale * picked / jnp.sum(picked, -1, keepdims=True)
    shared = _swiglu(y, w["mlp.shared_experts.gate_proj.weight"],
                     w["mlp.shared_experts.up_proj.weight"],
                     w["mlp.shared_experts.down_proj.weight"], matmul)
    return y, chosen, g, margin, x + shared


@functools.partial(jax.jit, static_argnames=("matmul",), donate_argnums=(0,))
def _expert(out, y, tokens, g, e, gate, up, down, *, matmul):
    """out[tokens] += g * E_e(y[tokens]) for the held expert `e` of the
    stacked leaves; padded entries carry g = 0."""
    def one(w):
        return jax.lax.dynamic_index_in_dim(w, e, keepdims=False).astype(
            jnp.float32)

    return out.at[tokens].add(
        g[:, None] * _swiglu(y[tokens], one(gate), one(up), one(down),
                             matmul))


def _experts(out, y, chosen, g, layer, offset, held, matmul):
    """A plain loop over the held experts that got tokens, on concrete
    indices; a pair of an expert held elsewhere adds nothing."""
    chosen, g = np.asarray(chosen), np.asarray(g)
    for e in np.unique(chosen):
        if not offset <= e < offset + held:
            continue
        tokens, slot = np.nonzero(chosen == e)
        n = max(_MIN_EXPERT_ROWS, 1 << int(len(tokens) - 1).bit_length())
        idx = np.zeros((n,), np.int32)
        weight = np.zeros((n,), np.float32)
        idx[:len(tokens)] = tokens
        weight[:len(tokens)] = g[tokens, slot]
        out = _expert(out, y, idx, weight, np.int32(e - offset),
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
    padding cannot reach an earlier position). With `with_margin` also,
    at each of `rows`, the least over the layers of two margins of the
    reference's own discrete choices: `routing` (of the experts and
    groups that reach the experts held here: `_route_and_share`) and
    `selection` (the index score of the last position attended less the
    first left out). Where one is all but nought, another precision may
    choose otherwise. And `selection_kept`, the least over the layers of
    the share of the exact choice that a stand-in with rounded index
    operands keeps (1 for the reference itself)."""
    d = _dims(cfg)
    ids = np.asarray(ids, np.int32)
    rows = np.asarray(rows, np.int32)
    multiple = _SEQ_MULTIPLE if len(ids) > _SEQ_MULTIPLE else 128
    seq = -(-len(ids) // multiple) * multiple
    ids = jnp.asarray(np.pad(ids, (0, seq - len(ids))))
    # the queries that anything asked for depends on: up to the last
    # asked position in every layer, from the first in the last layer
    stop = int(rows.max(initial=-1)) + 1
    layers = cfg["num_hidden_layers"]
    eps = cfg["rms_norm_eps"]
    freq = jnp.asarray(rope_frequencies(d["rope"], float(cfg["rope_theta"]),
                                        cfg.get("rope_scaling")))
    margins = {k: np.full((seq,), np.inf, np.float32)
               for k in ("routing", "selection", "selection_kept")}
    with jax.default_matmul_precision("highest"):
        x = params["model.embed_tokens.weight"][ids].astype(jnp.float32)
        for i in range(layers):
            p = f"model.layers.{i}."
            layer = {k[len(p):]: v for k, v in params.items()
                     if k.startswith(p)}
            attn = {k.replace("self_attn.", ""): v for k, v in layer.items()
                    if not k.startswith(("mlp.", "post_"))}
            x, m, kept = _attention(
                x, attn, cfg, d, freq, matmul,
                int(rows.min(initial=stop)) if i == layers - 1 else 0, stop)
            margins["selection"] = np.minimum(margins["selection"], m)
            margins["selection_kept"] = np.minimum(
                margins["selection_kept"], kept)
            if i < d["dense"]:
                x = _dense_mlp(
                    x, {k: v for k, v in layer.items()
                        if k.startswith(("mlp.", "post_"))},
                    eps=eps, matmul=matmul)
                continue
            small = {k: v for k, v in layer.items()
                     if k.startswith(("mlp.gate.", "mlp.shared", "post_"))}
            y, chosen, g, m, x = _route_and_share(
                x, small, eps=eps, top_k=d["top_k"], n_group=d["n_group"],
                topk_group=d["topk_group"],
                scale=float(cfg["routed_scaling_factor"]),
                offset=d["offset"], held=d["held"], matmul=matmul)
            margins["routing"] = np.minimum(margins["routing"],
                                            np.asarray(m))
            x = _experts(x, y, chosen, g, layer, d["offset"], d["held"],
                         matmul)
        asked = np.pad(rows, (0, -len(rows) % _ROWS_MULTIPLE))
        out = _head(x, params["model.norm.weight"], params["lm_head.weight"],
                    asked, eps=eps, matmul=matmul)[:len(rows)]
    if with_margin:
        return out, {k: v[rows] for k, v in margins.items()}
    return out
