"""Plain reference of a hybrid decoder of Mamba-2 and attention layers
as `granite-4.0-h-micro` publishes it (`model_type` `granitemoehybrid`,
https://huggingface.co/ibm-granite/granite-4.0-h-micro): one full
forward over a whole sequence in `jax.numpy` and float32 at matmul
precision `highest`. No cache, no slots, no kernels, no chunking, no
bf16 arithmetic. It imports nothing of the program.

With `h` the stream and `RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g`:
`h = E[ids] * embedding_multiplier`; layer i adds
`residual_multiplier * Mixer_i(RMS(h; g1_i))` and then
`residual_multiplier * MLP_i(RMS(h; g2_i))`; the logits are
`RMS(h; g_f) E^T / logits_scaling`. The MLP is `(silu(a) * b) W_out`
with `[a, b] = split(x W_in)`. Attention is grouped-query, causal,
without positions, its scores times `attention_multiplier`. The Mamba-2
mixer is `[z, xBC, dt] = split(x W_in)`; a causal depthwise conv of
`xBC` as `mamba_d_conv` shifted products, then silu; `[x, B, C] =
split(xBC)`; `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`; the
recurrence **one token at a time in a `lax.scan`**,
`S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t`,
`y_t[h] = S_t[h] C_t + D[h] x_t[h]` (the program computes it in chunks:
its chunking is what this checks); `y = RMS(y * silu(z); g_n)` over all
columns; `out = y W_out`.

Layers run one at a time, each leaf widened to float32 as it is used;
the two kinds of layer are one jitted function each, compiled once a
sequence width since every layer of a kind has the same shapes.
Attention runs a kv head at a time (`lax.map`), so that 3k positions of
32 heads' scores are never held at once.

Departures from the published code: none in the mathematics. The
published `time_step_limit` is (0, inf), which clamps nothing.

`matmul` is the control's hook, put in the place of every projection,
feed-forward and vocabulary matmul (`chipbench/lowprec.py`).
`state_dtype` is a second stand-in's: the recurrence's state rounded to
that type after every token, as a cache kept in it would hold it.
`carry_every` is a planted fault's: the state set to zero in front of
every position that is a multiple of it, which is what a chunked scan
computes that drops its carry between chunks, or a decode step that
reads another slot than the one it wrote.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _sizes(cfg: dict) -> dict:
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, groups = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    d_inner = heads * p
    return {"heads": heads, "p": p, "n": n, "d_inner": d_inner,
            "conv_dim": d_inner + 2 * groups * n,
            "hd": cfg["hidden_size"] // cfg["num_attention_heads"]}


def shapes(cfg: dict) -> dict:
    z = _sizes(cfg)
    h, f = cfg["hidden_size"], cfg["shared_intermediate_size"]
    out = {"model.embed_tokens.weight": ((cfg["vocab_size"], h), "weight"),
           "model.norm.weight": ((h,), "gain")}
    for i, kind in enumerate(cfg["layer_types"]):
        p = f"model.layers.{i}."
        out.update({
            p + "input_layernorm.weight": ((h,), "gain"),
            p + "post_attention_layernorm.weight": ((h,), "gain"),
            p + "shared_mlp.input_linear.weight": ((h, 2 * f), "weight"),
            p + "shared_mlp.output_linear.weight": ((f, h), "weight"),
        })
        if kind == "mamba":
            out.update({
                p + "mamba.in_proj.weight": (
                    (h, z["d_inner"] + z["conv_dim"] + z["heads"]),
                    "weight"),
                # these five replaced by `own_leaves`: Mamba-2's own draws
                p + "mamba.conv1d.weight": (
                    (z["conv_dim"], cfg["mamba_d_conv"]), "weight"),
                p + "mamba.conv1d.bias": ((z["conv_dim"],), "bias"),
                p + "mamba.A_log": ((z["heads"],), "weight"),
                p + "mamba.D": ((z["heads"],), "gain"),
                p + "mamba.dt_bias": ((z["heads"],), "bias"),
                p + "mamba.norm.weight": ((z["d_inner"],), "gain"),
                p + "mamba.out_proj.weight": ((z["d_inner"], h), "weight"),
            })
        else:
            q = cfg["num_attention_heads"] * z["hd"]
            kv = cfg["num_key_value_heads"] * z["hd"]
            out.update({
                p + "self_attn.q_proj.weight": ((h, q), "weight"),
                p + "self_attn.k_proj.weight": ((h, kv), "weight"),
                p + "self_attn.v_proj.weight": ((h, kv), "weight"),
                p + "self_attn.o_proj.weight": ((q, h), "weight"),
            })
    return out


# The embedding is drawn a quarter as wide as the other weights. With
# N(0, 0.02) the tied head over `embedding_multiplier` 12 gives the token
# a position was fed a logit of 1.2, where the best of the other 100,351
# reaches 0.5: every position's first choice is its own input, by a
# margin no precision moves, and a comparison of served tokens with the
# reference's logits reads 0 whatever computed them (PERF.md section 6,
# PR 33: 0 at 6,158 of 6,158 positions on the chip). At 0.005 the fed
# token stands 2.7 deviations out, under the best of the others (4.4),
# and the layers decide the token. A power of two: exact in bf16.
_EMBED_SCALE = 0.25


# The conv's taps and bias are drawn as the published Mamba-2 code leaves
# them: `nn.Conv1d`'s default, U(-1/sqrt(fan_in), 1/sqrt(fan_in)) with a
# fan-in of `mamba_d_conv` taps (depthwise), so U(-0.5, 0.5) at width 4.
# With N(0, 0.02) taps `B` and `C` come out of the silu at 0.02, `B . C`
# at 1e-3, and the state's term of `y` at 4e-4 of the skip's `D x`: a
# comparison of logits then reads the same whatever the recurrence did
# (PERF.md section 6, PR 33: a state in bf16, and on the CPU a decay ten
# times too fast, both read 0). At the published width the state's term
# is a tenth of the skip's over all heads and a quarter in the slowest.
def _conv_bound(cfg: dict) -> float:
    return 1.0 / math.sqrt(cfg["mamba_d_conv"])


def own_leaves(leaves: dict, cfg: dict, seed: int) -> dict:
    """`leaves` (as `weights.make` drew them from `shapes`) with what this
    configuration draws its own way: the embedding scaled by
    `_EMBED_SCALE`, and every Mamba layer's `A_log`, `dt_bias`, `D` and
    conv as Mamba-2 initialises them, from the seed: A ~ U(1, 16) and
    `A_log = log A`; dt ~ logU(1e-3, 1e-1) and `dt_bias = dt +
    log(-expm1(-dt))`, the softplus's inverse; `D = 1` (these three in
    float32, 192 numbers a layer); the conv's taps and bias uniform
    within `_conv_bound`, in the leaves' own type. With N(0, 0.02) in
    their place every head would forget within two tokens, `B` and `C`
    would be a fiftieth of what they are, and the state would carry
    nothing a check could see."""
    rng = np.random.default_rng([int(seed) % (2 ** 32), 0x55D])
    out = dict(leaves)
    embed = "model.embed_tokens.weight"
    out[embed] = leaves[embed] * jnp.asarray(_EMBED_SCALE,
                                             leaves[embed].dtype)
    heads = cfg["mamba_n_heads"]
    for i, kind in enumerate(cfg["layer_types"]):
        if kind != "mamba":
            continue
        p = f"model.layers.{i}.mamba."
        a = rng.uniform(1.0, 16.0, heads)
        dt = np.exp(rng.uniform(math.log(1e-3), math.log(1e-1), heads))
        out[p + "A_log"] = jnp.asarray(np.log(a), jnp.float32)
        out[p + "dt_bias"] = jnp.asarray(dt + np.log(-np.expm1(-dt)),
                                         jnp.float32)
        out[p + "D"] = jnp.ones((heads,), jnp.float32)
        bound = _conv_bound(cfg)
        for leaf in (p + "conv1d.weight", p + "conv1d.bias"):
            out[leaf] = jnp.asarray(
                rng.uniform(-bound, bound, leaves[leaf].shape),
                leaves[leaf].dtype)
    return out


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _mm(x, w, matmul):
    return x @ w if matmul is None else matmul(x, w)


def _mlp(x, w, eps, res, matmul):
    y = _rms(x, w["post_attention_layernorm.weight"], eps)
    both = _mm(y, w["shared_mlp.input_linear.weight"], matmul)
    f = both.shape[-1] // 2
    return x + res * _mm(jax.nn.silu(both[:, :f]) * both[:, f:],
                         w["shared_mlp.output_linear.weight"], matmul)


@functools.partial(jax.jit, static_argnames=(
    "heads", "p", "n", "eps", "res", "matmul", "state_dtype", "carry_every"))
def _mamba_block(x, w, *, heads, p, n, eps, res, matmul, state_dtype,
                 carry_every):
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    seq = x.shape[0]
    d_inner = heads * p
    conv_dim = d_inner + 2 * n
    proj = _mm(_rms(x, w["input_layernorm.weight"], eps),
               w["mamba.in_proj.weight"], matmul)
    z = proj[:, :d_inner]
    xbc = proj[:, d_inner:d_inner + conv_dim]
    dt = jax.nn.softplus(proj[:, d_inner + conv_dim:] + w["mamba.dt_bias"])
    taps = w["mamba.conv1d.weight"]                    # (C, W)
    width = taps.shape[1]
    padded = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
    conv = w["mamba.conv1d.bias"] + sum(
        taps[:, j] * padded[j:j + seq] for j in range(width))
    conv = jax.nn.silu(conv)
    xs = conv[:, :d_inner].reshape(seq, heads, p)
    b_t, c_t = conv[:, d_inner:d_inner + n], conv[:, d_inner + n:]
    a = -jnp.exp(w["mamba.A_log"])

    # the planted fault alone: positions in front of which the carry is lost
    lost = (jnp.zeros((seq,), bool) if carry_every is None
            else jnp.arange(seq) % carry_every == 0)

    def token(state, inp):
        x_t, b, c, dt_t, lost_t = inp
        if carry_every is not None:
            state = jnp.where(lost_t, 0.0, state)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b[None, None, :])
        if state_dtype is not None:
            # not `.astype(state_dtype).astype(float32)`: the TPU's
            # compiler may keep the excess precision of such a round
            # trip, and the stand-in then is the exact reference (it
            # read 0.0 on every seed on the chip: PERF.md section 6)
            kind = jnp.finfo(state_dtype)
            state = jax.lax.reduce_precision(state, kind.nexp, kind.nmant)
        return state, jnp.einsum("hpn,n->hp", state, c)

    state, y = jax.lax.scan(token, jnp.zeros((heads, p, n), jnp.float32),
                            (xs, b_t, c_t, dt, lost))
    y = y + w["mamba.D"][None, :, None] * xs
    y = _rms(y.reshape(seq, d_inner) * jax.nn.silu(z),
             w["mamba.norm.weight"], eps)
    x = x + res * _mm(y, w["mamba.out_proj.weight"], matmul)
    return _mlp(x, w, eps, res, matmul), state


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "scale", "eps", "res", "matmul"))
def _attention_block(x, w, *, heads, kv_heads, scale, eps, res, matmul):
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    seq, h = x.shape
    hd, rep = h // heads, heads // kv_heads
    y = _rms(x, w["input_layernorm.weight"], eps)
    q = _mm(y, w["self_attn.q_proj.weight"], matmul).reshape(
        seq, kv_heads, rep, hd)
    k = _mm(y, w["self_attn.k_proj.weight"], matmul).reshape(
        seq, kv_heads, hd)
    v = _mm(y, w["self_attn.v_proj.weight"], matmul).reshape(
        seq, kv_heads, hd)
    causal = jnp.tril(jnp.ones((seq, seq), bool))

    def group(qkv):                # the query heads of one kv head
        qg, kg, vg = qkv           # (seq, rep, hd), (seq, hd), (seq, hd)
        scores = jnp.einsum("qrd,kd->rqk", qg, kg) * scale
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("rqk,kd->qrd", jax.nn.softmax(scores, -1), vg)

    ctx = jax.lax.map(group, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
                              jnp.moveaxis(v, 1, 0)))   # (kvh, seq, rep, hd)
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(seq, h)
    x = x + res * _mm(ctx, w["self_attn.o_proj.weight"], matmul)
    return _mlp(x, w, eps, res, matmul)


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "matmul"))
def _head(x, gain, embed, rows, *, eps, scaling, matmul):
    y = _rms(x[rows], gain.astype(jnp.float32), eps)
    return _mm(y, embed.astype(jnp.float32).T, matmul) / scaling


def _layers(params: dict, ids, cfg: dict, matmul, state_dtype,
            carry_every=None):
    """The stream after the last layer, and the state each Mamba layer's
    recurrence ended in."""
    z = _sizes(cfg)
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    x = (params["model.embed_tokens.weight"][ids].astype(jnp.float32)
         * cfg["embedding_multiplier"])
    states = []
    for i, kind in enumerate(cfg["layer_types"]):
        p = f"model.layers.{i}."
        layer = {k[len(p):]: v for k, v in params.items()
                 if k.startswith(p)}
        if kind == "mamba":
            x, state = _mamba_block(
                x, layer, heads=z["heads"], p=z["p"], n=z["n"], eps=eps,
                res=res, matmul=matmul, state_dtype=state_dtype,
                carry_every=carry_every)
            states.append(state)
        else:
            x = _attention_block(
                x, layer, heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                scale=cfg["attention_multiplier"], eps=eps, res=res,
                matmul=matmul)
    return x, states


def logits(params: dict, ids, rows, cfg: dict, matmul=None,
           state_dtype=None, carry_every=None):
    """Float32 logits at positions `rows` of the sequence `ids` (1-D,
    padded on the right as the caller likes: the model is causal in both
    kinds of layer, so the padding cannot reach an earlier position)."""
    with jax.default_matmul_precision("highest"):
        x, _ = _layers(params, jnp.asarray(ids, jnp.int32), cfg, matmul,
                       state_dtype, carry_every)
        return _head(x, params["model.norm.weight"],
                     params["model.embed_tokens.weight"],
                     jnp.asarray(rows, jnp.int32), eps=cfg["rms_norm_eps"],
                     scaling=float(cfg["logits_scaling"]), matmul=matmul)


def final_states(params: dict, ids, cfg: dict, state_dtype=None) -> list:
    """The recurrence's state after the last position of `ids` (no
    padding), one (H, P, N) array a Mamba layer: what the program's slot
    must hold. The tests of the state's precision read it."""
    with jax.default_matmul_precision("highest"):
        return _layers(params, jnp.asarray(ids, jnp.int32), cfg, None,
                       state_dtype)[1]
