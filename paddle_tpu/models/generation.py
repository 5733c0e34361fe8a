"""TPU-native autoregressive generation with a static-shape KV cache.

Ref surface: PaddleNLP `model.generate` (greedy/sampling; ecosystem atop
the reference fork — mount empty, layout unverified). TPU-first design:

- the KV cache is a pair of fixed-size arrays per layer, updated in place
  with `lax.dynamic_update_slice` (XLA keeps the buffer donated/aliased
  across steps — no reallocation, no dynamic shapes);
- prefill is ONE jitted call over the whole padded prompt; decode is ONE
  jitted single-token step reused for every position (two compilations
  total, both MXU-shaped);
- sampling (greedy / temperature / top-k / top-p) runs inside the jitted
  step with threefry keys, so the logits never leave the device.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..jit.functional import call_functional, extract_state
from ..nn import functional as F

__all__ = ["generate", "attend_with_cache", "init_caches"]


def attend_with_cache(q, k, v, cache, start_pos, rep, bias=None):
    """Write this block's K/V into the cache at `start_pos`, then attend q
    over the full (masked) cache.

    q: Tensor (b, s, heads, hd); k/v: Tensor (b, s, kv_heads, hd);
    cache: (k_cache, v_cache) raw jnp arrays (b, max_len, kv_heads, hd),
    OR a serving.PagedLayerCache — then the write/attend runs on the paged
    pool (ragged per-row positions, `start_pos` may be a (b,) vector) and
    every attention module here serves the continuous-batching engine
    unmodified; bias: optional additive (1, heads, s, max_len) attention
    bias (T5's relative position bias), folded into the visibility mask.
    Returns (ctx Tensor (b, s, heads, hd), new_cache).
    """
    if hasattr(cache, "page_table"):
        from ..serving.attention import paged_attend

        return paged_attend(q, k, v, cache, start_pos, rep, bias=bias)
    kc, vc = cache
    kd = k._data.astype(kc.dtype)
    vd = v._data.astype(vc.dtype)
    start = jnp.asarray(start_pos, jnp.int32)
    kc = jax.lax.dynamic_update_slice(kc, kd, (0, start, 0, 0))
    vc = jax.lax.dynamic_update_slice(vc, vd, (0, start, 0, 0))
    max_len = kc.shape[1]
    s = q.shape[1]
    kf, vf = kc, vc
    if rep > 1:  # GQA: expand kv heads to match q heads
        kf = jnp.repeat(kf, rep, axis=2)
        vf = jnp.repeat(vf, rep, axis=2)
    # position j visible to query i iff j <= start_pos + i
    pos_q = start + jnp.arange(s, dtype=jnp.int32)
    allowed = jnp.arange(max_len, dtype=jnp.int32)[None, :] <= pos_q[:, None]
    mask = jnp.where(allowed, 0.0, -1e9).astype(jnp.float32)[None, None]
    if bias is not None:
        bias_d = bias._data if hasattr(bias, "_data") else bias
        mask = mask + bias_d.astype(jnp.float32)
    ctx = F.scaled_dot_product_attention(
        q, Tensor(kf), Tensor(vf), attn_mask=Tensor(mask), is_causal=False)
    return ctx, (kc, vc)


def init_caches(model, batch, max_len, dtype=jnp.float32):
    """Zeroed (k, v) cache pair per decoder layer, sized from the config."""
    cfg = _config_of(model)
    if getattr(cfg, "latent_cache_dim", None) is not None:
        raise NotImplementedError(
            f"{type(model).__name__} caches a latent row a token (and, "
            "where its attention is sparse, an index key), which "
            "the static (k, v) cache of models.generation cannot hold: "
            "serve it through paddle_tpu.serving.ServingEngine")
    if getattr(cfg, "state_cache_spec", None) is not None:
        raise NotImplementedError(
            f"{type(model).__name__} keeps a recurrent state a layer, "
            "which the static (k, v) cache of models.generation cannot "
            "hold: serve it through paddle_tpu.serving.ServingEngine")
    kv_heads = getattr(cfg, "num_key_value_heads", cfg.num_attention_heads)
    head_dim = cfg.hidden_size // cfg.num_attention_heads
    shape = (batch, max_len, kv_heads, head_dim)
    return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
            for _ in range(cfg.num_hidden_layers)]


def _config_of(model):
    for attr in ("llama", "gpt"):
        if hasattr(model, attr):
            return getattr(model, attr).config
    if hasattr(model, "config"):
        return model.config
    raise ValueError("model exposes no config for cache sizing")


def _sample(logits, key, temperature, top_k, top_p):
    """Sample the next token from (b, vocab) logits inside jit."""
    if temperature == 0.0:  # greedy
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    vocab = logits.shape[-1]
    if top_k and top_k > 0:
        kth = jax.lax.top_k(logits, min(top_k, vocab))[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest set with cumulative prob >= top_p (first element always in)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


def generate(model, input_ids, max_new_tokens=32, temperature=1.0,
             top_k=0, top_p=1.0, eos_token_id: Optional[int] = None,
             seed: Optional[int] = None, cache_dtype=jnp.float32,
             num_beams: int = 1, length_penalty: float = 0.0):
    """Autoregressive generation. input_ids: Tensor/array (b, prompt_len).
    Returns a Tensor (b, prompt_len + max_new_tokens) of token ids; rows
    that hit `eos_token_id` are padded with eos afterwards.

    num_beams > 1 selects beam search (greedy within beams; temperature/
    top_k/top_p are sampling knobs and must stay at their defaults)."""
    if num_beams > 1:
        # temperature 0.0 (the library's greedy spelling) and 1.0 are both
        # fine — beam search is greedy within beams either way
        if temperature not in (0.0, 1.0) or top_k or top_p != 1.0:
            raise ValueError(
                "beam search (num_beams>1) does not combine with "
                "temperature/top_k/top_p sampling")
        return _beam_generate(model, input_ids, max_new_tokens, num_beams,
                              eos_token_id, cache_dtype, length_penalty)
    was_training = model.training
    model.eval()
    try:
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        if ids.ndim == 1:
            ids = ids[None]
        b, prompt_len = ids.shape
        total = prompt_len + max_new_tokens
        params, buffers = extract_state(model)
        caches = init_caches(model, b, total, cache_dtype)
        if seed is None:
            # fresh entropy per call: unseeded sampling must differ between
            # calls (PaddleNLP generate semantics)
            seed = int(np.random.randint(0, 2 ** 31 - 1))
        key = jax.random.key(seed)

        # jitted steps are memoized on the model: jax's jit cache is keyed
        # by function identity, so fresh closures per call would recompile
        # every generate() invocation
        # key omissions are deliberate: `model` scopes the cache dict
        # itself (model.__dict__), `seed` enters as the traced key arg,
        # and num_beams>1 dispatched to _beam_generate above
        cache_key = (b, prompt_len, total, float(temperature), int(top_k),  # noqa: JIT-CACHE-KEY — omitted params scoped/traced, see above
                     float(top_p), jnp.dtype(cache_dtype).name,
                     eos_token_id)
        jit_cache = model.__dict__.setdefault("_generate_jit_cache", {})
        if cache_key not in jit_cache:
            def prefill(params, buffers, ids, caches):
                (logits, new_caches), _ = call_functional(
                    model, params, buffers, (Tensor(ids),),
                    kwargs={"caches": caches, "start_pos": 0},
                    training=False)
                return logits[:, -1], new_caches

            def decode(params, buffers, token, caches, pos, key, finished):
                (logits, new_caches), _ = call_functional(
                    model, params, buffers, (Tensor(token[:, None]),),
                    kwargs={"caches": caches, "start_pos": pos},
                    training=False)
                nxt = _sample(logits[:, 0], key, temperature, top_k, top_p)
                if eos_token_id is not None:
                    # already-finished rows keep emitting eos; the finished
                    # mask lives on device so steady-state decode never
                    # forces a per-token host round-trip (the host polls it
                    # only every _EOS_POLL steps)
                    nxt = jnp.where(finished, eos_token_id, nxt)
                    finished = finished | (nxt == eos_token_id)
                return nxt, new_caches, finished

            jit_cache[cache_key] = (jax.jit(prefill),
                                    jax.jit(decode, donate_argnums=(3,)))
        prefill_j, decode_j = jit_cache[cache_key]

        last_logits, caches = prefill_j(params, buffers, ids, caches)
        key, sub = jax.random.split(key)
        token = _sample(last_logits, sub, temperature, top_k, top_p)

        finished = jnp.zeros((b,), bool)
        if eos_token_id is not None:
            finished = token == eos_token_id
        out = [ids, token[:, None]]
        _EOS_POLL = 16  # host-side early-exit check cadence
        for step in range(1, max_new_tokens):
            key, sub = jax.random.split(key)
            token, caches, finished = decode_j(
                params, buffers, token, caches,
                jnp.int32(prompt_len + step - 1), sub, finished)
            out.append(token[:, None])
            if (eos_token_id is not None and step % _EOS_POLL == 0
                    and bool(np.asarray(finished).all())):
                # all rows hit eos; pad the rest with eos and stop early
                remaining = max_new_tokens - 1 - step
                if remaining:
                    out.append(jnp.full((b, remaining), eos_token_id,
                                        ids.dtype))
                break
        return Tensor(jnp.concatenate(
            [o.astype(ids.dtype) for o in out], axis=1))
    finally:
        if was_training:
            model.train()


# ------------------------------------------------------------- beam search

def _beam_generate(model, input_ids, max_new_tokens, num_beams,
                   eos_token_id, cache_dtype, length_penalty):
    """Beam search over the same static-shape KV cache: beams ride the
    batch axis (b*k rows), each decode step is ONE jitted call — sample,
    score, and beam-reorder (a cache gather over the batch axis) all
    happen on device; the host loop only counts steps.

    Scores are summed token log-probs; finished beams (eos) are frozen
    and keep emitting eos with no score change. Final ranking divides by
    length**length_penalty (0.0 = raw sum, paddle's default shape)."""
    was_training = model.training
    model.eval()
    try:
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        if ids.ndim == 1:
            ids = ids[None]
        b, prompt_len = ids.shape
        k = int(num_beams)
        total = prompt_len + max_new_tokens
        params, buffers = extract_state(model)
        caches = init_caches(model, b * k, total, cache_dtype)
        eos = -1 if eos_token_id is None else int(eos_token_id)

        # `model` scopes the cache dict itself; `length_penalty` is only
        # used in the eager post-loop ranking, never inside the traced fns
        cache_key = ("beam", b, k, prompt_len, total,  # noqa: JIT-CACHE-KEY — omitted params scoped/eager, see above
                     jnp.dtype(cache_dtype).name, eos)
        jit_cache = model.__dict__.setdefault("_generate_jit_cache", {})
        if cache_key not in jit_cache:
            def prefill(params, buffers, ids_rep, caches):
                (logits, new_caches), _ = call_functional(
                    model, params, buffers, (Tensor(ids_rep),),
                    kwargs={"caches": caches, "start_pos": 0},
                    training=False)
                logp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))
                # row-major beams: batch i occupies rows [i*k, (i+1)*k).
                # All k beams are identical after prefill, so beam 0 keeps
                # its top-k candidates and the rest start at -inf (else the
                # first step would pick k copies of the same argmax)
                lp = logp.reshape(b, k, -1)
                mask = jnp.where(jnp.arange(k)[None, :, None] == 0,
                                 0.0, -jnp.inf)
                tok, scores, beam_idx = _beam_select(lp + mask)
                return tok, scores, beam_idx, new_caches

            def decode(params, buffers, token, caches, pos, scores,
                       finished):
                (logits, new_caches), _ = call_functional(
                    model, params, buffers, (Tensor(token[:, None]),),
                    kwargs={"caches": caches, "start_pos": pos},
                    training=False)
                logp = jax.nn.log_softmax(
                    logits[:, 0].astype(jnp.float32)).reshape(b, k, -1)
                if eos >= 0:
                    # a finished beam contributes exactly one continuation:
                    # eos at zero cost (keeps its score; others -inf)
                    vocab = logp.shape[-1]
                    frozen = jnp.where(
                        jnp.arange(vocab)[None, None, :] == eos, 0.0,
                        -jnp.inf)
                    logp = jnp.where(finished.reshape(b, k)[..., None],
                                     frozen, logp)
                tok, new_scores, beam_idx = _beam_select(
                    logp + scores.reshape(b, k)[..., None])
                flat_src = (jnp.arange(b)[:, None] * k
                            + beam_idx).reshape(-1)
                new_caches = [(kc[flat_src], vc[flat_src])
                              for kc, vc in new_caches]
                new_finished = finished
                if eos >= 0:
                    new_finished = (finished.reshape(b, k)[
                        jnp.arange(b)[:, None], beam_idx].reshape(-1)
                        | (tok.reshape(-1) == eos))
                return (tok.reshape(-1), new_scores.reshape(-1),
                        flat_src, new_caches, new_finished)

            jit_cache[cache_key] = (jax.jit(prefill),
                                    jax.jit(decode, donate_argnums=(3,)))
        prefill_j, decode_j = jit_cache[cache_key]

        ids_rep = jnp.repeat(ids, k, axis=0)           # (b*k, prompt)
        tok, scores, beam_idx, caches = prefill_j(params, buffers, ids_rep,
                                                  caches)
        prev_tok = tok.reshape(-1)
        scores = scores.reshape(-1)
        finished = (prev_tok == eos) if eos >= 0 else \
            jnp.zeros((b * k,), bool)
        histories = [prev_tok[:, None]]                # per-step columns
        reorders = []                                  # per-step beam srcs

        _EOS_POLL = 16
        for step in range(1, max_new_tokens):
            prev_tok, scores, flat_src, caches, finished = decode_j(
                params, buffers, prev_tok, caches,
                jnp.int32(prompt_len + step - 1), scores, finished)
            reorders.append(flat_src)
            histories.append(prev_tok[:, None])
            if (eos >= 0 and step % _EOS_POLL == 0
                    and bool(np.asarray(finished).all())):
                break   # history length tracks the early exit

        # reconstruct each surviving beam's token history by walking the
        # reorder chain backwards (beams swap parents every step)
        cols = [histories[-1]]
        src = jnp.arange(b * k)
        for step in range(len(reorders) - 1, -1, -1):
            src = reorders[step][src]
            cols.append(histories[step][src])
        cols.reverse()
        gen = jnp.concatenate(cols, axis=1)            # (b*k, steps_run)
        if gen.shape[1] < max_new_tokens and eos >= 0:
            gen = jnp.concatenate(
                [gen, jnp.full((b * k, max_new_tokens - gen.shape[1]),
                               eos, gen.dtype)], axis=1)

        lengths = (jnp.argmax(gen == eos, axis=1) + 1
                   if eos >= 0 else jnp.full((b * k,), gen.shape[1]))
        lengths = jnp.where((gen == eos).any(axis=1) if eos >= 0
                            else jnp.zeros((b * k,), bool),
                            lengths, gen.shape[1])
        ranked = scores / jnp.maximum(
            lengths.astype(jnp.float32), 1.0) ** length_penalty
        best = jnp.argmax(ranked.reshape(b, k), axis=1)
        gen_best = gen[jnp.arange(b) * k + best]
        if eos >= 0:
            # pad everything after the first eos with eos
            hit = jnp.cumsum(gen_best == eos, axis=1) > 0
            after = jnp.concatenate(
                [jnp.zeros((b, 1), bool), hit[:, :-1]], axis=1)
            gen_best = jnp.where(after, eos, gen_best)
        return Tensor(jnp.concatenate(
            [ids, gen_best.astype(ids.dtype)], axis=1))
    finally:
        if was_training:
            model.train()


def _beam_select(scored):
    """(b, k, V) cumulative scores -> top-k over the flattened k*V
    continuations: returns tokens (b, k), scores (b, k), parent beam
    indices (b, k)."""
    b, k, v = scored.shape
    flat = scored.reshape(b, k * v)
    top_s, top_i = jax.lax.top_k(flat, k)
    return top_i % v, top_s, top_i // v
