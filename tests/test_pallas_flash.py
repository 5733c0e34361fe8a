"""Pallas flash attention — interpret-mode CI (verdict item #4).

The round-1 kernel never ran in CI (CPU always took the jnp fallback) and had
no backward. These tests run the REAL kernel via pallas_call(interpret=True)
on CPU, forward and backward, against the jnp reference, across the widened
shape space: head_dim 64 (flagship), seq not a multiple of the block, causal,
additive masks (broadcast and per-head).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas_kernels import (_BLOCK_K, _BLOCK_Q,
                                           _causal_block_live,
                                           _flash_attention_data,
                                           _flash_layout, _fwd_call,
                                           _fwd_live_call, _keep_mask,
                                           _live_grid, _qkv_layout,
                                           _round_up, flash_block_steps,
                                           flash_prefill,
                                           flash_prefill_steps)


def _ref_attention(q, k, v, mask=None, is_causal=False):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if mask is not None:
        s = s + mask
    if is_causal:
        sq, sk = s.shape[-2], s.shape[-1]
        causal = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _rand_qkv(rng, b, sq, sk, h, d):
    q = jnp.asarray(rng.randn(b, sq, h, d).astype("float32"))
    k = jnp.asarray(rng.randn(b, sk, h, d).astype("float32"))
    v = jnp.asarray(rng.randn(b, sk, h, d).astype("float32"))
    return q, k, v


CASES = [
    # (sq, sk, h, d, causal) — d=64 is the ERNIE/GPT-base flagship shape
    (128, 128, 2, 64, False),
    (128, 128, 2, 64, True),
    (200, 200, 1, 64, True),     # seq not a multiple of 128
    (256, 384, 2, 32, False),    # cross-attention, small head
    (96, 96, 1, 80, False),      # d not a power of two
    (128, 128, 4, 128, True),    # d=128: PACKED (b, S, h*d) layout
    (200, 200, 2, 128, False),   # packed + ragged seq padding
    # several 512-blocks a side: the causal grid skips whole blocks
    (1536, 1536, 1, 64, True),
    (640, 1536, 2, 128, True),   # packed, sk > sq: k columns past every row
    (1536, 640, 1, 192, True),   # d padded to 256, sq > sk, ragged blocks
]


# ------------------------------------------------- the causal block skip
GRIDS = [
    # (sq, sk, block_q, block_k): real lengths, ragged last blocks
    (16384, 16384, 512, 512),
    (2048, 2048, 512, 512),
    (1024, 1024, 512, 512),
    (1536, 640, 512, 512),
    (640, 1536, 512, 512),
    (1000, 1900, 256, 512),
    (1900, 1000, 512, 128),
    (700, 700, 128, 384),
    (300, 300, 384, 384),
]


def _padded(sq, sk, block_q, block_k):
    return _round_up(sq, block_q), _round_up(sk, block_k)


@pytest.mark.parametrize("sq,sk,block_q,block_k", GRIDS)
def test_block_predicate_matches_tril(sq, sk, block_q, block_k):
    """No block with an allowed score is skipped, no wholly masked block
    is computed: the predicate against numpy's own lower triangle."""
    sq_p, sk_p = _padded(sq, sk, block_q, block_k)
    n_q, n_k = sq_p // block_q, sk_p // block_k
    allowed = np.tril(np.ones((sq_p, sk_p), bool))          # rows >= cols
    by_block = allowed.reshape(n_q, block_q, n_k, block_k).any(axis=(1, 3))
    live = np.array([[bool(_causal_block_live(qi, ki, block_q, block_k))
                      for ki in range(n_k)] for qi in range(n_q)])
    np.testing.assert_array_equal(live, by_block)
    assert flash_block_steps(sq_p, sk_p, block_q, block_k, True) == (
        int(by_block.sum()), n_q * n_k)
    assert flash_block_steps(sq_p, sk_p, block_q, block_k, False) == (
        n_q * n_k, n_q * n_k)
    # a ring step's offsets shift the same rule
    q_off, k_off = 3 * sq, 2 * sk
    shifted = (np.arange(sq_p)[:, None] + q_off
               >= np.arange(sk_p)[None, :] + k_off)
    np.testing.assert_array_equal(
        np.array([[bool(_causal_block_live(qi, ki, block_q, block_k,
                                           (q_off, k_off)))
                   for ki in range(n_k)] for qi in range(n_q)]),
        shifted.reshape(n_q, block_q, n_k, block_k).any(axis=(1, 3)))


def test_block_steps_of_the_serving_buckets():
    assert flash_block_steps(16384, 16384, _BLOCK_Q, _BLOCK_K, True) == (
        528, 1024)
    assert flash_block_steps(8192, 8192, _BLOCK_Q, _BLOCK_K, True) == (
        136, 256)
    assert flash_block_steps(2048, 2048, _BLOCK_Q, _BLOCK_K, True) == (10, 16)
    assert flash_block_steps(1024, 1024, _BLOCK_Q, _BLOCK_K, True) == (3, 4)
    assert flash_block_steps(512, 512, _BLOCK_Q, _BLOCK_K, True) == (1, 1)


@pytest.mark.parametrize("kv_major", [False, True])
@pytest.mark.parametrize("sq,sk,block_q,block_k", GRIDS[3:])
def test_skipped_step_names_a_block_in_vmem(sq, sk, block_q, block_k,
                                            kv_major):
    """The clamped index maps: a live step names its own blocks; a
    skipped one names the block of the nearest live step of its row of
    the grid (the one the pipeline holds), or any block in range where
    the row has no live step at all."""
    sq_p, sk_p = _padded(sq, sk, block_q, block_k)
    n_q, n_k = sq_p // block_q, sk_p // block_k
    coords = _qkv_layout(
        jax.ShapeDtypeStruct((1, 1, sq_p, 64), jnp.float32),
        jax.ShapeDtypeStruct((1, 1, sk_p, 64), jnp.float32),
        heads=None, block_q=block_q, block_k=block_k, kv_major=kv_major,
        vma=None, clamp_causal=True)[-1]
    for qi in range(n_q):
        for ki in range(n_k):
            got = tuple(int(c) for c in (coords(ki, qi) if kv_major
                                         else coords(qi, ki)))
            assert 0 <= got[0] < n_q and 0 <= got[1] < n_k
            if _causal_block_live(qi, ki, block_q, block_k):
                assert got == (qi, ki)
                continue
            if kv_major:   # q runs innermost, live steps come last
                row = [q for q in range(n_q)
                       if _causal_block_live(q, ki, block_q, block_k)]
                assert got == ((row[0] if row else n_q - 1), ki)
            else:          # k runs innermost, live steps come first
                row = [k for k in range(n_k)
                       if _causal_block_live(qi, k, block_q, block_k)]
                assert got == (qi, row[-1])


# ------------------------------------------------ the prompt-length grid
def _latent_cell_prompts():
    """The latent serving cell's 32 prompt lengths (the mid-quantiles of a
    log-uniform law over 2,048-16,384) and their power-of-two buckets."""
    lens = [int(round(np.exp(np.log(2048) + (i + 0.5) / 32 * np.log(8))))
            for i in range(32)]
    return [(n, 1 << (n - 1).bit_length()) for n in lens]


def test_prompt_steps_of_the_latent_cell():
    """The reckoning the prompt-length grid rests on: an 8,400-token
    prompt in the 16,384 bucket computes 17 q blocks' causal steps, and
    the cell's 32 prompts 4,330 of their buckets' 7,564."""
    assert flash_block_steps(16384, 16384, 512, 512, True, live=8400) == (
        153, 1024)
    assert flash_block_steps(16384, 16384, 512, 512, True, live=16384) == (
        528, 1024)
    assert flash_block_steps(2048, 2048, 512, 512, True, live=1) == (1, 16)
    prompts = _latent_cell_prompts()
    assert sum(n for n, _ in prompts) / 32 == pytest.approx(6893, abs=1)
    assert {b for _, b in prompts} == {4096, 8192, 16384}
    computed = sum(flash_prefill_steps(b, n)[0] for n, b in prompts)
    whole = sum(flash_prefill_steps(b, n)[1] for n, b in prompts)
    assert (computed, whole) == (4330, 7564)


@pytest.mark.parametrize("n_q", [1, 4, 8, 34])
def test_a_step_past_the_prompt_names_a_block_in_vmem(n_q):
    """The flattened grid: the prompt's causal blocks q-major, each once,
    then one step a q block past the prompt, which writes that block and
    reads the blocks of the last computed step (the pipeline holds them,
    so it brings nothing)."""
    for live in sorted({1, 511, 512, 513, n_q * 256 + 3, n_q * 512 - 1,
                        n_q * 512} & set(range(1, n_q * 512 + 1))):
        steps, q_out, q_in, k_in = (np.asarray(a) for a in _live_grid(
            jnp.int32(live), n_q, n_q, _BLOCK_Q, _BLOCK_K))
        rows = -(-live // _BLOCK_Q)
        want = [(qi, ki) for qi in range(rows) for ki in range(qi + 1)]
        computed = len(want)
        assert int(steps) == computed + n_q - rows <= q_out.shape[0]
        got = list(zip(q_out[:computed].tolist(), k_in[:computed].tolist()))
        assert got == want
        assert (q_in[:computed] == q_out[:computed]).all()
        assert q_out[computed:int(steps)].tolist() == list(range(rows, n_q))
        assert (q_in[computed:int(steps)] == q_in[computed - 1]).all()
        assert (k_in[computed:int(steps)] == k_in[computed - 1]).all()


@pytest.mark.parametrize("nan_padding", [False, True])
@pytest.mark.parametrize("blocks,h,d", [(1, 2, 192), (4, 1, 64),
                                        (8, 2, 128)])
def test_prefill_over_the_prompt_s_blocks(blocks, h, d, nan_padding):
    """`flash_prefill` over buckets of 1, 4 and 8 blocks (heads padded
    to 256, transposed at 64, packed at 128): the prompt's rows are the
    float32 causal reference's over the prompt alone and the
    whole-bucket kernel's bit for bit, the rows past it are zero and
    every row's logsumexp is finite, also where the padding is NaN."""
    s = blocks * _BLOCK_Q
    rng = np.random.RandomState(44)
    q, k, v = _rand_qkv(rng, 1, s, s, h, d)
    whole = np.asarray(_flash_attention_data(q, k, v, is_causal=True,
                                             interpret=True))

    @jax.jit
    def lse_of(q, k, v, live):
        qt, kt, vt, heads, block_q, block_k, *_ = _flash_layout(q, k, v)
        return _fwd_live_call(qt, kt, vt, live.reshape((1,)),
                              scale=d ** -0.5, block_q=block_q,
                              block_k=block_k, interpret=True,
                              heads=heads)[1]

    for live in sorted({1, 511, 512, 513, s // 2 + 37, s}
                       & set(range(1, s + 1))):
        qq, kk, vv = q, k, v
        if nan_padding:
            qq, kk, vv = (x.at[:, live:].set(jnp.nan) for x in (q, k, v))
        out = np.asarray(flash_prefill(qq, kk, vv, jnp.int32(live),
                                       interpret=True))
        ref = _ref_attention(q[:, :live], k[:, :live], v[:, :live],
                             is_causal=True)
        np.testing.assert_allclose(out[:, :live], np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_array_equal(out[:, :live], whole[:, :live])
        assert not out[:, live:].any()
        lse = np.asarray(lse_of(qq, kk, vv, jnp.int32(live)))
        assert np.isfinite(lse).all()
        assert not lse[..., live:].any()


def _dropout_reference(q, k, v, seed, dropout_p, mask=None):
    """Causal attention with the keep mask the interpret-mode kernels
    draw: one block of bits a (b, h, qi, ki), by `_keep_mask` itself."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_q, block_k = min(_BLOCK_Q, sq), min(_BLOCK_K, sk)
    keep = np.zeros((b, h, sq, sk), bool)
    for b_ in range(b):
        for h_ in range(h):
            for qi in range(sq // block_q):
                for ki in range(sk // block_k):
                    keep[b_, h_, qi * block_q:(qi + 1) * block_q,
                         ki * block_k:(ki + 1) * block_k] = np.asarray(
                        _keep_mask(None, seed, b_, h_, qi, ki,
                                   (block_q, block_k), dropout_p, True))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if mask is not None:
        s = s + mask
    s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.asarray(keep), p / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("sq,sk,h,d", [(1536, 1536, 1, 64),
                                       (1024, 1536, 2, 128)])
def test_causal_dropout_over_several_blocks(sq, sk, h, d):
    """Skipped blocks draw no bits and the computed ones keep theirs:
    forward and gradients against the same keep mask, block by block."""
    rng = np.random.RandomState(41)
    q, k, v = _rand_qkv(rng, 1, sq, sk, h, d)
    seed = jnp.asarray([2024], jnp.int32)

    def loss_pallas(q, k, v):
        out = _flash_attention_data(q, k, v, seed=seed, dropout_p=0.25,
                                    is_causal=True, interpret=True)
        return jnp.sum(out * jnp.cos(out)), out

    def loss_ref(q, k, v):
        out = _dropout_reference(q, k, v, seed, 0.25)
        return jnp.sum(out * jnp.cos(out)), out

    gp, out = jax.grad(loss_pallas, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    gr, ref = jax.grad(loss_ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    for a, b, name in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("sq,sk,h,d,mask_shape", [
    (1536, 1536, 1, 64, (1, 1, 1536, 1536)),
    (640, 1536, 2, 128, (1, 2, 640, 1536)),    # per head, packed
    (1536, 640, 1, 192, (1, 1, 1, 640)),       # broadcast over q
])
def test_causal_with_additive_mask_over_several_blocks(sq, sk, h, d,
                                                       mask_shape):
    """Causal + a trainable additive mask: the mask's blocks follow the
    clamped K blocks, and d(mask) is zero, not unwritten memory, on the
    blocks the causal mask rules out."""
    rng = np.random.RandomState(42)
    q, k, v = _rand_qkv(rng, 1, sq, sk, h, d)
    mask = jnp.asarray(rng.randn(*mask_shape).astype("float32"))

    def loss_pallas(q, k, v, m):
        out = _flash_attention_data(q, k, v, m, has_mask=True,
                                    mask_needs_grad=True, is_causal=True,
                                    interpret=True)
        return jnp.sum(out * jnp.cos(out)), out

    def loss_ref(q, k, v, m):
        out = _ref_attention(q, k, v, mask=m, is_causal=True)
        return jnp.sum(out * jnp.cos(out)), out

    gp, out = jax.grad(loss_pallas, argnums=(0, 1, 2, 3),
                       has_aux=True)(q, k, v, mask)
    gr, ref = jax.grad(loss_ref, argnums=(0, 1, 2, 3),
                       has_aux=True)(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    for a, b, name in zip(gp, gr, ("q", "k", "v", "mask")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("sq,sk,d", [(1536, 1536, 64), (1536, 640, 128),
                                     (640, 1536, 256)])
def test_causal_lse_finite_on_every_row(sq, sk, d):
    """Every row of a causal call sees column 0, so no row's logsumexp
    is the clamp's 0.0 or -inf, skipped blocks or not."""
    rng = np.random.RandomState(43)
    block_q, block_k = min(_BLOCK_Q, sq), min(_BLOCK_K, sk)
    sq_p, sk_p = _padded(sq, sk, block_q, block_k)
    q = jnp.asarray(rng.randn(1, 2, sq_p, d).astype("float32"))
    k = jnp.asarray(rng.randn(1, 2, sk_p, d).astype("float32"))
    v = jnp.asarray(rng.randn(1, 2, sk_p, d).astype("float32"))
    _, lse = _fwd_call(
        q, k, v, jnp.zeros((1, 1, 1, 1), jnp.float32),
        jnp.zeros((1,), jnp.int32), scale=d ** -0.5, sk=sk, is_causal=True,
        has_mask=False, mask_b_is_one=True, mask_h_is_one=True,
        mask_q_is_one=True, block_q=block_q, block_k=block_k,
        dropout_p=0.0, interpret=True, keep_neg_inf_lse=True)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k[:, :, :sk]) * d ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((sq_p, sk), bool)), s, -jnp.inf)
    ref = jax.scipy.special.logsumexp(s, axis=-1)
    got = np.asarray(lse[:, :, 0, :])
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("sq,sk,h,d,causal", CASES)
def test_forward_matches_reference(sq, sk, h, d, causal):
    rng = np.random.RandomState(0)
    q, k, v = _rand_qkv(rng, 2, sq, sk, h, d)
    out = _flash_attention_data(q, k, v, is_causal=causal, interpret=True)
    ref = _ref_attention(q, k, v, is_causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_forward_with_additive_mask():
    rng = np.random.RandomState(1)
    q, k, v = _rand_qkv(rng, 2, 128, 128, 2, 64)
    # block half the keys for the first batch element, broadcast over heads
    mask = np.zeros((2, 1, 128, 128), dtype="float32")
    mask[0, :, :, 64:] = -1e9
    mask = jnp.asarray(mask)
    out = _flash_attention_data(q, k, v, mask, has_mask=True,
                                interpret=True)
    ref = _ref_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_forward_per_head_mask():
    rng = np.random.RandomState(2)
    q, k, v = _rand_qkv(rng, 1, 128, 128, 2, 64)
    mask = jnp.asarray(
        rng.choice([0.0, -1e9], size=(1, 2, 128, 128),
                   p=[0.9, 0.1]).astype("float32"))
    out = _flash_attention_data(q, k, v, mask, has_mask=True,
                                interpret=True)
    ref = _ref_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("sq,sk,h,d,causal", [
    (128, 128, 2, 64, False),
    (128, 128, 1, 64, True),
    (200, 200, 1, 32, True),
    (128, 128, 2, 128, True),    # d=128: PACKED layout backward
    (1536, 1536, 1, 64, True),   # skipped blocks in dq and in dk/dv
    (640, 1536, 2, 128, True),
    (1536, 640, 1, 192, True),
])
def test_backward_matches_reference(sq, sk, h, d, causal):
    rng = np.random.RandomState(3)
    q, k, v = _rand_qkv(rng, 1, sq, sk, h, d)

    def loss_pallas(q, k, v):
        out = _flash_attention_data(q, k, v, is_causal=causal,
                                    interpret=True)
        return jnp.sum(out * jnp.cos(out))  # nontrivial cotangent

    def loss_ref(q, k, v):
        out = _ref_attention(q, k, v, is_causal=causal)
        return jnp.sum(out * jnp.cos(out))

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_backward_with_mask():
    rng = np.random.RandomState(4)
    q, k, v = _rand_qkv(rng, 1, 128, 128, 2, 64)
    mask = np.zeros((1, 1, 128, 128), dtype="float32")
    mask[..., 100:] = -1e9
    mask = jnp.asarray(mask)

    def loss_pallas(q, k, v):
        return jnp.sum(_flash_attention_data(
            q, k, v, mask, has_mask=True, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref_attention(q, k, v, mask=mask) ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4)


def test_tensor_level_wrapper_backward():
    """flash_attention through the framework tape (Tensor.backward)."""
    import paddle_tpu as paddle
    from paddle_tpu.ops.pallas_kernels import flash_attention

    rng = np.random.RandomState(5)
    q = paddle.to_tensor(rng.randn(1, 128, 2, 64).astype("float32"),
                         stop_gradient=False)
    k = paddle.to_tensor(rng.randn(1, 128, 2, 64).astype("float32"),
                         stop_gradient=False)
    v = paddle.to_tensor(rng.randn(1, 128, 2, 64).astype("float32"),
                         stop_gradient=False)
    out = flash_attention(q, k, v, is_causal=True, interpret=True)
    out.sum().backward()
    assert q.grad is not None and np.isfinite(
        np.asarray(q.grad.numpy())).all()
    assert k.grad is not None and v.grad is not None


def test_trainable_mask_gets_gradient():
    """A learned additive bias passed as attn_mask must receive d(mask)=ds,
    not silent zeros (round-2 review finding)."""
    rng = np.random.RandomState(6)
    q, k, v = _rand_qkv(rng, 2, 128, 128, 2, 64)
    mask = jnp.asarray(rng.randn(1, 1, 128, 128).astype("float32") * 0.1)

    def loss_pallas(m):
        return jnp.sum(_flash_attention_data(
            q, k, v, m, has_mask=True, mask_needs_grad=True,
            interpret=True) ** 2)

    def loss_ref(m):
        return jnp.sum(_ref_attention(q, k, v, mask=m) ** 2)

    gp = jax.grad(loss_pallas)(mask)
    gr = jax.grad(loss_ref)(mask)
    assert float(jnp.abs(gr).max()) > 1e-4  # reference grad is nonzero
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               rtol=5e-3, atol=1e-5)


def test_attention_dropout_applied():
    """dropout_p>0 in training must actually drop attention probs."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    rng = np.random.RandomState(7)
    q = paddle.to_tensor(rng.randn(1, 16, 2, 8).astype("float32"))
    out_nodrop = F.scaled_dot_product_attention(q, q, q, dropout_p=0.0)
    out_drop = F.scaled_dot_product_attention(q, q, q, dropout_p=0.5,
                                              training=True)
    # with p=0.5 over 16 keys, outputs must differ from the dense result
    assert not np.allclose(np.asarray(out_drop.numpy()),
                           np.asarray(out_nodrop.numpy()))
    # eval mode: no dropout regardless of p
    out_eval = F.scaled_dot_product_attention(q, q, q, dropout_p=0.5,
                                              training=False)
    np.testing.assert_allclose(np.asarray(out_eval.numpy()),
                               np.asarray(out_nodrop.numpy()), rtol=1e-6)


def test_padding_mask_broadcast_q_dim():
    """(b,1,1,sk) padding mask — must not materialize O(s^2); numerics match."""
    rng = np.random.RandomState(8)
    q, k, v = _rand_qkv(rng, 2, 128, 128, 2, 64)
    mask = np.zeros((2, 1, 1, 128), dtype="float32")
    mask[0, :, :, 100:] = -1e9  # pad out the first element's tail keys
    mask = jnp.asarray(mask)
    out = _flash_attention_data(q, k, v, mask, has_mask=True,
                                interpret=True)
    ref = _ref_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    def loss_pallas(m):
        return jnp.sum(_flash_attention_data(
            q, k, v, m, has_mask=True, mask_needs_grad=True,
            interpret=True) ** 2)

    def loss_ref(m):
        return jnp.sum(_ref_attention(q, k, v, mask=m) ** 2)

    gp = jax.grad(loss_pallas)(mask)
    gr = jax.grad(loss_ref)(mask)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                               rtol=5e-3, atol=1e-4)


# ------------------------------------------------------- in-kernel dropout
class TestKernelDropout:
    """dropout_p > 0 runs INSIDE the kernel (on-chip PRNG), fwd and bwd
    regenerating the same mask from the same (seed, b, h, qi, ki) tuple."""

    def test_deterministic_given_seed(self):
        rng = np.random.RandomState(11)
        q, k, v = _rand_qkv(rng, 1, 128, 128, 2, 64)
        seed = jnp.asarray([123], jnp.int32)
        a = _flash_attention_data(q, k, v, seed=seed, dropout_p=0.3,
                                  interpret=True)
        b = _flash_attention_data(q, k, v, seed=seed, dropout_p=0.3,
                                  interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        c = _flash_attention_data(q, k, v, seed=seed + 1, dropout_p=0.3,
                                  interpret=True)
        assert not np.allclose(np.asarray(a), np.asarray(c))

    def test_differs_from_dense_and_preserves_expectation(self):
        rng = np.random.RandomState(12)
        q, k, v = _rand_qkv(rng, 1, 128, 128, 1, 64)
        dense = _flash_attention_data(q, k, v, interpret=True)
        drops = [
            np.asarray(_flash_attention_data(
                q, k, v, seed=jnp.asarray([s], jnp.int32), dropout_p=0.5,
                interpret=True))
            for s in range(8)
        ]
        assert not np.allclose(drops[0], np.asarray(dense))
        # upscale_in_train: the mean over seeds approaches the dense output
        mean = np.mean(drops, axis=0)
        corr = np.corrcoef(mean.ravel(), np.asarray(dense).ravel())[0, 1]
        assert corr > 0.9, corr

    def test_grads_consistent_with_forward(self):
        """Finite differences validate that bwd regenerates the SAME keep
        mask as fwd — a seed mismatch would fail wildly."""
        rng = np.random.RandomState(13)
        q, k, v = _rand_qkv(rng, 1, 128, 128, 1, 32)
        seed = jnp.asarray([7], jnp.int32)
        w = jnp.asarray(rng.randn(1, 128, 1, 32).astype("float32"))

        def f(qq):
            out = _flash_attention_data(qq, k, v, seed=seed, dropout_p=0.4,
                                        interpret=True)
            return jnp.sum(out * w)

        g = jax.grad(f)(q)
        eps = 1e-2
        idxs = [(0, 3, 0, 5), (0, 60, 0, 12), (0, 120, 0, 31)]
        for idx in idxs:
            dq = jnp.zeros_like(q).at[idx].set(eps)
            fd = (f(q + dq) - f(q - dq)) / (2 * eps)
            np.testing.assert_allclose(np.asarray(fd), np.asarray(g[idx]),
                                       rtol=0.08, atol=5e-3)

    def test_training_dispatch_reaches_flash_policy(self, monkeypatch):
        """The functional dispatch must hand dropout>0 training calls to the
        flash path whenever the kernel is available — regression guard for
        the round-2 policy that silently fell back to materialized softmax
        for every training config with attention dropout."""
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        from paddle_tpu.ops import pallas_kernels

        q = jnp.ones((1, 128, 2, 64), jnp.float32)
        # CPU: unavailable regardless of dropout — the reference runs
        assert not pallas_kernels.flash_attention_available(q, q, q)

        calls = {}

        def fake_available(*a, **k):
            return True

        def fake_flash(q, k, v, attn_mask=None, is_causal=False,
                       dropout_p=0.0, rng_key=None, interpret=False):
            calls["dropout_p"] = dropout_p
            calls["rng_key"] = rng_key
            return q

        monkeypatch.setattr(pallas_kernels, "flash_attention_available",
                            fake_available)
        monkeypatch.setattr(pallas_kernels, "flash_attention", fake_flash)
        t = paddle.to_tensor(np.zeros((1, 16, 2, 8), np.float32))
        F.scaled_dot_product_attention(t, t, t, dropout_p=0.25,
                                       training=True)
        assert calls["dropout_p"] == 0.25      # training reaches flash
        assert calls["rng_key"] is not None    # with a derived seed
        F.scaled_dot_product_attention(t, t, t, dropout_p=0.25,
                                       training=False)
        assert calls["dropout_p"] == 0.0       # eval: no dropout


# --------------------------------------------------------- real-TPU gates
_on_real_tpu = jax.devices()[0].platform not in ("cpu",)


@pytest.mark.skipif(not _on_real_tpu, reason="needs a real TPU chip")
class TestRealTPU:
    """Non-interpret compilation on the actual chip (VERDICT r2 item 1b:
    every round-2 test ran interpret=True and the kernel failed Mosaic
    lowering for all multi-head inputs)."""

    def test_fwd_bwd_compile_and_match_reference(self):
        rng = np.random.RandomState(21)
        q, k, v = _rand_qkv(rng, 2, 512, 512, 8, 64)
        out = _flash_attention_data(q, k, v, is_causal=True)
        ref = _ref_attention(q, k, v, is_causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

        def loss(q, k, v):
            return jnp.sum(
                _flash_attention_data(q, k, v, is_causal=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(_ref_attention(q, k, v, is_causal=True) ** 2)

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-2, atol=2e-2)

    def test_dropout_compiles_on_tpu(self):
        rng = np.random.RandomState(22)
        q, k, v = _rand_qkv(rng, 1, 512, 512, 8, 64)
        seed = jnp.asarray([5], jnp.int32)
        out = _flash_attention_data(q, k, v, seed=seed, dropout_p=0.1,
                                    is_causal=True)
        assert np.all(np.isfinite(np.asarray(out)))

    def test_eval_mha_on_tpu_does_not_crash(self):
        """Round-2 regression: eval-mode MultiHeadAttention crashed with the
        Mosaic lowering ValueError on every real-TPU forward."""
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn

        mha = nn.MultiHeadAttention(embed_dim=128, num_heads=8)
        mha.eval()
        x = paddle.randn([2, 256, 128])
        out = mha(x)
        assert np.all(np.isfinite(out.numpy()))


def test_bf16_inputs_match_reference_loosely():
    """bf16 q/k/v ride the MXU-native matmul path (f32 accumulation)."""
    rng = np.random.RandomState(31)
    q, k, v = _rand_qkv(rng, 1, 128, 128, 2, 64)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = _flash_attention_data(qb, kb, vb, is_causal=True, interpret=True)
    ref = _ref_attention(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)

    def loss(qq):
        return jnp.sum(_flash_attention_data(
            qq, kb, vb, is_causal=True, interpret=True).astype(jnp.float32))

    g = jax.grad(loss)(qb)
    assert g.dtype == jnp.bfloat16
    assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))


class TestPublicFlashAPI:
    """paddle.nn.functional.flash_attention parity surface (round 3)."""

    def test_matches_sdpa(self):
        import numpy as np
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        r = np.random.RandomState(0)
        q = paddle.to_tensor(
            r.standard_normal((2, 32, 4, 16)).astype(np.float32))
        out, softmax = F.flash_attention(q, q, q, causal=True,
                                         training=False)
        assert softmax is None
        ref = F.scaled_dot_product_attention(q, q, q, is_causal=True,
                                             training=False)
        np.testing.assert_allclose(out.numpy(), ref.numpy(), atol=1e-5)

    def test_return_softmax_rejected(self):
        import numpy as np
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        q = paddle.to_tensor(np.zeros((1, 8, 2, 8), np.float32))
        with pytest.raises(NotImplementedError):
            F.flash_attention(q, q, q, return_softmax=True)

    def test_unpadded_rejected_with_guidance(self):
        import paddle_tpu.nn.functional as F
        with pytest.raises(NotImplementedError, match="pad"):
            F.flash_attn_unpadded(None, None, None, None, None, 0, 0)


class TestPackedLayout:
    """d=128 heads ride the PACKED (b, S, h*d) layout (r5): every feature
    combination the d=64 transpose path is tested with must also hold
    packed — mask, trainable-mask gradient, in-kernel dropout, ragged
    backward (review finding r5)."""

    def test_backward_with_mask_packed(self):
        rng = np.random.RandomState(11)
        q, k, v = _rand_qkv(rng, 1, 128, 128, 2, 128)
        mask = np.zeros((1, 1, 128, 128), dtype="float32")
        mask[..., 100:] = -1e9
        mask = jnp.asarray(mask)

        def loss_pallas(q, k, v):
            return jnp.sum(_flash_attention_data(
                q, k, v, mask, has_mask=True, interpret=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(_ref_attention(q, k, v, mask=mask) ** 2)

        gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4)

    def test_trainable_mask_gradient_packed(self):
        rng = np.random.RandomState(12)
        q, k, v = _rand_qkv(rng, 2, 128, 128, 2, 128)
        mask = jnp.asarray(rng.randn(1, 1, 128, 128).astype("float32")
                           * 0.1)

        def loss_pallas(m):
            return jnp.sum(_flash_attention_data(
                q, k, v, m, has_mask=True, mask_needs_grad=True,
                interpret=True) ** 2)

        def loss_ref(m):
            return jnp.sum(_ref_attention(q, k, v, mask=m) ** 2)

        gp = jax.grad(loss_pallas)(mask)
        gr = jax.grad(loss_ref)(mask)
        assert float(jnp.abs(gr).max()) > 1e-4
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   rtol=5e-3, atol=1e-5)

    def test_ragged_backward_packed(self):
        # sq=200 pads to 256: padded rows must contribute zero grads
        rng = np.random.RandomState(13)
        q, k, v = _rand_qkv(rng, 1, 200, 200, 2, 128)

        def loss_pallas(q, k, v):
            out = _flash_attention_data(q, k, v, is_causal=True,
                                        interpret=True)
            return jnp.sum(out * jnp.cos(out))

        def loss_ref(q, k, v):
            out = _ref_attention(q, k, v, is_causal=True)
            return jnp.sum(out * jnp.cos(out))

        gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gp, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4,
                                       err_msg=f"d{name} mismatch")

    def test_dropout_fwd_bwd_consistent_packed(self):
        # same seed fwd/bwd: E[out] preserved and grads finite/consistent
        rng = np.random.RandomState(14)
        q, k, v = _rand_qkv(rng, 1, 128, 128, 2, 128)
        seed = jnp.asarray([77], jnp.int32)

        def loss(q):
            out = _flash_attention_data(q, k, v, seed=seed,
                                        dropout_p=0.3, interpret=True)
            return jnp.sum(out ** 2)

        g = jax.grad(loss)(q)
        assert np.all(np.isfinite(np.asarray(g)))
        out_drop = _flash_attention_data(q, k, v, seed=seed,
                                         dropout_p=0.3, interpret=True)
        out_dense = _flash_attention_data(q, k, v, interpret=True)
        assert not np.allclose(np.asarray(out_drop),
                               np.asarray(out_dense))
