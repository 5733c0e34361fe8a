"""ERNIE's MLM head over the labelled rows alone: the loss and every
parameter's gradient are those of the head over every row, for any count
of labels (under the capacity, at it, past it, none) and either loss
(fused chunked CE, plain CE)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import tape
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit.functional import bind_state, call_functional, \
    extract_state
from paddle_tpu.models import ErnieConfig, ErnieForPretraining
from paddle_tpu.models.ernie import mlm_capacity

ROWS, SEQ, HIDDEN, VOCAB = 4, 256, 64, 256
N = ROWS * SEQ
K = mlm_capacity(N)


@pytest.fixture(scope="module")
def models():
    """A one-layer float32 ERNIE for each loss, the same weights."""
    out = {}
    for fused in (True, False):
        paddle.seed(11)
        model = ErnieForPretraining(ErnieConfig(
            vocab_size=VOCAB, hidden_size=HIDDEN, num_hidden_layers=1,
            num_attention_heads=2, intermediate_size=128,
            max_position_embeddings=SEQ, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0, fused_mlm_loss=fused))
        model.train()
        out[fused] = model
    return out


def _batch(positions, seed=0):
    r = np.random.default_rng(seed)
    ids = r.integers(0, VOCAB, (ROWS, SEQ))
    labels = np.full(N, -100)
    labels[positions] = r.integers(0, VOCAB, len(positions))
    return jnp.asarray(ids), jnp.asarray(labels.reshape(ROWS, SEQ))


def _gathered_and_all_rows(model, ids, labels):
    """(loss, gradients) of the model's own forward, and of the head over
    every row on the same encoder output."""
    params, buffers = extract_state(model)

    def forward(p):
        (loss, _), _ = call_functional(
            model, p, buffers, (ids, None, None, None, labels),
            training=True)
        return loss

    def all_rows(p):
        with bind_state(model, p, buffers), tape.no_grad():
            seq, _ = model.ernie(Tensor(ids))
            return model._mlm_loss(seq.reshape([N, HIDDEN]),
                                   Tensor(labels.reshape(N)))._data

    return (jax.jit(jax.value_and_grad(forward))(params),
            jax.jit(jax.value_and_grad(all_rows))(params))


def _assert_same(got, want):
    (loss, grads), (want_loss, want_grads) = got, want
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5,
                                        abs=1e-7)
    assert grads.keys() == want_grads.keys()
    for name, g in want_grads.items():
        gap = float(jnp.linalg.norm(grads[name] - g))
        assert gap <= 1e-5 * float(jnp.linalg.norm(g)), name


def test_capacity_is_bert_s_share_in_whole_tiles():
    assert mlm_capacity(32 * 512) == 2560    # the train cell: 2,464 labels
    assert mlm_capacity(8 * 512) == 640
    assert mlm_capacity(N) == 256 < N
    assert mlm_capacity(64) >= 64           # small batches keep every row


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "plain"])
@pytest.mark.parametrize("count", [100, K, K + 1, 0],
                         ids=["under", "at", "over", "none"])
def test_gathered_head_matches_every_row(models, fused, count):
    positions = np.random.default_rng(count).choice(N, count, replace=False)
    _assert_same(*_gathered_and_all_rows(models[fused],
                                         *_batch(positions)))


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "plain"])
def test_first_and_last_positions_are_gathered(models, fused):
    positions = np.concatenate([[0, N - 1], np.arange(300, 340)])
    _assert_same(*_gathered_and_all_rows(models[fused],
                                         *_batch(positions, seed=1)))


def test_eager_backward_matches_the_functional_gradient(models):
    """Outside a functional trace the head is one op on the tape: its
    parameters' gradients are the vjp of the gathered head."""
    model = models[True]
    ids, labels = _batch(np.arange(0, N, 7)[:120], seed=2)
    (want_loss, want), _ = _gathered_and_all_rows(model, ids, labels)
    model.clear_gradients()
    loss, _ = model(Tensor(ids), masked_lm_labels=Tensor(labels))
    loss.backward()
    assert float(loss.numpy()) == pytest.approx(float(want_loss), rel=1e-5)
    for name, p in model.named_parameters():
        got = (jnp.zeros_like(p._data) if p.grad is None
               else p.grad._data)
        gap = float(jnp.linalg.norm(got - want[name]))
        assert gap <= 1e-5 * float(jnp.linalg.norm(want[name])) + 1e-12, \
            name
