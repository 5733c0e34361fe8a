"""L3 tests: amp, metric, vision (transforms/datasets/models), hapi Model.

Mirrors the reference's hapi + vision test strategy (SURVEY.md §4): behavioral
API tests plus an e2e fit that asserts the loss decreases.
"""
import os
import tempfile
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.metric import Accuracy, Auc, Precision, Recall
from paddle_tpu.vision import transforms
from paddle_tpu.vision.datasets import MNIST, Cifar10
from paddle_tpu.vision.models import (
    LeNet, MobileNetV2, MobileNetV3Small, mobilenet_v1, resnet18, vgg11,
)

warnings.filterwarnings("ignore", message=".*synthetic.*")


# ------------------------------------------------------------------- metrics
def test_accuracy_metric():
    m = Accuracy()
    pred = paddle.to_tensor(np.array(
        [[0.1, 0.9], [0.8, 0.2], [0.3, 0.7], [0.6, 0.4]], dtype="float32"))
    label = paddle.to_tensor(np.array([[1], [0], [1], [1]]))
    correct = m.compute(pred, label)
    m.update(correct)
    assert abs(m.accumulate() - 0.75) < 1e-6
    m.reset()
    assert m.accumulate() == 0.0


def test_precision_recall():
    p, r = Precision(), Recall()
    preds = np.array([0.9, 0.8, 0.2, 0.6])
    labels = np.array([1, 0, 1, 1])
    p.update(preds, labels)
    r.update(preds, labels)
    # predicted positive: 0.9,0.8,0.6 -> tp=2 fp=1; actual pos=3, fn=1
    assert abs(p.accumulate() - 2 / 3) < 1e-6
    assert abs(r.accumulate() - 2 / 3) < 1e-6


def test_auc():
    m = Auc()
    preds = np.stack([1 - np.array([0.9, 0.8, 0.7, 0.2]),
                      np.array([0.9, 0.8, 0.7, 0.2])], axis=1)
    labels = np.array([[1], [1], [0], [0]])
    m.update(preds, labels)
    assert m.accumulate() == pytest.approx(1.0, abs=0.01)


# ---------------------------------------------------------------------- amp
def test_auto_cast_o1_matmul_bf16():
    import jax.numpy as jnp

    a = paddle.to_tensor(np.random.rand(4, 4).astype("float32"))
    b = paddle.to_tensor(np.random.rand(4, 4).astype("float32"))
    with paddle.amp.auto_cast(level="O1"):
        out = paddle.matmul(a, b)
    assert out._data.dtype == jnp.bfloat16
    # black-listed op stays fp32
    with paddle.amp.auto_cast(level="O1"):
        s = paddle.nn.functional.softmax(a)
    assert s._data.dtype == jnp.float32
    # outside context: no casting
    out2 = paddle.matmul(a, b)
    assert out2._data.dtype == jnp.float32


def test_grad_scaler_identity_bf16():
    scaler = paddle.amp.GradScaler(enable=False)
    x = paddle.to_tensor(np.array(2.0, dtype="float32"))
    assert scaler.scale(x) is x


def test_grad_scaler_dynamic():
    scaler = paddle.amp.GradScaler(
        init_loss_scaling=8.0, decr_every_n_nan_or_inf=1)
    lin = nn.Linear(2, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=lin.parameters())
    x = paddle.to_tensor(np.ones((1, 2), dtype="float32"), stop_gradient=False)
    loss = scaler.scale(lin(x).sum())
    loss.backward()
    scaler.step(opt)
    scaler.update()
    assert scaler.get_loss_scaling() == 8.0  # no overflow, no change yet


# ----------------------------------------------------------------- transforms
def test_transforms_pipeline():
    img = (np.random.rand(32, 36, 3) * 255).astype(np.uint8)
    t = transforms.Compose([
        transforms.Resize((28, 28)),
        transforms.RandomHorizontalFlip(0.5),
        transforms.ToTensor(),
        transforms.Normalize(mean=[0.5] * 3, std=[0.5] * 3),
    ])
    out = t(img)
    assert out.shape == [3, 28, 28]
    assert float(out.numpy().max()) <= 1.0


def test_resize_shapes():
    img = (np.random.rand(20, 40, 3) * 255).astype(np.uint8)
    assert transforms.resize(img, 10).shape == (10, 20, 3)
    assert transforms.resize(img, (7, 9)).shape == (7, 9, 3)
    assert transforms.center_crop(img, 16).shape == (16, 16, 3)
    assert transforms.pad(img, 2).shape == (24, 44, 3)


# ------------------------------------------------------------------ datasets
def test_mnist_synthetic():
    ds = MNIST(mode="train")
    img, label = ds[0]
    assert img.shape == (28, 28, 1)
    assert 0 <= int(label) < 10
    assert len(ds) == 8192
    # deterministic across constructions
    ds2 = MNIST(mode="train")
    np.testing.assert_array_equal(ds.images[0], ds2.images[0])


def test_cifar_synthetic():
    ds = Cifar10(mode="test")
    img, label = ds[3]
    assert img.shape == (32, 32, 3)
    assert len(ds) == 1024


# -------------------------------------------------------------------- models
@pytest.mark.parametrize("ctor,chw", [
    (lambda: LeNet(), (1, 28, 28)),
    (lambda: resnet18(num_classes=10), (3, 32, 32)),
])
def test_model_forward(ctor, chw):
    net = ctor()
    x = paddle.to_tensor(np.random.rand(2, *chw).astype("float32"))
    net.eval()
    out = net(x)
    assert out.shape == [2, 10]


def test_model_zoo_constructs(host_drawn_weights):
    # constructor-only smoke (forwards are expensive on CPU)
    for ctor in (vgg11, mobilenet_v1):
        net = ctor(num_classes=4)
        assert len(net.parameters()) > 0
    for cls in (MobileNetV2, MobileNetV3Small):
        net = cls(num_classes=4)
        assert len(net.parameters()) > 0


# ----------------------------------------------------------------- hapi Model
def _make_model():
    net = LeNet()
    model = paddle.Model(net)
    model.prepare(
        paddle.optimizer.Adam(learning_rate=1e-3,
                              parameters=net.parameters()),
        nn.CrossEntropyLoss(),
        Accuracy(),
    )
    return model


def test_model_fit_loss_decreases():
    rng = np.random.RandomState(0)
    n = 256
    labels = rng.randint(0, 10, (n, 1))
    # separable data: class k has mean k/10
    x = (labels.reshape(-1, 1, 1, 1) / 10.0
         + 0.05 * rng.randn(n, 1, 28, 28)).astype("float32")
    ds = paddle.io.TensorDataset(
        [paddle.to_tensor(x), paddle.to_tensor(labels)])
    model = _make_model()
    first = model.train_batch([x[:64]], [labels[:64]])
    loss0 = float(first[0][0])
    model.fit(ds, batch_size=64, epochs=3, verbose=0, shuffle=True,
              drop_last=True)
    last = model.eval_batch([x[:64]], [labels[:64]])
    assert float(last[0][0]) < loss0


def test_model_evaluate_predict():
    model = _make_model()
    x = np.random.rand(16, 1, 28, 28).astype("float32")
    y = np.random.randint(0, 10, (16, 1))
    ds = paddle.io.TensorDataset([paddle.to_tensor(x), paddle.to_tensor(y)])
    res = model.evaluate(ds, batch_size=16, verbose=0)
    assert "loss" in res and "acc" in res
    preds = model.predict(ds, batch_size=16, stack_outputs=True, verbose=0)
    assert preds[0].shape == (16, 10)


def test_model_save_load(tmp_path):
    model = _make_model()
    x = np.random.rand(8, 1, 28, 28).astype("float32")
    y = np.random.randint(0, 10, (8, 1))
    model.train_batch([x], [y])
    path = os.path.join(str(tmp_path), "ck", "model")
    model.save(path)
    assert os.path.exists(path + ".pdparams")
    assert os.path.exists(path + ".pdopt")

    model2 = _make_model()
    model2.load(path)
    p1 = model.network.parameters()[0].numpy()
    p2 = model2.network.parameters()[0].numpy()
    np.testing.assert_allclose(p1, p2)


def test_model_summary():
    net = LeNet()
    info = paddle.summary(net, (1, 1, 28, 28))
    assert info["total_params"] == sum(
        int(np.prod(p.shape)) for p in net.parameters())


def test_paddle_save_load_roundtrip(tmp_path):
    obj = {"w": paddle.to_tensor(np.arange(6, dtype="float32").reshape(2, 3)),
           "meta": {"lr": 0.1, "steps": [1, 2]}}
    p = os.path.join(str(tmp_path), "obj.pd")
    paddle.save(obj, p)
    back = paddle.load(p)
    np.testing.assert_allclose(back["w"].numpy(), obj["w"].numpy())
    assert back["meta"] == obj["meta"]


class TestRound3Transforms:
    def test_affine_identity_and_translate(self):
        from paddle_tpu.vision.transforms import affine
        img = np.arange(5 * 5 * 3, dtype=np.uint8).reshape(5, 5, 3)
        np.testing.assert_array_equal(affine(img), img)
        out = affine(img, translate=(1, 0))
        np.testing.assert_array_equal(out[:, 1:], img[:, :-1])

    def test_perspective_identity(self):
        from paddle_tpu.vision.transforms import perspective
        img = np.arange(4 * 4 * 3, dtype=np.uint8).reshape(4, 4, 3)
        pts = [(0, 0), (3, 0), (3, 3), (0, 3)]
        np.testing.assert_array_equal(perspective(img, pts, pts), img)

    def test_random_affine_and_perspective_shapes(self):
        import paddle_tpu.vision.transforms as T
        img = np.zeros((8, 8, 3), np.uint8)
        assert T.RandomAffine(15, translate=(0.2, 0.2), scale=(0.8, 1.2),
                              shear=10)(img).shape == (8, 8, 3)
        assert T.RandomPerspective(prob=1.0)(img).shape == (8, 8, 3)
