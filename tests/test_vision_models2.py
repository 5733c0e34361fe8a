"""New vision model families (densenet/squeezenet/shufflenetv2/googlenet/
inceptionv3) + channel_shuffle op. Mirrors the reference's API/layer test
strategy (SURVEY.md §4): behavioral checks against NumPy where a closed
form exists, shape/finiteness elsewhere (full ImageNet-sized forwards are
bench territory, not unit tests)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.vision import models as M
from paddle_tpu.vision.models import densenet

pytestmark = pytest.mark.usefixtures("host_drawn_weights")


def _x(shape, seed=0):
    return paddle.to_tensor(
        np.random.RandomState(seed).standard_normal(shape).astype(np.float32))


class TestChannelShuffle:
    def test_matches_numpy(self, rng):
        x = rng.standard_normal((2, 6, 4, 4)).astype(np.float32)
        out = F.channel_shuffle(paddle.to_tensor(x), 3).numpy()
        ref = x.reshape(2, 3, 2, 4, 4).transpose(0, 2, 1, 3, 4).reshape(
            2, 6, 4, 4)
        np.testing.assert_array_equal(out, ref)

    def test_nhwc(self, rng):
        x = rng.standard_normal((2, 4, 4, 6)).astype(np.float32)
        out = F.channel_shuffle(paddle.to_tensor(x), 2, "NHWC").numpy()
        ref = x.reshape(2, 4, 4, 2, 3).swapaxes(3, 4).reshape(2, 4, 4, 6)
        np.testing.assert_array_equal(out, ref)

    def test_pixel_shuffle_nhwc(self, rng):
        # regression: F.pixel_shuffle dropped data_format (review finding)
        x = rng.standard_normal((1, 2, 2, 8)).astype(np.float32)
        out = F.pixel_shuffle(paddle.to_tensor(x), 2, "NHWC").numpy()
        nchw = F.pixel_shuffle(
            paddle.to_tensor(x.transpose(0, 3, 1, 2)), 2).numpy()
        np.testing.assert_allclose(out, nchw.transpose(0, 2, 3, 1))

    def test_layer_and_involution(self, rng):
        # shuffling with g then with c//g restores the original order
        x = rng.standard_normal((1, 8, 2, 2)).astype(np.float32)
        layer = nn.ChannelShuffle(4)
        once = layer(paddle.to_tensor(x))
        back = F.channel_shuffle(once, 2).numpy()
        np.testing.assert_array_equal(back, x)


class TestNewFamilies:
    # small inputs: an eager forward's time is its compiles, one an op
    # shape, and what runs after them (inception_v3's stem wants 75)
    @pytest.mark.parametrize("ctor,size", [
        (M.densenet121, 64),
        (M.squeezenet1_1, 64),
        (M.shufflenet_v2_x0_25, 64),
        (M.inception_v3, 96),
    ])
    def test_forward_shape(self, ctor, size):
        m = ctor(num_classes=7)
        m.eval()
        out = m(_x((2, 3, size, size)))
        assert tuple(out.shape) == (2, 7)
        assert np.isfinite(out.numpy()).all()

    def test_headless_feature_dims(self):
        # the batch and size of test_forward_shape's: its op shapes, up
        # to the head, are compiled already
        m = M.squeezenet1_1(num_classes=0)
        m.eval()
        out = m(_x((2, 3, 64, 64)))
        assert tuple(out.shape) == (2, 512)

    def test_googlenet_aux_heads(self):
        m = M.googlenet(num_classes=5)
        m.eval()
        out, aux1, aux2 = m(_x((1, 3, 64, 64)))
        assert tuple(out.shape) == (1, 5)
        assert tuple(aux1.shape) == (1, 5)
        assert tuple(aux2.shape) == (1, 5)

    def test_shufflenet_variants_param_counts_increase(self):
        small = sum(int(np.prod(p.shape))
                    for p in M.shufflenet_v2_x0_25().parameters())
        big = sum(int(np.prod(p.shape))
                  for p in M.shufflenet_v2_x1_0().parameters())
        assert small < big

    def test_pretrained_raises(self):
        with pytest.raises(ValueError):
            M.densenet121(pretrained=True)
        with pytest.raises(ValueError):
            M.inception_v3(pretrained=True)

    def test_densenet_train_step_decreases_loss(self, monkeypatch):
        # one tiny supervised step: grads flow through dense-blocks/concat.
        # Two blocks of one layer and the transition between them: each
        # of DenseNet-121's 58 layers is an op shape of its own to compile
        monkeypatch.setitem(densenet._ARCH, "short", (1, 1))
        m = M.DenseNet(layers="short", num_classes=4)
        m.train()
        x = _x((4, 3, 64, 64))
        y = paddle.to_tensor(np.array([0, 1, 2, 3]))
        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=m.parameters())
        losses = []
        for _ in range(2):
            loss = F.cross_entropy(m(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert np.isfinite(losses).all()
        assert losses[1] < losses[0]
