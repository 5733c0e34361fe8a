"""ResNet-50 data-parallel throughput (BASELINE.json config #2: Fleet
DP + AMP O2, images/sec/device).

Runs on whatever devices are visible: the real chip(s), or the hermetic
8-fake-device CPU mesh (--cpu; conftest-style XLA_FLAGS forced here).
The train step is the product shape: functional forward + CE + SGD
momentum under amp O2 autocast, batch sharded over the dp mesh axis via
NamedSharding, params replicated — XLA inserts the gradient psum.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true",
                    help="force the hermetic 8-fake-device CPU mesh")
    ap.add_argument("--batch", type=int, default=None,
                    help="global batch (default: 32/device on TPU, "
                    "16 total on CPU)")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--image-size", type=int, default=None,
                    help="default 224 on TPU, 64 on CPU smoke")
    args = ap.parse_args()

    if args.cpu:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8")
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.core import tape as tape_mod
    from paddle_tpu.jit.functional import call_functional, extract_state
    from paddle_tpu.vision import models as V

    devs = jax.devices()
    n_dev = len(devs)
    on_tpu = devs[0].platform == "tpu"
    size = args.image_size if args.image_size is not None else (
        224 if on_tpu else 64)
    batch = args.batch if args.batch is not None else (
        32 * n_dev if on_tpu else 16)
    batch -= batch % n_dev
    if batch <= 0 or size <= 0:
        ap.error(f"batch must be >= device count ({n_dev}) and "
                 "image-size positive")
    print(f"[resnet-dp] devices={n_dev} ({devs[0].platform}), "
          f"global batch={batch}, image={size}", file=sys.stderr)

    paddle.seed(0)
    LR = 0.1
    model = V.resnet50(num_classes=1000)
    model.train()
    opt = paddle.optimizer.Momentum(learning_rate=LR, momentum=0.9,
                                    parameters=model.parameters())
    params, buffers = extract_state(model)
    opt_state = opt.functional_state(params)

    mesh = jax.sharding.Mesh(np.array(devs), ("dp",))
    data_sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("dp"))
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    def train_step(params, buffers, opt_state, images, labels):
        def loss_of(p):
            with amp.auto_cast(level="O2", dtype="bfloat16"):
                logits, new_buffers = call_functional(
                    model, p, buffers, (images,), training=True)
            with tape_mod.no_grad():
                loss = paddle.nn.functional.cross_entropy(
                    paddle.Tensor(logits), paddle.Tensor(labels))
            return loss._data, new_buffers

        (loss, new_buffers), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
        new_params, new_opt = opt.functional_step(params, grads, opt_state,
                                                  jnp.float32(LR),
                                                  jnp.int32(1))
        return loss, new_params, new_buffers, new_opt

    jitted = jax.jit(train_step, donate_argnums=(0, 1, 2))

    put = lambda t: jax.device_put(t, repl)  # noqa: E731
    params = jax.tree_util.tree_map(put, params)
    buffers = jax.tree_util.tree_map(put, buffers)
    opt_state = jax.tree_util.tree_map(put, opt_state)

    rng = np.random.RandomState(0)
    images = jax.device_put(
        jnp.asarray(rng.randn(batch, 3, size, size), jnp.float32), data_sh)
    labels = jax.device_put(
        jnp.asarray(rng.randint(0, 1000, (batch,))), data_sh)

    t0 = time.perf_counter()
    loss, params, buffers, opt_state = jitted(params, buffers, opt_state,
                                              images, labels)
    float(np.asarray(loss))
    print(f"[resnet-dp] compile+first step {time.perf_counter()-t0:.1f}s",
          file=sys.stderr)

    t0 = time.perf_counter()
    for _ in range(args.steps):
        loss, params, buffers, opt_state = jitted(
            params, buffers, opt_state, images, labels)
    final = float(np.asarray(loss))
    dt = time.perf_counter() - t0
    ips = batch * args.steps / dt
    print(f"[resnet-dp] {ips:,.1f} img/s total, {ips/n_dev:,.1f} "
          f"img/s/device, loss {final:.3f}", file=sys.stderr)
    import json

    print(json.dumps({"metric": "resnet50_dp_images_per_sec",
                      "value": round(ips, 1), "unit": "img/s",
                      "devices": n_dev, "batch": batch,
                      "image_size": size,
                      "amp": "O2", "loss": final}))


if __name__ == "__main__":
    main()
