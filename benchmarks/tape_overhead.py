"""Eager-tape dispatch overhead measurement (SURVEY §3.1 hot-loop risk;
VERDICT r2 weak #10).

Quantifies what one eager op costs through the framework dispatch
(tape recording via jax.vjp) versus no_grad dispatch versus raw jnp, and
what a full eager training step costs versus the jitted functional step —
the number that justifies the design rule "hot loops belong in jitted step
functions; the tape exists for dygraph parity and debugging".

Usage: python benchmarks/tape_overhead.py  (prints one JSON line; the test
suite smoke-runs measure() with a tiny n_ops in tests/test_domain_packages).
"""
from __future__ import annotations

import json
import os
import time


def measure(n_ops: int = 300) -> dict:
    import jax

    if os.environ.get("TAPE_BENCH_FORCE_CPU", "1") == "1":
        # pin through the config, before any backend starts
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.core import tape as tape_mod
    from paddle_tpu.jit.functional import call_functional, extract_state

    x = paddle.to_tensor(np.ones((32, 32), np.float32))
    x.stop_gradient = False
    xd = x._data

    def timed(fn, reps=3):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    # raw jnp chain (async dispatch; sync at the end)
    def raw():
        v = xd
        for _ in range(n_ops):
            v = jnp.add(v, 1.0)
        v.block_until_ready()

    # framework dispatch, tape OFF
    def eager_nograd():
        with tape_mod.no_grad():
            v = x
            for _ in range(n_ops):
                v = v + 1.0
            v._data.block_until_ready()

    # framework dispatch, tape ON (jax.vjp per op)
    def eager_tape():
        v = x
        for _ in range(n_ops):
            v = v + 1.0
        v._data.block_until_ready()

    raw()  # warm the add kernel
    t_raw = timed(raw)
    t_nograd = timed(eager_nograd)
    t_tape = timed(eager_tape)

    # full-step comparison: eager backward loop vs jitted functional step
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(64, 128), nn.ReLU(), nn.Linear(128, 8))
    opt = paddle.optimizer.SGD(learning_rate=0.01,
                               parameters=net.parameters())
    loss_fn = nn.CrossEntropyLoss()
    bx = paddle.to_tensor(np.random.RandomState(0)
                          .rand(64, 64).astype("float32"))
    by = paddle.to_tensor(np.random.RandomState(1)
                          .randint(0, 8, (64, 1)))

    def eager_step():
        loss = loss_fn(net(bx), by)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    params, buffers = extract_state(net)
    opt_state = opt.functional_state(params)

    def step(params, buffers, opt_state, lr, t, xa, ya):
        def loss_of(p):
            out, _ = call_functional(net, p, buffers, (xa,), training=True)
            if isinstance(out, (tuple, list)):
                out = out[0]
            with tape_mod.no_grad():
                return loss_fn(paddle.Tensor(out), paddle.Tensor(ya))._data

        loss, grads = jax.value_and_grad(loss_of)(params)
        new_params, new_state = opt.functional_step(params, grads,
                                                    opt_state, lr, t)
        return loss, new_params, new_state

    jitted = jax.jit(step)
    lr = jnp.float32(0.01)

    eager_step()  # warm
    t_eager_step = timed(lambda: float(eager_step().numpy()))
    loss, params, opt_state = jitted(params, buffers, opt_state, lr,
                                     jnp.int32(1), bx._data, by._data)
    float(loss)  # compile + warm

    def jitted_once():
        out = jitted(params, buffers, opt_state, lr, jnp.int32(2),
                     bx._data, by._data)
        float(out[0])

    t_jit_step = timed(jitted_once)

    us = 1e6
    return {
        "per_op_us": {
            "raw_jnp": round(t_raw / n_ops * us, 2),
            "dispatch_no_grad": round(t_nograd / n_ops * us, 2),
            "dispatch_tape": round(t_tape / n_ops * us, 2),
            "tape_overhead_vs_raw_x": round(t_tape / max(t_raw, 1e-12), 1),
        },
        "train_step_ms": {
            "eager_tape": round(t_eager_step * 1e3, 2),
            "jitted_functional": round(t_jit_step * 1e3, 2),
            "speedup_x": round(t_eager_step / max(t_jit_step, 1e-12), 1),
        },
        "n_ops": n_ops,
    }


if __name__ == "__main__":
    print(json.dumps(measure()))
