"""Fused MoE dispatch (SURVEY §7 Pallas fusion set; VERDICT r4 #9).

gather_rows is the dispatch/combine primitive: out[m] = src[idx[m]] with
zero rows for over-capacity slots, scatter-add transpose for grads. The
fused _routed_forward must match the einsum reference bit-for-tolerance,
forward AND backward, in interpret mode on CPU; the Mosaic compile of the
kernel itself is a case of tests/test_chip_compile.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.incubate.distributed.models.moe import MoELayer
from paddle_tpu.ops import pallas_kernels as pk


class TestGatherRows:
    def test_forward_with_empty_slots(self):
        rng = np.random.RandomState(0)
        src = jnp.asarray(rng.randn(37, 12).astype("float32"))
        idx = jnp.asarray(np.array([3, 0, -1, 36, 7, 7, -1, 20], np.int32))
        out = pk.gather_rows(src, idx, interpret=True)
        ref = np.where((np.asarray(idx) >= 0)[:, None],
                       np.asarray(src)[np.maximum(np.asarray(idx), 0)], 0)
        np.testing.assert_allclose(np.asarray(out), ref)

    def test_grad_is_scatter_add(self):
        rng = np.random.RandomState(1)
        src = jnp.asarray(rng.randn(16, 8).astype("float32"))
        idx = jnp.asarray(np.array([5, 5, -1, 0, 15], np.int32))
        w = jnp.arange(1.0, 6.0)[:, None]

        g = jax.grad(lambda s: (pk.gather_rows(s, idx, interpret=True)
                                * w).sum())(src)
        ref = np.zeros((16, 8), np.float32)
        for m, i in enumerate(np.asarray(idx)):
            if i >= 0:
                ref[i] += (m + 1)
        np.testing.assert_allclose(np.asarray(g), ref)

    def test_jit_and_odd_sizes(self):
        rng = np.random.RandomState(2)
        src = jnp.asarray(rng.randn(301, 9).astype("float32"))
        idx = jnp.asarray(rng.randint(-1, 301, 413).astype(np.int32))
        out = jax.jit(lambda s, i: pk.gather_rows(s, i, interpret=True))(
            src, idx)
        ref = np.where((np.asarray(idx) >= 0)[:, None],
                       np.asarray(src)[np.maximum(np.asarray(idx), 0)], 0)
        np.testing.assert_allclose(np.asarray(out), ref)


def _build_moe(d_model=16, n_experts=4, topk=2, seed=0):
    paddle.seed(seed)
    experts = [nn.Sequential(nn.Linear(d_model, 32), nn.GELU(),
                             nn.Linear(32, d_model))
               for _ in range(n_experts)]
    return MoELayer(d_model=d_model, experts=experts, gate={"type": "gshard", "top_k": topk})


class TestFusedDispatchParity:
    def _routed(self, layer, x, gate_w, fused):
        def expert_run(expert_in):
            outs = []
            from paddle_tpu.core import tape as tape_mod
            from paddle_tpu.core.tensor import Tensor

            with tape_mod.no_grad():
                for e, expert in enumerate(layer.experts):
                    ye = expert(Tensor(expert_in[e]))
                    outs.append(ye._data)
            return jnp.stack(outs)

        return layer._routed_forward(x, gate_w, expert_run, fused=fused)

    def test_fused_matches_einsum_fwd_and_grads(self):
        layer = _build_moe()
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(24, 16).astype("float32"))
        gw = layer.gate.gate_weight._data

        y_ref, aux_ref = self._routed(layer, x, gw, fused=False)
        y_fused, aux_fused = self._routed(layer, x, gw, fused=True)
        np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(float(aux_fused), float(aux_ref),
                                   rtol=1e-6)

        def loss(fused):
            def f(xd, gwd):
                y, aux = self._routed(layer, xd, gwd, fused=fused)
                return (y ** 2).sum() + aux
            return f

        gx_r, gw_r = jax.grad(loss(False), argnums=(0, 1))(x, gw)
        gx_f, gw_f = jax.grad(loss(True), argnums=(0, 1))(x, gw)
        np.testing.assert_allclose(np.asarray(gx_f), np.asarray(gx_r),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gw_f), np.asarray(gw_r),
                                   rtol=1e-4, atol=1e-5)

    def test_fused_under_jit_one_program(self):
        layer = _build_moe(seed=4)
        rng = np.random.RandomState(5)
        gw = layer.gate.gate_weight._data

        @jax.jit
        def step(xd):
            y, aux = self._routed(layer, xd, gw, fused=True)
            return y.sum() + aux

        for _ in range(3):
            v = step(jnp.asarray(rng.randn(24, 16).astype("float32")))
            assert np.isfinite(float(v))
