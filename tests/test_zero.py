"""ZeRO-sharded data-parallel training on the unified mesh substrate
(ISSUE 16): `paddle_tpu.parallel.zero_train_step`.

THE claims under test (arxiv 2004.13336, acceptance criteria):
- sharded-vs-replicated bit-parity (fp32) at dp in {1, 2, 4} x stage
  {1, 2} — same fixed-order grad sum, elementwise update on the 1/dp
  slice, so equality is exact, not allclose;
- per-chip optimizer-state bytes scale as 1/dp;
- dp=2 x tp=2 composition parity on ONE mesh (Megatron region helpers);
- degree-blind checkpoints: save at dp=2, restore at dp=4, keep
  training in lockstep with the replicated baseline;
- grad accumulation composes (parity holds at every accum);
- the paddle-compat GroupSharded surface bridges to the same engine.

Cross-DEGREE bit-parity is deliberately NOT claimed (changing dp
changes the batch summation order).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.parallel import (
    DP_AXIS, TP_AXIS, ZeroTrainStep, build_mesh, carve_submeshes,
    copy_to_tp_region, device_order, group_sharded_parallel, ordered_psum,
    ordered_psum_scatter, reduce_from_tp_region, zero_train_step,
)

HID = 48
_rng = np.random.RandomState(0)
X = _rng.randn(32, 16).astype("float32")
Y = _rng.randn(32, 8).astype("float32")


def _build():
    paddle.seed(7)
    return nn.Sequential(nn.Linear(16, HID), nn.ReLU(), nn.Linear(HID, 8))


def _run(stage, dp, steps=3, grad_accum=1, net=None, lr=0.01):
    net = net if net is not None else _build()
    opt = paddle.optimizer.Adam(learning_rate=lr,
                                parameters=net.parameters())
    step = zero_train_step(net, opt, stage=stage, dp=dp,
                           grad_accum=grad_accum)
    params, st = step.init_state()
    loss = None
    for t in range(1, steps + 1):
        loss, params, st = step(params, st, (X, Y), lr, t)
    return (float(loss), {k: np.asarray(v) for k, v in params.items()},
            step, st)


def _bit_equal(a, b):
    return all(np.array_equal(a[k], b[k]) for k in a)


# ------------------------------------------------- substrate (mesh layer)

class TestMeshSubstrate:
    def test_build_mesh_permutation_independent(self):
        devs = list(jax.devices())
        shuffled = [devs[3], devs[0], devs[2], devs[1]]
        m1 = build_mesh(((DP_AXIS, 2), (TP_AXIS, 2)), devs[:4])
        m2 = build_mesh(((DP_AXIS, 2), (TP_AXIS, 2)), shuffled)
        assert m1 == m2
        assert [d.id for d in m1.devices.reshape(-1)] == \
            sorted(d.id for d in devs[:4])

    def test_build_mesh_needs_enough_devices(self):
        with pytest.raises(ValueError, match="devices"):
            build_mesh(((DP_AXIS, 4), (TP_AXIS, 4)))

    def test_carve_submeshes_sorted_disjoint(self):
        devs = list(jax.devices())
        carved = carve_submeshes(2, 2, list(reversed(devs)))
        assert [[d.id for d in grp] for grp in carved] == \
            [[devs[0].id, devs[1].id], [devs[2].id, devs[3].id]]
        with pytest.raises(ValueError, match="devices"):
            carve_submeshes(8, 2)

    def test_ordered_psum_scatter_matches_sliced_sum(self):
        """reduce-scatter shard i == slice i of the ordered all-reduce,
        bit-for-bit — the identity ZeRO-2's parity rests on."""
        from jax import shard_map

        mesh = build_mesh(((DP_AXIS, 4),))
        x = _rng.randn(4, 64).astype("float32")

        def body(v):
            full = ordered_psum(v, DP_AXIS)
            mine = ordered_psum_scatter(v.reshape(-1), DP_AXIS)
            i = jax.lax.axis_index(DP_AXIS)
            ref = jax.lax.dynamic_slice(full.reshape(-1), (i * 16,), (16,))
            return jax.lax.all_gather(mine, DP_AXIS), \
                jax.lax.all_gather(ref, DP_AXIS)

        got, want = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P(DP_AXIS),
            out_specs=(P(DP_AXIS), P(DP_AXIS)),
            check_vma=False,  # noqa: COLLECTIVE-MESH — test fixture gathers per-shard views on purpose
            ))(x)
        assert np.array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------ bit-parity (tentpole)

class TestZeroParity:
    @pytest.mark.parametrize("dp", [1, 2, 4])
    @pytest.mark.parametrize("stage", [1, 2])
    def test_sharded_equals_replicated_bitwise(self, dp, stage):
        loss0, p0, s0, st0 = _run(0, dp)
        loss1, p1, s1, st1 = _run(stage, dp)
        assert loss0 == loss1
        assert _bit_equal(p0, p1)
        # per-chip optimizer-state bytes scale as 1/dp (every param size
        # here divides dp, so the scaling is exact)
        b0 = s0.optimizer_state_bytes_per_chip(st0)
        b1 = s1.optimizer_state_bytes_per_chip(st1)
        assert b1 * dp == b0

    @pytest.mark.parametrize("accum", [2, 4])
    def test_grad_accumulation_parity(self, accum):
        loss0, p0, _, _ = _run(0, 2, grad_accum=accum)
        loss1, p1, _, _ = _run(1, 2, grad_accum=accum)
        loss2, p2, _, _ = _run(2, 2, grad_accum=accum)
        assert loss0 == loss1 == loss2
        assert _bit_equal(p0, p1) and _bit_equal(p0, p2)

    def test_grad_accumulation_approximates_full_batch(self):
        """Accumulated micro-batches are numerically (not bitwise) the
        full-batch step: the mean is resummed in micro order."""
        _, p1, _, _ = _run(1, 2, grad_accum=1)
        _, p4, _, _ = _run(1, 2, grad_accum=4)
        for k in p1:
            np.testing.assert_allclose(p1[k], p4[k], rtol=1e-4, atol=1e-5)


# -------------------------------------------------- dp x tp composition

def _tp_loss_fn(params, x, y):
    """Megatron 2-layer MLP: column-parallel w1, row-parallel w2, the
    tp region bracketed by the substrate's custom_vjp boundaries."""
    h = jax.nn.relu(copy_to_tp_region(x) @ params["w1"])
    out = reduce_from_tp_region(h @ params["w2"])
    return jnp.mean((out - y) ** 2)


class TestTpComposition:
    TP_SPECS = {"w1": P(None, TP_AXIS), "w2": P(TP_AXIS, None)}

    def _run_tp(self, stage, steps=3):
        rng = np.random.RandomState(3)
        full = {"w1": rng.randn(16, 32).astype("float32"),
                "w2": rng.randn(32, 8).astype("float32")}
        # the functional API ignores _parameter_list; Adam just insists
        # one exists at construction
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=nn.Linear(2, 2).parameters())
        step = ZeroTrainStep(None, opt, _tp_loss_fn, stage=stage, dp=2,
                             tp=2, param_specs=self.TP_SPECS)
        params, st = step.init_state(full)
        loss = None
        for t in range(1, steps + 1):
            loss, params, st = step(params, st, (X, Y[:, :8]), 0.01, t)
        host = {k: np.asarray(jax.device_put(
            v, jax.sharding.NamedSharding(step.mesh, P())))
            for k, v in params.items()}
        return float(loss), host, step, st

    def test_dp2_tp2_parity_and_bytes(self):
        loss0, p0, s0, st0 = self._run_tp(0)
        for stage in (1, 2):
            loss1, p1, s1, st1 = self._run_tp(stage)
            assert loss0 == loss1
            assert _bit_equal(p0, p1)
            assert s1.optimizer_state_bytes_per_chip(st1) * 2 == \
                s0.optimizer_state_bytes_per_chip(st0)

    def test_tp_param_placement(self):
        _, _, step, st = self._run_tp(1)
        # state leaves carry the (dp, tp, chunk) layout on the one mesh
        leaf = st["w1"]["moment1"]
        assert leaf.shape[0] == 2 and leaf.shape[1] == 2
        assert leaf.sharding.spec == P(DP_AXIS, TP_AXIS)


# ---------------------------------------- degree-blind checkpointing

class TestDegreeBlindCheckpoint:
    def test_layout_roundtrip_any_degree(self):
        """save(load(x)) == x for every dp — the host form carries no
        degree imprint."""
        net = _build()
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        sizes = {}
        host0 = None
        for dp in (1, 2, 4, 8):
            step = zero_train_step(net, opt, stage=1, dp=dp)
            _, st = step.init_state()
            host = step.save_optimizer_state(st)
            if host0 is None:
                host0 = host
            for k in host0:
                for slot in host0[k]:
                    assert np.array_equal(host0[k][slot], host[k][slot])
            sizes[dp] = step.optimizer_state_bytes_per_chip(st)
        assert sizes[8] < sizes[4] < sizes[2] < sizes[1]

    def test_save_dp2_restore_dp4_stays_in_lockstep(self):
        """Train 2 steps sharded at dp=2, save, restore at dp=4 (and as
        a stage-2 engine), take a step — bit-identical to the
        REPLICATED dp=4 engine continuing from the same checkpoint."""
        _, p2, s2, st2 = _run(1, 2, steps=2)
        host = s2.save_optimizer_state(st2)

        def _continue(stage):
            net = _build()
            opt = paddle.optimizer.Adam(learning_rate=0.01,
                                        parameters=net.parameters())
            step = zero_train_step(net, opt, stage=stage, dp=4)
            params, _ = step.init_state(dict(p2))
            st = step.load_optimizer_state(host)
            loss, params, st = step(params, st, (X, Y), 0.01, 3)
            return float(loss), {k: np.asarray(v)
                                 for k, v in params.items()}
        loss_z, params_z = _continue(2)
        loss_r, params_r = _continue(0)
        assert loss_z == loss_r
        assert _bit_equal(params_z, params_r)

    def test_sharded_state_equals_replicated_state_on_save(self):
        """After identical steps, the gathered sharded state IS the
        replicated state, bit-for-bit — parity reaches the moments, not
        just the params."""
        _, _, s0, st0 = _run(0, 2, steps=2)
        _, _, s1, st1 = _run(1, 2, steps=2)
        h0 = s0.save_optimizer_state(st0)
        h1 = s1.save_optimizer_state(st1)
        for k in h0:
            for slot in h0[k]:
                assert np.array_equal(h0[k][slot], h1[k][slot]), (k, slot)


# ------------------------------------------------------- validation

class TestValidation:
    def test_stage3_refused_with_pointer_to_gspmd(self):
        net = _build()
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        with pytest.raises(ValueError, match="p_g_os"):
            zero_train_step(net, opt, stage=3)

    def test_global_norm_clip_refused(self):
        net = _build()
        opt = paddle.optimizer.Adam(
            learning_rate=0.01, parameters=net.parameters(),
            grad_clip=nn.ClipGradByGlobalNorm(1.0))
        with pytest.raises(NotImplementedError, match="norm"):
            zero_train_step(net, opt, stage=1)

    def test_non_elementwise_optimizer_refused(self):
        net = _build()
        opt = paddle.optimizer.Lamb(learning_rate=0.01,
                                    parameters=net.parameters())
        with pytest.raises(NotImplementedError, match="Lamb"):
            zero_train_step(net, opt, stage=1)

    def test_accum_needs_dp_sharded_batch(self):
        net = _build()
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        with pytest.raises(ValueError, match="grad_accum"):
            zero_train_step(net, opt, stage=1, grad_accum=2,
                            batch_specs=(P(DP_AXIS), P()))


# --------------------------------------- paddle-compat surface bridge

class TestGroupShardedBridge:
    def test_wrapper_bridges_to_the_one_engine(self):
        """group_sharded_parallel('os') -> .zero_train_step() is the
        SAME engine: bit-parity with the native builder at the same
        degree."""
        net = _build()
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        wrapped, _ = group_sharded_parallel(net, opt, level="os")
        step = wrapped.zero_train_step()
        assert isinstance(step, ZeroTrainStep)
        assert step.stage == 1
        assert step.dp == len(jax.devices())
        params, st = step.init_state()
        loss, params, st = step(params, st, (X, Y), 0.01, 1)

        loss_n, p_n, _, _ = _run(1, len(jax.devices()), steps=1)
        assert float(loss) == loss_n
        assert _bit_equal({k: np.asarray(v) for k, v in params.items()},
                          p_n)

    def test_stage3_bridge_refused(self):
        net = _build()
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        wrapped, _ = group_sharded_parallel(net, opt, level="p_g_os")
        with pytest.raises(NotImplementedError, match="GSPMD"):
            wrapped.zero_train_step()

    def test_fleet_distributed_optimizer_bridge(self):
        """fleet.distributed_optimizer rebinding: the hybrid wrapper
        builds the zero engine at the hcg's sharding degree."""
        from paddle_tpu.distributed.fleet import (
            DistributedStrategy, fleet,
        )

        strategy = DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "sharding_degree": 4}
        fleet.init(is_collective=True, strategy=strategy)
        net = _build()
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        hybrid = fleet.distributed_optimizer(opt, strategy)
        step = hybrid.zero_train_step(net)
        assert step.dp == 4 and step.stage == 1
        params, st = step.init_state()
        loss, params, st = step(params, st, (X, Y), 0.01, 1)
        assert np.isfinite(float(loss))

    def test_legacy_import_paths_resolve_to_parallel_zero(self):
        """The deprecated fleet.meta_parallel.sharding shim and
        distributed.sharding re-export THE implementation."""
        from paddle_tpu.distributed.fleet.meta_parallel import sharding
        from paddle_tpu.distributed import sharding as dist_sharding
        from paddle_tpu.parallel import zero

        assert sharding.group_sharded_parallel is zero.group_sharded_parallel
        assert dist_sharding.group_sharded_parallel is \
            zero.group_sharded_parallel
        assert dist_sharding.save_group_sharded_model is \
            zero.save_group_sharded_model

    def test_serving_tp_axis_is_the_substrate_axis(self):
        from paddle_tpu.parallel import mesh as pmesh
        from paddle_tpu.serving import tp as serving_tp

        assert serving_tp.TP_AXIS is pmesh.TP_AXIS
        assert serving_tp.tp_device_order([]) == []
        devs = list(reversed(jax.devices()))
        assert serving_tp.tp_device_order(devs) == device_order(devs)


# --------------------------------------------------------- observability

class TestObservability:
    def test_collective_probe_and_describe(self):
        net = _build()
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        step = zero_train_step(net, opt, stage=1, dp=2)
        step.init_state()
        times = step.collective_seconds(samples=2)
        assert len(times) == 2 and all(t >= 0 for t in times)
        d = step.describe()
        assert d["dp"] == 2 and d["stage"] == 1 and d["tp"] == 1
        assert d["devices"] == [0, 1]
