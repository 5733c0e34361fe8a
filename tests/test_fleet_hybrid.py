"""L6 tests: TP layers, PP 1F1B, GroupSharded, SP, ring/Ulysses attention,
MoE, recompute — each checked sharded-vs-replica allclose (SURVEY §4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
try:                                    # jax >= 0.5 exports it at top level
    from jax import shard_map
except ImportError:                     # jax 0.4.x: experimental home
    from jax.experimental.shard_map import shard_map

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.core import tape as tape_mod
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit.functional import call_functional, extract_state
from paddle_tpu.distributed.fleet.meta_parallel import (
    ColumnParallelLinear, GroupShardedStage3, LayerDesc, PipelineLayer,
    PipelineParallel, RowParallelLinear, VocabParallelEmbedding,
    get_rng_state_tracker, group_sharded_parallel, mp_shardings,
    ring_flash_attention, ulysses_attention,
)
from paddle_tpu.distributed.fleet import (
    CommunicateTopology, DistributedStrategy, HybridCommunicateGroup, fleet,
    recompute,
)


def _mp_mesh(n=4):
    return Mesh(np.asarray(jax.devices()[:n]), ("mp",))


# --------------------------------------------------------------- TP layers
def test_tp_layers_match_dense():
    """Column->Row parallel MLP under mp=4 shardings == dense replica."""
    paddle.seed(0)

    class MLP(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = ColumnParallelLinear(16, 32, gather_output=False)
            self.fc2 = RowParallelLinear(32, 8, input_is_parallel=True)

        def forward(self, x):
            return self.fc2(nn.functional.relu(self.fc1(x)))

    net = MLP()
    x = np.random.RandomState(0).rand(4, 16).astype("float32")

    # dense run (eager, no mesh)
    net.eval()
    y_dense = net(paddle.to_tensor(x)).numpy()

    # sharded run: params placed per dist_spec on an mp mesh
    mesh = _mp_mesh(4)
    params, buffers = extract_state(net)
    shardings = mp_shardings(net, mesh)
    placed = {k: jax.device_put(v, shardings[k]) for k, v in params.items()}

    def fwd(p, b, xx):
        out, _ = call_functional(net, p, b, (xx,), training=False)
        return out

    y_sharded = jax.jit(fwd, in_shardings=(shardings, None, None))(
        placed, buffers, jnp.asarray(x))
    np.testing.assert_allclose(y_dense, np.asarray(y_sharded), rtol=2e-5,
                               atol=1e-6)
    # the weight really is sharded over mp
    assert placed["fc1.weight"].sharding.spec == P(None, "mp")


def test_vocab_parallel_embedding():
    paddle.seed(1)
    emb = VocabParallelEmbedding(64, 8)
    ids = np.random.RandomState(1).randint(0, 64, (2, 10))
    y_dense = emb(paddle.to_tensor(ids)).numpy()

    mesh = _mp_mesh(4)
    params, buffers = extract_state(emb)
    sh = mp_shardings(emb, mesh)
    placed = {k: jax.device_put(v, sh[k]) for k, v in params.items()}

    def fwd(p, b, xx):
        out, _ = call_functional(emb, p, b, (xx,), training=False)
        return out

    y_sharded = jax.jit(fwd, in_shardings=(sh, None, None))(
        placed, buffers, jnp.asarray(ids))
    np.testing.assert_allclose(y_dense, np.asarray(y_sharded), rtol=1e-6)
    assert placed["weight"].sharding.spec == P("mp", None)


def test_rng_states_tracker():
    tr = get_rng_state_tracker()
    paddle.seed(5)
    with tr.rng_state("model-parallel-rng"):
        a = paddle.rand([4])
    with tr.rng_state("model-parallel-rng"):
        b = paddle.rand([4])
    # separate draws from the same stream differ
    assert not np.allclose(a.numpy(), b.numpy())
    # the default generator was untouched by the tracker context
    paddle.seed(5)
    c = paddle.rand([4])
    paddle.seed(5)
    d = paddle.rand([4])
    np.testing.assert_allclose(c.numpy(), d.numpy())


# ---------------------------------------------------------------------- PP
def _pp_engine_and_replica(num_stages=2, micro=4):
    paddle.seed(7)
    layers = [LayerDesc(nn.Linear, 8, 16), LayerDesc(nn.ReLU),
              LayerDesc(nn.Linear, 16, 16), LayerDesc(nn.ReLU),
              LayerDesc(nn.Linear, 16, 4)]
    loss_fn = nn.CrossEntropyLoss()
    pipe = PipelineLayer(layers, num_stages=num_stages, loss_fn=loss_fn)

    # replica: same weights flattened into one sequential
    replica = nn.Sequential(*pipe._all_layers)
    return pipe, replica, loss_fn


def test_pipeline_parallel_matches_replica():
    topo = CommunicateTopology(["pp", "dp", "sharding", "sep", "mp"],
                               [2, 1, 1, 1, 1])
    hcg = HybridCommunicateGroup(topo, global_rank=0)
    pipe, replica, loss_fn = _pp_engine_and_replica(2)
    rng = np.random.RandomState(3)
    x = rng.rand(8, 8).astype("float32")
    y = rng.randint(0, 4, (8, 1))

    # replica loss with the SAME weights (shared layer objects) — must run
    # BEFORE engine construction places stage params on their submeshes
    with tape_mod.no_grad():
        ref_loss = float(loss_fn(replica(paddle.to_tensor(x)),
                                 paddle.to_tensor(y)).numpy())

    strategy = DistributedStrategy()
    strategy.pipeline_configs = {"accumulate_steps": 4}
    engine = PipelineParallel(pipe, hcg, strategy)

    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=pipe.parameters())

    loss = engine.train_batch(
        (paddle.to_tensor(x), paddle.to_tensor(y)), opt)
    # micro-batched mean loss == full-batch loss for mean-reduced CE
    assert abs(float(loss.numpy()) - ref_loss) < 1e-5

    # params actually moved
    l2 = engine.train_batch((paddle.to_tensor(x), paddle.to_tensor(y)), opt)
    assert float(l2.numpy()) < float(loss.numpy())


def test_pipeline_vs_single_process_sgd():
    """Two SGD steps through the PP engine == two eager full-model steps."""
    paddle.seed(11)
    layers_a = [nn.Linear(6, 12), nn.ReLU(), nn.Linear(12, 3)]
    paddle.seed(11)
    layers_b = [nn.Linear(6, 12), nn.ReLU(), nn.Linear(12, 3)]
    for la, lb in zip(layers_a, layers_b):
        for pa, pb in zip(la.parameters(), lb.parameters()):
            np.testing.assert_allclose(pa.numpy(), pb.numpy())

    loss_fn = nn.CrossEntropyLoss()
    pipe = PipelineLayer([LayerDesc(l) for l in layers_a], num_stages=2,
                         loss_fn=loss_fn)
    topo = CommunicateTopology(["pp", "dp", "sharding", "sep", "mp"],
                               [2, 1, 1, 1, 1])
    hcg = HybridCommunicateGroup(topo, global_rank=0)
    st = DistributedStrategy()
    st.pipeline_configs = {"accumulate_steps": 2}
    engine = PipelineParallel(pipe, hcg, st)
    opt_a = paddle.optimizer.SGD(learning_rate=0.05,
                                 parameters=pipe.parameters())

    seq = nn.Sequential(*layers_b)
    opt_b = paddle.optimizer.SGD(learning_rate=0.05,
                                 parameters=seq.parameters())

    rng = np.random.RandomState(5)
    x = rng.rand(4, 6).astype("float32")
    y = rng.randint(0, 3, (4, 1))

    for _ in range(2):
        engine.train_batch((paddle.to_tensor(x), paddle.to_tensor(y)), opt_a)
        out = seq(paddle.to_tensor(x))
        loss = loss_fn(out, paddle.to_tensor(y))
        loss.backward()
        opt_b.step()
        opt_b.clear_grad()

    for pa, pb in zip(pipe.parameters(), seq.parameters()):
        np.testing.assert_allclose(pa.numpy(), pb.numpy(), rtol=2e-4,
                                   atol=1e-5)


def test_interleaved_vpp_matches_single_process():
    """Interleaved schedule (num_virtual_pipeline_stages=2): S=2 stages x
    V=2 chunks, chunk c on stage c%S, numerics == eager full model."""
    def build():
        paddle.seed(13)
        return [nn.Linear(6, 12), nn.ReLU(), nn.Linear(12, 12), nn.ReLU(),
                nn.Linear(12, 3)]

    layers_a, layers_b = build(), build()
    loss_fn = nn.CrossEntropyLoss()
    pipe = PipelineLayer([LayerDesc(l) for l in layers_a], num_stages=2,
                         loss_fn=loss_fn, num_virtual_pipeline_stages=2)
    assert pipe.num_chunks == 4
    # round-robin chunk placement (Megatron interleaved layout)
    assert [pipe.chunk_to_stage(c) for c in range(4)] == [0, 1, 0, 1]
    # physical stage 0 holds chunks 0 and 2
    assert pipe.stage_layers[0] == pipe.chunk_layers[0] + pipe.chunk_layers[2]

    topo = CommunicateTopology(["pp", "dp", "sharding", "sep", "mp"],
                               [2, 1, 1, 1, 1])
    hcg = HybridCommunicateGroup(topo, global_rank=0)
    st = DistributedStrategy()
    st.pipeline_configs = {"accumulate_steps": 2}
    engine = PipelineParallel(pipe, hcg, st)
    opt_a = paddle.optimizer.SGD(learning_rate=0.05,
                                 parameters=pipe.parameters())

    seq = nn.Sequential(*layers_b)
    opt_b = paddle.optimizer.SGD(learning_rate=0.05,
                                 parameters=seq.parameters())

    rng = np.random.RandomState(6)
    x = rng.rand(4, 6).astype("float32")
    y = rng.randint(0, 3, (4, 1))

    for _ in range(2):
        engine.train_batch((paddle.to_tensor(x), paddle.to_tensor(y)), opt_a)
        out = seq(paddle.to_tensor(x))
        loss = loss_fn(out, paddle.to_tensor(y))
        loss.backward()
        opt_b.step()
        opt_b.clear_grad()

    for pa, pb in zip(pipe.parameters(), seq.parameters()):
        np.testing.assert_allclose(pa.numpy(), pb.numpy(), rtol=2e-4,
                                   atol=1e-5)


def test_vpp_too_few_layers_raises():
    with pytest.raises(ValueError, match="virtual"):
        PipelineLayer([LayerDesc(nn.Linear, 4, 4)] * 3, num_stages=2,
                      num_virtual_pipeline_stages=2)


# ------------------------------------------------------------ GroupSharded
def test_group_sharded_stage3_matches_replica():
    def build():
        paddle.seed(21)
        return nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8),
                             nn.ReLU(), nn.Linear(8, 4))

    rng = np.random.RandomState(2)
    x = rng.rand(32, 16).astype("float32")
    y = rng.randint(0, 4, (32, 1))

    net1 = build()
    m1 = paddle.Model(net1)
    m1.prepare(paddle.optimizer.Adam(learning_rate=0.01,
                                     parameters=net1.parameters()),
               nn.CrossEntropyLoss())
    losses1 = [float(m1.train_batch([x], [y])[0]) for _ in range(3)]

    net2 = build()
    opt2 = paddle.optimizer.Adam(learning_rate=0.01,
                                 parameters=net2.parameters())
    wrapped, opt2w = group_sharded_parallel(net2, opt2, level="p_g_os")
    m2 = paddle.Model(wrapped)
    m2.prepare(opt2w._optim, nn.CrossEntropyLoss())
    losses2 = [float(m2.train_batch([x], [y])[0]) for _ in range(3)]

    np.testing.assert_allclose(losses1, losses2, rtol=3e-5)
    # stage-3: divisible dim-0 params really sharded
    w32 = dict(wrapped.named_parameters())["2.weight"]
    assert w32._data.sharding.spec in (P("sharding"), P(("sharding",)))


def test_group_sharded_levels():
    net = nn.Linear(8, 8)
    opt = paddle.optimizer.Adam(parameters=net.parameters())
    for level, stage in (("os", 1), ("os_g", 2), ("p_g_os", 3)):
        w, o = group_sharded_parallel(nn.Linear(8, 8),
                                      paddle.optimizer.Adam(
                                          parameters=net.parameters()),
                                      level=level)
        assert w.stage == stage


# ------------------------------------------------- ring/Ulysses attention
def _attn_inputs(b=2, h=4, s=32, d=8, seed=0):
    rng = np.random.RandomState(seed)
    q = rng.randn(b, h, s, d).astype("float32")
    k = rng.randn(b, h, s, d).astype("float32")
    v = rng.randn(b, h, s, d).astype("float32")
    return q, k, v


def _dense_attention(q, k, v, causal):
    scale = q.shape[-1] ** -0.5
    scores = np.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        s = q.shape[2]
        mask = np.tril(np.ones((s, s), dtype=bool))
        scores = np.where(mask[None, None], scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal):
    q, k, v = _attn_inputs()
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("sep",))

    def f(q, k, v):
        return ring_flash_attention(q, k, v, axis_name="sep", causal=causal)

    out = shard_map(f, mesh=mesh,
                    in_specs=(P(None, None, "sep", None),) * 3,
                    out_specs=P(None, None, "sep", None))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    ref = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_dense(causal):
    q, k, v = _attn_inputs(h=8)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("sep",))

    def f(q, k, v):
        return ulysses_attention(q, k, v, axis_name="sep", causal=causal)

    out = shard_map(f, mesh=mesh,
                    in_specs=(P(None, None, "sep", None),) * 3,
                    out_specs=P(None, None, "sep", None))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    ref = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------- MoE
def test_moe_layer_routes_and_learns():
    from paddle_tpu.incubate.distributed.models.moe import (
        GShardGate, MoELayer,
    )

    paddle.seed(3)
    d = 16
    experts = [nn.Linear(d, d) for _ in range(4)]
    gate = GShardGate(d, num_expert=4, topk=2)
    moe = MoELayer(d_model=d, experts=experts, gate=gate)
    x = paddle.to_tensor(np.random.RandomState(0).rand(2, 8, d)
                         .astype("float32"))
    out = moe(x)
    assert out.shape == [2, 8, d]
    assert moe.aux_loss is not None and float(moe.aux_loss.numpy()) > 0
    # with generous capacity every token is routed: combine weights ~ 1
    out2 = moe(x)
    np.testing.assert_allclose(out.numpy(), out2.numpy())  # deterministic


def test_moe_expert_parallel_alltoall_matches_dense():
    """EP dispatch over the 8-device ep axis (lax.all_to_all inside
    shard_map) == the dense einsum path, forward AND grads (no drops)."""
    from paddle_tpu.incubate.distributed.models.moe import (
        GShardGate, MoELayer,
    )

    paddle.seed(17)
    d, E = 16, 8
    experts = [nn.Linear(d, d) for _ in range(E)]
    # capacity_factor 8 → no token ever dropped, so both paths agree exactly
    gate = GShardGate(d, num_expert=E, topk=2, capacity=(8.0, 16.0))
    moe = MoELayer(d_model=d, experts=experts, gate=gate)
    x_np = np.random.RandomState(1).rand(2, 16, d).astype("float32")

    x1 = paddle.to_tensor(x_np)
    x1.stop_gradient = False
    dense = moe(x1)
    dense.sum().backward()
    g_dense = {n: p.grad.numpy().copy()
               for n, p in moe.named_parameters() if p.grad is not None}
    for p in moe.parameters():
        p.clear_gradient()

    mesh = Mesh(np.array(jax.devices()), ("ep",))
    x2 = paddle.to_tensor(x_np)
    x2.stop_gradient = False
    ep = moe.expert_parallel_forward(x2, mesh, ep_axis="ep")
    np.testing.assert_allclose(ep.numpy(), dense.numpy(), rtol=2e-5,
                               atol=2e-6)
    ep.sum().backward()
    g_ep = {n: p.grad.numpy().copy()
            for n, p in moe.named_parameters() if p.grad is not None}
    assert set(g_ep) == set(g_dense)
    for n in g_dense:
        np.testing.assert_allclose(g_ep[n], g_dense[n], rtol=2e-4,
                                   atol=2e-5, err_msg=n)


# ----------------------------------------------------------------- recompute
def test_recompute_matches_plain():
    paddle.seed(9)
    net = nn.Sequential(nn.Linear(8, 32), nn.GELU(), nn.Linear(32, 8))
    x = paddle.to_tensor(np.random.RandomState(4).rand(4, 8)
                         .astype("float32"), stop_gradient=False)

    y1 = net(x)
    loss1 = y1.sum()
    loss1.backward()
    g1 = {n: p.grad.numpy().copy() for n, p in net.named_parameters()}
    for p in net.parameters():
        p.clear_gradient()

    x2 = paddle.to_tensor(x.numpy(), stop_gradient=False)
    y2 = recompute(net, x2)
    loss2 = y2.sum()
    loss2.backward()
    g2 = {n: p.grad.numpy() for n, p in net.named_parameters()}

    np.testing.assert_allclose(y1.numpy(), y2.numpy(), rtol=1e-6)
    for n in g1:
        np.testing.assert_allclose(g1[n], g2[n], rtol=1e-5, atol=1e-7)


# ----------------------------------------------------- sequence parallel
def test_sequence_parallel_linears_match_dense():
    from paddle_tpu.distributed.fleet.meta_parallel import (
        ColumnSequenceParallelLinear, RowSequenceParallelLinear,
    )

    paddle.seed(13)
    col = ColumnSequenceParallelLinear(8, 16, gather_output=False)
    row = RowSequenceParallelLinear(16, 8, input_is_parallel=True)
    x = paddle.to_tensor(np.random.RandomState(6).rand(2, 12, 8)
                         .astype("float32"))
    # eager (no mesh): pure dense behavior
    y = row(col(x))
    ref = x.matmul(col.weight).matmul(row.weight) + row.bias
    np.testing.assert_allclose(y.numpy(), ref.numpy(), rtol=1e-5)


# ------------------------------------------- hybrid global-norm grad clip
def test_hybrid_clip_grad_tp_matches_dense():
    """ClipGradByGlobalNorm under TP sharding == dense replica (round-2:
    HybridParallelOptimizer owns the cross-mesh clip, previously untested)."""
    from paddle_tpu.distributed.fleet.meta_parallel import (
        HybridParallelOptimizer,
    )

    def build():
        paddle.seed(11)

        class MLP(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc1 = ColumnParallelLinear(16, 32, gather_output=False)
                self.fc2 = RowParallelLinear(32, 8, input_is_parallel=True)

            def forward(self, x):
                return self.fc2(nn.functional.relu(self.fc1(x)))

        return MLP()

    rng = np.random.RandomState(3)
    x = rng.rand(8, 16).astype("float32") * 4  # big grads so the clip bites
    y = rng.rand(8, 8).astype("float32")

    def train(net, opt, sharded):
        params, buffers = extract_state(net)
        if sharded:
            sh = mp_shardings(net, _mp_mesh(4))
            params = {k: jax.device_put(v, sh[k])
                      for k, v in params.items()}
        for name, p in net.named_parameters():
            p._data = params[name]
        for _ in range(3):
            out = net(paddle.to_tensor(x))
            loss = ((out - paddle.to_tensor(y)) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
        return {k: np.asarray(v.numpy())
                for k, v in net.named_parameters()}

    net1 = build()
    opt1 = paddle.optimizer.SGD(
        learning_rate=0.1, parameters=net1.parameters(),
        grad_clip=paddle.nn.ClipGradByGlobalNorm(0.05))
    dense = train(net1, opt1, sharded=False)

    net2 = build()
    opt2 = HybridParallelOptimizer(paddle.optimizer.SGD(
        learning_rate=0.1, parameters=net2.parameters(),
        grad_clip=paddle.nn.ClipGradByGlobalNorm(0.05)))
    sharded = train(net2, opt2, sharded=True)

    for k in dense:
        np.testing.assert_allclose(dense[k], sharded[k], rtol=2e-4,
                                   atol=1e-6, err_msg=k)


def test_hybrid_clip_psum_inside_shard_map():
    """Inside shard_map the clip psums distributed-param norms over mp and
    counts replicated params once."""
    from paddle_tpu.distributed.fleet.meta_parallel import (
        HybridParallelClipGrad,
    )
    from paddle_tpu.nn.clip import ClipGradByGlobalNorm

    mesh = _mp_mesh(4)
    clip = HybridParallelClipGrad(ClipGradByGlobalNorm(1.0))

    # distributed param shard: each rank holds [1.0], global vector of 4
    # replicated param: [2.0] on every rank
    dist_shard = jnp.ones((4,))          # sharded dim-0 over mp
    repl = jnp.full((1,), 2.0)

    def body(d, r):
        class P_:
            need_clip = True
            is_distributed = True
            stop_gradient = False

        class R_:
            need_clip = True
            is_distributed = False
            stop_gradient = False

        from paddle_tpu.core.tensor import Tensor as T

        out = clip([(P_(), T(d)), (R_(), T(r))])
        return out[0][1]._data, out[1][1]._data

    d_clipped, r_clipped = shard_map(
        body, mesh=mesh, in_specs=(P("mp"), P(None)),
        out_specs=(P("mp"), P(None)))(dist_shard, repl)
    # global norm = sqrt(4*1 + 4) = sqrt(8); factor = 1/sqrt(8)
    expect = 1.0 / np.sqrt(8.0)
    np.testing.assert_allclose(np.asarray(d_clipped),
                               np.full(4, expect), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(r_clipped),
                               np.full(1, 2 * expect), rtol=1e-5)


def test_recompute_accepts_none_args_and_matches():
    """r5 regression: a literal None argument (attention_mask=None) used to
    collide with recompute's tensor-slot sentinel and crash; and the
    rematerialized backward must reproduce the exact losses (dropout keys
    ride the functional trace stream). Two steps of the one measured
    trainer, `ZeroTrainStep`."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.jit.functional import call_functional, extract_state
    from paddle_tpu.models import ErnieConfig, ErnieForPretraining
    from paddle_tpu.parallel import ZeroTrainStep

    def run(recompute):
        paddle.seed(3)
        cfg = ErnieConfig.tiny()
        cfg.recompute = recompute
        cfg.fused_mlm_loss = True
        model = ErnieForPretraining(cfg)
        model.train()
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=model.parameters())
        _, buffers = extract_state(model)
        key = jax.random.key(7)

        def loss_fn(params, ids, labels):
            (loss, _nsp), _ = call_functional(
                model, params, buffers, (ids, None, None, None, labels),
                rng_key=key, training=True)
            return loss.astype(jnp.float32)

        step = ZeroTrainStep(model, opt, loss_fn, stage=0, dp=1)
        params, state = step.init_state()
        ids = jnp.asarray(np.random.RandomState(0).randint(
            0, cfg.vocab_size, (2, 32)))
        losses = []
        for t in range(1, 3):
            loss, params, state = step(params, state, (ids, ids), 1e-3, t)
            losses.append(float(np.asarray(loss)))
        return losses

    dense, remat = run(False), run(True)
    assert dense[1] < dense[0]      # the update was applied
    np.testing.assert_allclose(dense, remat, rtol=1e-5)
