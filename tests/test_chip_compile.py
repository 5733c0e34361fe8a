"""The main path's Pallas kernels compiled at real widths for a described
(not attached) TPU v5e — the third rehearsal of a chip run, kept as a
test — and the names the device trace is read by (kernels, scopes,
executables), read from the compiler's own text of the programs the
benchmark's cells run, at tiny widths; and on that same text, what
those programs must not hold: a whole attention matrix, whole logits,
a matmul operand wider than bf16, the MLM head over every position
outside its fallback. A compile that passes is a compile: nothing here
runs on a chip.

Only one process may hold libtpu, so the topology is described inside a
fixture of this one file and never at import; under xdist only the
worker that is handed this file loads the library.
"""
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.profiler import scopes
from paddle_tpu.serving import attention as paged

# libtpu otherwise spends minutes asking a metadata server that is not
# there who it is
_DESCRIBED = {"TPU_SKIP_MDS_QUERY": "true",
              "TPU_ACCELERATOR_TYPE": "v5litepod-4",
              "TPU_WORKER_ID": "0",
              "TPU_WORKER_HOSTNAMES": "localhost",
              "TPU_LOG_DIR": "disabled"}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    saved = {k: os.environ.get(k) for k in _DESCRIBED}
    for k, v in _DESCRIBED.items():
        os.environ.setdefault(k, v)
    # a compile for a described chip can be written to a persistent
    # cache but never read back: keep the cache off around these
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# GPT-3 1.3B serving widths: 16 heads of 128, pages of 16 tokens, 8 rows
# of up to 2048 tokens; ERNIE-base training widths: 12 heads of 64,
# hidden 768, batch 32 x 512
_POOL = ((16, 1024, 16, 128), jnp.bfloat16)
_TABLE = ((8, 128), jnp.int32)


def _paged_decode(rows=16, heads=16, pool=((16, 2049, 16, 128), jnp.bfloat16),
                  pages=128, scales=False):
    """The serve cell's own decode call: 16 kv heads of 128, pages of 16
    tokens, 128 pages a row, the engine's pool of 2,049 pages, bf16; one
    executable a power-of-two row count."""
    args = [((rows, 1, heads, 128), jnp.bfloat16), pool, pool,
            ((rows, pages), jnp.int32), ((rows,), jnp.int32)]
    if not scales:
        return paged._paged_decode_pallas, args

    def fn(q, k, v, table, pos, k_scale, v_scale):
        return paged._paged_decode_pallas(q, k, v, table, pos,
                                          k_scale=k_scale, v_scale=v_scale)

    return fn, args + [(pool[0][:3] + (1,), jnp.float32)] * 2


def _ragged_paged():
    args = [((1, 256, 16, 128), jnp.bfloat16), _POOL, _POOL, _TABLE,
            ((256,), jnp.int32), ((256,), jnp.int32)]
    return paged._ragged_paged_pallas, args


def _flash_causal():
    def fn(q, k, v):
        return pk._flash_attention_data(q, k, v, is_causal=True)

    return fn, [((1, 2048, 16, 128), jnp.bfloat16)] * 3


def _flash_train_dropout():
    def fn(q, k, v, seed):
        def loss(q, k, v):
            out = pk._flash_attention_data(q, k, v, seed=seed,
                                           dropout_p=0.1)
            return out.astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    return fn, [((32, 512, 12, 64), jnp.bfloat16)] * 3 + [((1,), jnp.int32)]


def _layer_norm_train():
    def fn(x, w, b):
        def loss(x, w, b):
            return pk.layer_norm_fused(x, w, b).astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(x, w, b)

    return fn, [((16384, 768), jnp.bfloat16), ((768,), jnp.bfloat16),
                ((768,), jnp.bfloat16)]


def _layer_norm_decode():
    return pk.layer_norm_fused, [((8, 2048), jnp.bfloat16),
                                 ((2048,), jnp.bfloat16),
                                 ((2048,), jnp.bfloat16)]


def _mla_decode(rows=32):
    """The latent cell's own decode call: 32 heads over one cached row
    of 512 + 64 a token held in 640 columns, pages of 16, 1,088 pages a
    row, the configuration's pool of 26,000 pages, bf16."""
    def fn(q, pool, table, pos):
        return paged._mla_decode_pallas(q, pool, table, pos,
                                        scale=192 ** -0.5, latent=512)

    return fn, [((rows, 32, 576), jnp.bfloat16),
                ((26000, 16, 640), jnp.bfloat16),
                ((rows, 1088), jnp.int32), ((rows,), jnp.int32)]


def _dsa_index(rows=32):
    """The sparse cell's own index call: 64 index heads of 128 over one
    key of 128 a token, pages of 16, 2,496 pages a row, the
    configuration's pool of 47,800 pages, bf16."""
    return paged._dsa_index_pallas, [
        ((rows, 64, 128), jnp.bfloat16), ((rows, 64), jnp.float32),
        ((47800, 16, 128), jnp.bfloat16), ((rows, 2496), jnp.int32),
        ((rows,), jnp.int32)]


def _mla_sparse_decode(rows=32):
    """The sparse cell's own attention call: 128 heads over the 2,048
    chosen rows of 512 + 64 held in 640 columns, gathered from the same
    pool by token index."""
    def fn(q, pool, table, chosen, n):
        return paged._mla_sparse_decode_pallas(
            q, pool, table, chosen, n, scale=192 ** -0.5 * 1.87386,
            latent=512)

    return fn, [((rows, 128, 576), jnp.bfloat16),
                ((47800, 16, 640), jnp.bfloat16),
                ((rows, 2496), jnp.int32), ((rows, 2048), jnp.int32),
                ((rows,), jnp.int32)]


def _moe_grouped_matmul(pairs, down=False):
    """One projection of the 256 experts of 2048 x 768 over the sorted
    pairs of a decode step (256, or 8 for one row) or of a prefill's
    8,192-token chunk (65,536)."""
    from paddle_tpu.models import mla_moe

    def fn(xs, w, sizes):
        was, mla_moe.GROUPED_MATMUL = mla_moe.GROUPED_MATMUL, "gmm"
        try:
            return mla_moe._grouped_matmul(xs, w, sizes)
        finally:
            mla_moe.GROUPED_MATMUL = was

    k, n = (768, 2048) if down else (2048, 768)
    return fn, [((pairs, k), jnp.bfloat16), ((256, k, n), jnp.bfloat16),
                ((256,), jnp.int32)]


def _moe_gather_dispatch():
    """The capacity-bound expert layer's dispatch gather: indices
    prefetched as scalars, one HBM -> VMEM copy a row."""
    return pk.gather_rows, [((1024, 512), jnp.bfloat16),
                            ((2048,), jnp.int32)]


_ONE_CHIP = {
    "paged_decode": _paged_decode,
    **{f"paged_decode_rows{n}": functools.partial(_paged_decode, rows=n)
       for n in (1, 2, 4, 8)},
    # grouped-query: 32 q heads on 8 kv heads
    "paged_decode_gqa": functools.partial(
        _paged_decode, rows=8, heads=32,
        pool=((8, 1024, 16, 128), jnp.bfloat16)),
    # int8 pages of 32 tokens with their fp32 scale slabs
    "paged_decode_int8": functools.partial(
        _paged_decode, rows=8, pool=((16, 512, 32, 128), jnp.int8),
        pages=64, scales=True),
    "ragged_paged": _ragged_paged,
    "flash_causal": _flash_causal,
    "flash_train_dropout": _flash_train_dropout,
    "layer_norm_train": _layer_norm_train,
    "layer_norm_decode": _layer_norm_decode,
    "mla_decode": _mla_decode,
    "mla_decode_rows1": functools.partial(_mla_decode, rows=1),
    "dsa_index": _dsa_index,
    "dsa_index_rows1": functools.partial(_dsa_index, rows=1),
    "mla_sparse_decode": _mla_sparse_decode,
    "mla_sparse_decode_rows1": functools.partial(_mla_sparse_decode, rows=1),
    "moe_grouped_matmul_decode": functools.partial(_moe_grouped_matmul, 256),
    "moe_grouped_matmul_one_row": functools.partial(_moe_grouped_matmul, 8),
    "moe_grouped_matmul_prefill": functools.partial(_moe_grouped_matmul,
                                                    65536),
    "moe_grouped_matmul_down": functools.partial(_moe_grouped_matmul, 65536,
                                                 down=True),
    "moe_gather_dispatch": _moe_gather_dispatch,
}


def _tp_overlap_ring(devices):
    """The split-collective ring of tensor-parallel serving."""
    from paddle_tpu.parallel.mesh import build_mesh
    from paddle_tpu.serving.overlap import overlap_probe_fn

    mesh = build_mesh((("tp", 4),), devices=devices)
    x = jax.ShapeDtypeStruct((8, 256), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    return jax.jit(overlap_probe_fn(mesh, 256, 2)), [x], "collective-permute"


def _ring_attention(devices):
    """Causal ring attention over a sequence split four ways: the step
    kernel's SMEM offsets and its `pl.when` block skip."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(devices), ("sep",))
    spec = P(None, "sep", None, None)
    sharding = NamedSharding(mesh, spec)

    def ring(q, k, v):
        return pk.ring_flash_attention_pallas(q, k, v, axis_name="sep",
                                              causal=True)

    fn = jax.jit(jax.shard_map(ring, mesh=mesh, in_specs=(spec,) * 3,
                               out_specs=spec),
                 in_shardings=sharding, out_shardings=sharding)
    x = jax.ShapeDtypeStruct((2, 1024, 4, 64), jnp.bfloat16)
    return fn, [x, x, x], "tpu_custom_call"


def _zero2_reduce_scatter(devices):
    """ZeRO-2 through hapi's step (`group_sharded_parallel`, level
    `os_g`): the TPU pipeline turns the gradient's all-reduce and shard
    slice into a reduce-scatter, which the CPU pipeline never creates."""
    from types import SimpleNamespace

    import paddle_tpu as paddle
    from jax.sharding import Mesh
    from paddle_tpu import nn
    from paddle_tpu.distributed.fleet.meta_parallel import (
        group_sharded_parallel)

    group = SimpleNamespace(mesh=Mesh(np.array(devices), ("sharding",)),
                            axis_name="sharding")
    paddle.seed(7)
    net = nn.Sequential(nn.Linear(64, 256), nn.ReLU(), nn.Linear(256, 64))
    opt = paddle.optimizer.Adam(learning_rate=0.01,
                                parameters=net.parameters())
    wrapped, _ = group_sharded_parallel(net, opt, level="os_g", group=group)
    model = paddle.Model(wrapped)
    model.prepare(optimizer=opt, loss=nn.MSELoss())
    params, buffers = model._sync_state_in()
    model._ensure_opt_state(params)

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    data = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    args = [abstract(params), abstract(buffers), abstract(model._opt_state),
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jax.random.key(0).dtype),
            (data,), (data,)]
    return model._build_train_step(), args, "reduce-scatter"


# over all four chips of the host
_FOUR_CHIPS = {"tp_overlap_ring": _tp_overlap_ring,
               "ring_attention": _ring_attention,
               "zero2_reduce_scatter": _zero2_reduce_scatter}


@pytest.mark.parametrize("case", [*_ONE_CHIP, *_FOUR_CHIPS])
def test_compiles_for_v5e(topo, case):
    if case in _FOUR_CHIPS:
        fn, args, wanted = _FOUR_CHIPS[case](topo.devices)
    else:
        fn, shapes = _ONE_CHIP[case]()
        fn, wanted = jax.jit(fn), "tpu_custom_call"
        one_chip = SingleDeviceSharding(topo.devices[0])
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in shapes]
    assert wanted in fn.lower(*args).compile().as_text()


# ------------------------------------------ the names the trace is read by

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The tiny programs' sizes that the gates below look for. 512 tokens a
# row and a prefill bucket of 512 are no other width of these models
# (two heads everywhere), and 4,096 train tokens are two of the fused
# loss's chunks of 2,048.
_VOCAB, _HEADS, _SEQ = 1024, 2, 512
_TRAIN_ROWS = 8


@pytest.fixture(scope="module")
def kernel_paths():
    """Traces here see the CPU and would take the reference paths: steer
    them onto the kernels, as the chip would."""
    was = pk._on_tpu
    pk._on_tpu = lambda: True
    try:
        yield
    finally:
        pk._on_tpu = was


def _custom_calls(text: str) -> set:
    """Own names, without their numbers, of the program's custom calls:
    what the trace's `XLA Ops` line calls a kernel's events."""
    return set(re.findall(r"%([A-Za-z_]+)[.\d]* = [^\n]*custom-call", text))


def _scoped(text: str, scope: str) -> list:
    """The `op_name`s that have `scope` as a path component, bare or
    inside `jvp(...)` / `transpose(jvp(...))`."""
    part = re.compile(r"(?:^|/)(?:transpose\()?(?:jvp\()?" + scope
                      + r"\)*(?:/|$)")
    return [n for n in re.findall(r'op_name="([^"]*)"', text)
            if part.search(n)]


@pytest.fixture(scope="module")
def engine():
    """A two-layer GPT behind a default engine, heads of 128 so that the
    paged kernel's gates open; built on the CPU, nothing is run."""
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServingEngine, SpecConfig

    cfg = GPTConfig(vocab_size=_VOCAB, hidden_size=256, num_hidden_layers=2,
                    num_attention_heads=_HEADS, intermediate_size=768,
                    max_position_embeddings=_SEQ)
    model = GPTForCausalLM(cfg).bfloat16()
    model.eval()
    kw = dict(page_size=16, max_batch_size=4, max_seq_len=_SEQ,
              kv_dtype="bf16")
    return {"plain": ServingEngine(model, **kw),
            "spec": ServingEngine(model, spec_config=SpecConfig(), **kw)}


def _compile_serve(eng, device, rows, bucket, decode=True):
    """The engine's decode block (horizon 8, `rows` rows; unless not
    `decode`) and its prefill of `bucket` tokens, compiled for one
    described chip from shapes alone."""
    one_chip = SingleDeviceSharding(device)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def abstract(tree):
        return jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), tree)

    def knobs(b):
        return (sds((b, 2), jnp.uint32), sds((b,), jnp.float32),
                sds((b,), jnp.int32), sds((b,), jnp.float32))

    state = (abstract(eng.params), abstract(eng.buffers))
    pools, pages = abstract(eng.cache.pools), eng.max_pages_per_seq

    def slots(b):
        # a model with recurrent layers is told each row's state slot
        return ({"slots": sds((b,), jnp.int32)}
                if eng.cache.slot_allocator is not None else {})

    compiled = {"prefill": eng._prefill_jit(bucket).lower(
        *state, sds((1, bucket), jnp.int32), pools,
        sds((1, pages), jnp.int32), sds((), jnp.int32), *knobs(1),
        **slots(1)).compile()}
    if decode:
        compiled["decode_block"] = eng._decode_block_jit(8).lower(
            *state, sds((rows,), jnp.int32), pools,
            sds((rows, pages), jnp.int32), sds((rows,), jnp.int32),
            *knobs(rows), sds((rows,), jnp.int32),
            sds((rows,), jnp.int32), **slots(rows)).compile()
    return compiled


@pytest.fixture(scope="module")
def serve_hlo(topo, kernel_paths, engine):
    """The compiler's text of the decode block and of a bucketed prefill
    for one described chip."""
    compiled = _compile_serve(engine["plain"], topo.devices[0], 4, _SEQ)
    return {name: c.as_text() for name, c in compiled.items()}


@pytest.fixture(scope="module")
def train_hlo(topo, kernel_paths):
    """The compiler's text of a one-layer ERNIE's `ZeroTrainStep`
    (forward, gradient, bf16-over-fp32 Adam) for one described chip:
    state placed on the CPU, the step built over a mesh of the described
    device and lowered from shapes."""
    import paddle_tpu as paddle
    from jax.sharding import Mesh
    from paddle_tpu.jit.functional import call_functional, extract_state
    from paddle_tpu.models import ErnieConfig, ErnieForPretraining
    from paddle_tpu.parallel import ZeroTrainStep
    from paddle_tpu.parallel.mesh import DP_AXIS, TP_AXIS

    model = ErnieForPretraining(ErnieConfig(
        vocab_size=_VOCAB, hidden_size=128, num_hidden_layers=1,
        num_attention_heads=_HEADS, intermediate_size=256,
        max_position_embeddings=_SEQ, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, fused_mlm_loss=True))
    model.train()
    _, buffers = extract_state(model)
    key = jax.random.key(0)

    def loss_fn(params, ids, labels):
        (loss, _nsp), _ = call_functional(
            model, params, buffers, (ids, None, None, None, labels),
            rng_key=key, training=True)
        return loss.astype(jnp.float32)

    opt = paddle.optimizer.Adam(learning_rate=1e-4,
                                parameters=model.parameters())
    step = ZeroTrainStep(model, opt, loss_fn, stage=2, dp=1,
                         param_dtype="bf16")
    params, state = step.init_state()
    step.mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1),
                     (DP_AXIS, TP_AXIS))
    step._build(2)

    def abstract(tree, specs):
        return jax.tree_util.tree_map(
            lambda a, spec: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(step.mesh, spec)),
            tree, specs)

    def scalar(dtype):
        return jax.ShapeDtypeStruct((), dtype,
                                    sharding=NamedSharding(step.mesh, P()))

    batch = (jax.ShapeDtypeStruct(
        (_TRAIN_ROWS, _SEQ), jnp.int32,
        sharding=NamedSharding(step.mesh, P(DP_AXIS))),) * 2
    return step._step.lower(
        abstract(params, {k: step._spec[k] for k in params}),
        abstract(state, {k: dict(v) for k, v in step._state_spec.items()}),
        batch, scalar(jnp.float32), scalar(jnp.int32)).compile().as_text()


def test_paged_decode_kernel_is_named_where_it_is_created(serve_hlo):
    calls = _custom_calls(serve_hlo["decode_block"])
    assert scopes.PAGED_DECODE_KERNEL in calls
    assert not any("closed_call" in c for c in calls)


def test_every_kernel_under_paged_attention_is_a_paged_decode(serve_hlo):
    """`paged_decode_kernel_roofline.serve` sums the events whose name
    holds `paged_decode`: a kernel of the decode step's paged attention
    under another name (a second call, a combine step) would be work the
    roofline leaves out."""
    calls = re.findall(r'%([\w.]+) = [^\n]*custom-call\([^\n]*'
                       r'custom_call_target="tpu_custom_call"[^\n]*'
                       r'op_name="([^"]*)"', serve_hlo["decode_block"])
    inside = [name for name, op_name in calls
              if _scoped(f'op_name="{op_name}"', scopes.PAGED_ATTENTION)]
    assert inside
    assert all(scopes.PAGED_DECODE_KERNEL in name for name in inside), inside


def test_paged_ragged_kernel_is_named_where_it_is_created(topo):
    fn, shapes = _ragged_paged()
    one_chip = SingleDeviceSharding(topo.devices[0])
    text = jax.jit(fn).lower(*[
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes]).compile().as_text()
    assert scopes.PAGED_RAGGED_KERNEL in _custom_calls(text)


def test_flash_keeps_the_names_its_metric_reads(train_hlo):
    """`flash_roofline.train` matches and counts the flash kernels by
    the names the compiler derives from the path in front of their
    `pallas_call`: a scope around the call would rename them."""
    calls = _custom_calls(train_hlo)
    assert "jvp_jit__flash_attention_data__" in calls
    assert "transpose_jvp_jit__flash_attention_data___" in calls
    with open(os.path.join(REPO, "chipbench", "metrics",
                           "flash_roofline.train.json")) as f:
        args = json.load(f)["args"]
    flash = [c for c in calls if args["match"] in c]
    units = [c for c in flash if args["count"] in c
             and args["skip"] not in c]
    assert len(units) == 1 and len(flash) == 2


@pytest.mark.parametrize("program", ["decode_block", "prefill"])
def test_serve_executables_are_named(serve_hlo, program):
    assert serve_hlo[program].startswith(f"HloModule jit_{program},")


def test_train_executable_is_named(train_hlo):
    assert train_hlo.startswith("HloModule jit_zero_train_step,")


@pytest.mark.parametrize("name,build", [
    ("prefill", lambda e: e["plain"]._prefill_jit(128)),
    ("prefill_offset", lambda e: e["plain"]._prefill_offset_jit(128)),
    ("prefill_chunk", lambda e: e["plain"]._chunked_prefill_jit()),
    ("decode_block", lambda e: e["plain"]._decode_block_jit(8)),
    ("ragged_block", lambda e: e["plain"]._ragged_jit(64)),
    ("spec_decode_block", lambda e: e["spec"]._spec_block_jit(8)),
    ("spec_ragged_block", lambda e: e["spec"]._spec_ragged_jit(64)),
])
def test_every_engine_executable_says_what_it_is(engine, name, build):
    # a jitted function's module is `jit_<__name__>`
    assert build(engine).__name__ == name


@pytest.mark.parametrize("scope", scopes.SERVE_SCOPES)
def test_serve_scope_reaches_the_compiled_step(serve_hlo, scope):
    program = ("prefill" if scope == scopes.PREFILL_ATTENTION
               else "decode_block")
    assert _scoped(serve_hlo[program], scope)


@pytest.mark.parametrize("scope", scopes.TRAIN_SCOPES)
def test_train_scope_reaches_the_compiled_step(train_hlo, scope):
    names = _scoped(train_hlo, scope)
    assert names
    if scope not in (scopes.GRAD_REDUCE, scopes.OPTIMIZER_UPDATE):
        # forward and backward both carry the layer's scope
        assert any("transpose(jvp(" + scope in n for n in names)


# ------------------------------- the K/V write leaves the pool where it is

_CELL_POOL = (16, 2049, 16, 128)   # one layer's K (or V) pool, 134 MB in bf16


@pytest.fixture(scope="module")
def serve_pool_compiled(topo, kernel_paths):
    """The serve steps of the GPT cells at their own attention widths and
    their own pool (16 heads of 128, pages of 16, 16 rows of 2,048
    tokens: 2,049 pages), two layers with a narrow MLP and vocabulary:
    the full decode block and the largest prefill bucket."""
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServingEngine

    cfg = GPTConfig(vocab_size=1024, hidden_size=2048, num_hidden_layers=2,
                    num_attention_heads=16, intermediate_size=512,
                    max_position_embeddings=2048)
    model = GPTForCausalLM(cfg).bfloat16()
    model.eval()
    eng = ServingEngine(model, page_size=16, max_batch_size=16,
                        max_seq_len=2048, kv_dtype="bf16")
    pools = jax.tree_util.tree_leaves(eng.cache.pools)
    assert all(p.shape == _CELL_POOL and p.dtype == jnp.bfloat16
               for p in pools)
    return _compile_serve(eng, topo.devices[0], 16, 2048)


def _pool_sized_moves(text: str, pool=_CELL_POOL,
                      ops: str = "copy|transpose") -> list:
    """The `copy` and `transpose` (or `ops`) instructions whose result
    has as many elements as `pool`: the pool itself or any view of it."""
    size = int(np.prod(pool))
    moves = re.findall(r"\n\s*(?:ROOT )?(%\S+ = \w+\[([\d,]+)\]\S* "
                       r"(?:" + ops + r")\([^\n]*)", text)
    return [line[:240] for line, dims in moves
            if np.prod([int(d) for d in dims.split(",")]) == size]


@pytest.mark.parametrize("program", ["decode_block", "prefill"])
def test_no_copy_of_a_whole_kv_pool(serve_pool_compiled, program):
    """`_write_pages` updates the donated pool in place. Written as
    `pool.at[:, entries, slots].set(...)` the compiler relayouts the
    whole operand and back at every write, 71% of the device's time in
    the GPT cells until PR 30: the op_name of what comes back says which
    consumer asked for another layout."""
    text = serve_pool_compiled[program].as_text()
    assert "[16,2049,16,128]" in text
    assert _pool_sized_moves(text) == []


def test_decode_block_s_temporaries_are_under_one_pool(serve_pool_compiled):
    temp = (serve_pool_compiled["decode_block"].memory_analysis()
            .temp_size_in_bytes)
    assert temp < int(np.prod(_CELL_POOL)) * 2


# ------------------------- the latent-attention, routed-expert decoder

@pytest.fixture(scope="module")
def mla_moe_hlo(topo, kernel_paths):
    """The compiler's text of the decode block and of a bucketed prefill
    of a small MlaMoe model (one dense and one expert layer, head widths
    and the latent rank as published) behind a default engine."""
    from paddle_tpu.models import MlaMoeConfig, MlaMoeForCausalLM
    from paddle_tpu.serving import ServingEngine

    cfg = MlaMoeConfig(vocab_size=_VOCAB, hidden_size=256,
                       num_hidden_layers=2, num_attention_heads=_HEADS,
                       q_lora_rank=128, intermediate_size=768,
                       moe_intermediate_size=128, n_routed_experts=8,
                       num_experts_per_tok=2, dtype="bfloat16",
                       deferred_weights=True)
    model = MlaMoeForCausalLM(cfg)
    model.eval()
    eng = ServingEngine(model, page_size=16, max_batch_size=4,
                        max_seq_len=_SEQ, kv_dtype="bf16")
    compiled = _compile_serve(eng, topo.devices[0], 4, _SEQ)
    return {name: c.as_text() for name, c in compiled.items()}


def test_mla_decode_kernel_is_named_where_it_is_created(mla_moe_hlo):
    calls = _custom_calls(mla_moe_hlo["decode_block"])
    assert scopes.MLA_DECODE_KERNEL in calls
    assert scopes.PAGED_DECODE_KERNEL not in calls
    assert scopes.MLA_DECODE_KERNEL not in _custom_calls(
        mla_moe_hlo["prefill"])


@pytest.mark.parametrize("program", ["decode_block", "prefill"])
def test_expert_matmuls_keep_the_name_their_metric_reads(mla_moe_hlo,
                                                         program):
    """`moe_experts_roofline.serve` sums the events whose name holds its
    `match`: the name the compiler derives for the grouped matmul's
    kernel from the jitted function around its `pallas_call`, three a
    layer, all under `mlp/moe_experts`."""
    with open(os.path.join(REPO, "chipbench", "metrics",
                           "moe_experts_roofline.serve.json")) as f:
        match = json.load(f)["args"]["match"]
    text = mla_moe_hlo[program]
    calls = re.findall(r'%([\w.\-]+) = [^\n]*custom-call\([^\n]*'
                       r'custom_call_target="tpu_custom_call"[^\n]*'
                       r'op_name="([^"]*)"', text)
    under = [name for name, op_name in calls
             if _scoped(f'op_name="{op_name}"', scopes.MOE_EXPERTS)]
    assert len(under) >= 3
    assert all(match in name for name in under), under
    assert not [name for name, op_name in calls if match in name
                and not _scoped(f'op_name="{op_name}"', scopes.MOE_EXPERTS)]


@pytest.mark.parametrize("scope", scopes.SERVE_SCOPES)
def test_serve_scope_reaches_the_latent_model_s_step(mla_moe_hlo, scope):
    program = ("prefill" if scope == scopes.PREFILL_ATTENTION
               else "decode_block")
    assert _scoped(mla_moe_hlo[program], scope)


def _kernel_grids(text: str, name: str) -> list:
    """The grid of each Mosaic kernel of the program whose custom call's
    name holds `name`, read from its serialized body; a dimension the
    program sizes at run time reads None."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir, passmanager

    grids = []
    for line in text.splitlines():
        call = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = [^\n]*custom-call\(",
                        line)
        if not call or name not in call.group(1):
            continue
        body = base64.b64decode(re.search(r'"body":"([^"]+)"',
                                          line).group(1))
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(body)
            passmanager.PassManager.parse(
                "builtin.module(mosaic-serde{serialize=false})").run(
                    module.operation)
            bounds = re.search(r"iteration_bounds = array<i64: ([^>]*)>",
                               str(module)).group(1)
        grids.append(tuple(None if int(b) < 0 else int(b)
                           for b in bounds.split(",")))
    return grids


_LONG_BUCKET = 16384


@pytest.fixture(scope="module")
def mla_moe_long_prefill(topo, kernel_paths):
    """The compiler's text of the prefill of the latent cell's largest
    power-of-two bucket, 16,384 tokens, for a small MlaMoe model at the
    published head widths (192 for scores, 128 for values)."""
    from paddle_tpu.models import MlaMoeConfig, MlaMoeForCausalLM
    from paddle_tpu.serving import ServingEngine

    cfg = MlaMoeConfig(vocab_size=_VOCAB, hidden_size=256,
                       num_hidden_layers=2, num_attention_heads=_HEADS,
                       q_lora_rank=128, intermediate_size=768,
                       moe_intermediate_size=128, n_routed_experts=8,
                       num_experts_per_tok=2, dtype="bfloat16",
                       deferred_weights=True)
    model = MlaMoeForCausalLM(cfg)
    model.eval()
    eng = ServingEngine(model, page_size=16, max_batch_size=1,
                        max_seq_len=_LONG_BUCKET, kv_dtype="bf16",
                        num_pages=8)
    return _compile_serve(eng, topo.devices[0], 1, _LONG_BUCKET,
                          decode=False)["prefill"].as_text()


def test_latent_prefill_flash_walks_the_prompt_s_blocks(mla_moe_long_prefill):
    """Each layer's flash over the 16,384 bucket is the prompt-length
    kernel: a grid of (batch, heads, steps) whose steps the program
    sizes from the prompt's length, fed by the bucket's step tables
    (528 causal steps of 512-blocks); the whole-bucket kernel is not in
    the program."""
    text = mla_moe_long_prefill
    assert _kernel_grids(text, "flash_prefill") == [(1, _HEADS, None)] * 2
    assert not _kernel_grids(text, "_flash_attention_data")
    calls = re.findall(r"%flash_prefill[.\d]* = [^\n]*custom-call[^\n]*",
                       text)
    operands = ("operand_layout_constraints={s32[], s32[528]{0}, "
                "s32[528]{0}, s32[528]{0}, s32[1]{0}, bf16[1,2,16384,256]")
    assert len(calls) == 2
    assert all(operands in c for c in calls), [c[:400] for c in calls]


def test_train_flash_grids_are_static(train_hlo):
    """The train step's flash kernels keep their (batch, heads, q blocks,
    k blocks) grids, every dimension fixed at compile time."""
    for name in ("jvp_jit__flash_attention_data__",
                 "transpose_jvp_jit__flash_attention_data___"):
        grids = _kernel_grids(train_hlo, name)
        assert grids and all(g == (_TRAIN_ROWS, _HEADS, 1, 1)
                             for g in grids), (name, grids)
    assert "flash_prefill" not in train_hlo


# ------------------------------- the same decoder with sparse attention

# rows a decode block of the small sparse engine below is compiled for,
# the most tokens a row of its table holds, and its latent row in whole
# tiles: no array of the decode block may be (rows, context, row)
_SPARSE_ROWS, _SPARSE_CONTEXT, _SPARSE_ROW = 4, _SEQ, 640


@pytest.fixture(scope="module")
def mla_sparse_hlo(topo, kernel_paths):
    """As `mla_moe_hlo` with the indexer on (4 index heads of 128, 64 of
    512 positions attended), two groups of experts of which one stays,
    YaRN, and experts 2-5 of the router's 8 held."""
    from paddle_tpu.models import MlaMoeConfig, MlaMoeForCausalLM
    from paddle_tpu.serving import ServingEngine

    cfg = MlaMoeConfig(vocab_size=_VOCAB, hidden_size=256,
                       num_hidden_layers=2, num_attention_heads=_HEADS,
                       q_lora_rank=128, intermediate_size=768,
                       moe_intermediate_size=128, n_routed_experts=4,
                       router_experts=8, expert_offset=2,
                       num_experts_per_tok=2, n_group=2, topk_group=1,
                       index_topk=64, index_n_heads=4, index_head_dim=128,
                       rope_scaling={"type": "yarn", "factor": 4.0,
                                     "original_max_position_embeddings": 128,
                                     "beta_fast": 32, "beta_slow": 1,
                                     "mscale": 1.0, "mscale_all_dim": 1.0},
                       dtype="bfloat16", deferred_weights=True)
    model = MlaMoeForCausalLM(cfg)
    model.eval()
    eng = ServingEngine(model, page_size=16, max_batch_size=_SPARSE_ROWS,
                        max_seq_len=_SPARSE_CONTEXT, kv_dtype="bf16")
    compiled = _compile_serve(eng, topo.devices[0], _SPARSE_ROWS, _SEQ)
    return {name: c.as_text() for name, c in compiled.items()}


def test_sparse_decode_kernels_are_named_where_they_are_created(
        mla_sparse_hlo):
    calls = _custom_calls(mla_sparse_hlo["decode_block"])
    assert {scopes.DSA_INDEX_KERNEL,
            scopes.MLA_SPARSE_DECODE_KERNEL} <= calls
    # the dense latent kernel is off the sparse model's path
    assert scopes.MLA_DECODE_KERNEL not in calls
    assert not {scopes.DSA_INDEX_KERNEL, scopes.MLA_SPARSE_DECODE_KERNEL
                } & _custom_calls(mla_sparse_hlo["prefill"])


def _whole_context_arrays(text: str) -> list:
    """Arrays of the program's text that hold a cached row of every
    position of every row's context: (rows, context, row width), the rows
    and the context in either order, whole or folded into one
    dimension."""
    rows, ctx, row = _SPARSE_ROWS, _SPARSE_CONTEXT, _SPARSE_ROW
    shapes = {f"{rows},{ctx},{row}", f"{ctx},{rows},{row}",
              f"{rows * ctx},{row}", f"{rows},{ctx // 16},16,{row}"}
    return sorted({m.group(0) for m in re.finditer(
        r"(?:bf16|f32)\[([\d,]+)\]", text) if m.group(1) in shapes})


def test_no_decode_executable_holds_a_row_s_whole_context(mla_sparse_hlo,
                                                          mla_moe_hlo):
    """Sparse attention reads the chosen rows alone: the decode block
    has no array of (rows, max context, 640). The same search finds
    nothing in the dense latent model's block either (its kernel walks
    the pages in place), and is shown to see: the jnp path of the dense
    decode, compiled for the same sizes, has the array."""
    assert _whole_context_arrays(mla_sparse_hlo["decode_block"]) == []
    assert _whole_context_arrays(mla_moe_hlo["decode_block"]) == []
    from paddle_tpu.serving.kv_cache import LatentLayerCache

    def gathers(q, pool, table, pos):
        return paged._mla_decode_reference(
            q, LatentLayerCache(pool, table), pos, 0.1, 512)

    text = jax.jit(gathers).lower(
        jax.ShapeDtypeStruct((_SPARSE_ROWS, _HEADS, 576), jnp.bfloat16),
        jax.ShapeDtypeStruct((200, 16, _SPARSE_ROW), jnp.bfloat16),
        jax.ShapeDtypeStruct((_SPARSE_ROWS, _SPARSE_CONTEXT // 16),
                             jnp.int32),
        jax.ShapeDtypeStruct((_SPARSE_ROWS,), jnp.int32)).as_text()
    assert re.search(
        rf"tensor<{_SPARSE_ROWS}x{_SPARSE_CONTEXT // 16}x16x{_SPARSE_ROW}x"
        rf"|tensor<{_SPARSE_ROWS}x{_SPARSE_CONTEXT}x{_SPARSE_ROW}x", text)


# each sub-scope's serving scope and the programs it is found in; the
# Mamba-2 mixer's are the hybrid decoder's, sparse attention's the sparse
# latent decoder's, the others the latent one's
_DSA_HOME = {"decode_block": scopes.PAGED_ATTENTION,
             "prefill": scopes.PREFILL_ATTENTION}
_SUBSCOPE_HOME = {
    scopes.MLA_ABSORB: (scopes.PAGED_ATTENTION, ["decode_block"]),
    scopes.SSM_IN_PROJ: (scopes.ATTN_QKV, ["decode_block", "prefill"]),
    scopes.SSM_CONV: (scopes.ATTN_QKV, ["decode_block", "prefill"]),
    scopes.SSM_STATE_UPDATE: (scopes.PAGED_ATTENTION, ["decode_block"]),
    scopes.SSM_CHUNK_SCAN: (scopes.PREFILL_ATTENTION, ["prefill"]),
    scopes.SSM_GATE_OUT: (scopes.ATTN_OUT, ["decode_block", "prefill"]),
}


@pytest.mark.parametrize("scope", scopes.SERVE_SUBSCOPES)
def test_sub_scope_reaches_the_compiled_step(mla_moe_hlo, hybrid_hlo,
                                             mla_sparse_hlo, scope):
    parent, programs = _SUBSCOPE_HOME.get(
        scope, (scopes.MLP, ["decode_block", "prefill"]))
    hlo = (hybrid_hlo if scope.startswith("ssm_")
           else mla_sparse_hlo if scope.startswith("dsa_") else mla_moe_hlo)
    for program in programs:
        names = _scoped(hlo[program], scope)
        assert names, (program, scope)
        if scope.startswith("dsa_"):
            # the indexer's projections lie under `attn_qkv`, its scores,
            # the choice and the attention under the step's attention
            parent = "(?:" + _DSA_HOME[program] + (
                "|" + scopes.ATTN_QKV if scope == scopes.DSA_INDEX else ""
            ) + ")"
            assert any(re.search(_DSA_HOME[program] + r"/(?:[^/]+/)*"
                                 + scope, n) for n in names)
        # nested inside the serving scope the benchmark's list holds
        assert all(re.search(parent + r"/(?:[^/]+/)*" + scope, n)
                   for n in names)
    if scope.startswith("dsa_"):
        # and the model without an indexer has none of it
        assert not _scoped(mla_moe_hlo["decode_block"], scope)


# ------------------ the hybrid (Mamba-2 + attention) decoder's state

# one layer's pools behind the small engine below: 4 rows + the null
# slot of 8 heads of 64 with a state of 128, two heads a 128-lane row;
# 2 kv heads of 64 packed into one row block, 4 x 32 + 1 pages of 16
_SSM_POOL = (5, 4, 128, 128)
_HYBRID_KV_POOL = (1, 129, 16, 128)


@pytest.fixture(scope="module")
def hybrid_compiled(topo, kernel_paths):
    """The decode block and a bucketed prefill of a small hybrid decoder
    (one Mamba-2 layer at the published head width and state, one
    attention layer of 64-wide heads) behind a default engine."""
    from paddle_tpu.models import HybridSsmConfig, HybridSsmForCausalLM
    from paddle_tpu.serving import ServingEngine

    cfg = HybridSsmConfig(
        vocab_size=_VOCAB, hidden_size=256, num_hidden_layers=2,
        layer_types=("mamba", "attention"), num_attention_heads=4,
        num_key_value_heads=2, shared_intermediate_size=768,
        mamba_n_heads=8, max_position_embeddings=_SEQ, dtype="bfloat16",
        deferred_weights=True)
    model = HybridSsmForCausalLM(cfg)
    model.eval()
    eng = ServingEngine(model, page_size=16, max_batch_size=4,
                        max_seq_len=_SEQ, kv_dtype="bf16")
    ssm_pool, conv_pool = eng.cache.pools[0]
    assert ssm_pool.shape == _SSM_POOL and ssm_pool.dtype == jnp.float32
    assert eng.cache.pools[1][0].shape == _HYBRID_KV_POOL
    return _compile_serve(eng, topo.devices[0], 4, _SEQ)


@pytest.fixture(scope="module")
def hybrid_hlo(hybrid_compiled):
    return {name: c.as_text() for name, c in hybrid_compiled.items()}


def test_ssm_decode_kernel_is_named_where_it_is_created(hybrid_hlo):
    calls = _custom_calls(hybrid_hlo["decode_block"])
    assert scopes.SSM_DECODE_KERNEL in calls
    # the attention layer of the same step takes the K/V pools' kernel
    assert scopes.PAGED_DECODE_KERNEL in calls
    assert scopes.SSM_DECODE_KERNEL not in _custom_calls(
        hybrid_hlo["prefill"])


def _ssm_pool_faults(text: str, pool=_SSM_POOL) -> list:
    """What is wrong with the state pools of a decode step's text: a
    pool that is not float32, or an `ssm_decode` call that does not
    write its pool operand (operand 5) in place."""
    dims = ",".join(str(d) for d in pool)
    faults = [f"{t}[{dims}]" for t in set(re.findall(
        r"\b(\w+)\[" + dims + r"\]", text)) if t != "f32"]
    calls = re.findall(r"%ssm_decode[.\d]* = [^\n]*custom-call\([^\n]*",
                       text)
    faults += ["not aliased: " + c[:80] for c in calls
               if not re.search(r"output_to_operand_aliasing=\{[^\n]*"
                                r"\{1\}: \(5, \{\}\)", c)]
    return faults + ([] if calls else ["no ssm_decode call"])


def test_ssm_pools_are_float32_and_updated_in_place(hybrid_hlo):
    text = hybrid_hlo["decode_block"]
    assert "f32[5,4,128,128]" in text
    assert _ssm_pool_faults(text) == []


def test_an_ssm_pool_that_is_copied_or_narrowed_is_caught(topo,
                                                          monkeypatch):
    """The same checks on a kernel broken on purpose: a pool in bf16,
    and the call without `input_output_aliases`, which hands back a
    second pool instead of the first one updated."""
    from jax.experimental import pallas as pl
    from paddle_tpu.serving import ssm

    one_chip = SingleDeviceSharding(topo.devices[0])

    def lowered(pool_dtype):
        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        b, hk, n, lanes = 4, *_SSM_POOL[1:]
        # a function of its own each time: nothing traced before the
        # kernel was broken is found again
        return jax.jit(lambda *a: ssm._ssm_decode_pallas.__wrapped__(
            *a)).lower(
            sds((b, hk, lanes), jnp.float32),
            sds((b, hk, lanes), jnp.float32), sds((b, n, 1), jnp.float32),
            sds((b, n, 1), jnp.float32), sds(_SSM_POOL, pool_dtype),
            sds((b,), jnp.int32)).compile().as_text()

    sound = lowered(jnp.float32)
    assert _ssm_pool_faults(sound) == []
    # the kernel writes float32 and refuses a narrower pool outright; a
    # text that held one all the same is told
    with pytest.raises(ValueError, match="bfloat16"):
        lowered(jnp.bfloat16)
    assert "bf16[5,4,128,128]" in _ssm_pool_faults(
        sound.replace("f32[5,4,128,128]", "bf16[5,4,128,128]"))
    real = pl.pallas_call

    def unaliased(*args, input_output_aliases=None, **kwargs):
        return real(*args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", unaliased)
    assert any(f.startswith("not aliased")
               for f in _ssm_pool_faults(lowered(jnp.float32)))


@pytest.mark.parametrize("program", ["decode_block", "prefill"])
def test_no_move_of_a_state_pool_or_a_kv_pool(hybrid_hlo, program):
    """Neither kind of sequence state is copied, padded or transposed
    whole: the state kernel and the K/V write update donated pools in
    place, and a row of two packed 64-wide heads is a whole tile that
    the decode kernel takes as it lies."""
    text = hybrid_hlo[program]
    assert "[5,4,128,128]" in text and "[1,129,16,128]" in text
    for pool in (_SSM_POOL, _HYBRID_KV_POOL):
        assert _pool_sized_moves(text, pool, "copy|transpose|pad") == []


def test_a_padded_kv_pool_is_caught(topo):
    """The same check on the path 64-wide heads took before their pool
    was packed: one head a row, and `_paged_decode_pallas` pads the
    whole pool to 128 columns at every call."""
    unpacked = (2, 129, 16, 64)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def text(pool, pack):
        step = functools.partial(paged._paged_decode_pallas.__wrapped__,
                                 pack=pack)
        return jax.jit(step).lower(
            sds((4, 1, 4, 64), jnp.bfloat16), sds(pool, jnp.bfloat16),
            sds(pool, jnp.bfloat16), sds((4, 32), jnp.int32),
            sds((4,), jnp.int32)).compile().as_text()

    moved = "copy|transpose|pad|fusion"
    assert _pool_sized_moves(text(_HYBRID_KV_POOL, 2), _HYBRID_KV_POOL,
                             moved) == []
    # the one-head rows padded to a tile: as many elements as two rows
    assert _pool_sized_moves(text(unpacked, 1), (2, 129, 16, 128),
                             moved) != []


@pytest.mark.parametrize("scope", scopes.SERVE_SCOPES)
def test_serve_scope_reaches_the_hybrid_model_s_step(hybrid_hlo, scope):
    program = ("prefill" if scope == scopes.PREFILL_ATTENTION
               else "decode_block")
    assert _scoped(hybrid_hlo[program], scope)


# --------- what the measured programs must not hold (no chip: the text)

@pytest.fixture(scope="module")
def programs(train_hlo, serve_hlo, mla_moe_hlo, hybrid_hlo):
    """The compiler's text of the programs the benchmark's cells run, by
    name: `ZeroTrainStep` over ERNIE with the fused loss, and the decode
    block and bucketed prefill of a default engine over GPT and over the
    latent-attention decoder."""
    return {"train": train_hlo,
            **{f"gpt_{name}": text for name, text in serve_hlo.items()},
            **{f"mla_moe_{name}": text
               for name, text in mla_moe_hlo.items()},
            **{f"hybrid_{name}": text
               for name, text in hybrid_hlo.items()}}


def _arrays(text: str) -> set:
    """Every array type the text names, as (dtype, dims)."""
    return {(dtype, tuple(int(d) for d in dims.split(",")))
            for dtype, dims in re.findall(
                r"\b(pred|[su]\d+|bf16|f16|f32|f64)\[([\d,]+)\]", text)}


@pytest.mark.parametrize("program,rows", [
    ("train", _TRAIN_ROWS), ("gpt_prefill", 1), ("mla_moe_prefill", 1),
    ("hybrid_prefill", 1)])
def test_no_whole_attention_matrix(programs, program, rows):
    """Flash keeps the scores in VMEM a block at a time: no array has
    two dimensions of the sequence and room for every row's every head
    (`[b, heads, s, s]` in whatever order or merged)."""
    whole = [a for a in _arrays(programs[program])
             if a[1].count(_SEQ) >= 2
             and np.prod(a[1]) >= rows * _HEADS * _SEQ * _SEQ]
    assert whole == []


def _sequence_squares(text: str, rank: int = 2) -> list:
    """Arrays of at least `rank` dimensions, two of them the sequence's,
    of any dtype and any size: a dense `(1, 1, s, s)` mask is one, 16.8
    MB in float32 at the 2,048 bucket, which the gate above is too
    coarse to refuse."""
    return sorted(a for a in _arrays(text)
                  if a[1].count(_SEQ) >= 2 and len(a[1]) >= rank)


@pytest.mark.parametrize("program,rank", [("gpt_prefill", 2),
                                          ("hybrid_prefill", 4)])
def test_prefill_holds_no_sequence_by_sequence_array(programs, program,
                                                     rank):
    """The exact prefill tells flash that it is causal and builds no
    mask: nothing in the program is `[.., s, s]`. The small hybrid
    model's Mamba-2 mixer is as wide as the sequence is long (512), so
    its `[1, s, 512]` activations stand and the check there is on what
    flash would be handed, the mask's own four dimensions."""
    assert _sequence_squares(programs[program], rank) == []


def test_a_dense_prefill_mask_is_caught(topo, kernel_paths, monkeypatch):
    """The same check with `_prefill_attention` put back as it was: the
    mask built from the positions and handed to flash, `is_causal` off."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn import functional as F
    from paddle_tpu.serving.kv_cache import PagedLayerCache

    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def text():
        # a function of its own each time: nothing traced before the
        # patch is found again
        def prefill(q, k, v, k_pool, v_pool, page_table):
            view = PagedLayerCache(k_pool, v_pool, page_table)
            return paged.paged_attend(Tensor(q), Tensor(k), Tensor(v),
                                      view, 0, 1)[0]._data
        row = sds((1, _SEQ, _HEADS, 128), jnp.bfloat16)
        pool = sds((_HEADS, 33, 16, 128), jnp.bfloat16)
        return jax.jit(prefill).lower(
            row, row, row, pool, pool,
            sds((1, _SEQ // 16), jnp.int32)).compile().as_text()

    def dense_mask(q, kd, vd, rep, bias=None):
        pos = jnp.arange(_SEQ, dtype=jnp.int32)[None]
        allowed = pos[:, None, :] <= pos[:, :, None]
        mask = jnp.where(allowed, 0.0, -1e9).astype(jnp.float32)[:, None]
        return F.scaled_dot_product_attention(
            q, Tensor(kd), Tensor(vd), attn_mask=Tensor(mask),
            is_causal=False)

    sound = text()
    assert "tpu_custom_call" in sound and _sequence_squares(sound) == []
    monkeypatch.setattr(paged, "_prefill_attention", dense_mask)
    assert ("f32", (1, 1, _SEQ, _SEQ)) in _sequence_squares(text())


@pytest.mark.parametrize("program,tokens", [
    ("train", _TRAIN_ROWS * _SEQ), ("mla_moe_prefill", _SEQ),
    ("hybrid_prefill", _SEQ)])
def test_no_whole_logits(programs, program, tokens):
    """The fused linear + cross-entropy of the train step sees the
    vocabulary a chunk of 2,048 tokens at a time, and the latent model's
    prefill projects the one position it samples from (`logits_at`; at
    the cell's 16,384 x 129,280 the whole would be 4.2 GB): no array has
    the vocabulary beside every token of the step. GPT's prefill does
    hold `(bucket, vocab)` today (`PERF.md` section 7) and is no case."""
    whole = [a for a in _arrays(programs[program])
             if _VOCAB in a[1] and np.prod(a[1]) >= tokens * _VOCAB]
    assert whole == []


def _matmul_operands(text: str) -> list:
    """(op_name, operand dtypes) of every `dot` and `convolution`, the
    two forms a matmul has in the chip's text."""
    dtype_of = dict(re.findall(r"%([\w.\-]+) = (\w+)\[", text))
    found = re.findall(r" (?:convolution|dot)\(([^)]*)\)[^\n]*?"
                       r'op_name="([^"]*)"', text)
    return [(op_name, [dtype_of[name] for name in
                       re.findall(r"%([\w.\-]+)", operands)])
            for operands, op_name in found]


@pytest.mark.parametrize("program", [
    "train", "gpt_decode_block", "gpt_prefill", "mla_moe_decode_block",
    "mla_moe_prefill", "hybrid_decode_block", "hybrid_prefill"])
def test_every_matmul_takes_bf16_operands(programs, program):
    """One float32 operand forfeits the MXU's bf16 rate. No exception is
    needed: where the design computes in float32 (the latent model's
    router, the fused loss's logits and its weight gradient) the
    operands were bf16 before the cast, so the compiler folds the cast
    into the matmul and keeps the float32 in the result alone. The
    experts' grouped matmuls are kernels, not in this list."""
    matmuls = _matmul_operands(programs[program])
    assert len(matmuls) >= 9
    assert [m for m in matmuls if set(m[1]) != {"bf16"}] == []


# ------------- a batch of greedy rows never runs the sampler's sorts

def _computations(text: str) -> tuple:
    """The text's computations as ({name: body}, the entry's name)."""
    found = re.findall(r"^(ENTRY )?%([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text,
                       re.M | re.S)
    entry, = [name for is_entry, name, _ in found if is_entry]
    return {name: body for _, name, body in found}, entry


def _reached(comps: dict, start: str, enter=lambda line, callee: True):
    """Names of the computations `start` reaches (fusions, loop bodies
    and conditions, branches, reducers) through the calls of the lines
    for which `enter(line, callee)` holds."""
    called = re.compile(r"\w+=\{?(%[\w.\-]+(?:, %[\w.\-]+)*)\}?")
    seen, todo = set(), [start]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name].splitlines():
            for group in called.findall(line):
                todo.extend(n for n in (g.lstrip("%") for g in
                                        group.split(", "))
                            if n in comps and enter(line, n))
    return seen


def _reached_outside_branches(text: str) -> dict:
    """{name: body} of the computations the entry reaches without
    entering a branch of a `conditional`: what the device runs whatever
    the predicates read."""
    comps, entry = _computations(text)
    return {name: comps[name] for name in _reached(
        comps, entry, lambda line, _: " conditional(" not in line)}


def _sampler_sorts(body: str) -> list:
    """The `sort`s of the scope `sampling` among a computation's lines
    (the expert layer sorts its pairs by expert: another scope's)."""
    return [line for line in body.splitlines() if " sort(" in line
            and _scoped(line, scopes.SAMPLING)]


@pytest.mark.parametrize("program", [
    "gpt_decode_block", "gpt_prefill", "mla_moe_decode_block",
    "mla_moe_prefill", "hybrid_decode_block", "hybrid_prefill"])
def test_sampler_sorts_only_inside_a_conditional_s_branch(programs, program):
    """`_sample_batch` sorts the vocabulary (twice) only where some row
    of the batch samples: in the compiler's text both sorts sit in a
    branch of a `conditional`, none in the entry computation or in the
    decode loop's body, where every greedy step would pay for them. A
    `select` in the conditional's place (a `vmap` over the sampler, a
    `where` on the predicate) runs both sides and fails here."""
    text = programs[program]
    always = _reached_outside_branches(text)
    assert [(name, line[:120]) for name, body in always.items()
            for line in _sampler_sorts(body)] == []
    assert len(_sampler_sorts(text)) == 2


# ------------- the MLM head runs over the labelled rows, not every row

def _vocabulary_matmuls(body: str, shape_of: dict) -> list:
    """The shapes (result first, then operands) of every `dot` and
    `convolution` of a computation's lines with the vocabulary in one of
    them."""
    found = []
    for result, operands in re.findall(
            r"= (\w+\[[\d,]*\])\S* (?:convolution|dot)\(([^)]*)\)", body):
        shapes = [result] + [shape_of[name] for name in
                             re.findall(r"%([\w.\-]+)", operands)]
        dims = [[int(d) for d in re.findall(r"\d+", s.split("[")[1])]
                for s in shapes]
        if any(_VOCAB in d for d in dims):
            found.append(dims)
    return found


def test_the_vocabulary_head_runs_over_the_gathered_rows(train_hlo):
    """Outside the branch that takes the head over every row (a batch
    with more labels than the capacity: scope `mlm_head_all_rows`), each
    matmul against the vocabulary, forward and backward, has at most the
    capacity's 640 rows of the step's 4,096 positions; the fallback
    keeps the fused loss's chunks of 2,048."""
    from paddle_tpu.models.ernie import mlm_capacity

    k = mlm_capacity(_TRAIN_ROWS * _SEQ)
    comps, entry = _computations(train_hlo)
    shape_of = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])",
                               train_hlo))

    def fallback(name):
        return any(_scoped(comps[n], scopes.MLM_HEAD_ALL_ROWS)
                   for n in _reached(comps, name))

    always = _reached(comps, entry, lambda line, callee: not (
        " conditional(" in line and fallback(callee)))
    gathered = [dims for name in always
                for dims in _vocabulary_matmuls(comps[name], shape_of)]
    every_row = [dims for name in set(comps) - always
                 for dims in _vocabulary_matmuls(comps[name], shape_of)]
    # the logits (and their recomputation in the backward), dx and dw
    assert len(gathered) >= 3 and len(every_row) >= 3
    # the scope names nothing the device runs in a batch within the
    # capacity: its self time in the trace is the fallback's engagement
    assert [name for name in always
            if _scoped(comps[name], scopes.MLM_HEAD_ALL_ROWS)] == []
    assert all(n <= k for dims in gathered for d in dims for n in d
               if n != _VOCAB), gathered
    assert all(2048 in sum(dims, []) for dims in every_row), every_row


@pytest.mark.parametrize("scope", scopes.TRAIN_SUBSCOPES)
def test_train_sub_scope_reaches_the_compiled_step(train_hlo, scope):
    names = _scoped(train_hlo, scope)
    # forward and backward both carry it
    assert [n for n in names if "transpose(" not in n]
    assert [n for n in names if "transpose(" in n]
