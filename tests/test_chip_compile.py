"""The main path's Pallas kernels compiled at real widths for a described
(not attached) TPU v5e — the third rehearsal of a chip run, kept as a
test. A compile that passes is a compile: nothing here runs on a chip.

Only one process may hold libtpu, so the topology is described inside a
fixture of this one file and never at import; under xdist only the
worker that is handed this file loads the library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import pallas_kernels as pk
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


def _paged_decode():
    args = [((8, 1, 16, 128), jnp.bfloat16), _POOL, _POOL, _TABLE,
            ((8,), jnp.int32)]
    return paged._paged_decode_pallas, args


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


_ONE_CHIP = {
    "paged_decode": _paged_decode,
    "ragged_paged": _ragged_paged,
    "flash_causal": _flash_causal,
    "flash_train_dropout": _flash_train_dropout,
    "layer_norm_train": _layer_norm_train,
    "layer_norm_decode": _layer_norm_decode,
}


@pytest.mark.parametrize("case", [*_ONE_CHIP, "tp_overlap_ring"])
def test_compiles_for_v5e(topo, case):
    if case == "tp_overlap_ring":
        # the split-collective ring of tensor-parallel serving, over all
        # four chips of the host
        from paddle_tpu.parallel.mesh import build_mesh
        from paddle_tpu.serving.overlap import overlap_probe_fn

        mesh = build_mesh((("tp", 4),), devices=topo.devices)
        fn = overlap_probe_fn(mesh, 256, 2)
        args = [jax.ShapeDtypeStruct((8, 256), jnp.float32,
                                     sharding=NamedSharding(mesh, P()))]
        wanted = "collective-permute"
    else:
        fn, shapes = _ONE_CHIP[case]()
        one_chip = SingleDeviceSharding(topo.devices[0])
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in shapes]
        wanted = "tpu_custom_call"
    compiled = jax.jit(fn).lower(*args).compile()
    assert wanted in compiled.as_text()
