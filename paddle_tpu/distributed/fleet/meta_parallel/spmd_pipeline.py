"""SPMD collective pipeline — the multi-host pipeline-parallel engine.

Ref: fleet/meta_parallel/pipeline_parallel.py + pp_utils/p2p_communication.py
(upstream layout, unverified — mount empty). Upstream runs one process per
stage exchanging activations over NCCL send_v2/recv_v2; SURVEY §7 names
MPMD-style PP "the single riskiest component" on TPU because XLA wants ONE
program on every rank.

This module is that one program: the GPipe schedule expressed as a
collective computation inside ``shard_map`` over a ``pp`` mesh axis that may
SPAN HOSTS (validated by the 2-process test). Per tick, every stage computes
its block on its current activation and hands it to the next stage via
``lax.ppermute`` — the send/recv analog, riding ICI/DCN and inserted as an
XLA collective rather than a hand-written NCCL call. Stage masking keeps the
program identical on every rank (warmup/drain ticks compute on garbage and
their results are never collected), and because ``ppermute`` has a transpose
rule the BACKWARD schedule is derived by jax.grad — no hand-written 1F1B
backward pass.

Contract: pipeline stages must be structurally identical (the stacked-stage
SPMD requirement) — embeddings/heads run replicated outside the pipelined
region, exactly how the flagship models segment. The single-controller
``PipelineParallel`` engine (per-stage submesh jits, true 1F1B dispatch)
remains the intra-host scheduler; this path is what scales PP past one
process.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map as _shard_map

__all__ = ["spmd_pipeline", "make_spmd_pipeline_fn"]


def spmd_pipeline(stage_fn, stage_params, x_mb, *, num_stages: int,
                  axis_name: str = "pp"):
    """Run ``num_stages`` pipeline stages over microbatches, inside
    ``shard_map``.

    stage_fn(params, x) -> y with ``y.shape == x.shape`` (homogeneous
    stages); ``stage_params``: pytree whose leaves carry a leading
    stacked-stage dim, sharded 1-per-device over ``axis_name`` (each device
    sees leading dim 1); ``x_mb``: (M, mb, ...) microbatches, replicated
    over ``axis_name``. Returns (M, mb, ...) last-stage outputs, replicated
    over ``axis_name`` (a masked psum broadcasts them so every stage can
    compute the loss — keeping the program SPMD).
    """
    s = lax.axis_index(axis_name)
    params = jax.tree_util.tree_map(lambda p: jnp.squeeze(p, 0),
                                    stage_params)
    m = x_mb.shape[0]
    ticks = m + num_stages - 1
    perm = [(i, i + 1) for i in range(num_stages - 1)]

    def tick(carry, t):
        state, outputs = carry
        # activation handoff: stage s receives stage s-1's last output
        # (stage 0 receives garbage from the open ring end — masked off)
        shifted = lax.ppermute(state, axis_name, perm)
        mb_idx = jnp.clip(t, 0, m - 1)
        x_in = jnp.where(s == 0, x_mb[mb_idx], shifted)
        new_state = stage_fn(params, x_in)
        out_idx = t - (num_stages - 1)
        take = jnp.logical_and(s == num_stages - 1,
                               jnp.logical_and(out_idx >= 0, out_idx < m))
        upd = jnp.where(take, new_state, outputs[jnp.clip(out_idx, 0,
                                                          m - 1)])
        outputs = lax.dynamic_update_index_in_dim(
            outputs, upd, jnp.clip(out_idx, 0, m - 1), 0)
        return (new_state, outputs), None

    # mark the zero-init carries as pp-varying: the scan body makes them
    # vary over the pp axis (ppermute/stage compute) and shard_map's
    # varying-axes check requires carry-in == carry-out
    state0 = lax.pcast(jnp.zeros_like(x_mb[0]), (axis_name,), to="varying")
    out0 = lax.pcast(jnp.zeros_like(x_mb), (axis_name,), to="varying")
    (_, outputs), _ = lax.scan(tick, (state0, out0), jnp.arange(ticks))
    # broadcast the last stage's collected outputs to every stage
    return lax.psum(jnp.where(s == num_stages - 1, outputs, 0.0),
                    axis_name)


def make_spmd_pipeline_fn(stage_fn, mesh, *, num_stages: int,
                          num_micro: int, axis_name: str = "pp",
                          data_axis: str | None = "dp"):
    """Jittable (stacked_params, x) -> y over ``mesh``: splits the batch
    into ``num_micro`` microbatches, pipelines them over ``axis_name`` and
    returns outputs in batch layout. The batch dim may additionally be
    sharded over ``data_axis`` (dp inside each stage)."""
    from jax.sharding import PartitionSpec as P

    dspec = data_axis if (data_axis and mesh.shape.get(data_axis, 1) > 1) \
        else None

    def fn(stacked_params, x):
        b = x.shape[0]
        x_mb = x.reshape((num_micro, b // num_micro) + x.shape[1:])
        y_mb = _shard_map(
            partial(spmd_pipeline, stage_fn, num_stages=num_stages,
                    axis_name=axis_name),
            mesh=mesh,
            in_specs=(P(axis_name), P(None, dspec)),
            out_specs=P(None, dspec),
        )(stacked_params, x_mb)
        return y_mb.reshape((b,) + x.shape[1:])

    return fn
