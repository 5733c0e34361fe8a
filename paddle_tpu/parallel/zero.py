"""ZeRO-sharded data-parallel training (ISSUE 16 tentpole, layer 2).

"Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (arxiv 2004.13336): instead of every dp replica holding the
full optimizer state and redundantly applying the identical weight
update, shard the update itself —

    reduce-scatter grads -> shard-local optimizer update on the 1/dp
    parameter slice -> all-gather updated params

`ZeroTrainStep` / `zero_train_step` builds that step jit/shard_map-
native on the unified (dp x tp) mesh from `parallel/mesh.py`:

- **stage 0** (the baseline the parity claim is against): fixed-order
  dp all-reduce of every grad, full replicated elementwise update.
- **stage 1** (ZeRO-1, paddle level "os"): same all-reduced grad, but
  the optimizer update runs on this shard's 1/dp flat slice only —
  optimizer-state bytes/chip drop to 1/dp.
- **stage 2** (ZeRO-2, "os_g"): the grad is reduce-SCATTERED (fixed
  shard order), so the full summed gradient never materializes in the
  update path.

**Bit-parity (fp32), by construction**: all stages sum grads with the
same fixed-shard-order `ordered_psum` (and `ordered_psum_scatter`,
whose shard i output is bit-identical to slicing the ordered sum —
the sum is elementwise); the optimizer update is the optimizer's OWN
elementwise `functional_step`, so updating a slice and concatenating
equals slicing the full update. Hence ZeRO-1/2 == replicated dp,
bit-for-bit, at every dp degree (pinned by tests/test_zero.py).
Cross-DEGREE bit-parity is NOT claimed: changing dp changes the batch
summation order, which fp addition does not forgive.

**Optimizer-state layout + degree-blind checkpoints**: each slot is
stored as a (dp, tp, chunk) array placed P("dp", "tp"), where chunk =
ceil(tp_local_flat_size / dp). `save_optimizer_state` reassembles full
logical arrays (host-side, numpy), `load_optimizer_state` re-slices
them for ANY (dp, tp) — save at dp=2, restore at dp=4, keep training:
the same degree-blind contract the serving journal honors for tp.

**tp composition**: params may carry Megatron PartitionSpecs over the
tp axis; the dp machinery slices each shard's TP-LOCAL flat view, so
dp x tp composes on one mesh with no special cases. Loss functions
crossing tp regions must use `mesh.copy_to_tp_region` /
`mesh.reduce_from_tp_region` (differentiating raw collectives under
`shard_map(check_vma=False)` has no defined transpose).

**Limits** (validated loudly at construction): elementwise optimizers
only (Lamb's trust ratio and LBFGS's history are whole-tensor
operations — a 1/dp slice changes them); `grad_clip` is rejected (the
global-norm clip over a slice is wrong — use the GSPMD GroupSharded
surface with `HybridParallelClipGrad` instead).

**Bucketing + ring-pipelined overlap (ISSUE 20 tentpole)**: with
`bucket_bytes` set, the per-leaf grads are packed into fixed-byte flat
buckets in a SHARD-MAJOR layout — each leaf's padded flat grad is
shaped (dp, chunk) and the bucket concatenates those along the chunk
axis, so one `ordered_psum_scatter` of the packed bucket hands shard i
exactly the concatenation of each leaf's shard-i slice, with every
per-element sum in the identical fixed shard order as the per-leaf
scatter (bit-identical by construction; pinned across the bucket-size
sweep in tests/test_zero_bucket.py). With `overlap=True` the buckets
additionally ride the fixed-order ppermute ring
(`mesh.ring_collect` / `mesh.ring_pipeline` — the same scheduler
serving TP decode overlap uses): bucket j+1's transport is emitted
before bucket j's reduce + shard-local optimizer update, and the
updated-slice all-gather of bucket j rides as ring hops ahead of
bucket j+1's update math — transport changes, arithmetic does not, so
fp32 overlapped stays bit-identical to the serial step at every
(dp, stage, tp, grad_accum).

**Mixed precision** (`param_dtype="bf16"`): params are placed in
bfloat16 (backward FLOPs + bytes on the wire halve; floating batch
leaves are cast to bf16 inside the step), the optimizer state carries
fp32 MASTER weights (the optimizer's own `multi_precision` slot,
riding the (dp, tp, chunk) layout — degree-blind save/restore for
free) and the shard-local update runs in fp32 against them. Dynamic
loss scaling guards the bf16 backward: the loss is scaled by a
power-of-two scale (exact — no mantissa change), grads travel scaled
in bf16, the update unscales in fp32, and a traced nonfinite check
over the local grads skips the update (params AND state where-
reverted) and backs the scale off; `scale_growth_interval` good steps
double it again. bf16 is a BOUNDED-ERROR mode: the dp grad sums run
in bf16, so cross-stage/overlap bit-parity is NOT claimed — the
contract is a loss trajectory within documented tolerance of fp32
(pinned on the pretrain bench) with nonfinite/loss-scale events
visible in telemetry.

The paddle-compat GroupSharded/`group_sharded_parallel` surface
(GSPMD sharding-annotation flavor, stages 1-3) lives at the bottom of
this module — `fleet.meta_parallel.sharding` and
`distributed.sharding` are re-export shims onto it — and bridges to
the explicit engine via `_ShardedBase.zero_train_step()`.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..nn import Layer
from ..profiler import scopes
from .mesh import (
    DP_AXIS, TP_AXIS, build_mesh, collected_shard_sum, device_order,
    local_shape, ordered_psum, ordered_psum_scatter, ring_collect,
    ring_pipeline, shard_leaf, tp_dim_spec,
)

__all__ = [
    "ZeroTrainStep", "zero_train_step", "model_loss",
    "build_bucket_layout",
    "save_optimizer_state", "load_optimizer_state",
    "GroupShardedStage2", "GroupShardedStage3",
    "GroupShardedOptimizerStage2", "group_sharded_parallel",
    "save_group_sharded_model", "shard_leaf",
]

# whole-tensor update rules: slicing changes the math, so the sharded
# engine refuses them instead of silently diverging from the replica
_NON_ELEMENTWISE = ("Lamb", "LBFGS")

# reserved opt-state entry holding the dynamic loss scaler's replicated
# scalars under param_dtype="bf16" (never a param name — params come
# from named_parameters, which cannot produce dunder keys)
_SCALER_KEY = "__scaler__"
# paddle GradScaler-shaped constants: halve on a nonfinite step, double
# after `scale_growth_interval` consecutive good ones, clamped so the
# scale can neither vanish nor overflow f32
_SCALE_BACKOFF = 0.5
_SCALE_GROWTH = 2.0
_SCALE_MIN = 1.0
_SCALE_MAX = 2.0 ** 24


def model_loss(model, criterion=None):
    """Build a `loss_fn(params, x, y) -> scalar` over a Layer via the
    functional forward (`call_functional`), defaulting to mean squared
    error. The mean must be over the LOCAL batch rows — the engine's
    fixed-order dp reduction averages the shard losses."""
    from ..core.tensor import Tensor
    from ..jit.functional import call_functional

    def loss_fn(params, x, y):
        out, _ = call_functional(model, params, {}, (x,), training=True)
        if criterion is None:
            return jnp.mean((out - y) ** 2)
        loss = criterion(Tensor(out), Tensor(y))
        return getattr(loss, "_data", loss)

    return loss_fn


def _pad_flat(x, n: int):
    """Flatten and zero-pad to length n (n >= x.size). Zero padding is
    update-neutral for every elementwise rule: pad params and grads are
    both 0, so the padded slots never feed back into real elements."""
    flat = x.reshape(-1)
    return jnp.pad(flat, (0, n - flat.shape[0]))


# ------------------------------------------------------ bucket layout
def build_bucket_layout(names: Sequence[str], chunks: Dict[str, int],
                        itemsize: int, dp: int,
                        bucket_bytes: Optional[int]) -> List[Dict]:
    """Greedy fixed-byte bucketing of the padded per-leaf flats,
    computed ONCE at build time (pure host function — unit-tested
    directly in tests/test_zero_bucket.py).

    Leaves are taken in param order; a leaf's padded footprint is
    dp * chunk * itemsize bytes. A new bucket starts when adding the
    next leaf would exceed `bucket_bytes`; a leaf larger than the cap
    by itself gets its own bucket (leaves are never split — the
    shard-major packing needs whole (dp, chunk) blocks).
    `bucket_bytes=None` yields one bucket per leaf (the overlap
    pipeline's finest granularity when no byte cap is set).

    Returns one dict per bucket: `names` (leaf order inside the
    bucket), `offs` (each leaf's offset inside the bucket's per-shard
    slice) and `width` (the per-shard slice length, sum of the member
    chunks)."""
    if dp < 1:
        raise ValueError(f"dp must be >= 1, got {dp}")
    cap = None
    if bucket_bytes is not None:
        cap = int(bucket_bytes)
        if cap <= 0:
            raise ValueError(
                f"bucket_bytes must be > 0 (or None), got {bucket_bytes}")
    groups: List[List[str]] = []
    cur: List[str] = []
    cur_bytes = 0
    for k in names:
        nbytes = dp * int(chunks[k]) * int(itemsize)
        if cur and (cap is None or cur_bytes + nbytes > cap):
            groups.append(cur)
            cur, cur_bytes = [], 0
        cur.append(k)
        cur_bytes += nbytes
    if cur:
        groups.append(cur)
    out = []
    for member_names in groups:
        offs: Dict[str, int] = {}
        width = 0
        for k in member_names:
            offs[k] = width
            width += int(chunks[k])
        out.append({"names": tuple(member_names), "offs": offs,
                    "width": width})
    return out


def _pack_bucket(ctx, bucket, grads):
    """Pack one bucket's leaves into the SHARD-MAJOR flat the fixed-
    order scatter consumes: each leaf's flat grad is zero-padded to
    dp * chunk and shaped (dp, chunk); the bucket concatenates those
    along the chunk axis into (dp, width) and flattens. Row d of the
    packed layout is then exactly the concatenation of every leaf's
    shard-d slice, so `ordered_psum_scatter` of the packed flat sums
    each element in the identical fixed shard order as the per-leaf
    scatter — the bucketed shard slice is bit-identical to
    concatenating the per-leaf slices."""
    rows = [_pad_flat(grads[k], ctx.dp * ctx._chunks[k])
            .reshape(ctx.dp, ctx._chunks[k]) for k in bucket["names"]]
    packed = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=1)
    return packed.reshape(-1)


# ------------------------------------------------------------- step bodies
# module-level on purpose: these ARE the hot per-step path (traced into
# the one train executable), and graftlint's HOST-SYNC rule audits them
# by name — nested closures would dodge the audit.

def _accumulated_grads(ctx, params, batch, scale=None):
    """Local (this dp shard's) loss and grads, averaged over
    `ctx.grad_accum` micro-batches split from the local rows (static
    unroll — one executable, no host loop).

    With `scale` (the traced loss-scale scalar, bf16 mode only) the
    loss is multiplied by it before differentiation, so the bf16
    cotangents travel scaled; the returned loss is unscaled (exact —
    the scale is a power of two), while the returned grads stay
    SCALED and UNAVERAGED: the shard-local update folds 1/(dp *
    grad_accum * scale) into one fp32 multiply (`_unscale_shard`),
    instead of averaging in bf16 here."""
    loss_fn = ctx.loss_fn
    if scale is None:
        vg = jax.value_and_grad(loss_fn)
    else:
        def scaled_loss(p, *args):
            return loss_fn(p, *args) * scale

        vg = jax.value_and_grad(scaled_loss)
    k = ctx.grad_accum
    if k == 1:
        loss, grads = vg(params, *batch)
        if scale is not None:
            loss = loss / scale
        return loss, grads
    per = batch[0].shape[0] // k
    loss = None
    gsum = None
    for j in range(k):
        micro = tuple(jax.lax.dynamic_slice_in_dim(b, j * per, per, axis=0)
                      for b in batch)
        step_loss, g = vg(params, *micro)
        loss = step_loss if loss is None else loss + step_loss
        gsum = g if gsum is None else jax.tree_util.tree_map(
            lambda a, b: a + b, gsum, g)
    inv = jnp.float32(1.0 / k)
    if scale is not None:
        return loss * inv / scale, gsum
    return loss * inv, jax.tree_util.tree_map(lambda g: g * inv, gsum)


def _unscale_shard(ctx, shard, scale):
    """Finish one reduced grad shard: fp32 mode multiplies by 1/dp
    (the dp-mean — bit-identical to the legacy per-leaf constant);
    scaled bf16 mode casts to fp32 FIRST, then applies the folded
    1/(dp * grad_accum) mean and the loss-scale inverse in one fp32
    multiply — the grads travelled scaled/unaveraged in bf16, and the
    unscale is the entry into the fp32 master-weight update."""
    if scale is None:
        return shard * jnp.float32(1.0 / ctx.dp)
    inv = jnp.float32(1.0 / (ctx.dp * ctx.grad_accum))
    return shard.astype(jnp.float32) * (inv / scale)


def _grad_nonfinite(ctx, grads):
    """Traced scalar count of nonfinite elements over the LOCAL
    (scaled, pre-reduction) grads, combined across dp (and tp when
    composed) with the same fixed-order psum as the update — the
    loss scaler's skip signal. Pre-reduction on purpose: a backward
    overflow is caught on the shard that produced it, before the bf16
    sums can fold it into every shard's slice."""
    total = jnp.float32(0.0)
    for k in grads:
        total = total + jnp.sum(
            (~jnp.isfinite(grads[k])).astype(jnp.float32))
    total = ordered_psum(total, DP_AXIS)
    if ctx.tp > 1:
        total = ordered_psum(total, TP_AXIS)
    return total


def _scaler_next(ctx, scaler, finite):
    """One dynamic-loss-scale transition (traced, replicated scalars):
    a nonfinite step halves the scale (clamped at `_SCALE_MIN`) and
    resets the good-step counter; `scale_growth_interval` consecutive
    good steps double it (clamped at `_SCALE_MAX`). All transitions
    are power-of-two multiplies — scaling never costs mantissa."""
    scale, good = scaler["scale"], scaler["good_steps"]
    good1 = good + jnp.float32(1.0)
    grown = jnp.logical_and(
        finite, good1 >= jnp.float32(ctx.scale_growth_interval))
    up = jnp.minimum(scale * jnp.float32(_SCALE_GROWTH),
                     jnp.float32(_SCALE_MAX))
    down = jnp.maximum(scale * jnp.float32(_SCALE_BACKOFF),
                       jnp.float32(_SCALE_MIN))
    new_scale = jnp.where(finite, jnp.where(grown, up, scale), down)
    new_good = jnp.where(finite, jnp.where(grown, jnp.float32(0.0), good1),
                         jnp.float32(0.0))
    return {"scale": new_scale, "good_steps": new_good}


def _replicated_update(ctx, params, grads, state, lr, t, scale=None):
    """Stage 0: fixed-order dp all-reduce of every grad, full
    elementwise update everywhere — the reference the sharded stages
    are bit-identical to. Returns `(new_params, new_state, grad_aux)`
    where grad_aux is the telemetry (grad_sumsq, nonfinite) pair over
    the MEAN grad (None when telemetry is off — the telemetry-off
    trace is unchanged). Under bf16 (`scale` set) the all-reduced
    scaled grad is unscaled into fp32 before the master-weight
    update."""
    with jax.named_scope(scopes.GRAD_REDUCE):
        if scale is None:
            inv = jnp.float32(1.0 / ctx.dp)
            g = {k: ordered_psum(grads[k], DP_AXIS) * inv for k in grads}
        else:
            g = {k: _unscale_shard(ctx, ordered_psum(grads[k], DP_AXIS),
                                   scale)
                 for k in grads}
    # functional_step indexes state by param name, so the reserved
    # scaler entry (when present) is naturally out of its reach
    with jax.named_scope(scopes.OPTIMIZER_UPDATE):
        new_p, new_s = ctx.optimizer.functional_step(params, g, state,
                                                     lr, t)
    aux = None
    if ctx._telemetry is not None:
        # g is replicated across dp (already all-reduced): no dp
        # combine, tp-sharded leaves combined inside grad_leaf_stats
        aux = ctx._trmod.grad_leaf_stats(ctx, g, dp_reduce=False)
    return new_p, new_s, aux


def _sharded_update(ctx, params, grads, state, lr, t, scale=None):
    """ZeRO-1/2: slice params + grads to this shard's 1/dp flat chunk,
    run the optimizer's own elementwise update on the slice against the
    (dp, tp, chunk)-laid-out state, then all-gather the updated slices
    back into the tp-local param. Stage 1 all-reduces the full grad
    first; stage 2 reduce-scatters so the full summed gradient never
    materializes in the update path.

    Telemetry keeps that property: the grad health stats are taken
    over each shard's SLICE of the mean grad (the slices partition the
    padded flat grad; zero padding contributes 0 to both sumsq and the
    nonfinite count), then dp-combined as per-leaf scalars inside
    `grad_leaf_stats` — the full summed gradient still never
    materializes. Returns `(new_params, new_state, grad_aux)`;
    grad_aux is None when telemetry is off."""
    inv = jnp.float32(1.0 / ctx.dp)
    names = list(params)
    i = jax.lax.axis_index(DP_AXIS)
    sliced_p, sliced_g, local_state = {}, {}, {}
    for k in names:
        chunk = ctx._chunks[k]
        padded = ctx.dp * chunk
        with jax.named_scope(scopes.GRAD_REDUCE):
            if ctx.stage >= 2:
                gs = ordered_psum_scatter(_pad_flat(grads[k], padded),
                                          DP_AXIS)
                gs = gs * inv if scale is None else _unscale_shard(
                    ctx, gs, scale)
            elif scale is None:
                gfull = ordered_psum(grads[k], DP_AXIS) * inv
                gs = jax.lax.dynamic_slice(_pad_flat(gfull, padded),
                                           (i * chunk,), (chunk,))
            else:
                gfull = ordered_psum(grads[k], DP_AXIS)
                gs = _unscale_shard(
                    ctx, jax.lax.dynamic_slice(_pad_flat(gfull, padded),
                                               (i * chunk,), (chunk,)),
                    scale)
        with jax.named_scope(scopes.OPTIMIZER_UPDATE):
            sliced_p[k] = jax.lax.dynamic_slice(
                _pad_flat(params[k], padded), (i * chunk,), (chunk,))
        sliced_g[k] = gs
        # state leaves arrive as this shard's (1, 1, chunk) block
        local_state[k] = {slot: v.reshape(-1)
                          for slot, v in state[k].items()}
    new_params = {}
    with jax.named_scope(scopes.OPTIMIZER_UPDATE):
        new_slices, new_state = ctx.optimizer.functional_step(
            sliced_p, sliced_g, local_state, lr, t)
        for k in names:
            full = jax.lax.all_gather(new_slices[k], DP_AXIS).reshape(-1)
            new_params[k] = full[:ctx._loc_sizes[k]].reshape(
                ctx._loc_shapes[k])
    aux = None
    if ctx._telemetry is not None:
        aux = ctx._trmod.grad_leaf_stats(ctx, sliced_g, dp_reduce=True)
    return new_params, {k: {slot: v.reshape(1, 1, -1)
                            for slot, v in new_state[k].items()}
                        for k in names}, aux


def _slice_local(ctx, params, state, bucket, i, sliced_p, sliced_g,
                 local_state, shard):
    """Split one bucket's reduced shard slice back into per-leaf
    (chunk,) grads at the layout's static offsets, and slice this
    shard's param chunk + (1,1,chunk) state block for each member
    leaf — the shard-local inputs of the bucket's optimizer update."""
    for k in bucket["names"]:
        off = bucket["offs"][k]
        chunk = ctx._chunks[k]
        sliced_g[k] = jax.lax.slice_in_dim(shard, off, off + chunk)
        sliced_p[k] = jax.lax.dynamic_slice(
            _pad_flat(params[k], ctx.dp * chunk), (i * chunk,), (chunk,))
        local_state[k] = {slot: v.reshape(-1)
                          for slot, v in state[k].items()}


def _unpack_gathered(ctx, bucket, gathered, new_params):
    """(dp, width) gathered bucket -> per-leaf tp-local params: column
    block [off, off+chunk) of the gathered buffer is leaf k's
    (dp, chunk) padded layout — flatten, trim the dp padding, reshape.
    Pure data movement (same values the per-leaf all_gather lays out),
    so the gather tail adds no arithmetic to the parity surface."""
    for k in bucket["names"]:
        off = bucket["offs"][k]
        chunk = ctx._chunks[k]
        full = gathered[:, off:off + chunk].reshape(-1)
        new_params[k] = full[:ctx._loc_sizes[k]].reshape(
            ctx._loc_shapes[k])


def _bucketed_update(ctx, params, grads, state, lr, t, scale=None):
    """ZeRO-1/2 with bucketed collectives, serial schedule
    (`bucket_bytes` set, `overlap=False`): one fixed-order
    reduce-scatter (stage 2) or all-reduce + slice (stage 1) per
    BUCKET instead of per leaf, over the shard-major packed flat
    (`_pack_bucket` — bit-identical sums by construction), one
    whole-tree optimizer update, then one all-gather per bucket on
    the tail. Fewer, larger collectives; same arithmetic."""
    inv = jnp.float32(1.0 / ctx.dp)
    names = list(params)
    i = jax.lax.axis_index(DP_AXIS)
    sliced_p, sliced_g, local_state = {}, {}, {}
    for bucket in ctx._buckets:
        width = bucket["width"]
        with jax.named_scope(scopes.GRAD_REDUCE):
            flat = _pack_bucket(ctx, bucket, grads)
            if ctx.stage >= 2:
                shard = ordered_psum_scatter(flat, DP_AXIS)
            else:
                full = ordered_psum(flat, DP_AXIS)
                shard = jax.lax.dynamic_slice(full, (i * width,), (width,))
            shard = shard * inv if scale is None else _unscale_shard(
                ctx, shard, scale)
        with jax.named_scope(scopes.OPTIMIZER_UPDATE):
            _slice_local(ctx, params, state, bucket, i, sliced_p, sliced_g,
                         local_state, shard)
    new_params = {}
    with jax.named_scope(scopes.OPTIMIZER_UPDATE):
        new_slices, new_state = ctx.optimizer.functional_step(
            sliced_p, sliced_g, local_state, lr, t)
        for bucket in ctx._buckets:
            members = bucket["names"]
            cat = (jnp.concatenate([new_slices[k] for k in members])
                   if len(members) > 1 else new_slices[members[0]])
            gathered = jax.lax.all_gather(cat, DP_AXIS)    # (dp, width)
            _unpack_gathered(ctx, bucket, gathered, new_params)
    aux = None
    if ctx._telemetry is not None:
        aux = ctx._trmod.grad_leaf_stats(
            ctx, {k: sliced_g[k] for k in names}, dp_reduce=True)
    return new_params, {k: {slot: v.reshape(1, 1, -1)
                            for slot, v in new_state[k].items()}
                        for k in names}, aux


def _overlapped_update(ctx, params, grads, state, lr, t, scale=None):
    """ZeRO-1/2 with the bucketed collectives ring-pipelined against
    the shard-local optimizer compute (`overlap=True`): each bucket's
    packed flat rides the fixed-order ppermute ring
    (`mesh.ring_collect`) and the shared `mesh.ring_pipeline`
    double-buffers — bucket j+1's grad transport is emitted before
    bucket j's reduce + optimizer update, and bucket j's updated-slice
    all-gather is itself ring transport emitted BEFORE bucket j+1's
    update math (the mirrored tail). The collected buffer has the
    all_gather layout and the reduce is the identical static
    shard-order sum (`collected_shard_sum`), so fp32 results stay
    bit-identical to the serial step — the schedule moves bytes
    earlier, it never reorders a sum. The optimizer update runs once
    per bucket (`functional_step` is per-leaf elementwise, so
    per-bucket calls equal the whole-tree call bitwise)."""
    names = list(params)
    i = jax.lax.axis_index(DP_AXIS)
    n = ctx.dp
    buckets = ctx._buckets
    gathered: List = [None] * len(buckets)
    new_state: Dict = {}
    stat_slices: Dict = {}

    @jax.named_scope(scopes.GRAD_REDUCE)
    def transport(bucket):
        return ring_collect(_pack_bucket(ctx, bucket, grads), DP_AXIS, n)

    @jax.named_scope(scopes.GRAD_REDUCE)
    def reduce(moved):
        if ctx.stage >= 2:
            return collected_shard_sum(moved, DP_AXIS)
        full = moved[0]
        for s in range(1, n):
            full = full + moved[s]
        width = moved.shape[1] // n
        return jax.lax.dynamic_slice(full, (i * width,), (width,))

    @jax.named_scope(scopes.OPTIMIZER_UPDATE)
    def consume(j, shard):
        bucket = buckets[j]
        shard = shard * jnp.float32(1.0 / n) if scale is None \
            else _unscale_shard(ctx, shard, scale)
        sliced_p, sliced_g, local_state = {}, {}, {}
        _slice_local(ctx, params, state, bucket, i, sliced_p, sliced_g,
                     local_state, shard)
        new_sl, new_st = ctx.optimizer.functional_step(
            sliced_p, sliced_g, local_state, lr, t)
        for k in bucket["names"]:
            new_state[k] = {slot: v.reshape(1, 1, -1)
                            for slot, v in new_st[k].items()}
            stat_slices[k] = sliced_g[k]
        cat = jnp.concatenate([new_sl[k] for k in bucket["names"]]) \
            if len(bucket["names"]) > 1 else new_sl[bucket["names"][0]]
        # the mirrored tail: bucket j's updated-slice gather goes into
        # flight here, ahead of bucket j+1's reduce + update in the
        # pipeline's next iteration
        gathered[j] = ring_collect(cat, DP_AXIS, n)        # (dp, width)

    ring_pipeline(buckets, transport, reduce, consume)
    new_params: Dict = {}
    with jax.named_scope(scopes.OPTIMIZER_UPDATE):
        for j, bucket in enumerate(buckets):
            _unpack_gathered(ctx, bucket, gathered[j], new_params)
    aux = None
    if ctx._telemetry is not None:
        aux = ctx._trmod.grad_leaf_stats(
            ctx, {k: stat_slices[k] for k in names}, dp_reduce=True)
    return new_params, new_state, aux


# ------------------------------------------- degree-blind state layout
def _to_zero_layout(full, spec_dim: Optional[int], dp: int, tp: int,
                    chunk: int) -> np.ndarray:
    """Full logical array -> (dp, tp, chunk) sharded layout (host-side
    numpy; the inverse of `_from_zero_layout` at ANY dp)."""
    full = np.asarray(full)
    parts = (np.split(full, tp, axis=spec_dim) if spec_dim is not None
             else [full] * tp)
    blocks = []
    for part in parts:
        flat = np.ravel(part)
        flat = np.pad(flat, (0, dp * chunk - flat.size))
        blocks.append(flat.reshape(dp, chunk))
    return np.stack(blocks, axis=1)


def _from_zero_layout(arr, shape: Tuple[int, ...],
                      spec_dim: Optional[int], tp: int) -> np.ndarray:
    """(dp, tp, chunk) sharded layout -> full logical array. Degree
    blind: only the layout's own leading dim says what dp it was saved
    at; nothing else depends on it."""
    arr = np.asarray(arr)
    if spec_dim is None:
        flat = np.ravel(arr[:, 0])
        return flat[:int(np.prod(shape))].reshape(shape)
    loc_shape = list(shape)
    loc_shape[spec_dim] //= tp
    loc = int(np.prod(loc_shape))
    parts = [np.ravel(arr[:, j])[:loc].reshape(loc_shape)
             for j in range(tp)]
    return np.concatenate(parts, axis=spec_dim)


class ZeroTrainStep:
    """One jitted shard_map train step
    `(params, opt_state, batch, lr, t) -> (loss, params, opt_state)`
    over the unified (dp x tp) mesh, with the optimizer update sharded
    across dp per `stage` (see module docstring). Build once per
    (model, optimizer, degree); `init_state` places params/state, the
    instance is the step callable."""

    def __init__(self, model, optimizer, loss_fn=None, *, criterion=None,
                 dp: Optional[int] = None, tp: int = 1, stage: int = 1,
                 param_specs: Optional[Dict[str, P]] = None,
                 batch_specs: Optional[Sequence[P]] = None,
                 grad_accum: int = 1, devices=None,
                 bucket_bytes: Optional[int] = None,
                 overlap: bool = False,
                 param_dtype: Optional[str] = None,
                 loss_scale: float = 2.0 ** 15,
                 scale_growth_interval: int = 200,
                 telemetry=None, enable_telemetry: bool = False):
        if stage not in (0, 1, 2):
            raise ValueError(
                f"stage must be 0 (replicated baseline), 1 (ZeRO-1) or 2 "
                f"(ZeRO-2); got {stage} — stage 3 (param sharding) is the "
                "GSPMD GroupSharded surface (level='p_g_os')")
        opt_name = type(optimizer).__name__
        if opt_name in _NON_ELEMENTWISE:
            raise NotImplementedError(
                f"{opt_name} applies whole-tensor update rules; the "
                "dp-sliced update would change its math. Use an "
                "elementwise optimizer (SGD/Momentum/Adam/AdamW/...)")
        if getattr(optimizer, "_grad_clip", None) is not None:
            raise NotImplementedError(
                "grad_clip inside the sharded update would clip by the "
                "SLICE norm, not the global norm; clip before the step or "
                "use the GSPMD GroupSharded surface with "
                "HybridParallelClipGrad")
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = (loss_fn if loss_fn is not None
                        else model_loss(model, criterion))
        self.tp = int(tp)
        devs = device_order(devices)
        self.dp = int(dp) if dp is not None else max(
            len(devs) // self.tp, 1)
        if self.dp < 1:
            raise ValueError(f"dp must be >= 1, got {self.dp}")
        self.stage = int(stage)
        self.grad_accum = int(grad_accum)
        if self.grad_accum < 1:
            raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
        self.param_specs = dict(param_specs or {})
        self.batch_specs = (tuple(batch_specs) if batch_specs is not None
                            else None)
        if self.grad_accum > 1 and self.batch_specs is not None and any(
                tuple(s) != (DP_AXIS,) for s in self.batch_specs):
            raise ValueError(
                "grad_accum > 1 splits every batch leaf along its local "
                "rows, so all batch_specs must be P('dp')")
        self.mesh = build_mesh(((DP_AXIS, self.dp), (TP_AXIS, self.tp)),
                               devices)
        self.devices = tuple(self.mesh.devices.reshape(-1))
        # per-param geometry, discovered at init_state/load time
        # dp=1 "sharding" is an identity: the 1/dp slice IS the whole
        # param, so the engine runs the stage-0 program outright — same
        # math, and literally the same executable, so bit-parity with
        # the replicated baseline is definitional rather than lucky
        # (even boundary reshapes steer XLA's FMA selection enough to
        # drift low bits otherwise)
        self._sharded = self.stage >= 1 and self.dp > 1
        # ---- bucketing / overlap knobs (ISSUE 20). Both describe HOW
        # the sharded collectives run, so stage 0 (no sharded
        # collectives) rejects them outright; at dp=1 the engine runs
        # the literal stage-0 executable (see above) and the knobs are
        # inert by the same identity.
        if bucket_bytes is not None and int(bucket_bytes) <= 0:
            raise ValueError(
                f"bucket_bytes must be > 0 (or None), got {bucket_bytes}")
        if self.stage == 0 and (overlap or bucket_bytes is not None):
            raise ValueError(
                "bucket_bytes/overlap schedule the SHARDED collectives; "
                "stage 0 has none — use stage 1 or 2")
        self.bucket_bytes = (int(bucket_bytes) if bucket_bytes is not None
                             else None)
        self.overlap = bool(overlap)
        self._bucketed = self._sharded and (self.bucket_bytes is not None
                                            or self.overlap)
        self._overlap = self._sharded and self.overlap
        # ---- mixed precision (ISSUE 20): bf16 working weights + wire
        # format, fp32 master weights in the sharded optimizer state
        if param_dtype in (None, "float32", "fp32", "f32"):
            self._param_dtype = None
        elif param_dtype in ("bf16", "bfloat16"):
            self._param_dtype = jnp.bfloat16
        else:
            raise ValueError(
                f"param_dtype must be None/'float32' or 'bf16', "
                f"got {param_dtype!r}")
        self.loss_scale = float(loss_scale)
        self.scale_growth_interval = int(scale_growth_interval)
        if self._param_dtype is not None:
            if self.loss_scale < 1.0:
                raise ValueError(
                    f"loss_scale must be >= 1, got {loss_scale}")
            if self.scale_growth_interval < 1:
                raise ValueError(
                    "scale_growth_interval must be >= 1, got "
                    f"{scale_growth_interval}")
            # the optimizer's own multi-precision machinery IS the
            # master-weight store: force it on so functional_state
            # allocates the fp32 "master_weight" slot for bf16 params
            # (documented in the class docstring — the engine owns this
            # decision, a bf16 step without masters is never correct)
            self.optimizer._multi_precision = True
        self._buckets: List[Dict] = []
        self._overlap_fraction: Optional[float] = None
        self._shapes: Dict[str, Tuple[int, ...]] = {}
        self._spec: Dict[str, P] = {}
        self._spec_dim: Dict[str, Optional[int]] = {}
        self._loc_shapes: Dict[str, Tuple[int, ...]] = {}
        self._loc_sizes: Dict[str, int] = {}
        self._chunks: Dict[str, int] = {}
        self._state_spec: Dict[str, Dict[str, P]] = {}
        self._step = None
        self._probes: Dict[int, object] = {}
        # ---- training observability (ISSUE 19), opt-in. The import is
        # lazy AND conditional: a telemetry-off trainer never imports
        # observability/training.py at all (poisoned-module pinned in
        # tests/test_training_obs.py — zero cost when off means zero
        # code, not just zero work).
        self._telemetry = None
        self._trmod = None
        if telemetry is not None or enable_telemetry:
            from ..observability import training as _trmod

            self._trmod = _trmod
            self._telemetry = (telemetry if telemetry is not None
                               else _trmod.TrainingTelemetry())
            self._telemetry.bind(
                dp=self.dp, tp=self.tp, stage=self.stage,
                device_ids=[d.id for d in self.devices])

    # ------------------------------------------------------------ geometry
    def _record_geometry(self, params: Dict[str, jnp.ndarray]) -> None:
        sizes = {DP_AXIS: self.dp, TP_AXIS: self.tp}
        for name, arr in params.items():
            shape = tuple(int(d) for d in arr.shape)
            spec = self.param_specs.get(name, P())
            self._shapes[name] = shape
            self._spec[name] = spec
            self._spec_dim[name] = tp_dim_spec(spec)
            loc = local_shape(shape, spec, sizes)
            self._loc_shapes[name] = loc
            self._loc_sizes[name] = int(np.prod(loc)) if loc else 1
            self._chunks[name] = max(
                math.ceil(self._loc_sizes[name] / self.dp), 1)
        if self._bucketed:
            # layout computed once per geometry; itemsize is the WIRE
            # dtype (the packed grads travel in the compute dtype)
            itemsize = 2 if self._param_dtype is not None else 4
            self._buckets = build_bucket_layout(
                list(params), self._chunks, itemsize, self.dp,
                self.bucket_bytes)

    def _slot_spec(self, name: str, slot_arr) -> P:
        """Stage-0 placement of one state slot: follow the param's tp
        spec when shaped like the param, else replicate (scalars)."""
        if tuple(slot_arr.shape) == self._shapes[name]:
            return self._spec[name]
        return P()

    # ------------------------------------------------------------ placement
    def init_state(self, params: Optional[Dict[str, jnp.ndarray]] = None):
        """Place full logical params on the mesh and build the sharded
        optimizer state; returns `(params, opt_state)` ready for the
        step callable."""
        if params is None:
            from ..jit.functional import extract_state

            params, _ = extract_state(self.model)
        params = {k: jnp.asarray(v) for k, v in params.items()}
        self._record_geometry(params)
        work = params
        if self._param_dtype is not None:
            # working weights live (and travel) in bf16; the fp32
            # originals become the master_weight slots below, so the
            # cast here loses nothing — masters round-trip exact
            work = {k: (v.astype(self._param_dtype)
                        if jnp.issubdtype(v.dtype, jnp.floating) else v)
                    for k, v in params.items()}
        placed = {k: jax.device_put(
            v, NamedSharding(self.mesh, self._spec[k]))
            for k, v in work.items()}
        host_state = self.optimizer.functional_state(work)
        host_np = {k: {s: np.asarray(v) for s, v in acc.items()}
                   for k, acc in host_state.items()}
        if self._param_dtype is not None:
            for k, v in params.items():
                if "master_weight" in host_np.get(k, {}):
                    # seed the master from the ORIGINAL fp32 param, not
                    # the bf16 round-trip functional_state produced
                    host_np[k]["master_weight"] = np.asarray(
                        v, dtype=np.float32)
            host_np[_SCALER_KEY] = {
                "scale": np.float32(self.loss_scale),
                "good_steps": np.float32(0.0)}
        return placed, self.load_optimizer_state(host_np)

    def load_optimizer_state(self, host_state):
        """Full-logical host state -> placed sharded state for THIS
        (dp, tp, stage). Degree-blind restore: the host form carries no
        dp imprint, so state saved at any degree loads at any other."""
        if not self._shapes:
            raise RuntimeError(
                "call init_state() (or pass params to it) before "
                "load_optimizer_state — the engine needs param geometry")
        out = {}
        for name, acc in host_state.items():
            if name == _SCALER_KEY:
                # replicated f32 scalars — no dp/tp imprint, so the
                # scaler restores degree-blind for free
                slots = {}
                for slot, arr in acc.items():
                    slots[slot] = jax.device_put(
                        jnp.asarray(arr, jnp.float32),
                        NamedSharding(self.mesh, P()))
                    self._state_spec.setdefault(name, {})[slot] = P()
                out[name] = slots
                continue
            slots = {}
            for slot, arr in acc.items():
                arr = np.asarray(arr)
                if not self._sharded:
                    spec = self._slot_spec(name, arr)
                    slots[slot] = jax.device_put(
                        jnp.asarray(arr), NamedSharding(self.mesh, spec))
                    self._state_spec.setdefault(name, {})[slot] = spec
                else:
                    laid = _to_zero_layout(arr, self._spec_dim[name],
                                           self.dp, self.tp,
                                           self._chunks[name])
                    slots[slot] = jax.device_put(
                        jnp.asarray(laid),
                        NamedSharding(self.mesh, P(DP_AXIS, TP_AXIS)))
                    self._state_spec.setdefault(name, {})[slot] = \
                        P(DP_AXIS, TP_AXIS)
            out[name] = slots
        return out

    def save_optimizer_state(self, opt_state):
        """Placed sharded state -> full-logical host arrays (numpy),
        restorable at ANY dp via `load_optimizer_state`."""
        out = {}
        for name, acc in opt_state.items():
            if name == _SCALER_KEY:
                out[name] = {slot: np.asarray(arr)
                             for slot, arr in acc.items()}
                continue
            slots = {}
            for slot, arr in acc.items():
                if not self._sharded:
                    slots[slot] = np.asarray(arr)
                else:
                    slots[slot] = _from_zero_layout(
                        arr, self._shapes[name], self._spec_dim[name],
                        self.tp)
            out[name] = slots
        return out

    # ----------------------------------------------------------- step build
    def _build(self, batch_len: int):
        pspec = {k: self._spec[k] for k in self._shapes}
        sspec = {k: dict(v) for k, v in self._state_spec.items()}
        bspec = (self.batch_specs if self.batch_specs is not None
                 else tuple(P(DP_AXIS) for _ in range(batch_len)))
        if len(bspec) != batch_len:
            raise ValueError(
                f"batch has {batch_len} leaves but batch_specs has "
                f"{len(bspec)}")
        ctx = self
        inv_dp = jnp.float32(1.0 / self.dp)
        # static dispatch: the schedule is a build-time property, the
        # jaxpr contains exactly one update path
        if not self._sharded:
            update_fn = _replicated_update
        elif self._overlap:
            update_fn = _overlapped_update
        elif self._bucketed:
            update_fn = _bucketed_update
        else:
            update_fn = _sharded_update
        scaled = self._param_dtype is not None

        def body(params, state, batch, lr, t):
            scale = None
            if scaled:
                scaler = state[_SCALER_KEY]
                scale = scaler["scale"]
                # floating batch leaves enter the bf16 compute dtype
                # here — part of the documented bounded-error contract
                batch = tuple(
                    b.astype(ctx._param_dtype)
                    if jnp.issubdtype(b.dtype, jnp.floating) else b
                    for b in batch)
            loss, grads = _accumulated_grads(ctx, params, batch, scale)
            # pin the backward: without the barrier XLA fuses the grad
            # computation with its CONSUMERS, and the stage-0 (full
            # update) vs stage-1/2 (slice/gather) consumers steer it to
            # differently-ordered reductions — observed bit drift at
            # dp=1. The barrier makes the grads a sealed subprogram, so
            # every stage (and every bucket/overlap schedule) compiles
            # the identical backward.
            loss, grads = jax.lax.optimization_barrier((loss, grads))
            finite = None
            with jax.named_scope(scopes.GRAD_REDUCE):
                loss = ordered_psum(loss, DP_AXIS) * inv_dp
                if scaled:
                    # skip signal BEFORE any reduction mixes shards
                    finite = (_grad_nonfinite(ctx, grads)
                              == jnp.float32(0.0))
            # the update functions put their own work under grad_reduce
            # and optimizer_update
            new_p, new_s, aux = update_fn(ctx, params, grads, state,
                                          lr, t, scale=scale)
            extras = None
            if scaled:
                # nonfinite step: revert params AND state wholesale (the
                # update ran on garbage), then let the scaler back off
                with jax.named_scope(scopes.OPTIMIZER_UPDATE):
                    new_p = {k: jnp.where(finite, v, params[k])
                             for k, v in new_p.items()}
                    new_s = {k: {slot: jnp.where(finite, v, state[k][slot])
                                 for slot, v in acc.items()}
                             for k, acc in new_s.items()}
                    new_scaler = _scaler_next(ctx, scaler, finite)
                new_s[_SCALER_KEY] = new_scaler
                extras = (new_scaler["scale"],
                          jnp.float32(1.0)
                          - finite.astype(jnp.float32))
            if ctx._telemetry is None:
                return loss, new_p, new_s
            # seal the update the same way the backward is sealed: the
            # health packing only CONSUMES barriered copies, so it
            # cannot steer how XLA compiles the update itself — the
            # telemetry-on step stays bit-identical to telemetry-off
            # (pinned across the whole (dp, stage) matrix in
            # tests/test_training_obs.py)
            if extras is None:
                (loss, new_p, new_s, params,
                 aux) = jax.lax.optimization_barrier(
                    (loss, new_p, new_s, params, aux))
            else:
                (loss, new_p, new_s, params, aux,
                 extras) = jax.lax.optimization_barrier(
                    (loss, new_p, new_s, params, aux, extras))
            health = ctx._trmod.pack_health(ctx, loss, params, new_p, aux,
                                            extras=extras)
            return loss, new_p, new_s, health

        out_specs = ((P(), pspec, sspec) if self._telemetry is None
                     else (P(), pspec, sspec, P()))
        self._step = jax.jit(_shard_map(
            scopes.named(body, "zero_train_step"), mesh=self.mesh,
            in_specs=(pspec, sspec, bspec, P(), P()),
            out_specs=out_specs,
            check_vma=False,  # noqa: COLLECTIVE-MESH — the ordered fixed-shard-order collectives and the (dp,tp,chunk) state outputs are per-shard by design; varying-axes tracking can't see through the custom_vjp boundaries
            ))

    def __call__(self, params, opt_state, batch, lr, t):
        """One training step. `batch` is a tuple of GLOBAL arrays
        (row-sharded over dp per batch_specs); `lr` scalar; `t` the
        1-based step count (drives Adam bias correction).

        With telemetry enabled the returned loss is the HOST float the
        telemetry plane drained (same value, already synced) — the one
        per-step host sync covers the caller's loss read too — and the
        call may raise `TrainingDiverged` when the sentinel trips."""
        tele = self._telemetry
        if tele is None:
            batch = tuple(batch)
            if self._step is None:
                self._build(len(batch))
            return self._step(params, opt_state, batch,
                              jnp.asarray(lr, jnp.float32),
                              jnp.asarray(t, jnp.int32))
        t_in = tele.clock()
        batch = tuple(batch)
        if self._step is None:
            self._build(len(batch))
        lr_ = jnp.asarray(lr, jnp.float32)
        t_ = jnp.asarray(t, jnp.int32)
        # tokens from batch SHAPE metadata — never a device read
        rows = batch[0].shape[0]
        tokens = (tele.tokens_per_step if tele.tokens_per_step is not None
                  else int(rows))
        t0 = tele.clock()
        loss, new_p, new_s, health = self._step(params, opt_state, batch,
                                                lr_, t_)
        t1 = tele.clock()
        host_loss = tele.record_step(
            health, step=int(t), tokens=tokens,
            batch_build_s=t0 - t_in, dispatch_s=t1 - t0)
        return host_loss, new_p, new_s

    # -------------------------------------------------------- observability
    @staticmethod
    def bytes_per_chip(tree) -> int:
        """Max-over-devices resident bytes of a placed pytree — THE
        1/dp measurement for the optimizer-state claim."""
        total = 0
        for arr in jax.tree_util.tree_leaves(tree):
            total += max(s.data.size * s.data.dtype.itemsize
                         for s in arr.addressable_shards)
        return total

    def optimizer_state_bytes_per_chip(self, opt_state) -> int:
        return self.bytes_per_chip(opt_state)

    def collective_seconds(self, samples: int = 3, rows: int = 1,
                           width: int = 1024) -> List[float]:
        """Measured wall seconds per fixed-order dp all-reduce of a
        replicated (rows, width) f32 buffer — the training twin of
        `TPContext.collective_seconds`. Feeds the
        `parallel_dp_collective_seconds` bench probe. On CPU meshes one
        dispatch's host overhead dominates — which is the honest
        number."""
        fn = self._probes.get((rows, width))
        if fn is None:
            mesh = self.mesh

            def reduce_one(y):
                return ordered_psum(y, DP_AXIS)

            def allreduce(x):
                return _shard_map(
                    reduce_one, mesh=mesh, in_specs=P(), out_specs=P(),
                    check_vma=False,  # noqa: COLLECTIVE-MESH — probe psum of a replicated buffer; rep tracking adds latency to the very overhead being measured
                    )(x)
            fn = jax.jit(allreduce)
            self._probes[(rows, width)] = fn
        x = jax.device_put(jnp.zeros((rows, width), jnp.float32),
                           NamedSharding(self.mesh, P()))
        fn(x).block_until_ready()              # compile + warm
        out = []
        for _ in range(max(int(samples), 1)):
            t0 = time.perf_counter()
            fn(x).block_until_ready()
            out.append(time.perf_counter() - t0)
        # the training twin of serving_tp_collective_seconds: same
        # registry, same construction-time-probe discipline (per-step
        # timing would measure dispatch queueing, not the collective)
        from ..observability import global_registry

        hist = global_registry().histogram(
            "parallel_dp_collective_seconds",
            "fixed-order dp all-reduce probe (ZeroTrainStep)")
        for s in out:
            hist.observe(s)
        return out

    def shard_step_seconds(self, samples: int = 3, rows: int = 128,
                           width: int = 128,
                           best_of: int = 3) -> Dict[str, float]:
        """Per-dp-shard straggler probe: a warmed best-of-N single-
        device micro-step (matmul-shaped) timed on EACH dp row's lead
        device, published as `training_shard_step_seconds{shard=}`.
        Same discipline as `collective_seconds`/`TPContext.
        collective_seconds`: two warm-up dispatches, then best-of-N per
        sample (`observability.training.probe_best_of` = min, monotone
        as trials are added) — so a shard whose BEST case is slow is a
        real straggler, not scheduler noise, and it shows up before it
        stalls the whole mesh at the next collective."""
        from ..observability import training as trmod

        fn = self._probes.get(("shard", rows, width))
        if fn is None:
            fn = jax.jit(lambda a: (a @ a.T).sum())
            self._probes[("shard", rows, width)] = fn
        out: Dict[str, float] = {}
        # enumerate over the mesh's (dp, tp) device grid rows — the
        # shard label cardinality is the dp degree, bounded by the mesh
        for shard, dev_row in enumerate(self.mesh.devices):
            dev = dev_row.reshape(-1)[0]
            x = jax.device_put(jnp.ones((rows, width), jnp.float32), dev)
            fn(x).block_until_ready()          # compile + warm
            fn(x).block_until_ready()
            best = []
            for _ in range(max(int(samples), 1)):
                trials = []
                for _ in range(max(int(best_of), 1)):
                    t0 = time.perf_counter()
                    fn(x).block_until_ready()
                    trials.append(time.perf_counter() - t0)
                best.append(trmod.probe_best_of(trials))
            if self._telemetry is not None:
                for s in best:
                    self._telemetry.observe_shard_step(str(shard), s)
            else:
                from ..observability import global_registry

                hist = global_registry().histogram(
                    "training_shard_step_seconds",
                    "warmed best-of-N per-dp-shard step-time probe",
                    labels={"shard": str(shard)})
                for s in best:
                    hist.observe(s)
            out[str(shard)] = trmod.probe_best_of(best)
        return out

    def comm_seconds(self, samples: int = 3, elems: int = 65536,
                     best_of: int = 3) -> Dict[str, float]:
        """Warmed best-of-N wall seconds for the two ZeRO wire
        primitives at this dp degree — the fixed-order reduce-scatter
        of a replicated (dp * elems,) f32 flat and the matching
        updated-shard all-gather — published as
        `training_comm_seconds{collective=reduce_scatter|all_gather}`.
        Same construction-time-probe discipline as
        `collective_seconds`: per-step timing would measure dispatch
        queueing, not the wire."""
        from ..observability import training as trmod

        n = self.dp
        key = ("comm", elems)
        fns = self._probes.get(key)
        if fns is None:
            mesh = self.mesh

            def rs_body(x):
                return ordered_psum_scatter(x, DP_AXIS)

            def ag_body(s):
                return jax.lax.all_gather(s, DP_AXIS).reshape(-1)

            rs = jax.jit(_shard_map(
                rs_body, mesh=mesh, in_specs=P(), out_specs=P(DP_AXIS),
                check_vma=False,  # noqa: COLLECTIVE-MESH — probe scatter of a replicated buffer; rep tracking adds latency to the very overhead being measured
                ))
            ag = jax.jit(_shard_map(
                ag_body, mesh=mesh, in_specs=P(DP_AXIS), out_specs=P(),
                check_vma=False,  # noqa: COLLECTIVE-MESH — probe gather; the all_gather output is replicated by construction
                ))
            fns = (rs, ag)
            self._probes[key] = fns
        rs, ag = fns
        x = jax.device_put(jnp.zeros((n * elems,), jnp.float32),
                           NamedSharding(self.mesh, P()))
        s = jax.device_put(jnp.zeros((n * elems,), jnp.float32),
                           NamedSharding(self.mesh, P(DP_AXIS)))
        out: Dict[str, float] = {}
        for name, fn, arg in (("reduce_scatter", rs, x),
                              ("all_gather", ag, s)):
            fn(arg).block_until_ready()        # compile + warm
            fn(arg).block_until_ready()
            best = []
            for _ in range(max(int(samples), 1)):
                trials = []
                for _ in range(max(int(best_of), 1)):
                    t0 = time.perf_counter()
                    fn(arg).block_until_ready()
                    trials.append(time.perf_counter() - t0)
                best.append(trmod.probe_best_of(trials))
            if self._telemetry is not None:
                for sec in best:
                    self._telemetry.observe_comm(name, sec)
            else:
                from ..observability import global_registry

                hist = global_registry().histogram(
                    "training_comm_seconds",
                    "warmed best-of-N ZeRO collective probe "
                    "(reduce-scatter / all-gather wall seconds)",
                    labels={"collective": name})
                for sec in best:
                    hist.observe(sec)
            out[name] = trmod.probe_best_of(best)
        return out

    def measure_overlap_fraction(self, samples: int = 3,
                                 best_of: int = 3) -> float:
        """Measured fraction of the bucket collectives' wall time the
        ring pipeline hides behind shard-local update math — the
        training twin of serving's `measure_overlap_fraction`. Three
        probes over the REAL recorded bucket layout (so the measured
        schedule is the step's schedule): (a) collectives only, (b)
        strictly serialized transport→reduce→update→gather per bucket
        (`optimization_barrier` fences between buckets pin the serial
        order), (c) the shared `ring_pipeline` double-buffered
        schedule. fraction = clip((b - c) / a, 0, 1), warmed
        best-of-N. On a CPU mesh the backends can't overlap transport
        with compute, so ~0.0 is the honest null — the probe measures,
        it does not assume. Stored on the instance and pushed into
        telemetry (`training_overlap_fraction` +
        `describe()["telemetry"]["overlap_fraction"]`) when bound."""
        from ..observability import training as trmod

        if not self._buckets:
            raise RuntimeError(
                "no bucket layout — call init_state() first on a "
                "bucketed/overlap engine (stage >= 1, dp > 1 with "
                "bucket_bytes or overlap set)")
        n = self.dp
        buckets = self._buckets
        dtype = (self._param_dtype if self._param_dtype is not None
                 else jnp.float32)
        mesh = self.mesh

        def surrogate(shard):
            # Adam-shaped elementwise cost stand-in for the shard-local
            # update (the probe times schedules, not the optimizer)
            m = shard * jnp.float32(0.9) + shard * jnp.float32(0.1)
            v = shard * shard
            return shard - jnp.float32(0.01) * m / (
                jnp.sqrt(v) + jnp.float32(1e-8))

        def coll_body(x):
            acc = jnp.float32(0.0)
            for b in buckets:
                flat = jnp.full((n * b["width"],), x).astype(dtype)
                moved = ring_collect(flat, DP_AXIS, n)
                red = collected_shard_sum(moved, DP_AXIS)
                gat = ring_collect(red, DP_AXIS, n)
                acc = acc + gat.astype(jnp.float32).sum()
            return acc

        def serial_body(x):
            acc = jnp.float32(0.0)
            for b in buckets:
                flat = jnp.full((n * b["width"],), x).astype(dtype)
                # fence: bucket j+1's transport may not hoist above
                # bucket j's consume — this IS the serial schedule
                flat, acc = jax.lax.optimization_barrier((flat, acc))
                moved = ring_collect(flat, DP_AXIS, n)
                red = collected_shard_sum(moved, DP_AXIS)
                upd = surrogate(red.astype(jnp.float32))
                gat = ring_collect(upd.astype(dtype), DP_AXIS, n)
                acc = acc + gat.astype(jnp.float32).sum()
            return acc

        def overlap_body(x):
            acc = [jnp.float32(0.0)]

            def transport(b):
                flat = jnp.full((n * b["width"],), x).astype(dtype)
                return ring_collect(flat, DP_AXIS, n)

            def reduce(moved):
                return collected_shard_sum(moved, DP_AXIS)

            def consume(j, red):
                upd = surrogate(red.astype(jnp.float32))
                gat = ring_collect(upd.astype(dtype), DP_AXIS, n)
                acc[0] = acc[0] + gat.astype(jnp.float32).sum()

            ring_pipeline(buckets, transport, reduce, consume)
            return acc[0]

        def timed(body):
            fn = jax.jit(_shard_map(
                body, mesh=mesh, in_specs=P(), out_specs=P(),
                check_vma=False,  # noqa: COLLECTIVE-MESH — schedule probe over the ring collectives; per-shard by design
                ))
            x = jnp.float32(1.0)
            fn(x).block_until_ready()          # compile + warm
            fn(x).block_until_ready()
            trials = []
            for _ in range(max(int(samples) * max(int(best_of), 1), 1)):
                t0 = time.perf_counter()
                fn(x).block_until_ready()
                trials.append(time.perf_counter() - t0)
            return trmod.probe_best_of(trials)

        t_coll = timed(coll_body)
        t_serial = timed(serial_body)
        t_overlap = timed(overlap_body)
        frac = 0.0
        if t_coll > 0.0:
            frac = float(np.clip((t_serial - t_overlap) / t_coll,
                                 0.0, 1.0))
        self._overlap_fraction = frac
        if self._telemetry is not None:
            self._telemetry.set_overlap_fraction(frac)
        return frac

    def describe(self) -> Dict[str, object]:
        return {
            "dp": self.dp,
            "tp": self.tp,
            "stage": self.stage,
            "grad_accum": self.grad_accum,
            "devices": [d.id for d in self.devices],
            "params": len(self._shapes),
            "chunk_elems": sum(self._chunks.values()),
            "param_dtype": ("bf16" if self._param_dtype is not None
                            else "fp32"),
            "bucket_bytes": self.bucket_bytes,
            "overlap": self.overlap,
            "buckets": len(self._buckets),
            "overlap_fraction": self._overlap_fraction,
            "telemetry": (self._telemetry.summary()
                          if self._telemetry is not None else None),
        }


def zero_train_step(model, optimizer, loss_fn=None, *, stage: int = 1,
                    **kwargs) -> ZeroTrainStep:
    """Builder form of `ZeroTrainStep` (the API named in ROADMAP item
    4): `step = zero_train_step(model, opt, stage=1); params, st =
    step.init_state(); loss, params, st = step(params, st, (x, y), lr,
    t)`."""
    return ZeroTrainStep(model, optimizer, loss_fn, stage=stage, **kwargs)


def save_optimizer_state(step: ZeroTrainStep, opt_state):
    """Module-level alias of the degree-blind save (mirrors the serving
    journal's snapshot helpers)."""
    return step.save_optimizer_state(opt_state)


def load_optimizer_state(step: ZeroTrainStep, host_state):
    return step.load_optimizer_state(host_state)


# ===================================================================
# paddle-compat GroupSharded surface (GSPMD sharding-annotation flavor)
# -------------------------------------------------------------------
# Ref: fleet/meta_parallel/sharding/group_sharded_stage{2,3}.py,
# group_sharded_optimizer_stage2.py + python/paddle/distributed/
# sharding/group_sharded.py (upstream layout, unverified — mount empty).
#
# Paddle implements ZeRO with explicit param slicing, pre-forward
# allgathers, grad reduce-scatter hooks and rank-local optimizer
# updates. This surface keeps the TPU-native GSPMD equivalent —
# sharding ANNOTATIONS consumed by a jitted train step (stage 1:
# opt-state dim-0 sharded; stage 2: + grads constrained to the
# scattered layout; stage 3: + params sharded with gather-on-use
# scheduled by XLA) — and now shares the repo's one mesh substrate and
# bridges to the explicit shard_map engine above via
# `zero_train_step()`.
# ===================================================================

def _default_mesh(axis: str = "sharding"):
    devs = device_order()
    return build_mesh(((axis, len(devs)),))


class _ShardedBase(Layer):
    stage = None
    _shard_params = False

    def __init__(self, layer: Layer, optimizer=None, group=None,
                 sync_buffers: bool = False, device: str = "tpu",
                 segment_size: int = 2 ** 20, offload: bool = False,
                 hcg=None, **kwargs):
        super().__init__()
        self._layers = layer
        self._optimizer = optimizer
        self.offload = offload
        if offload:
            try:  # fail LOUDLY at construction, not mid-training
                jax.devices()[0].memory("pinned_host")
            except Exception as e:
                raise NotImplementedError(
                    "offload=True needs a backend with pinned_host memory "
                    f"support; {jax.devices()[0].platform} reports none"
                ) from e
        if hcg is not None and hcg.mesh is not None and \
                hcg.get_sharding_parallel_world_size() > 1:
            self.mesh = hcg.mesh
            self.axis = "sharding"
        elif group is not None and getattr(group, "mesh", None) is not None:
            self.mesh = group.mesh
            self.axis = group.axis_name
        else:
            self.mesh = _default_mesh()
            self.axis = "sharding"
        if self._shard_params:
            self._place_params()

    def forward(self, *inputs, **kwargs):
        return self._layers(*inputs, **kwargs)

    # ------------------------------------------------ sharding hint trees
    def data_sharding(self):
        axes = tuple(a for a in self.mesh.axis_names
                     if a in ("dp", "sharding") and self.mesh.shape[a] > 1)
        return NamedSharding(self.mesh, P(axes if axes else None))

    def param_sharding(self):
        """Prefix sharding for params: stage 1/2 replicate params."""
        return NamedSharding(self.mesh, P())

    def param_shardings(self, params: dict):
        if not self._shard_params:
            sh = self.param_sharding()
            return {k: sh for k in params}
        return {k: shard_leaf(v, self.mesh, self.axis)
                for k, v in params.items()}

    def opt_state_shardings(self, opt_state: dict):
        """Moment slots shaped like the param shard dim-0; scalars repl.
        With offload=True the slots additionally live in pinned host memory
        (ZeRO-offload: HBM holds only params/grads/activations; XLA streams
        the moments in for the update)."""
        out = {}
        for pname, acc in opt_state.items():
            shardings = {}
            for slot, v in acc.items():
                sh = shard_leaf(v, self.mesh, self.axis)
                if self.offload:
                    sh = sh.with_memory_kind("pinned_host")
                shardings[slot] = sh
            out[pname] = shardings
        return out

    def grad_shardings(self, params: dict):
        if self.stage >= 2:
            return {k: shard_leaf(v, self.mesh, self.axis)
                    for k, v in params.items()}
        return {k: NamedSharding(self.mesh, P()) for k in params}

    def _place_params(self):
        for _, p in self._layers.named_parameters():
            p._data = jax.device_put(
                p._data, shard_leaf(p._data, self.mesh, self.axis))

    # ------------------------------------------ explicit-engine bridge
    def zero_train_step(self, loss_fn=None, criterion=None,
                        **kwargs) -> ZeroTrainStep:
        """The one-implementation bridge (ISSUE 16 satellite): build
        the explicit shard_map ZeRO step for THIS wrapper's model +
        optimizer at dp = the sharding axis size. Stage 3 has no
        shard_map twin — its gather-on-use param sharding is the GSPMD
        placement-tree contract — so it refuses."""
        if self.stage >= 3:
            raise NotImplementedError(
                "stage 3 (p_g_os) shards params via the GSPMD placement "
                "trees (param_shardings); the explicit shard_map engine "
                "covers stages 1/2")
        return ZeroTrainStep(self._layers, self._optimizer,
                             loss_fn, criterion=criterion,
                             dp=int(self.mesh.shape[self.axis]),
                             stage=self.stage, **kwargs)

    # ------------------------------------------------------- delegation
    def parameters(self, *a, **k):
        return self._layers.parameters(*a, **k)

    def named_parameters(self, *a, **k):
        return self._layers.named_parameters(*a, **k)

    def state_dict(self, *a, **k):
        return self._layers.state_dict(*a, **k)

    def set_state_dict(self, sd, *a, **k):
        out = self._layers.set_state_dict(sd, *a, **k)
        if self._shard_params:
            self._place_params()
        return out

    def get_all_parameters(self, convert2cpu: bool = False):
        """stage3 API: gather full params (device_put to replicated)."""
        repl = NamedSharding(self.mesh, P())
        for _, p in self._layers.named_parameters():
            p._data = jax.device_put(p._data, repl)
        return self._layers.parameters()


class GroupShardedStage2(_ShardedBase):
    stage = 2
    _shard_params = False


class GroupShardedStage3(_ShardedBase):
    stage = 3
    _shard_params = True


class GroupShardedOptimizerStage2:
    """Optimizer wrapper partitioning state over the sharding axis (ZeRO-1/2
    optimizer side). Delegates the whole surface; the sharded placement is
    applied by the jitted step through opt_state_shardings."""

    def __init__(self, params, optim, group=None, offload: bool = False,
                 device: str = "tpu", **kwargs):
        self._optim = optim
        self._params = params
        self.offload = offload
        self.group = group

    def __getattr__(self, name):
        return getattr(self._optim, name)

    def step(self):
        return self._optim.step()

    def minimize(self, *a, **k):
        return self._optim.minimize(*a, **k)


def group_sharded_parallel(model: Layer, optimizer, level: str = "p_g_os",
                           scaler=None, group=None, offload: bool = False,
                           sync_buffers: bool = False, buffer_max_size=2 ** 23,
                           segment_size=2 ** 20, sync_comm: bool = False,
                           dp_group=None, exclude_layer=None):
    """paddle.distributed.sharding.group_sharded_parallel.

    level: 'os' (stage1) | 'os_g' (stage2) | 'p_g_os' (stage3).
    """
    if level not in ("os", "os_g", "p_g_os"):
        raise ValueError(
            f"group_sharded_parallel level must be 'os' (ZeRO-1), 'os_g' "
            f"(ZeRO-2) or 'p_g_os' (ZeRO-3); got {level!r}")
    if level == "p_g_os":
        wrapped = GroupShardedStage3(model, optimizer=optimizer, group=group,
                                     offload=offload)
    else:
        wrapped = GroupShardedStage2(model, optimizer=optimizer, group=group,
                                     offload=offload)
        wrapped.stage = 1 if level == "os" else 2
    opt = GroupShardedOptimizerStage2(model.parameters(), optimizer,
                                      group=group, offload=offload)
    if scaler is not None:
        return wrapped, opt, scaler
    return wrapped, opt


def save_group_sharded_model(model, output, optimizer=None):
    """Gather-on-rank0 save (ref: group_sharded.py save util)."""
    from ..framework.io import save as _save

    if hasattr(model, "get_all_parameters"):
        model.get_all_parameters()
    _save(model.state_dict(), str(output) + ".pdparams")
    if optimizer is not None:
        inner = getattr(optimizer, "_optim", optimizer)
        _save(inner.state_dict(), str(output) + ".pdopt")
