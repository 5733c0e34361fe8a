"""The unified mesh/sharding substrate (ISSUE 16 tentpole, layer 1).

One device-id-sorted, permutation-independent mesh module shared by
every parallel surface in the repo:

- `serving/tp.py` `TPContext` builds its 1-axis tp mesh here
  (`build_mesh`), and `serving/cluster.py` carves its disjoint replica
  sub-meshes here (`carve_submeshes`);
- `parallel/zero.py` builds its dp x tp training mesh here;
- the fleet GroupSharded compat surface builds its "sharding"-axis mesh
  here.

Why one module: `jax.devices()` ordering is not guaranteed stable
across processes, but device ids are. Sorting by id in exactly one
place (`device_order`) makes every mesh — serving sub-mesh, cluster
carving, training grid — a pure function of the device SET, so
snapshot/restore, cluster replica carving and sharded-checkpoint
resharding stay deterministic no matter how a caller's list was
shuffled ("portable collective communication" needs a portable mesh:
arxiv 2112.01075).

The module also owns the FIXED-SHARD-ORDER collectives
(`ordered_psum`, `ordered_psum_scatter`) and the Megatron
tensor-parallel region boundaries (`copy_to_tp_region`,
`reduce_from_tp_region`). Floating-point addition is not associative;
`lax.psum`'s reduction order is an implementation detail, so a
bit-determinism claim (ZeRO-vs-replicated parity, cross-process
reproducibility) must spell the order out: all_gather, then a
static-order shard sum. The same fixed-shard-order discipline the
quantized all-reduce (`serving/quant.py`) already uses.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "DP_AXIS", "TP_AXIS", "device_order", "build_mesh", "carve_submeshes",
    "shard_leaf", "ordered_psum", "ordered_psum_scatter",
    "ring_perm", "ring_collect", "ring_ordered_psum",
    "collected_shard_sum", "ring_ordered_psum_scatter",
    "chunk_bounds", "ring_pipeline",
    "copy_to_tp_region", "reduce_from_tp_region", "tp_dim_spec",
    "local_shape",
]

# canonical axis names: every training mesh is (dp, tp); serving meshes
# are 1-axis (tp,); the fleet compat surface uses its paddle name
# ("sharding") over the same constructor
DP_AXIS = "dp"
TP_AXIS = "tp"


def device_order(devices=None):
    """Sorted-by-id device list — THE canonical ordering for every mesh
    in the repo (serving sub-mesh, cluster carving, training grid).
    `jax.devices()` order is not guaranteed stable across processes;
    device ids are, so pinning the sort here keeps snapshot/restore,
    replica carving and sharded-checkpoint resharding deterministic no
    matter how the caller's list was shuffled."""
    devs = list(devices) if devices is not None else list(jax.devices())
    return sorted(devs, key=lambda d: d.id)


def build_mesh(axes: Sequence[Tuple[str, int]], devices=None) -> Mesh:
    """Build a Mesh from (axis_name, size) pairs over the id-sorted
    device prefix. `build_mesh(((\"tp\", 2),))` on any permutation of the
    same device list returns an identical mesh — permutation
    independence is the whole contract."""
    names = tuple(name for name, _ in axes)
    sizes = tuple(int(size) for _, size in axes)
    for name, size in zip(names, sizes):
        if size < 1:
            raise ValueError(
                f"mesh axis {name!r} must have size >= 1, got {size}")
    need = int(np.prod(sizes)) if sizes else 1
    devs = device_order(devices)
    if len(devs) < need:
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} needs {need} devices, got "
            f"{len(devs)}")
    grid = np.asarray(devs[:need]).reshape(sizes)
    return Mesh(grid, names)


def carve_submeshes(num_replicas: int, tp_size: int, devices=None
                    ) -> List[tuple]:
    """Carve the id-sorted device list into `num_replicas` disjoint
    `tp_size`-wide groups; replica i gets devices [i*tp : (i+1)*tp].
    Every process carves identically no matter how its `jax.devices()`
    happens to be ordered (pinned by the cluster determinism tests)."""
    devs = device_order(devices)
    need = num_replicas * tp_size
    if len(devs) < need:
        raise ValueError(
            f"{num_replicas} replicas x tp_size={tp_size} "
            f"needs {need} devices, got {len(devs)}")
    return [tuple(devs[i * tp_size:(i + 1) * tp_size])
            for i in range(num_replicas)]


def shard_leaf(arr_or_shape, mesh: Mesh, axis_name: str) -> NamedSharding:
    """Dim-0 sharding when divisible by the axis size, else replicated —
    paddle pads slices; GSPMD shards evenly-divisible dims and we keep
    the rest replicated (small params: biases, norms)."""
    shape = getattr(arr_or_shape, "shape", arr_or_shape)
    n = mesh.shape[axis_name]
    if len(shape) > 0 and shape[0] % n == 0 and shape[0] >= n:
        return NamedSharding(mesh, P(axis_name))
    return NamedSharding(mesh, P())


def tp_dim_spec(spec: Optional[P], axis: str = TP_AXIS) -> Optional[int]:
    """Index of the dimension `spec` shards over `axis`, or None when
    the spec is replicated w.r.t. that axis. Specs sharding one dim over
    multiple axes (e.g. P((\"dp\", \"tp\"))) are rejected — the training
    engine only composes with single-axis Megatron specs."""
    if spec is None:
        return None
    hit = None
    for dim, entry in enumerate(tuple(spec)):
        entries = entry if isinstance(entry, tuple) else (entry,)
        if axis in entries:
            if len(entries) > 1:
                raise ValueError(
                    f"spec {spec} shards one dim over multiple axes; "
                    f"only single-axis {axis!r} sharding is supported")
            if hit is not None:
                raise ValueError(
                    f"spec {spec} shards {axis!r} over two dims")
            hit = dim
    return hit


def local_shape(shape: Sequence[int], spec: Optional[P], sizes: Dict[str, int]
                ) -> Tuple[int, ...]:
    """Per-shard shape of a global `shape` placed under `spec` on a mesh
    with axis sizes `sizes` (e.g. {\"dp\": 2, \"tp\": 2})."""
    out = list(int(d) for d in shape)
    if spec is None:
        return tuple(out)
    for dim, entry in enumerate(tuple(spec)):
        entries = entry if isinstance(entry, tuple) else (entry,)
        for ax in entries:
            if ax is None:
                continue
            n = sizes.get(ax, 1)
            if out[dim] % n:
                raise ValueError(
                    f"dim {dim} of shape {tuple(shape)} not divisible by "
                    f"axis {ax!r} size {n}")
            out[dim] //= n
    return tuple(out)


# --------------------------------------------------------------- collectives
def ordered_psum(x, axis_name: str):
    """All-reduce with a SPELLED-OUT reduction order: all_gather, then a
    static python-loop sum over shard index 0..n-1. Bit-identical on
    every shard and across runs/processes (fp addition is not
    associative; `lax.psum`'s order is unspecified). This is the
    reduction every bit-parity claim in `parallel/zero.py` leans on."""
    g = jax.lax.all_gather(x, axis_name)         # (n, ...)
    out = g[0]
    for i in range(1, g.shape[0]):
        out = out + g[i]
    return out


def ring_perm(axis_size: int):
    """Fixed-order ring permutation table for `lax.ppermute`: shard s
    forwards to shard (s+1) % axis_size. ALWAYS built from the declared
    mesh axis size, never a hard-coded table — a literal written for one
    tp degree silently drops shards at another (the COLLECTIVE-MESH
    split-collective rule rejects literal perm tables for this reason)."""
    n = int(axis_size)
    if n < 1:
        raise ValueError(f"ring_perm needs axis_size >= 1, got {axis_size}")
    return [(s, (s + 1) % n) for s in range(n)]


def ring_collect(x, axis_name: str, axis_size: int):
    """Collect every shard's `x` into a SOURCE-INDEXED (axis_size, ...)
    buffer using axis_size-1 fixed-order `lax.ppermute` ring hops instead
    of one `all_gather`. After hop t, shard i holds the value that
    originated on shard (i - t) % n, so scattering each arrival into its
    source slot rebuilds exactly the all_gather layout — a static-order
    sum over the leading axis is then bit-identical to `ordered_psum`.
    The value of the ring form: each hop moves a micro-chunk and has no
    data dependency on the consumer of the previous chunk, so XLA's
    latency-hiding scheduler can overlap transport with compute
    (serving/overlap.py's split-psum pipeline; T3, arxiv 2401.16677)."""
    n = int(axis_size)
    perm = ring_perm(n)
    i = jax.lax.axis_index(axis_name)
    buf = jnp.zeros((n,) + x.shape, x.dtype)
    zeros = (0,) * x.ndim
    buf = jax.lax.dynamic_update_slice(buf, x[None], (i,) + zeros)
    val = x
    for t in range(1, n):
        val = jax.lax.ppermute(val, axis_name, perm)
        src = (i - t) % n
        buf = jax.lax.dynamic_update_slice(buf, val[None], (src,) + zeros)
    return buf


def ring_ordered_psum(x, axis_name: str, axis_size: int):
    """`ordered_psum` with the all_gather swapped for the fixed-order
    ppermute ring: identical static shard-order sum over the collected
    buffer, so the result is bit-identical to `ordered_psum` (and, pinned
    empirically by the serving overlap tests, to `lax.psum`) on every
    shard — the transport changes, the arithmetic does not."""
    g = ring_collect(x, axis_name, axis_size)    # (n, ...)
    out = g[0]
    for i in range(1, int(axis_size)):
        out = out + g[i]
    return out


def collected_shard_sum(g, axis_name: str):
    """The reduce half of a fixed-order reduce-scatter: `g` is the
    (n, flat) source-indexed buffer an `all_gather` or `ring_collect`
    produced; each shard keeps column-block i of the (src, dst, chunk)
    blocked view and sums it in static shard order 0..n-1. Split out so
    the overlapped training pipeline can emit the TRANSPORT of bucket
    j+1 before running this reduce for bucket j — the arithmetic is the
    one piece both the serial and the pipelined scatter share."""
    n = g.shape[0]
    blocked = g.reshape(n, n, -1)                # (src, dst, chunk)
    i = jax.lax.axis_index(axis_name)
    mine = jax.lax.dynamic_slice_in_dim(blocked, i, 1, axis=1)  # (src,1,chunk)
    out = mine[0, 0]
    for s in range(1, n):
        out = out + mine[s, 0]
    return out


def ordered_psum_scatter(x, axis_name: str):
    """Reduce-scatter with the same fixed shard order as `ordered_psum`:
    each shard keeps row i of the (n, n, chunk)-blocked ordered sum.
    `x` must be a flat vector divisible by the axis size; bit-identical
    to `ordered_psum(x)[i*chunk:(i+1)*chunk]` because the sum is
    elementwise — ZeRO-2's grad shard without ever materializing the
    full summed gradient in the update path."""
    g = jax.lax.all_gather(x, axis_name)         # (n, flat)
    return collected_shard_sum(g, axis_name)


def ring_ordered_psum_scatter(x, axis_name: str, axis_size: int):
    """`ordered_psum_scatter` with the all_gather swapped for the
    fixed-order ppermute ring: `ring_collect` rebuilds the identical
    source-indexed (n, flat) buffer, and `collected_shard_sum` runs the
    identical static shard-order arithmetic — so each shard's slice is
    bit-identical to the all_gather form (pinned in tests/test_zero_
    bucket.py), while the hop-by-hop transport is overlappable."""
    g = ring_collect(x, axis_name, axis_size)    # (n, flat)
    return collected_shard_sum(g, axis_name)


# ------------------------------------------------- ring-pipeline scheduler
def chunk_bounds(chunks: int, rows: int) -> List[Tuple[int, int]]:
    """Static micro-chunk bounds: up to `chunks` non-empty [lo, hi)
    ranges covering [0, rows). Degenerates gracefully — a 1-row payload
    yields one chunk (nothing to pipeline, but the ring transport is
    still bit-identical). Shared by the serving decode overlap
    (micro-row chunks of one activation) and any caller splitting a
    payload for `ring_pipeline`."""
    k = max(1, min(int(chunks), int(rows)))
    bounds = []
    for j in range(k):
        lo, hi = (j * rows) // k, ((j + 1) * rows) // k
        if hi > lo:
            bounds.append((lo, hi))
    return bounds


def ring_pipeline(items: Sequence, transport, reduce, consume) -> None:
    """THE double-buffered overlap schedule (T3, arxiv 2401.16677),
    shared by serving TP decode (serving/overlap.py, items = micro-row
    chunk bounds) and the ZeRO trainer (parallel/zero.py, items = grad
    buckets): for each item emit the NEXT item's ring transport before
    reducing and consuming the current one —

        moved = transport(items[0])
        for j: transport(items[j+1]); consume(j, reduce(moved))

    `transport(item)` issues the fixed-order ppermute hops and returns
    an opaque in-flight handle; `reduce(handle)` finishes the
    fixed-shard-order arithmetic; `consume(idx, reduced)` is the
    caller's dependent compute. Trace order puts the hops ahead of the
    consumer they overlap; the absence of a data dependency between
    them is what lets XLA's latency-hiding scheduler actually run
    transport and compute concurrently. The schedule changes WHEN bytes
    move, never what is summed in what order — every bit-identity claim
    layered on top rests on transport/reduce alone."""
    if not items:
        return
    moved = transport(items[0])
    for idx in range(len(items)):
        nxt = None
        if idx + 1 < len(items):
            nxt = transport(items[idx + 1])   # next item in flight
        consume(idx, reduce(moved))
        moved = nxt


# --------------------------------------------- Megatron tp region boundaries
# custom_vjp pairs instead of differentiating raw collectives:
# shard_map(check_vma=False) has no transpose story for `psum` that
# matches the replicated-input/partial-grad semantics Megatron needs, and
# the custom rules keep the backward reduction on the SAME fixed shard
# order as the forward.

@jax.custom_vjp
def copy_to_tp_region(x):
    """Megatron's `f`: identity forward into a tensor-parallel region,
    fixed-order tp all-reduce of the cotangent on the way back (each
    shard's backward contributes a partial input-grad)."""
    return x


def _copy_fwd(x):
    return x, None


def _copy_bwd(_, g):
    return (ordered_psum(g, TP_AXIS),)


copy_to_tp_region.defvjp(_copy_fwd, _copy_bwd)


@jax.custom_vjp
def reduce_from_tp_region(y):
    """Megatron's `g`: fixed-order tp all-reduce of the partial sums
    leaving a tensor-parallel region, identity on the cotangent (the
    incoming grad is already replicated across tp)."""
    return ordered_psum(y, TP_AXIS)


def _reduce_fwd(y):
    return ordered_psum(y, TP_AXIS), None


def _reduce_bwd(_, g):
    return (g,)


reduce_from_tp_region.defvjp(_reduce_fwd, _reduce_bwd)
