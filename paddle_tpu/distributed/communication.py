"""paddle.distributed communication API over XLA collectives.

Ref: python/paddle/distributed/communication/ + the c_* collective ops in
paddle/fluid/operators/collective/ (upstream layout, unverified — mount
empty). Two execution regimes:

* **Traced under shard_map** (the TPU-native hot path): each wrapper lowers to
  the XLA collective bound to the group's mesh-axis name — psum, all_gather,
  psum_scatter, ppermute, all_to_all — and XLA schedules it on ICI/DCN.
* **Eager, no named axis in scope**: the group degenerates to world_size 1
  (single-controller process owns all devices), so ops are identity — the
  same contract paddle gives before init_parallel_env.

In-place semantics follow paddle: all_reduce/broadcast rebind tensor._data.
"""
from __future__ import annotations

import warnings
from typing import List, Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from .group import Group, get_default_group, new_group  # noqa: F401

__all__ = [
    "ReduceOp", "all_reduce", "all_gather", "all_gather_object", "reduce",
    "reduce_scatter", "broadcast", "broadcast_object_list", "scatter",
    "scatter_object_list", "alltoall", "alltoall_single",
    "send", "recv", "isend", "irecv", "barrier", "batch_isend_irecv",
    "P2POp", "wait", "get_backend", "get_rank", "get_world_size",
    "is_initialized", "stream",
]


class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


#: one-time flag: warn the first time the jax._src fast path breaks, so a
#: jax upgrade that drops the private API is visible, not silent.
_PRIVATE_PROBE_WARNED = False


def _axis_in_scope(axis_name: str) -> bool:
    """True when `axis_name` is a live named axis (inside shard_map/pmap).

    A false negative here no longer produces a silent wrong answer: the
    eager fallbacks go through _no_axis_identity_ok, which raises for any
    >1-rank group. The broad except around the private-API fast path is
    deliberate — on any jax._src drift we fall THROUGH to the public probe,
    never out of the collective — but the first such drift warns once so a
    jax bump can never silently degrade this probe."""
    global _PRIVATE_PROBE_WARNED
    try:
        from jax._src import core as jcore

        if hasattr(jcore, "get_axis_env"):
            frame = jcore.get_axis_env()
            if frame is not None:
                return axis_name in frame.axis_sizes
    except Exception as e:  # noqa: BLE001 — private API; fall through to
        # the public probe (never out of the collective), warning once
        if not _PRIVATE_PROBE_WARNED:
            _PRIVATE_PROBE_WARNED = True
            warnings.warn(
                f"jax._src axis-env probe failed ({type(e).__name__}: {e}); "
                f"falling back to the public axis probe — check this jax "
                f"version's private-API layout",
                RuntimeWarning, stacklevel=2)
    try:
        jax.lax.axis_size(axis_name)
        return True
    except (NameError, KeyError, TypeError, ValueError):
        return False


def _resolve(group: Optional[Group]) -> Group:
    return group if group is not None else get_default_group()


def _no_axis_identity_ok(g: Group, op_name: str) -> None:
    """Called on the no-named-axis-in-scope path. Identity semantics are the
    paddle contract only for a trivial (<=1 rank) group; for a >1-rank group
    the collective would silently return the wrong answer (e.g. a typo'd
    axis name, or a mesh group used outside its shard_map region) — the
    silent failure mode the reference's PADDLE_ENFORCE culture forbids."""
    if g.nranks <= 1:
        return
    raise RuntimeError(
        f"paddle.distributed.{op_name}: group over mesh axis "
        f"{g.axis_name!r} spans {g.nranks} ranks, but no such named axis is "
        "in scope here — executing eagerly would silently degrade the "
        "collective to an identity. Run it inside the shard_map/jit region "
        "that binds the axis (the fleet engines do this), or use a <=1-rank "
        "group for eager code.")


def _axis_nranks(g: Group) -> int:
    """Rank count on the traced (axis-in-scope) path: the LIVE axis size —
    the default group's nranks reflects the process world, which can differ
    from the mesh axis a shard_map region binds."""
    try:
        return int(jax.lax.axis_size(g.axis_name))
    except (NameError, KeyError, TypeError, ValueError):
        return g.nranks


def _data(x):
    return x._data if isinstance(x, Tensor) else x


def _rebind(x, val):
    if isinstance(x, Tensor):
        x._data = val
        return x
    return Tensor(val)


def get_rank(group: Optional[Group] = None) -> int:
    g = group
    if g is not None and _axis_in_scope(g.axis_name):
        return jax.lax.axis_index(g.axis_name)
    from . import env as _env

    return _env.get_rank()


def get_world_size(group: Optional[Group] = None) -> int:
    if group is not None:
        return group.nranks
    from . import env as _env

    return _env.get_world_size()


def is_initialized() -> bool:
    from .env import is_initialized as _init

    return _init()


_REDUCERS = {
    ReduceOp.SUM: jax.lax.psum,
    ReduceOp.MAX: jax.lax.pmax,
    ReduceOp.MIN: jax.lax.pmin,
}


def all_reduce(tensor, op=ReduceOp.SUM, group: Optional[Group] = None,
               sync_op: bool = True):
    g = _resolve(group)
    if _axis_in_scope(g.axis_name):
        x = _data(tensor)
        if op == ReduceOp.AVG:
            out = jax.lax.pmean(x, g.axis_name)
        elif op == ReduceOp.PROD:
            # sign-correct product: |x| via exp-log-psum, sign via parity
            neg = jax.lax.psum((x < 0).astype(x.dtype), g.axis_name)
            mag = jnp.exp(jax.lax.psum(jnp.log(jnp.abs(x)), g.axis_name))
            out = mag * jnp.where(neg % 2 == 1, -1.0, 1.0).astype(x.dtype)
        else:
            out = _REDUCERS[op](x, g.axis_name)
        return _rebind(tensor, out)
    _no_axis_identity_ok(g, "all_reduce")
    return tensor  # world_size 1


def reduce(tensor, dst: int = 0, op=ReduceOp.SUM,
           group: Optional[Group] = None, sync_op: bool = True):
    """All ranks compute the reduction; only dst's value is meaningful —
    under SPMD the cheapest faithful implementation is an all_reduce."""
    return all_reduce(tensor, op, group, sync_op)


def all_gather(tensor_list: Optional[List], tensor=None,
               group: Optional[Group] = None, sync_op: bool = True, axis=0):
    """paddle signature: all_gather(tensor_list, tensor, group)."""
    g = _resolve(group)
    if tensor is None:  # functional style: all_gather(x) -> stacked
        tensor = tensor_list
        tensor_list = None
    x = _data(tensor)
    if _axis_in_scope(g.axis_name):
        out = jax.lax.all_gather(x, g.axis_name, axis=0, tiled=False)
        parts = [out[i] for i in range(_axis_nranks(g))]
    else:
        _no_axis_identity_ok(g, "all_gather")
        parts = [x]
    if tensor_list is not None:
        tensor_list.extend(Tensor(p) for p in parts)
        return tensor_list
    return Tensor(jnp.concatenate(parts, axis=axis) if parts[0].ndim
                  else jnp.stack(parts))


def all_gather_object(object_list: List, obj, group: Optional[Group] = None):
    g = _resolve(group)
    if _axis_in_scope(g.axis_name):
        raise RuntimeError("all_gather_object is host-side only; call it "
                           "outside jitted code")
    object_list.extend([obj] * 1)
    return object_list


def broadcast_object_list(object_list: List, src: int = 0,
                          group: Optional[Group] = None):
    """Host-side object broadcast. Single-controller: every process in a
    jax.distributed job holds the same Python program state, so the src
    rank's list is already what this rank holds — the call validates scope
    and returns the list unchanged (the reference pickles over NCCL)."""
    g = _resolve(group)
    if _axis_in_scope(g.axis_name):
        raise RuntimeError("broadcast_object_list is host-side only; call "
                           "it outside jitted code")
    return object_list


def scatter_object_list(out_object_list: List, in_object_list=None,
                        src: int = 0, group: Optional[Group] = None):
    """Host-side object scatter: this rank receives its slot of the src
    rank's list."""
    g = _resolve(group)
    if _axis_in_scope(g.axis_name):
        raise RuntimeError("scatter_object_list is host-side only; call it "
                           "outside jitted code")
    rank = get_rank(group)
    if in_object_list is not None:
        if len(in_object_list) < get_world_size(group):
            raise ValueError("in_object_list must have one entry per rank")
        val = in_object_list[rank]  # read BEFORE clear: lists may alias
        out_object_list.clear()
        out_object_list.append(val)
    return out_object_list


def get_backend(group: Optional[Group] = None) -> str:
    """The communication backend name — XLA collectives on this framework
    (the reference returns 'NCCL'/'GLOO')."""
    return "XLA"


def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM,
                   group: Optional[Group] = None, sync_op: bool = True):
    """Reduce across the group, scatter equal chunks (ZeRO's workhorse)."""
    g = _resolve(group)
    if tensor_list is not None:
        x = jnp.concatenate([_data(t) for t in tensor_list], axis=0)
    else:
        x = _data(tensor)
    if _axis_in_scope(g.axis_name):
        out = jax.lax.psum_scatter(x, g.axis_name, scatter_dimension=0,
                                   tiled=True)
        return _rebind(tensor, out)
    _no_axis_identity_ok(g, "reduce_scatter")
    return _rebind(tensor, x)


def broadcast(tensor, src: int = 0, group: Optional[Group] = None,
              sync_op: bool = True):
    g = _resolve(group)
    if _axis_in_scope(g.axis_name):
        x = _data(tensor)
        if src in g.ranks:
            src_local = g.get_group_rank(src)
        elif 0 <= src < _axis_nranks(g):
            src_local = src  # already a group-local rank
        else:
            raise ValueError(
                f"broadcast src={src} is not a member of group "
                f"{g.ranks} nor a valid group-local rank")
        # select src's value on every rank: gather then index (XLA folds this
        # into a broadcast collective)
        out = jax.lax.all_gather(x, g.axis_name)[src_local]
        return _rebind(tensor, out)
    _no_axis_identity_ok(g, "broadcast")
    return tensor


def scatter(tensor, tensor_list=None, src: int = 0,
            group: Optional[Group] = None, sync_op: bool = True):
    g = _resolve(group)
    if _axis_in_scope(g.axis_name):
        idx = jax.lax.axis_index(g.axis_name)
        if tensor_list is not None:
            stacked = jnp.stack([_data(t) for t in tensor_list])
        else:
            stacked = _data(tensor)
        out = jax.lax.dynamic_index_in_dim(stacked, idx, keepdims=False)
        return _rebind(tensor, out)
    _no_axis_identity_ok(g, "scatter")
    if tensor_list:
        return _rebind(tensor, _data(tensor_list[src]))
    return tensor


def alltoall(out_tensor_list, in_tensor_list=None,
             group: Optional[Group] = None, sync_op: bool = True):
    """Paddle alltoall: rank i sends in_tensor_list[j] to rank j."""
    g = _resolve(group)
    if in_tensor_list is None:
        in_tensor_list = out_tensor_list
        out_tensor_list = None
    if _axis_in_scope(g.axis_name):
        x = jnp.stack([_data(t) for t in in_tensor_list])  # [nranks, ...]
        out = jax.lax.all_to_all(x, g.axis_name, split_axis=0, concat_axis=0,
                                 tiled=False)
        parts = [Tensor(out[i]) for i in range(_axis_nranks(g))]
    else:
        _no_axis_identity_ok(g, "alltoall")
        parts = [Tensor(_data(t)) for t in in_tensor_list]
    if out_tensor_list is not None:
        out_tensor_list.clear()
        out_tensor_list.extend(parts)
        return out_tensor_list
    return parts


def alltoall_single(out_tensor, in_tensor=None,
                    in_split_sizes=None, out_split_sizes=None,
                    group: Optional[Group] = None, sync_op: bool = True):
    g = _resolve(group)
    if in_tensor is None:
        in_tensor = out_tensor
        out_tensor = None
    x = _data(in_tensor)
    if _axis_in_scope(g.axis_name):
        out = jax.lax.all_to_all(x, g.axis_name, split_axis=0, concat_axis=0,
                                 tiled=True)
    else:
        _no_axis_identity_ok(g, "alltoall_single")
        out = x
    if out_tensor is not None:
        return _rebind(out_tensor, out)
    return Tensor(out)


def _pshift(x, axis_name, n, offset):
    """ppermute ring shift by `offset` over the named axis."""
    perm = [(i, (i + offset) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


def send(tensor, dst: int = 0, group: Optional[Group] = None,
         sync_op: bool = True):
    """p2p under SPMD: only ring-neighbour sends are expressible; the PP
    engine uses ring ppermute via batch_isend_irecv instead. Eager mode:
    no-op (world_size 1)."""
    g = _resolve(group)
    if _axis_in_scope(g.axis_name):
        raise RuntimeError(
            "point-to-point send inside shard_map must go through "
            "batch_isend_irecv (ring ppermute); arbitrary src/dst p2p is not "
            "an SPMD primitive")
    _no_axis_identity_ok(g, "send")
    return tensor


def recv(tensor, src: int = 0, group: Optional[Group] = None,
         sync_op: bool = True):
    g = _resolve(group)
    if _axis_in_scope(g.axis_name):
        raise RuntimeError(
            "point-to-point recv inside shard_map must go through "
            "batch_isend_irecv (ring ppermute)")
    _no_axis_identity_ok(g, "recv")
    return tensor


isend = send
irecv = recv


class P2POp:
    """Mirror of paddle.distributed.P2POp for batch_isend_irecv."""

    def __init__(self, op, tensor, peer: int, group: Optional[Group] = None):
        self.op = op            # send / recv callables above
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list: List[P2POp]):
    """Fused ring exchange. Under shard_map, pairs of (send->peer, recv<-peer)
    become one ppermute; this is the primitive PP's p2p layer and ring
    attention build on."""
    if not p2p_op_list:
        return []
    g = _resolve(p2p_op_list[0].group)
    if not _axis_in_scope(g.axis_name):
        # world_size 1: recvs keep their buffers, sends vanish
        _no_axis_identity_ok(g, "batch_isend_irecv")
        return []
    n = _axis_nranks(g)
    sends = [p for p in p2p_op_list if p.op in (send, isend)]
    recvs = [p for p in p2p_op_list if p.op in (recv, irecv)]
    if len(sends) != len(recvs):
        raise ValueError(
            f"batch_isend_irecv under SPMD needs matching send/recv counts, "
            f"got {len(sends)} sends and {len(recvs)} recvs")
    tasks = []
    for s, r in zip(sends, recvs):
        # SPMD sees ONE program on all ranks, so peers must form a uniform
        # shift: under shard_map `peer` is the ring offset k, and the pair
        # (send k, recv) lowers to ppermute rank -> (rank+k) % n. The paired
        # recv must name the same shift — either k ("receive the shift-by-k
        # result") or -k mod n ("receive from rank-k"); anything else (e.g.
        # paddle-style global dst ranks) gets an error, not a silent shift.
        k = s.peer % n
        if r.peer % n not in (k, (-k) % n):
            raise ValueError(
                f"batch_isend_irecv: send offset {s.peer} and recv offset "
                f"{r.peer} do not form a uniform ring shift over {n} ranks "
                f"(expected recv peer ≡ {k} or {(-k) % n} mod {n}); "
                f"arbitrary src/dst p2p is not an SPMD primitive")
        out = jax.lax.ppermute(_data(s.tensor), g.axis_name,
                               [(i, (i + k) % n) for i in range(n)])
        r.tensor._data = out
        tasks.append(r.tensor)
    return tasks


def barrier(group: Optional[Group] = None):
    g = _resolve(group)
    if _axis_in_scope(g.axis_name):
        # a psum of a scalar is the canonical SPMD barrier
        jax.lax.psum(jnp.zeros((), jnp.float32), g.axis_name)
        return None
    from . import env as _env

    world = _env.get_world_size()
    if world > 1:
        if g.nranks not in (1, world):
            # no host-side SUBGROUP barrier exists on jax.distributed;
            # syncing all processes here would deadlock the ranks outside
            # the group — refuse loudly instead
            raise RuntimeError(
                f"paddle.distributed.barrier: subgroup barrier over "
                f"{g.nranks} of {world} processes is not supported on the "
                "eager path; barrier() outside shard_map syncs the WHOLE "
                "job (or run the barrier inside the group's shard_map "
                "region)")
        # multi-controller job: a REAL cross-process sync, not a no-op
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("paddle_dist_barrier")
    return None


def wait(tensor, group: Optional[Group] = None, use_calc_stream: bool = True):
    return tensor


class _StreamNS:
    """paddle.distributed.stream.* variants — on TPU streams are XLA's
    concern; these alias the sync wrappers."""

    all_reduce = staticmethod(all_reduce)
    all_gather = staticmethod(all_gather)
    reduce_scatter = staticmethod(reduce_scatter)
    broadcast = staticmethod(broadcast)
    alltoall = staticmethod(alltoall)
    scatter = staticmethod(scatter)
    send = staticmethod(send)
    recv = staticmethod(recv)


stream = _StreamNS()
