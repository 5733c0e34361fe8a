"""Process/rank environment (TCPStore + PADDLE_* env contract analog).

Ref: python/paddle/distributed/parallel.py (upstream layout, unverified).
On TPU the bootstrap is jax.distributed.initialize + slice metadata; in the
single-controller (one process, N devices) emulation used for tests, "rank"
follows paddle's env contract when set, else process index.
"""
from __future__ import annotations

import os

import jax

_STATE = {"initialized": False, "rank": None, "world_size": None}


def init_parallel_env():
    """paddle.distributed.init_parallel_env analog.

    Multi-host: call jax.distributed.initialize from PADDLE_* / JAX env.
    Single-host: no-op beyond marking state.
    """
    if _STATE["initialized"]:
        return
    endpoints = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
    n_nodes = len(endpoints.split(",")) if endpoints else 1
    if n_nodes > 1 and not jax.distributed.is_initialized():
        # must run before any backend init — the client-state check only
        # inspects the distributed client, unlike jax.process_count() which
        # would itself initialize the backends. Genuine failures (bad
        # coordinator, busy port, seeded-too-early backend) must propagate:
        # swallowing them would silently run every rank as a world-size-1 job.
        coordinator = endpoints.split(",")[0]
        rank = int(os.environ.get("PADDLE_TRAINER_ID", 0))
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=n_nodes,
            process_id=rank,
        )
    _STATE["initialized"] = True
    # the default group may have been touched (and cached at the pre-init
    # world size) before this point — rebuild it so eager misuse checks and
    # get_world_size(default) see the live world
    from .group import reset_default_group

    reset_default_group()


def is_initialized() -> bool:
    return _STATE["initialized"]


def get_rank() -> int:
    if _STATE["rank"] is not None:
        return _STATE["rank"]
    env = os.environ.get("PADDLE_TRAINER_ID")
    if env is not None:
        return int(env)
    return jax.process_index()


def get_world_size() -> int:
    if _STATE["world_size"] is not None:
        return _STATE["world_size"]
    env = os.environ.get("PADDLE_TRAINERS_NUM")
    if env is not None:
        return int(env)
    return jax.process_count()


def set_logical_env(rank: int, world_size: int):
    """Used by the logical-rank emulation (tests / fleet over one process)."""
    _STATE["rank"] = rank
    _STATE["world_size"] = world_size


def parallel_helper_initialized():
    return _STATE["initialized"]
