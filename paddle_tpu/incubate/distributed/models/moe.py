"""MoE layer with GShard/Switch/Naive gates + expert-parallel dispatch.

Ref: python/paddle/incubate/distributed/models/moe/moe_layer.py, gate/*.py +
the global_scatter/global_gather all-to-all ops (upstream layout, unverified
— mount empty). Paddle dispatches tokens to experts with explicit
all-to-all ops; the TPU-native formulation is the GShard einsum dispatch:
capacity-bucketed one-hot dispatch/combine tensors contracted against the
token batch, with the expert dim sharded over the ep axis so GSPMD emits the
all_to_all. Dense einsum dispatch is MXU-friendly and differentiable through
the gates.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map

from ....core.tensor import Tensor
from .... import nn
from ....nn import functional as F

__all__ = ["MoELayer", "NaiveGate", "SwitchGate", "GShardGate"]


class BaseGate(nn.Layer):
    def __init__(self, d_model: int, num_experts: int):
        super().__init__()
        self.d_model = d_model
        self.num_experts = num_experts
        self.gate_weight = self.create_parameter(
            [d_model, num_experts],
            default_initializer=nn.initializer.XavierUniform())

    def logits(self, x: Tensor) -> Tensor:
        return x.matmul(self.gate_weight)


class NaiveGate(BaseGate):
    """top-k gate, no capacity/aux loss."""

    def __init__(self, d_model, num_expert=1, world_size=1, topk=2):
        super().__init__(d_model, num_expert * world_size)
        self.topk = topk


class SwitchGate(BaseGate):
    """top-1 gate (Switch Transformer) with load-balance aux loss."""

    def __init__(self, d_model, num_expert=1, world_size=1, topk=1,
                 switch_eps=0.1, capacity=(1.2, 2.4)):
        super().__init__(d_model, num_expert * world_size)
        self.topk = 1
        self.capacity_factor = capacity[0]


class GShardGate(BaseGate):
    """top-2 gate with capacity + aux loss (GShard)."""

    def __init__(self, d_model, num_expert=1, world_size=1, topk=2,
                 capacity=(1.2, 2.4), random_routing=True):
        super().__init__(d_model, num_expert * world_size)
        self.topk = 2
        self.capacity_factor = capacity[0]


class MoELayer(nn.Layer):
    """Mixture of experts over an expert-parallel group.

    experts: list of Layers (the local experts; with ep sharding the expert
    dim of the stacked computation is partitioned over `gate`'s world).
    """

    def __init__(self, d_model: int, experts: Optional[List[nn.Layer]] = None,
                 gate=None, moe_group=None, mp_group=None,
                 recompute_interval: int = 0, **kwargs):
        super().__init__()
        self.d_model = d_model
        if isinstance(gate, dict):  # paddle config-dict form
            gtype = gate.get("type", "gshard")
            topk = gate.get("top_k", 2)
            cls = {"gshard": GShardGate, "switch": SwitchGate,
                   "naive": NaiveGate}[gtype]
            gate = cls(d_model, num_expert=len(experts), topk=topk)
        self.gate = gate
        self.experts = nn.LayerList(experts or [])
        self.num_experts = len(self.experts)
        self.moe_group = moe_group
        self.capacity_factor = getattr(gate, "capacity_factor", 2.0)
        self.aux_loss: Optional[Tensor] = None

    def _routed_forward(self, flat_data, gate_w, expert_run, fused=None):
        """Pure routing math over raw arrays (shared by eager vjp and jit).

        fused=None auto-selects the Pallas gather dispatch on TPU (SURVEY
        §7 fused-MoE-dispatch kernel): expert queues are filled by row
        GATHERS over routing indices instead of the [T, E, C] one-hot
        einsum — no materialized mask, no dead MXU work. fused=True forces
        it (interpret mode off-TPU, for the hermetic parity tests)."""
        from ....ops import pallas_kernels as pk

        tokens, d = flat_data.shape
        E = self.num_experts
        k = getattr(self.gate, "topk", 2)
        capacity = max(int(np.ceil(self.capacity_factor * tokens * k / E)), k)

        logits = flat_data @ gate_w
        probs = jax.nn.softmax(logits, axis=-1)              # [T, E]
        topv, topi = jax.lax.top_k(probs, k)                 # [T, k]
        onehot = jax.nn.one_hot(topi, E, dtype=probs.dtype)  # [T, k, E]
        # position of each token within its expert's queue, per k-slot
        pos = (jnp.cumsum(onehot.reshape(tokens * k, E), axis=0) - 1.0
               ).reshape(tokens, k, E)
        keep = (pos < capacity) * onehot                     # capacity mask
        gates = topv[..., None] * keep                       # [T, k, E]
        denom = jnp.maximum(gates.sum(axis=(1, 2), keepdims=True), 1e-9)
        gates = gates / denom * topv.sum(-1)[:, None, None]

        # aux load-balance loss (GShard): E * sum(me * ce)
        me = probs.mean(axis=0)
        ce = onehot[:, 0].mean(axis=0)
        aux = E * jnp.sum(me * ce)

        if fused is None:
            fused = pk.moe_dispatch_available(flat_data)
        if fused:
            interpret = not pk._on_tpu()
            pos_tk = (pos * onehot).sum(-1)                  # [T, k]
            keep_tk = keep.sum(-1)                           # [T, k] 0/1
            slot_token, tok_slot = pk.moe_dispatch_indices(
                topi, pos_tk.astype(jnp.int32), keep_tk, E, capacity)
            expert_in = pk.gather_rows(
                flat_data, slot_token, interpret=interpret
            ).reshape(E, capacity, d)
            expert_out = expert_run(expert_in)               # [E, C, d']
            d_out = expert_out.shape[-1]
            per_k = pk.gather_rows(
                expert_out.reshape(E * capacity, d_out),
                tok_slot.reshape(-1), interpret=interpret
            ).reshape(tokens, k, d_out)
            gate_tk = gates.sum(-1)                          # [T, k]
            y = (gate_tk[..., None] * per_k).sum(1)
            return y, aux

        pos_onehot = jax.nn.one_hot(
            jnp.clip(pos, 0, capacity - 1).astype(jnp.int32), capacity,
            dtype=probs.dtype) * keep[..., None]             # [T,k,E,C]
        dispatch = (pos_onehot.sum(1) > 0).astype(probs.dtype)  # [T, E, C]
        combine = jnp.einsum("tke,tkec->tec", gates, pos_onehot)

        expert_in = jnp.einsum("tec,td->ecd", dispatch, flat_data)
        expert_out = expert_run(expert_in)                   # [E, C, d']
        y = jnp.einsum("tec,ecd->td", combine, expert_out)
        return y, aux

    def forward(self, x: Tensor) -> Tensor:
        """x: [batch, seq, d_model] (or [tokens, d_model]).

        Routed through apply_callable with all params as vjp inputs, so the
        eager tape reaches the gate and expert weights (jit paths
        differentiate through the same pure function)."""
        from ....core.dispatch import apply_callable
        from ....core import tape as tape_mod
        from ....jit.functional import bind_state

        squeeze = x.ndim == 2
        if squeeze:
            x = x.unsqueeze(0)
        b, s, d = x.shape
        flat = x.reshape([b * s, d])

        named = [(n, p) for n, p in self.named_parameters()
                 if not p.stop_gradient]
        names = [n for n, _ in named]
        ptensors = [p for _, p in named]

        def pure(xd, *pdatas):
            bound = dict(zip(names, pdatas))
            gate_w = bound.get("gate.gate_weight",
                               self.gate.gate_weight._data)

            def expert_run(expert_in):
                outs = []
                with bind_state(self, bound, {}):
                    with tape_mod.no_grad():
                        for e, expert in enumerate(self.experts):
                            ye = expert(Tensor(expert_in[e]))
                            outs.append(ye._data if isinstance(ye, Tensor)
                                        else ye)
                return jnp.stack(outs)

            y, aux = self._routed_forward(xd, gate_w, expert_run)
            return y, aux

        y, aux = apply_callable("moe", pure, flat, *ptensors)
        self.aux_loss = aux
        out = y.reshape([b, s, -1])
        if squeeze:
            out = out.squeeze(0)
        return out

    def expert_parallel_forward(self, x: Tensor, mesh, ep_axis: str = "ep"):
        """All-to-all expert-parallel forward over a mesh axis (SURVEY §2.3
        EP/MoE row; the global_scatter/global_gather analog).

        Tokens are sharded over `ep_axis`; the GShard dispatch einsum runs
        per shard, expert queues are exchanged with `lax.all_to_all`, each
        rank runs its E/W local experts (param pytrees stacked over the
        expert dim and sharded on `ep_axis`), and a second all_to_all returns
        expert outputs for the local combine. Requires homogeneous experts
        and num_experts % ep_size == 0. With enough capacity (no drops) the
        result equals the dense einsum path bit-for-bit up to reduction
        order.
        """
        from ....core import tape as tape_mod
        from ....core.dispatch import apply_callable
        from ....jit.functional import bind_state, extract_state
        from jax.sharding import PartitionSpec as P

        W = mesh.shape[ep_axis]
        E = self.num_experts
        if E % W != 0:
            raise ValueError(f"num_experts {E} not divisible by "
                             f"{ep_axis} size {W}")

        squeeze = x.ndim == 2
        if squeeze:
            x = x.unsqueeze(0)
        b, s, d = x.shape
        flat = x.reshape([b * s, d])
        if (b * s) % W != 0:
            raise ValueError(f"{b * s} tokens not divisible by ep size {W}")

        # per-expert param pytrees; stacked over the expert dim INSIDE the
        # pure fn (jnp.stack is differentiable → grads reach each expert)
        pkeys = sorted(extract_state(self.experts[0])[0])
        L = len(pkeys)
        expert_params = []               # Tensor params, expert-major order
        for e in self.experts:
            named = dict(e.named_parameters())
            expert_params.extend(named[k] for k in pkeys)
        gate_w = self.gate.gate_weight

        def pure(xd, gw, *flat_params):
            stacked_leaves = [
                jnp.stack([flat_params[e * L + i] for e in range(E)])
                for i in range(L)
            ]
            def local_fn(x_loc, gw_loc, *leaves_loc):
                def apply_one(leaves_e, xin):
                    bound = dict(zip(pkeys, leaves_e))
                    with bind_state(self.experts[0], bound, {}):
                        with tape_mod.no_grad():
                            y = self.experts[0](Tensor(xin))
                    return y._data if isinstance(y, Tensor) else y

                def expert_run(expert_in):            # [E, C, d] local queues
                    ein = jax.lax.all_to_all(
                        expert_in, ep_axis, split_axis=0, concat_axis=1,
                        tiled=True)                   # [E/W, W*C, d]
                    y = jax.vmap(apply_one)(tuple(leaves_loc), ein)
                    return jax.lax.all_to_all(
                        y, ep_axis, split_axis=1, concat_axis=0,
                        tiled=True)                   # [E, C, d']

                y, aux = self._routed_forward(x_loc, gw_loc, expert_run)
                return y, jax.lax.pmean(aux, ep_axis)

            return _shard_map(
                local_fn, mesh=mesh,
                in_specs=(P(ep_axis), P()) + tuple(P(ep_axis)
                                                   for _ in stacked_leaves),
                out_specs=(P(ep_axis), P()),
            )(xd, gw, *stacked_leaves)

        y, aux = apply_callable("moe_ep", pure, flat, gate_w, *expert_params)
        self.aux_loss = aux
        out = y.reshape([b, s, -1])
        if squeeze:
            out = out.squeeze(0)
        return out
