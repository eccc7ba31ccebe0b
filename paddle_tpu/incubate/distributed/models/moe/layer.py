"""MoELayer: dense-dispatch mixture of experts.

Reference: incubate/distributed/models/moe/moe_layer.py (MoELayer:226 with
MoEScatter:99/MoEGather:149 all-to-all PyLayers over global_scatter/
global_gather CUDA ops, python/paddle/distributed/utils/moe_utils.py:20,146).

TPU-native redesign: dispatch/combine are einsums over a static [T, E, C]
routing tensor; expert weights are stacked [E, ...] and sharded over the 'ep'
mesh axis, so GSPMD partitions the "ec..." einsums and emits the all-to-all
over ICI that the reference issues by hand at runtime. Everything routes
through registry ops, so the layer works in eager autograd AND compiles into
one XLA program under paddle_tpu.jit.
"""
from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp
from jax.sharding import PartitionSpec

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nn import initializer as I
from paddle_tpu.nn.layer import Layer
from paddle_tpu.ops import api as F

from .gates import GShardGate, NaiveGate, SwitchGate


def _annotate(p: Tensor, spec: PartitionSpec):
    from paddle_tpu.distributed.mesh import annotate_param

    return annotate_param(p, spec)


class ExpertMLP(Layer):
    """Stacked expert FFN: weights [E, d_model, d_hidden] so all experts run
    as ONE batched matmul on the MXU (vs the reference's per-expert Linear
    loop)."""

    def __init__(self, num_experts, d_model, d_hidden, activation=None):
        super().__init__()
        self.num_experts = num_experts
        self.activation = activation or F.gelu
        s1 = 1.0 / math.sqrt(d_model)
        s2 = 1.0 / math.sqrt(d_hidden)
        self.w1 = self.create_parameter(
            [num_experts, d_model, d_hidden], default_initializer=I.Uniform(-s1, s1)
        )
        self.b1 = self.create_parameter(
            [num_experts, 1, d_hidden], default_initializer=I.Constant(0.0)
        )
        self.w2 = self.create_parameter(
            [num_experts, d_hidden, d_model], default_initializer=I.Uniform(-s2, s2)
        )
        self.b2 = self.create_parameter(
            [num_experts, 1, d_model], default_initializer=I.Constant(0.0)
        )
        _annotate(self.w1, PartitionSpec("ep", None, None))
        _annotate(self.b1, PartitionSpec("ep", None, None))
        _annotate(self.w2, PartitionSpec("ep", None, None))
        _annotate(self.b2, PartitionSpec("ep", None, None))

    def forward(self, expert_inputs: Tensor) -> Tensor:
        """expert_inputs: [E, C, d_model] -> [E, C, d_model]."""
        h = F.einsum("ecm,emh->ech", expert_inputs, self.w1) + self.b1
        h = self.activation(h)
        return F.einsum("ech,ehm->ecm", h, self.w2) + self.b2


class MoELayer(Layer):
    """Reference signature: MoELayer(d_model, experts, gate, moe_group, ...).

    Args:
        d_model: token feature size.
        experts: ExpertMLP (fused, preferred), a list of per-expert Layers
            (reference style), or None to build an ExpertMLP internally.
        gate: 'naive' | 'switch' | 'gshard' or a gate instance.
        num_experts / d_hidden: used when experts is None.
        top_k: routing fan-out for the naive gate.
        capacity_factor: expert capacity = cf * top_k * T / E (static shape).

    After forward, ``self.aux_loss`` holds the load-balancing loss to add to
    the training objective.
    """

    def __init__(
        self,
        d_model: int,
        experts=None,
        gate="gshard",
        num_experts: Optional[int] = None,
        d_hidden: Optional[int] = None,
        top_k: int = 2,
        capacity_factor: float = 1.25,
        moe_group=None,
        dispatch_mode: str = "auto",
        name=None,
    ):
        super().__init__()
        self.d_model = d_model
        self.capacity_factor = capacity_factor
        self.group = moe_group
        if dispatch_mode not in ("auto", "dense", "sparse"):
            raise ValueError(
                f"dispatch_mode must be auto|dense|sparse, got {dispatch_mode!r}")
        self.dispatch_mode = dispatch_mode

        if isinstance(experts, (list, tuple)):
            self.experts = list(experts)
            for i, e in enumerate(self.experts):
                self.add_sublayer(f"expert_{i}", e)
            self.num_experts = len(self.experts)
            self._fused = None
        else:
            if experts is None:
                if num_experts is None or d_hidden is None:
                    raise ValueError("need experts or (num_experts, d_hidden)")
                experts = ExpertMLP(num_experts, d_model, d_hidden)
            self._fused = experts
            self.add_sublayer("experts", experts)
            self.num_experts = experts.num_experts

        self._gate_kind = gate
        self._top_k = top_k
        self.gate = None  # built on first forward, when capacity is known
        self.aux_loss = None

    def _build_gate(self, capacity):
        if not isinstance(self._gate_kind, str):
            self.gate = self._gate_kind
        else:
            cls = {"naive": NaiveGate, "switch": SwitchGate, "gshard": GShardGate}[
                self._gate_kind
            ]
            if self._gate_kind == "naive":
                self.gate = cls(self.d_model, self.num_experts, capacity, top_k=self._top_k)
            else:
                self.gate = cls(self.d_model, self.num_experts, capacity)
        self.add_sublayer("gate", self.gate)
        self.gate.training = self.training  # lazy build must inherit train/eval mode

    def _routing_fanout(self) -> int:
        """Tokens-per-slot multiplier: top-k of the routing scheme."""
        if isinstance(self._gate_kind, str):
            return {"naive": self._top_k, "switch": 1, "gshard": 2}[self._gate_kind]
        g = self._gate_kind
        if isinstance(g, SwitchGate):
            return 1
        if isinstance(g, GShardGate):
            return 2
        return getattr(g, "top_k", 2)

    def forward(self, x: Tensor) -> Tensor:
        orig_shape = list(x.shape)
        d = orig_shape[-1]
        x2d = F.reshape(x, [-1, d])
        tokens = x2d.shape[0]
        k = self._routing_fanout()
        capacity = max(1, int(self.capacity_factor * k * tokens / self.num_experts))
        if self.gate is None:
            self._build_gate(capacity)
        else:
            self.gate.capacity = capacity

        mode = self.dispatch_mode
        if mode != "dense" and not self._gate_supports_sparse():
            # custom gate written against the routing()-only contract
            if mode == "sparse":
                import warnings

                warnings.warn(
                    f"gate {type(self.gate).__name__} does not implement "
                    "_choices()/routing_sparse(); using dense dispatch")
            mode = "dense"
        if mode == "auto":
            # dense dispatch burns T*E*C*M ~ cf*k*T^2*M flops in the routing
            # einsums (quadratic in tokens); the scatter/gather path is
            # O(k*T*M) memory-bound. Dense only wins for small token
            # counts / few experts where the einsum stays on the MXU's
            # fast path (the crossover is not measured on the chip).
            mode = "sparse" if (tokens * self.num_experts >= 1 << 15
                                or self.num_experts >= 16) else "dense"

        if mode == "sparse":
            out = self._forward_sparse(x2d, tokens, capacity)
        else:
            out = self._forward_dense(x2d)
        return F.reshape(out, orig_shape)

    def _gate_supports_sparse(self):
        from .gates import BaseGate

        cls = type(self.gate)
        return (cls._choices is not BaseGate._choices
                or cls.routing_sparse is not BaseGate.routing_sparse)

    def _run_experts(self, expert_in):
        if self._fused is not None:
            return self._fused(expert_in)
        parts = F.unbind(expert_in, axis=0)
        return F.stack([e(p) for e, p in zip(self.experts, parts)], axis=0)

    def _forward_dense(self, x2d):
        combine, dispatch, aux = self.gate.routing(x2d)
        self.aux_loss = aux
        # dispatch: [T,E,C] x [T,M] -> [E,C,M]  (GSPMD: all-to-all over 'ep')
        expert_in = F.einsum("tec,tm->ecm", F.cast(dispatch, x2d.dtype), x2d)
        expert_out = self._run_experts(expert_in)
        # combine: [T,E,C] x [E,C,M] -> [T,M]
        return F.einsum("tec,ecm->tm", F.cast(combine, expert_out.dtype), expert_out)

    def _forward_sparse(self, x2d, tokens, capacity):
        """Ragged dispatch: scatter tokens into their (expert, slot) rows and
        gather them back — O(k*T*M) instead of the dense einsum's
        cf*k*T^2*M (reference analog: moe_utils.py global_scatter/
        global_gather move only routed tokens)."""
        E, C, d = self.num_experts, capacity, x2d.shape[-1]
        eidx, slot, weights, aux = self.gate.routing_sparse(x2d)
        self.aux_loss = aux
        K = eidx.shape[1]

        valid = F.cast(slot >= 0, x2d.dtype)                      # [T,K]
        # dropped tokens route to a trash row E*C that never reaches experts
        flat = eidx * C + F.cast(F.clip(F.cast(slot, "int32"), 0, C - 1), "int32")
        flat = F.where(slot >= 0, flat, F.full_like(flat, E * C))  # [T,K]

        zeros = F.zeros([E * C + 1, d], dtype=x2d.dtype)
        contrib = F.reshape(
            F.expand(F.unsqueeze(x2d, 1), [tokens, K, d]) * F.unsqueeze(valid, -1),
            [tokens * K, d])
        expert_in_flat = F.index_add(zeros, F.reshape(flat, [-1]), 0, contrib)
        expert_in = F.reshape(expert_in_flat[:E * C], [E, C, d])

        expert_out = self._run_experts(expert_in)

        out_flat = F.reshape(expert_out, [E * C, d])
        out_flat = F.concat([out_flat, F.zeros([1, d], dtype=out_flat.dtype)], axis=0)
        gathered = F.reshape(
            F.gather(out_flat, F.reshape(flat, [-1]), axis=0), [tokens, K, d])
        w = F.cast(weights, gathered.dtype) * valid
        return F.sum(gathered * F.unsqueeze(w, -1), axis=1)
