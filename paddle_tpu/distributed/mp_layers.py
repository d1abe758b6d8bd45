"""Megatron-style tensor-parallel layers.

Reference parity: python/paddle/distributed/fleet/meta_parallel/
parallel_layers/mp_layers.py (VocabParallelEmbedding:30,
ColumnParallelLinear:97, RowParallelLinear:170, ParallelCrossEntropy:249)
backed by c_embedding_op.cu / c_softmax_with_cross_entropy_op.cu and the
c_identity/c_split/mp_allreduce collectives.

TPU-native design: layers annotate their Parameters with PartitionSpecs
(param.pspec) and constrain activations with with_sharding_constraint. The
sharded train step (fleet.distributed_jit) feeds these to pjit; GSPMD then
inserts the exact collectives the reference hand-writes (identity fwd /
allreduce bwd for column input, allreduce fwd for row output, masked
gather + allreduce for the sharded embedding and softmax-CE).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import dispatch
from ..nn.initializer import get_initializer
from ..nn.layer import Layer
from ..tensor import Tensor
from .topology import get_hybrid_communicate_group

F = dispatch.wrapped_ops

# Canonical activation layout over the hybrid mesh: batch over dp+sharding,
# sequence over sep, hidden replicated (or mp for the parallel interior).
def _act_spec(ndim, hidden_axis=None):
    if ndim == 3:
        return (("dp", "sharding"), "sep", hidden_axis)
    if ndim == 2:
        return (("dp", "sharding"), hidden_axis)
    return tuple([("dp", "sharding")] + [None] * (ndim - 2) +
                 [hidden_axis])


import contextlib as _contextlib
import threading as _threading

# THREAD-LOCAL, not a module global: jit traces run on the calling
# thread, and one process may trace a serving-mesh engine (which
# disables these constraints) and a fleet/training step (which needs
# them) concurrently — a shared flag's save/restore would race and
# leak the wrong state into the other thread's trace.
_constraints_state = _threading.local()


def _constraints_disabled() -> bool:
    return getattr(_constraints_state, "disabled", False)


@_contextlib.contextmanager
def no_sharding_constraints():
    """Disable activation constraints (for computations running on a mesh
    other than the global hybrid mesh, e.g. the pipeline pp x dp mesh).
    Per-thread: only the calling thread's traces are affected."""
    prev = _constraints_disabled()
    _constraints_state.disabled = True
    try:
        yield
    finally:
        _constraints_state.disabled = prev


def active_hybrid_mesh():
    """The global hybrid mesh when the CURRENT trace is partitioned over
    it by GSPMD (inside a fleet step's jit), else None: no fleet, an
    eager call, constraints disabled, or a manual (shard_map) context
    whose body already runs per shard."""
    hcg = get_hybrid_communicate_group()
    from jax._src import core as _jax_core
    if hcg is None or _constraints_disabled() or \
            _jax_core.trace_state_clean() or hcg.mesh.size == 1:
        return None
    if jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return hcg.mesh


def _constrain(x, *spec):
    """Apply a sharding constraint when a mesh is active (inside pjit).

    Inside a manual-subset shard_map (the hybrid pipeline runs manual
    over "pp" with dp/mp/sharding/sep left to GSPMD), the constraint must
    carry a bare PartitionSpec resolved against the context's abstract
    mesh — a NamedSharding over the concrete mesh has all-Auto axis types
    and is rejected in the backward pass."""
    hcg = get_hybrid_communicate_group()
    from jax._src import core as _jax_core
    if hcg is None or _constraints_disabled() or \
            _jax_core.trace_state_clean():
        return x
    raw = x.value if isinstance(x, Tensor) else x
    try:
        manual = bool(jax.sharding.get_abstract_mesh().manual_axes)
    except Exception:
        manual = False
    sharding = (P(*spec) if manual
                else jax.sharding.NamedSharding(hcg.mesh, P(*spec)))
    out = jax.lax.with_sharding_constraint(raw, sharding)
    return Tensor(out, stop_gradient=getattr(x, "stop_gradient", True)) \
        if isinstance(x, Tensor) else out


class VocabParallelEmbedding(Layer):
    """Embedding with the vocab dim sharded over the mp axis."""

    def __init__(self, num_embeddings: int, embedding_dim: int,
                 weight_attr=None, mp_group=None, name=None):
        super().__init__()
        self._num_embeddings = num_embeddings
        self._embedding_dim = embedding_dim
        init = get_initializer("xavier_uniform") if weight_attr is None \
            else None
        self.weight = self.create_parameter(
            (num_embeddings, embedding_dim), attr=weight_attr,
            default_initializer=init)
        self.weight.pspec = P("mp", None)
        self.weight.is_distributed = True

    def forward(self, x):
        out = F["embedding"](x, self.weight)
        return _constrain(out, *_act_spec(out.ndim))


class ColumnParallelLinear(Layer):
    """Linear with out_features sharded over mp; optional gather."""

    def __init__(self, in_features: int, out_features: int,
                 weight_attr=None, has_bias: bool = True,
                 gather_output: bool = True, mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.gather_output = gather_output
        self.weight = self.create_parameter((in_features, out_features),
                                            attr=weight_attr)
        self.weight.pspec = P(None, "mp")
        self.weight.is_distributed = True
        if has_bias:
            self.bias = self.create_parameter((out_features,), is_bias=True)
            self.bias.pspec = P("mp")
            self.bias.is_distributed = True
        else:
            self.bias = None

    def forward(self, x):
        out = F["linear"](x, self.weight, self.bias)
        if self.gather_output:
            return _constrain(out, *_act_spec(out.ndim))
        # keep the hidden dim sharded on mp
        return _constrain(out, *_act_spec(out.ndim, "mp"))


class RowParallelLinear(Layer):
    """Linear with in_features sharded over mp; partial sums all-reduced
    by GSPMD when the output is required replicated."""

    def __init__(self, in_features: int, out_features: int,
                 weight_attr=None, has_bias: bool = True,
                 input_is_parallel: bool = False, mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter((in_features, out_features),
                                            attr=weight_attr)
        self.weight.pspec = P("mp", None)
        self.weight.is_distributed = True
        if has_bias:
            self.bias = self.create_parameter((out_features,), is_bias=True)
        else:
            self.bias = None

    def forward(self, x):
        if self.input_is_parallel:
            x = _constrain(x, *_act_spec(x.ndim, "mp"))
        out = F["linear"](x, self.weight, None)
        # forces the psum over mp while keeping batch/seq sharding
        out = _constrain(out, *_act_spec(out.ndim))
        if self.bias is not None:
            out = out + self.bias
        return out


class ParallelCrossEntropy(Layer):
    """Softmax cross-entropy over vocab-sharded logits
    (reference: mp_layers.py:249 backed by
    c_softmax_with_cross_entropy_op.cu). Under GSPMD the reduction over the
    sharded vocab axis lowers to the same partial-softmax + allreduce."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, input, label):  # noqa: A002
        return F["cross_entropy"](input, label, reduction="none",
                                  ignore_index=self.ignore_index)
