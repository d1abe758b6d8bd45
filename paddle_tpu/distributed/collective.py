"""Collective communication API.

TPU-native equivalent of the reference's collective layer
(reference: python/paddle/distributed/collective.py:205 all_reduce etc.;
C++ kernels operators/collective/c_allreduce_op.h and friends; ring
management platform/collective_helper.h:68). The reference's ring_id
becomes a named mesh axis; inside a jitted/shard_mapped computation these
lower to XLA collectives over ICI/DCN (psum/all_gather/ppermute/
all_to_all) and XLA overlaps them with compute — no manual
calc/comm-stream sync ops needed (the reference's c_sync_*_stream ops have
no equivalent because the compiler schedules).

Outside a trace (eager, single-process SPMD) arrays are global: group-wide
reductions are identities w.r.t. the data the process already holds, and
multi-host eager transfers go through multihost_utils.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax._src import core as _jax_core

from ..tensor import Tensor


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


def _in_trace() -> bool:
    return not _jax_core.trace_state_clean()


def _unwrap(x):
    return x.value if isinstance(x, Tensor) else x


def _rewrap(x, out):
    return Tensor(out, stop_gradient=True) if isinstance(x, Tensor) else out


def _axis(group):
    """Resolve a 'group' to a mesh axis name (reference ring_id -> axis)."""
    if group is None:
        return "dp"
    if isinstance(group, str):
        return group
    return getattr(group, "axis_name", "dp")


def all_reduce(tensor, op: str = ReduceOp.SUM, group=None,
               sync_op: bool = True, use_calc_stream: bool = True):
    """In-trace: psum/pmax/pmin over the group axis. Eager single-process:
    identity (the process holds the global array)."""
    x = _unwrap(tensor)
    if not _in_trace():
        # eager host path only — a fault inside a trace would bake the
        # exception into the compiled program
        from .fault_inject import fault_point
        fault_point("collective.step")
    if _in_trace():
        axis = _axis(group)
        fn = {ReduceOp.SUM: jax.lax.psum, ReduceOp.MAX: jax.lax.pmax,
              ReduceOp.MIN: jax.lax.pmin,
              ReduceOp.AVG: jax.lax.pmean}.get(op)
        if fn is None:  # PROD via exp/log-free fallback
            out = jax.lax.all_gather(x, axis)
            out = jnp.prod(out, axis=0)
        else:
            out = fn(x, axis)
        return _rewrap(tensor, out)
    if isinstance(tensor, Tensor):
        return tensor
    return x


def all_gather(tensor_list, tensor=None, group=None, sync_op=True,
               use_calc_stream: bool = True, axis: int = 0):
    """In-trace gather along the group axis. Reference signature
    all_gather(tensor_list, tensor) appends per-rank shards to the list;
    the jax-native form returns the concatenated array."""
    if tensor is None:
        x = _unwrap(tensor_list)
        if _in_trace():
            out = jax.lax.all_gather(x, _axis(group), axis=axis,
                                     tiled=True)
            return _rewrap(tensor_list, out)
        return tensor_list
    # reference-style (list, tensor) call
    x = _unwrap(tensor)
    if _in_trace():
        out = jax.lax.all_gather(x, _axis(group))
        n = out.shape[0]
        tensor_list.extend(_rewrap(tensor, out[i]) for i in range(n))
    else:
        tensor_list.append(tensor)
    return tensor_list


def reduce_scatter(tensor, op: str = ReduceOp.SUM, group=None,
                   axis: int = 0):
    x = _unwrap(tensor)
    if _in_trace():
        out = jax.lax.psum_scatter(x, _axis(group), scatter_dimension=axis,
                                   tiled=True)
        return _rewrap(tensor, out)
    return tensor


def broadcast(tensor, src: int = 0, group=None, sync_op=True,
              use_calc_stream: bool = True):
    x = _unwrap(tensor)
    if _in_trace():
        axis = _axis(group)
        # select src's value on every member of the group
        gathered = jax.lax.all_gather(x, axis)
        return _rewrap(tensor, gathered[src])
    return tensor


def reduce(tensor, dst: int = 0, op: str = ReduceOp.SUM, group=None,
           use_calc_stream: bool = True):
    # SPMD collectives are symmetric; reduce == all_reduce w.r.t. content
    return all_reduce(tensor, op, group)


def scatter(tensor, tensor_list=None, src: int = 0, group=None,
            use_calc_stream: bool = True):
    if _in_trace():
        axis = _axis(group)
        idx = jax.lax.axis_index(axis)
        stacked = jnp.stack([_unwrap(t) for t in tensor_list]) \
            if tensor_list else _unwrap(tensor)
        picked = jax.lax.dynamic_index_in_dim(stacked, idx, keepdims=False)
        return _rewrap(tensor, picked)
    return tensor


def alltoall(in_tensor_list, out_tensor_list=None, group=None,
             use_calc_stream: bool = True,
             split_axis: int = 0, concat_axis: int = 0):
    """In-trace all_to_all (the exchange primitive behind expert and
    Ulysses sequence parallelism; reference only ships the raw op
    operators/collective/alltoall_op.cc)."""
    x = _unwrap(in_tensor_list) if not isinstance(in_tensor_list, list) \
        else jnp.concatenate([_unwrap(t) for t in in_tensor_list],
                             axis=split_axis)
    if _in_trace():
        out = jax.lax.all_to_all(x, _axis(group), split_axis=split_axis,
                                 concat_axis=concat_axis, tiled=True)
        return Tensor(out) if isinstance(in_tensor_list, Tensor) else out
    return in_tensor_list


def send(tensor, dst: int, group=None, use_calc_stream: bool = True):
    """P2P along the pipeline axis via ppermute (reference send_v2)."""
    x = _unwrap(tensor)
    if _in_trace():
        axis = _axis(group or "pp")
        n = jax.lax.axis_size(axis)
        out = jax.lax.ppermute(x, axis,
                               [(i, (i + 1) % n) for i in range(n)])
        return _rewrap(tensor, out)
    return tensor


def recv(tensor, src: int, group=None, use_calc_stream: bool = True):
    return send(tensor, src, group)


def p2p_shift(x, axis_name: str = "pp", shift: int = 1):
    """Shift values along a mesh axis (the pipeline hop primitive)."""
    if not _in_trace():
        return x
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(_unwrap(x), axis_name, perm)


def barrier(group=None):
    """Host-level sync point (reference barrier_op). In SPMD jit programs
    barriers are implicit in data dependencies; eager multi-host uses the
    coordination service."""
    from .fault_inject import fault_point
    fault_point("collective.step")
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("paddle_tpu_barrier")


def get_group(id="dp"):  # noqa: A002 - reference param name
    """reference: paddle.distributed.get_group(id) — retrieve a group
    created by new_group; an axis name returns a fresh handle for that
    mesh axis."""
    id_or_axis = id
    if isinstance(id_or_axis, int):
        g = _custom_groups.get(id_or_axis)
        if g is None:
            raise ValueError(f"no group with id {id_or_axis}")
        return g
    return Group(id_or_axis)


# -- TP helper collectives (reference: collective.py:747-919 c_identity /
#    c_concat / c_split / mp_allreduce) -------------------------------------

def _c_identity(x, group=None):
    """Forward identity, backward all-reduce (column-parallel input)."""
    axis = _axis(group or "mp")

    @jax.custom_vjp
    def ident(v):
        return v

    def fwd(v):
        return v, None

    def bwd(_, g):
        return (jax.lax.psum(g, axis) if _in_trace() else g,)

    ident.defvjp(fwd, bwd)
    return ident(x)


def _mp_allreduce(x, group=None):
    """Forward all-reduce, backward identity (row-parallel output)."""
    axis = _axis(group or "mp")

    @jax.custom_vjp
    def ar(v):
        return jax.lax.psum(v, axis) if _in_trace() else v

    def fwd(v):
        return ar(v), None

    def bwd(_, g):
        return (g,)

    ar.defvjp(fwd, bwd)
    return ar(x)


def all_gather_object(obj, group=None):
    """Gather an arbitrary picklable host object from every PROCESS
    (reference: distributed/collective.py all_gather_object over gloo;
    here pickled bytes ride process_allgather through the coordination
    service). Returns the list in rank order."""
    import pickle

    if jax.process_count() <= 1:
        return [obj]
    from jax.experimental import multihost_utils
    import numpy as np
    data = np.frombuffer(pickle.dumps(obj), np.uint8)
    sizes = multihost_utils.process_allgather(
        np.array([data.size], np.int64)).ravel()
    buf = np.zeros(int(sizes.max()), np.uint8)
    buf[:data.size] = data
    gathered = np.asarray(multihost_utils.process_allgather(buf))
    return [pickle.loads(gathered[i, :int(sizes[i])].tobytes())
            for i in range(len(sizes))]


class Group:
    """Communication-group handle (reference: distributed/collective.py
    Group). On the mesh runtime a group is a named mesh axis; ranks is
    informational."""

    def __init__(self, axis_name: str = "dp", ranks=None, id: int = 0):  # noqa: A002
        self.axis_name = axis_name
        self.ranks = list(ranks) if ranks is not None else []
        self.id = id
        self.nranks = len(self.ranks) if self.ranks else -1

    def is_member(self) -> bool:
        import jax
        return not self.ranks or jax.process_index() in self.ranks

    def get_group_rank(self, rank: int) -> int:
        return self.ranks.index(rank) if rank in self.ranks else -1

    def __repr__(self):
        return f"Group(axis={self.axis_name!r}, ranks={self.ranks})"


_custom_groups = {}


def new_group(ranks=None, backend=None, axis_name: str = "dp") -> Group:
    """reference: paddle.distributed.new_group — a handle for a rank
    subset. Collectives inside jit resolve groups by mesh axis name; the
    returned Group carries that axis."""
    gid = len(_custom_groups) + 1
    g = Group(axis_name, ranks, gid)
    _custom_groups[gid] = g
    return g


def wait(tensor, group=None, use_calc_stream: bool = True) -> None:
    """reference: paddle.distributed.wait (stream sync op) — on XLA,
    device-side ordering is by data dependency; this blocks the host on
    the value like c_sync_calc_stream."""
    v = tensor.value if hasattr(tensor, "value") else tensor
    if hasattr(v, "block_until_ready"):
        v.block_until_ready()


def split(x, size, operation: str = "linear", axis: int = 0,
          num_partitions: int = 1, gather_out: bool = True,
          weight_attr=None, bias_attr=None, name=None):
    """reference: paddle.distributed.split (collective.py split) — run a
    linear/embedding with its weight sharded over the mp mesh axis.

    operation='linear': size=(in, out); axis=1 shards columns
    (ColumnParallelLinear), axis=0 shards rows (RowParallelLinear).
    operation='embedding': size=(vocab, dim), vocab-sharded.
    """
    from .mp_layers import (ColumnParallelLinear, RowParallelLinear,
                            VocabParallelEmbedding)
    if operation == "embedding":
        layer = VocabParallelEmbedding(size[0], size[1],
                                       weight_attr=weight_attr, name=name)
        return layer(x)
    if operation != "linear":
        raise ValueError(f"unsupported split operation {operation!r}")
    if axis == 1:
        layer = ColumnParallelLinear(size[0], size[1],
                                     weight_attr=weight_attr,
                                     has_bias=bias_attr is not False,
                                     gather_output=gather_out, name=name)
    else:
        layer = RowParallelLinear(size[0], size[1],
                                  weight_attr=weight_attr,
                                  has_bias=bias_attr is not False,
                                  name=name)
    return layer(x)


def c_identity(x, group=None):
    """Public spelling of the identity-with-allreduce-grad collective
    (reference: operators/collective/c_identity_op.cc)."""
    from ..tensor import Tensor as _T
    raw = x.value if isinstance(x, _T) else x
    out = _c_identity(raw, group=group)
    return _T(out) if isinstance(x, _T) else out


def concat(x, group=None, axis: int = -1):
    """Gather mp-sharded activations and concatenate along ``axis``
    (reference: operators/collective/c_concat_op.cc — the
    gather_output path of ColumnParallelLinear)."""
    parts: list = []
    all_gather(parts, x, group=group)
    import jax.numpy as _jnp

    from ..tensor import Tensor as _T
    raw = [p.value if isinstance(p, _T) else p for p in parts]
    out = _jnp.concatenate(raw, axis=axis)
    return _T(out) if isinstance(x, _T) else out
