"""paddle.static module-level API tail.

Reference parity: python/paddle/static/__init__.py __all__ — scopes
(fluid/executor.py global_scope/scope_guard), program (de)serialization
(fluid/io.py serialize_program/save_to_file/...), program-state utilities
(fluid/io.py load_program_state/set_program_state), build/execution
strategies (framework/details/build_strategy.h:54,
execution_strategy.h), device_guard / name_scope (fluid/framework.py),
py_func (fluid/layers/nn.py py_func), append_backward / gradients
(fluid/backward.py:1363,1958).

TPU-native stance: a Program is one traced XLA computation, so several
reference knobs (BuildStrategy/ExecutionStrategy/ParallelExecutor) are
accepted-and-inert configuration shells — XLA owns scheduling and fusion.
Autodiff facades run on the eager tape (jax.vjp based) instead of
program-to-program rewriting.
"""

from __future__ import annotations

import contextlib
import os
import pickle
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.enforce import InvalidArgumentError
from ..core.place import CPUPlace, GPUPlace, Place, TPUPlace
from ..tensor import Parameter, Tensor
from .program import Program

# Variable: in the traced world every SSA value is a Tensor.
Variable = Tensor


# -- scopes -------------------------------------------------------------------

class Scope:
    """Name -> value tree with parent lookup (reference:
    framework/scope.h). Holds persistable variables (parameters created by
    paddle.static.nn builders, global vars)."""

    def __init__(self, parent: Optional["Scope"] = None):
        self._vars: Dict[str, Any] = {}
        self.parent = parent

    def var(self, name: str):
        return self._vars.get(name)

    def find_var(self, name: str):
        if name in self._vars:
            return self._vars[name]
        return self.parent.find_var(name) if self.parent else None

    def set_var(self, name: str, value) -> None:
        self._vars[name] = value

    def new_scope(self) -> "Scope":
        return Scope(self)

    def local_var_names(self) -> List[str]:
        return list(self._vars)


_global_scope = Scope()
_scope_stack: List[Scope] = [_global_scope]


def global_scope() -> Scope:
    """reference: paddle.static.global_scope (fluid/executor.py)."""
    return _scope_stack[-1]


@contextlib.contextmanager
def scope_guard(scope: Scope):
    """reference: paddle.static.scope_guard (fluid/executor.py)."""
    _scope_stack.append(scope)
    try:
        yield scope
    finally:
        _scope_stack.pop()


# -- strategies / ParallelExecutor (accepted-and-inert shells) ---------------

class BuildStrategy:
    """reference: framework/details/build_strategy.h:54. XLA owns graph
    scheduling; fields are accepted for API compatibility."""

    class ReduceStrategy:
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = \
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        self.memory_optimize = None
        self.enable_inplace = True
        self.fuse_all_optimizer_ops = True
        self.fuse_all_reduce_ops = True
        self.fuse_elewise_add_act_ops = True
        self.fuse_bn_act_ops = True
        self.build_cuda_graph = False
        self.debug_graphviz_path = ""


class ExecutionStrategy:
    """reference: framework/details/execution_strategy.h."""

    def __init__(self):
        self.num_threads = 0
        self.num_iteration_per_drop_scope = 100
        self.num_iteration_per_run = 1
        self.use_thread_barrier = False


class ParallelExecutor:
    """reference: framework/parallel_executor.h:51 — multi-device SSA
    graph engine. Subsumed by GSPMD: the wrapped Program is already one
    sharded XLA computation; this facade keeps the call surface."""

    def __init__(self, use_cuda=False, loss_name=None, main_program=None,
                 share_vars_from=None, exec_strategy=None,
                 build_strategy=None, num_trainers=1, trainer_id=0,
                 scope=None):
        self.program = main_program
        self.build_strategy = build_strategy or BuildStrategy()
        self.exec_strategy = exec_strategy or ExecutionStrategy()

    def run(self, fetch_list=None, feed=None, return_numpy=True):
        from .program import Executor
        return Executor().run(self.program, feed=feed,
                              fetch_list=fetch_list,
                              return_numpy=return_numpy)


# -- places -------------------------------------------------------------------

def cpu_places(device_count: Optional[int] = None) -> List[CPUPlace]:
    """reference: paddle.static.cpu_places."""
    if device_count is None:
        device_count = int(os.environ.get("CPU_NUM", 1))
    return [CPUPlace(i) for i in range(device_count)]


def cuda_places(device_ids=None) -> List[Place]:
    """reference: paddle.static.cuda_places — here: accelerator places
    (TPU chips first, GPU otherwise)."""
    try:
        accel = [d for d in jax.devices() if d.platform != "cpu"]
    except RuntimeError:
        accel = []
    cls = TPUPlace if any(d.platform == "tpu" for d in accel) else GPUPlace
    if device_ids is None:
        device_ids = list(range(max(1, len(accel))))
    return [cls(i) for i in device_ids]


# -- vars ---------------------------------------------------------------------

def create_global_var(shape, value, dtype, persistable=False,
                      force_cpu=False, name=None) -> Tensor:
    """reference: paddle.static.create_global_var
    (fluid/layers/tensor.py)."""
    from ..core.dtype import convert_dtype
    t = Tensor(jnp.full(tuple(shape), value, dtype=convert_dtype(dtype)),
               stop_gradient=True, name=name)
    t.persistable = persistable
    if name:
        global_scope().set_var(name, t)
    return t


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None) -> Parameter:
    """reference: paddle.static.create_parameter."""
    import paddle_tpu as pt
    p = pt.create_parameter(shape, dtype=dtype, name=name, attr=attr,
                            is_bias=is_bias,
                            default_initializer=default_initializer)
    if p.name:
        global_scope().set_var(p.name, p)
    return p


class WeightNormParamAttr:
    """reference: paddle.static.WeightNormParamAttr
    (fluid/param_attr.py WeightNormParamAttr) — ParamAttr plus the norm
    dim; consumed by nn.utils.weight_norm-style reparameterization."""

    def __init__(self, dim=None, name=None, initializer=None,
                 learning_rate=1.0, regularizer=None, trainable=True,
                 do_model_average=False, need_clip=True):
        self.dim = dim
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.do_model_average = do_model_average
        self.need_clip = need_clip


# -- context managers ---------------------------------------------------------

_device_stack: List[Optional[str]] = [None]


@contextlib.contextmanager
def device_guard(device: Optional[str] = None):
    """reference: paddle.static.device_guard (fluid/framework.py) — the
    annotation PipelineOptimizer uses to split stages. Here it records the
    tag; paddle_tpu.distributed.pp consumes explicit LayerDesc lists, and
    sharding is mesh-driven, so the tag is observational."""
    _device_stack.append(device)
    try:
        yield
    finally:
        _device_stack.pop()


def current_device_tag() -> Optional[str]:
    return _device_stack[-1]


@contextlib.contextmanager
def name_scope(prefix: Optional[str] = None):
    """reference: paddle.static.name_scope — maps to jax.named_scope so
    the prefix shows up in XLA HLO metadata / profiler traces."""
    from ..framework import unique_name
    prefix = prefix or "block"
    with jax.named_scope(unique_name.generate(prefix)):
        yield


# -- debug ops ----------------------------------------------------------------

def Print(input, first_n=-1, message=None, summarize=20,  # noqa: A002,N802
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_layout=True,
          print_tensor_lod=False, print_phase="both"):
    """reference: paddle.static.Print (fluid/layers/control_flow.py) —
    identity that prints the value, trace-safe via jax.debug.print."""
    from jax._src import core as _jax_core
    x = input.value if isinstance(input, Tensor) else jnp.asarray(input)
    msg = message or ""
    if _jax_core.trace_state_clean():
        # eager: print directly (debug.print is for traces)
        print(msg, np.asarray(x))
    else:
        jax.debug.print(msg + " {x}", x=x)
    return input


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """reference: paddle.static.py_func (fluid/layers/nn.py) — run a host
    python function as an op. Trace-safe: lowers to jax.pure_callback; an
    optional backward_func becomes the custom vjp (host callback too)."""
    xs = x if isinstance(x, (list, tuple)) else [x]
    raw = [t.value if isinstance(t, Tensor) else jnp.asarray(t) for t in xs]
    outs = out if isinstance(out, (list, tuple)) else [out]
    specs = [jax.ShapeDtypeStruct(tuple(o.shape), jnp.dtype(o.dtype))
             for o in outs]
    single_spec = specs[0] if not isinstance(out, (list, tuple)) else specs

    def host(*arrs):
        r = func(*arrs)
        rs = r if isinstance(r, (list, tuple)) else [r]
        rs = [np.asarray(v) for v in rs]
        return rs[0] if not isinstance(out, (list, tuple)) else tuple(rs)

    from jax._src import core as _jax_core
    if _jax_core.trace_state_clean() and backward_func is None:
        # eager fast path: no callback channel needed
        res = host(*[np.asarray(r) for r in raw])
    elif backward_func is None:
        res = jax.pure_callback(host, single_spec, *raw)
    else:
        @jax.custom_vjp
        def op(*args):
            return jax.pure_callback(host, single_spec, *args)

        def fwd(*args):
            return op(*args), args

        def bwd(args, g):
            gs = g if isinstance(g, (list, tuple)) else [g]
            in_specs = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                             for a in args)

            def bhost(*a_and_g):
                a = a_and_g[:len(args)]
                gg = a_and_g[len(args):]
                r = backward_func(*a, *gg)
                rs = r if isinstance(r, (list, tuple)) else [r]
                return tuple(np.asarray(v) for v in rs)

            return jax.pure_callback(bhost, in_specs, *args, *gs)

        op.defvjp(fwd, bwd)
        res = op(*raw)

    wrap = lambda v: Tensor(v)  # noqa: E731
    if isinstance(out, (list, tuple)):
        return [wrap(v) for v in res]
    return wrap(res)


# -- autodiff facades ---------------------------------------------------------

def _walk_leaf_params(t: Tensor):
    """Walk the grad graph from t, yielding reachable leaf Parameters."""
    seen, out, stack = set(), [], [t]
    while stack:
        cur = stack.pop()
        if id(cur) in seen:
            continue
        seen.add(id(cur))
        if isinstance(cur, Parameter):
            out.append(cur)
        node = getattr(cur, "grad_node", None)
        if node is not None:
            stack.extend(node.inputs)
    return out


def append_backward(loss: Tensor, parameter_list=None, no_grad_set=None,
                    callbacks=None, checkpoints=None):
    """reference: fluid/backward.py:1363 append_backward — returns
    (param, grad) pairs. Tape-based here: runs backward from the loss and
    reads accumulated grads."""
    params = parameter_list or _walk_leaf_params(loss)
    no_grad = set(id(p) for p in (no_grad_set or []))
    loss.backward()
    return [(p, p.grad) for p in params
            if id(p) not in no_grad and p.grad is not None]


def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    """reference: fluid/backward.py:1958 paddle.static.gradients."""
    from ..autograd.engine import grad as _grad
    outs = _grad(targets, inputs, grad_outputs=target_gradients,
                 allow_unused=True,
                 no_grad_vars=list(no_grad_set) if no_grad_set else None)
    return outs


# -- metrics ------------------------------------------------------------------

def accuracy(input, label, k=1, correct=None, total=None):  # noqa: A002
    """reference: paddle.static.accuracy (fluid/layers/metric_op.py);
    correct/total output vars are accepted and filled when given."""
    from ..metric import accuracy as _acc
    return _acc(input, label, k=k, correct=correct, total=total)


def auc(input, label, curve="ROC", num_thresholds=4095,  # noqa: A002
        topk=1, slide_steps=1):
    """reference: paddle.static.auc (fluid/layers/metric_op.py:257) —
    returns (auc_out, batch_auc_out, [batch_stat_pos, batch_stat_neg,
    stat_pos, stat_neg]). One-shot ROC AUC via the rank-statistic
    (Mann-Whitney) formulation; the batch AUC equals the global AUC and the
    stat vars hold the positive/negative histogram over thresholds."""
    x = input.value if isinstance(input, Tensor) else jnp.asarray(input)
    y = label.value if isinstance(label, Tensor) else jnp.asarray(label)
    score = x[:, 1] if x.ndim == 2 and x.shape[1] == 2 else x.reshape(-1)
    y = y.reshape(-1).astype(jnp.float32)
    order = jnp.argsort(score)
    ranks = jnp.empty_like(order).at[order].set(
        jnp.arange(1, score.size + 1))
    pos = jnp.sum(y)
    neg = y.size - pos
    sum_rank_pos = jnp.sum(jnp.where(y > 0, ranks.astype(jnp.float32), 0.0))
    a = (sum_rank_pos - pos * (pos + 1) / 2.0) / jnp.maximum(pos * neg, 1.0)
    auc_out = Tensor(a)
    # Threshold-bucketed stat vars, same shape contract as the reference's
    # StatPos/StatNeg ([1, num_thresholds + 1]).
    bucket = jnp.clip((score * num_thresholds).astype(jnp.int32),
                      0, num_thresholds)
    stat_pos = jnp.zeros((1, num_thresholds + 1), jnp.int32).at[
        0, bucket].add(jnp.where(y > 0, 1, 0).astype(jnp.int32))
    stat_neg = jnp.zeros((1, num_thresholds + 1), jnp.int32).at[
        0, bucket].add(jnp.where(y > 0, 0, 1).astype(jnp.int32))
    batch_auc_out = Tensor(a)
    states = [Tensor(stat_pos), Tensor(stat_neg),
              Tensor(stat_pos), Tensor(stat_neg)]
    return auc_out, batch_auc_out, states


# -- program (de)serialization ------------------------------------------------

def serialize_program(feed_vars=None, fetch_vars=None,
                      program: Program = None) -> bytes:
    """reference: paddle.static.serialize_program(feed_vars, fetch_vars)
    (static/io.py). Trace-based programs are self-contained, so the
    program itself is accepted (positionally or via ``program=``) and
    feed/fetch pruning is already done by the trace."""
    if program is None and isinstance(feed_vars, Program):
        program = feed_vars
    if not isinstance(program, Program):
        raise InvalidArgumentError(
            "serialize_program needs a Program (pass it positionally or "
            "as program=...)")
    meta = {"input_specs": [(s.shape, str(s.dtype), s.name)
                            for s in program.input_specs],
            "name": program.name}
    return pickle.dumps({"stablehlo": program.export(), "meta": meta},
                        protocol=4)


def deserialize_program(data: bytes):
    """reference: paddle.static.deserialize_program — returns the
    deserialized exported computation (callable via .call)."""
    from jax import export as jexport
    blob = pickle.loads(data)
    return jexport.deserialize(blob["stablehlo"])


def serialize_persistables(feed_vars=None, fetch_vars=None,
                           executor=None, program: Program = None) -> bytes:
    """reference: paddle.static.serialize_persistables."""
    program = program or feed_vars  # allow positional program
    if not isinstance(program, Program):
        raise InvalidArgumentError("serialize_persistables needs a Program")
    return pickle.dumps({k: np.asarray(v)
                         for k, v in program.params.items()}, protocol=4)


def deserialize_persistables(program, data: bytes, executor=None):
    """reference: paddle.static.deserialize_persistables — loads params
    back into the Program."""
    params = pickle.loads(data)
    program.params = {k: jnp.asarray(v) for k, v in params.items()}
    return program


def save_to_file(path: str, content: bytes) -> None:
    """reference: paddle.static.save_to_file."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "wb") as f:
        f.write(content)


def load_from_file(path: str) -> bytes:
    """reference: paddle.static.load_from_file."""
    with open(path, "rb") as f:
        return f.read()


def normalize_program(program: Program, feed_vars=None, fetch_vars=None):
    """reference: paddle.static.normalize_program — prunes a program to
    the inference subgraph. Traced programs are already pruned (XLA DCE),
    so this is the identity."""
    return program


def save(program: Program, model_path: str, protocol: int = 4,
         **configs) -> None:
    """reference: paddle.static.save(program, model_path)
    (fluid/io.py:1840) — persist params (+ a .pdmodel next to them)."""
    program.save(model_path)


def load(program: Program, model_path: str, executor=None,
         var_list=None) -> None:
    """reference: paddle.static.load(program, model_path)
    (fluid/io.py:1948) — restore params into program."""
    with open(model_path + ".pdiparams", "rb") as f:
        params = pickle.load(f)
    program.params = {k: jnp.asarray(v) for k, v in params.items()}


def load_program_state(model_path: str, var_list=None) -> Dict[str, Any]:
    """reference: paddle.static.load_program_state."""
    with open(model_path + ".pdiparams", "rb") as f:
        return {k: np.asarray(v) for k, v in pickle.load(f).items()}


def set_program_state(program: Program, state_dict: Dict[str, Any]) -> None:
    """reference: paddle.static.set_program_state."""
    program.params = {k: jnp.asarray(v) for k, v in state_dict.items()}


def default_startup_program():
    """reference: paddle.static.default_startup_program. Initialization
    happens eagerly at parameter creation on the traced path; returns the
    (empty) startup scope holder for API parity."""
    return _startup_program


class _StartupProgram:
    """Placeholder startup program: random_seed attr is honored by
    seeding the default generator."""

    def __init__(self):
        self._seed = 0

    @property
    def random_seed(self):
        return self._seed

    @random_seed.setter
    def random_seed(self, s):
        self._seed = int(s)
        import paddle_tpu as pt
        pt.seed(self._seed)


_startup_program = _StartupProgram()
