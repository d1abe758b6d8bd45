"""paddle_tpu.jit — traced execution.

TPU-native replacement for the reference's two static paths:
- ``to_static`` / ``TrainStep``: capture eager-style Layer code into ONE
  jitted XLA computation (replaces ProgramDesc+Executor op-loop,
  reference: python/paddle/fluid/dygraph/dygraph_to_static/
  program_translator.py:232 StaticFunction). Autodiff happens inside the
  trace via jax.grad — the analog of append_backward's program transform.
- ``save``/``load``: serialize a traced function + params
  (reference: fluid/dygraph/jit.py:515 save / :851 load).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from ..autograd.engine import no_grad
from ..core import rng as rng_mod
from ..core.profiler import host_phase
from ..nn.layer import Layer, bind_state, functional_state
from ..tensor import Tensor


def _unwrap_tree(tree):
    return jax.tree_util.tree_map(
        lambda t: t.value if isinstance(t, Tensor) else t, tree,
        is_leaf=lambda t: isinstance(t, Tensor))


def _wrap_tree(tree):
    return jax.tree_util.tree_map(
        lambda v: Tensor(v) if isinstance(v, jax.Array) else v, tree)


def cached_lr_device(obj, optimizer):
    """Device f32 scalar for the current lr, re-uploaded only when the
    value changes — a fresh jnp.asarray per step is a host->device
    transfer."""
    lr = float(optimizer.get_lr())
    cache = getattr(obj, "_lr_cache", None)
    if cache is None or lr != cache[0]:
        obj._lr_cache = (lr, jnp.asarray(lr, jnp.float32))
    return obj._lr_cache[1]


class TrainStep:
    """One fused, jitted train step over an eager-style step function.

    ``train_fn(model, batch) -> loss`` is ordinary eager Layer code; it is
    traced once into an XLA computation containing forward, backward
    (jax.grad) and the optimizer update — the op-by-op interpreter loop the
    reference executes per step collapses into a single device launch.
    """

    def __init__(self, model: Layer, optimizer, train_fn: Callable,
                 donate: bool = True, seed: int = 0):
        self.model = model
        self.optimizer = optimizer
        self.train_fn = train_fn
        state = functional_state(model)
        self.params = state["params"]
        self.buffers = state["buffers"]
        self.opt_state = optimizer.init(self.params)
        self._key = jax.random.key(seed)
        self._lr_cache = None
        self._step, self._multi = self._build(donate)

    def _build(self, donate: bool):
        model, optimizer, train_fn = self.model, self.optimizer, \
            self.train_fn

        def one_step(params, buffers, opt_state, key, lr, batch):
            key, sub = jax.random.split(key)

            def loss_of(p):
                model.train()
                with bind_state(model, {"params": p, "buffers": buffers}), \
                        no_grad(), rng_mod.key_scope(sub):
                    loss = train_fn(model, _wrap_tree(batch))
                    new_buf = {n: b.value for n, b in model.named_buffers()
                               if b is not None}
                loss_raw = loss.value if isinstance(loss, Tensor) else loss
                return loss_raw, new_buf

            (loss, new_buf), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params)
            new_params, new_opt = optimizer.apply_gradients(
                params, grads, opt_state, lr=lr)
            return new_params, new_buf, new_opt, key, loss

        # The PRNG key evolves INSIDE the jitted step: one device dispatch
        # per step total. A separate host-side jax.random.split is a whole
        # extra launch.
        kwargs = {"donate_argnums": (0, 1, 2, 3)} if donate else {}
        step = jax.jit(one_step, **kwargs)

        def multi_impl(params, buffers, opt_state, key, lr, batches):
            def body(carry, batch):
                p, b, o, k = carry
                p, b, o, k, loss = one_step(p, b, o, k, lr, batch)
                return (p, b, o, k), loss

            (params, buffers, opt_state, key), losses = jax.lax.scan(
                body, (params, buffers, opt_state, key), batches)
            return params, buffers, opt_state, key, losses

        multi = jax.jit(multi_impl, **kwargs)
        return step, multi

    def _lr_device(self):
        return cached_lr_device(self, self.optimizer)

    def __call__(self, batch) -> jax.Array:
        batch_raw = _unwrap_tree(batch)
        # named for a profiler session only: the trainer keeps no
        # step timeline (core/profiler.py host_phase)
        with host_phase("train_launch"):
            self.params, self.buffers, self.opt_state, self._key, loss = \
                self._step(self.params, self.buffers, self.opt_state,
                           self._key, self._lr_device(), batch_raw)
        return loss

    def multi_step(self, batches) -> jax.Array:
        """Run a whole micro-epoch in ONE device launch: ``batches`` is a
        pytree whose leaves are stacked along a leading steps axis; the
        jitted program lax.scans the train step over it. TPU-native analog
        of the reference's C++ trainer loop (Executor::RunFromDataset,
        framework/trainer.h) — the hot loop never returns to Python.
        Returns the per-step losses [n_steps]."""
        batches_raw = _unwrap_tree(batches)
        with host_phase("train_launch"):
            self.params, self.buffers, self.opt_state, self._key, losses = \
                self._multi(self.params, self.buffers, self.opt_state,
                            self._key, self._lr_device(), batches_raw)
        return losses

    def sync_to_model(self) -> None:
        """Write the jitted state back into the eager Layer's parameters."""
        named_p = dict(self.model.named_parameters())
        for n, v in self.params.items():
            if n in named_p:
                named_p[n].value = v
        named_b = dict(self.model.named_buffers())
        for n, v in self.buffers.items():
            if n in named_b:
                named_b[n].value = v


class EvalStep:
    """Jitted inference step: out = model(*inputs) with frozen state."""

    def __init__(self, model: Layer, seed: int = 0):
        self.model = model
        state = functional_state(model)
        self.params = state["params"]
        self.buffers = state["buffers"]

        def fwd(params, buffers, key, args, kwargs):
            model.eval()
            with bind_state(model, {"params": params, "buffers": buffers}), \
                    no_grad(), rng_mod.key_scope(key):
                out = model(*_wrap_tree(args), **_wrap_tree(kwargs))
            return _unwrap_tree(out)

        self._fwd = jax.jit(fwd)
        self._key = jax.random.key(seed)

    def __call__(self, *args, **kwargs):
        self._key, sub = jax.random.split(self._key)
        return self._fwd(self.params, self.buffers, sub,
                         _unwrap_tree(args), _unwrap_tree(kwargs))


class StaticFunction:
    """to_static-decorated function: cached jit over Layer state; the
    dy2static AST pass first rewrites tensor-dependent Python control
    flow into lax control flow so it survives tracing
    (reference: program_translator.py StaticFunction)."""

    def __init__(self, fn: Callable, model: Optional[Layer] = None):
        self._orig_fn = fn
        self._converted_fn = None
        self.model = model
        self._jitted_by_mode: Dict[bool, Any] = {}

    @property
    def fn(self) -> Callable:
        """Resolve per call so enable_to_static() toggles take effect
        after decoration (reference: ProgramTranslator.enable)."""
        if not ProgramTranslator.enabled:
            return self._orig_fn
        if self._converted_fn is None:
            self._converted_fn = convert_to_static(self._orig_fn)
        return self._converted_fn

    @property
    def _jitted(self):
        return self._jitted_by_mode.get(ProgramTranslator.enabled)

    @_jitted.setter
    def _jitted(self, value):
        self._jitted_by_mode[ProgramTranslator.enabled] = value

    def _resolve_model(self, args):
        if self.model is not None:
            return self.model
        if args and isinstance(args[0], Layer):
            return args[0]
        return None

    def __call__(self, *args, **kwargs):
        model = self._resolve_model(args)
        if model is None:
            if self._jitted is None:
                raw_fn = self.fn
                self._jitted = jax.jit(lambda a, k: _unwrap_tree(
                    raw_fn(*_wrap_tree(a), **_wrap_tree(k))))
            return _wrap_tree(self._jitted(_unwrap_tree(args),
                                           _unwrap_tree(kwargs)))
        rest = args[1:] if args and args[0] is model else args
        if self._jitted is None:
            fn = self.fn

            def traced(params, buffers, a, k):
                with bind_state(model, {"params": params,
                                        "buffers": buffers}), no_grad():
                    out = fn(model, *_wrap_tree(a), **_wrap_tree(k)) \
                        if args and args[0] is model else \
                        fn(*_wrap_tree(a), **_wrap_tree(k))
                return _unwrap_tree(out)

            self._jitted = jax.jit(traced)
        state = functional_state(model)
        out = self._jitted(state["params"], state["buffers"],
                           _unwrap_tree(rest), _unwrap_tree(kwargs))
        return _wrap_tree(out)


def to_static(function=None, input_spec=None, **kwargs):
    """Decorator: trace an eager function/Layer into a cached jitted
    computation (reference: paddle.jit.to_static)."""
    def deco(fn):
        if isinstance(fn, Layer):
            layer = fn
            sf = StaticFunction(type(layer).forward, model=layer)
            layer._static_forward = sf
            layer.forward = functools.partial(_call_static, layer)
            return layer
        return functools.wraps(fn)(StaticFunction(fn))
    if function is not None:
        return deco(function)
    return deco


def _call_static(layer, *args, **kwargs):
    return layer._static_forward(layer, *args, **kwargs)


def save(layer, path: str, input_spec=None) -> None:
    """Serialize layer state + config for later load
    (reference: paddle.jit.save). The exported artifact stores the
    state_dict; the program artifact (StableHLO export) is produced by
    paddle_tpu.static.export when shapes are pinned."""
    from ..framework.io import save as fsave
    fsave({"state_dict": layer.state_dict(),
           "class": f"{type(layer).__module__}.{type(layer).__qualname__}"},
          path + ".pdparams")


def load(path: str):
    from ..framework.io import load as fload
    return fload(path + ".pdparams")


from .dy2static import (ProgramTranslator, convert_to_static,  # noqa: E402
                        enable_to_static)


def not_to_static(fn=None):
    """Mark a function to be skipped by to_static conversion
    (reference: paddle.jit.not_to_static)."""
    def deco(f):
        f.__pt_not_to_static__ = True
        return f
    return deco(fn) if fn is not None else deco


_code_level = 0
_verbosity = 0


def set_code_level(level: int = 100, also_to_stdout: bool = False) -> None:
    """reference: paddle.jit.set_code_level — controls dumping of the
    transformed code (here: the dy2static-rewritten AST source)."""
    global _code_level
    _code_level = int(level)


def set_verbosity(level: int = 0, also_to_stdout: bool = False) -> None:
    """reference: paddle.jit.set_verbosity."""
    global _verbosity
    _verbosity = int(level)


class TracedLayer:
    """reference: paddle.jit.TracedLayer (fluid/dygraph/jit.py) — a
    layer captured by running it once on example inputs. Here the trace
    is a static Program; ``trace`` returns (eager_outputs, traced)."""

    def __init__(self, program, layer):
        self._program = program
        self._layer = layer

    @staticmethod
    def trace(layer, inputs):
        from ..static import InputSpec, build_program
        ins = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        outs = layer(*ins)
        specs = [InputSpec.from_tensor(i) for i in ins]
        program = build_program(layer, specs)
        return outs, TracedLayer(program, layer)

    def __call__(self, inputs):
        ins = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        return self._program.run(*ins)

    def save_inference_model(self, path, feed=None, fetch=None):
        self._program.save(path)


class TranslatedLayer:
    """reference: paddle.jit.TranslatedLayer (fluid/dygraph/io.py:1082) —
    a Layer reconstructed from a saved program artifact; forward runs the
    loaded StableHLO computation."""

    def __init__(self, loaded_program):
        self._loaded = loaded_program
        self.training = False

    @classmethod
    def from_path(cls, path_prefix: str):
        from ..static import load_inference_model
        return cls(load_inference_model(path_prefix))

    def __call__(self, *inputs):
        return self.forward(*inputs)

    def forward(self, *inputs):
        return self._loaded.run(*inputs)

    def eval(self):
        self.training = False
        return self

    def train(self):
        raise RuntimeError(
            "TranslatedLayer wraps a frozen inference artifact; retraining "
            "requires the original Layer (reference TranslatedLayer "
            "supports train mode only for programs saved with dropout "
            "etc. intact)")
