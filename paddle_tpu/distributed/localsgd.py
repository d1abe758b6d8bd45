"""LocalSGD: k local optimizer steps per replica, then parameter
averaging over the data-parallel axis.

Reference parity: meta_optimizers/localsgd_optimizer.py (LocalSGD and
AdaptiveLocalSGD — the static-graph rewrite inserting periodic
c_allreduce-based parameter averaging). TPU-native design: instead of
rewriting a program, each dp shard holds its OWN copy of the parameters
(stacked along a leading axis sharded over "dp" in a shard_map), local
steps run with zero cross-replica traffic, and a sync step does one
psum-average over the dp axis. The adaptive variant shrinks k as the
loss drops (AdaComm-style), like the reference's AdaptiveLocalSGD.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..nn.layer import Layer, functional_state
from ..tensor import Tensor
from .topology import get_hybrid_communicate_group


class LocalSGDTrainStep:
    """Per-replica local training with periodic model averaging.

    Parameters and optimizer slots are stacked with a leading replica
    axis sharded over the mesh's "dp" axis, so replicas genuinely
    diverge between syncs (unlike SPMD-replicated params, which XLA
    keeps identical). ``sync()`` psum-averages params; it runs
    automatically every ``k_steps`` once ``begin_step`` is reached.
    """

    def __init__(self, model: Layer, optimizer, train_fn: Callable,
                 k_steps: int = 1, begin_step: int = 1,
                 adaptive: bool = False, hcg=None, seed: int = 0,
                 donate: bool = True):
        self.model = model
        self.optimizer = optimizer
        self.train_fn = train_fn
        self.k_steps = max(1, int(k_steps))
        self._k0 = self.k_steps
        self.begin_step = int(begin_step)
        self.adaptive = adaptive
        self.hcg = hcg or get_hybrid_communicate_group()
        if self.hcg is None:
            raise RuntimeError("call fleet.init(strategy) first")
        mesh = self.hcg.mesh
        self.mesh = mesh
        self.dp = mesh.shape["dp"]
        for ax in ("mp", "pp", "sep", "sharding"):
            if mesh.shape.get(ax, 1) > 1:
                raise ValueError(
                    f"LocalSGD is a data-parallel strategy; {ax} degree "
                    "must be 1 (reference meta-optimizer conflicts the "
                    "same way)")

        state = functional_state(model)
        dp = self.dp

        def stack(v):
            return jnp.broadcast_to(v[None], (dp,) + v.shape)

        rep = NamedSharding(mesh, P("dp"))
        self.params = jax.tree_util.tree_map(
            lambda v: jax.device_put(stack(v), rep), state["params"])
        self.buffers = jax.tree_util.tree_map(
            lambda v: jax.device_put(stack(v), rep), state["buffers"])
        opt_state = optimizer.init(state["params"])
        self.opt_state = jax.tree_util.tree_map(
            lambda v: jax.device_put(stack(jnp.asarray(v)), rep),
            opt_state)
        self._key = jax.random.key(seed)
        self._t = 0
        self._loss0: Optional[float] = None
        self._since_sync = 0
        self.donate = bool(donate)
        self._step_cache: dict = {}
        self._sync_fn = self._build_sync()

    # ------------------------------------------------------------- build

    def _build_step(self, batch_specs):
        model, optimizer, train_fn = self.model, self.optimizer, \
            self.train_fn
        mesh = self.mesh

        from .fleet import make_functional_loss
        loss_of = make_functional_loss(model, train_fn)

        def local_step(params, buffers, opt_state, key, lr, batch):
            # leading replica axis has local extent 1 inside shard_map
            p = jax.tree_util.tree_map(lambda v: v[0], params)
            b = jax.tree_util.tree_map(lambda v: v[0], buffers)
            s = jax.tree_util.tree_map(lambda v: v[0], opt_state)
            key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
            (loss, nb), g = jax.value_and_grad(
                loss_of, has_aux=True)(p, b, key, batch)
            np_, ns = optimizer.apply_gradients(p, g, s, lr=lr)
            ex = lambda t: jax.tree_util.tree_map(lambda v: v[None], t)
            return ex(np_), ex(nb), ex(ns), loss[None]

        smapped = shard_map(
            local_step, mesh=mesh,
            in_specs=(P("dp"), P("dp"), P("dp"), P(), P(), batch_specs),
            out_specs=(P("dp"), P("dp"), P("dp"), P("dp")),
            check_vma=False)
        donate = (0, 1, 2) if self.donate else ()
        return jax.jit(smapped, donate_argnums=donate)

    def _build_sync(self):
        mesh = self.mesh
        dp = self.dp

        def avg(params):
            p = jax.tree_util.tree_map(lambda v: v[0], params)
            m = jax.tree_util.tree_map(
                lambda v: jax.lax.psum(v, "dp") / dp, p)
            return jax.tree_util.tree_map(lambda v: v[None], m)

        return jax.jit(shard_map(avg, mesh=mesh, in_specs=(P("dp"),),
                                 out_specs=P("dp"), check_vma=False))

    # --------------------------------------------------------------- api

    def __call__(self, batch):
        batch_raw = jax.tree_util.tree_map(
            lambda t: t.value if isinstance(t, Tensor) else t, batch,
            is_leaf=lambda t: isinstance(t, Tensor))
        # scalar/0-d leaves are replicated; arrays shard over dp
        specs = jax.tree_util.tree_map(
            lambda v: P("dp") if np.ndim(v) >= 1 else P(), batch_raw)
        batch_raw = jax.tree_util.tree_map(
            lambda v, sp: jax.device_put(
                jnp.asarray(v), NamedSharding(self.mesh, sp)),
            batch_raw, specs)
        cache_key = (jax.tree_util.tree_structure(batch_raw),
                     tuple(jax.tree_util.tree_leaves(specs)))
        step_fn = self._step_cache.get(cache_key)
        if step_fn is None:
            step_fn = self._step_cache[cache_key] = self._build_step(specs)
        self._key, sub = jax.random.split(self._key)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        self.params, self.buffers, self.opt_state, losses = step_fn(
            self.params, self.buffers, self.opt_state, sub, lr, batch_raw)
        self._t += 1
        self._since_sync += 1
        loss = jnp.mean(losses)  # lazy: no host sync on local steps
        # Before begin_step the reference trains fully synchronously
        # (averaging every step); only afterwards does k-step local SGD
        # kick in (localsgd_optimizer.py begin_step semantics).
        if self._t < self.begin_step or self._since_sync >= self.k_steps:
            self.sync()
            if self.adaptive and self._t >= self.begin_step:
                self._adapt(float(loss))
        return loss

    def sync(self) -> None:
        """Average parameters across replicas (the periodic allreduce the
        reference inserts into the program)."""
        self.params = self._sync_fn(self.params)
        self._since_sync = 0

    def _adapt(self, loss: float) -> None:
        """AdaComm schedule: k shrinks as loss drops — sync MORE often
        late in training, when replica divergence hurts convergence
        most (reference: AdaptiveLocalSGD avg-loss heuristic)."""
        if self._loss0 is None:
            self._loss0 = max(loss, 1e-12)
            return
        ratio = max(loss, 1e-12) / self._loss0
        self.k_steps = max(1, int(math.ceil(self._k0 * math.sqrt(ratio))))

    def sync_to_model(self) -> None:
        self.sync()
        named_p = dict(self.model.named_parameters())
        for n, v in self.params.items():
            if n in named_p:
                named_p[n].value = v[0]
        named_b = dict(self.model.named_buffers())
        for n, v in self.buffers.items():
            if n in named_b:
                named_b[n].value = v[0]
