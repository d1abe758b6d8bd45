"""Pipeline parallelism.

Reference parity: python/paddle/distributed/fleet/meta_parallel/
parallel_layers/pp_layers.py (LayerDesc:44, SharedLayerDesc:62,
PipelineLayer:76) + pipeline_parallel.py train_batch micro-batch loop and
the C++ SectionWorker F-then-B / 1F1B schedules
(paddle/fluid/framework/section_worker.cc:130-180).

TPU-native design: a pipeline stage is a position along the "pp" mesh
axis. Inside ONE jitted SPMD program, ``spmd_pipeline`` runs the classic
collective-permute microbatch loop: every device applies ITS stage's
params each step and ppermutes activations to the next stage. jax.grad
through the loop reverses the permutes, yielding the F-then-B schedule;
XLA overlaps the permute hop with the next microbatch's compute. The
reference's send_v2/recv_v2 + per-microbatch scopes collapse into this
scan. ``spmd_pipeline_1f1b`` is the true 1F1B schedule: interleaved
forward/backward ticks with manual vjp composition, bounding in-flight
activations at O(pp) regardless of microbatch count (``remat=True`` on
the F-then-B path only trades FLOPs for memory within a microbatch).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..core.offload import remat_policy as _remat_policy
from ..nn.layer import Layer
from ..nn.container import LayerList


class LayerDesc:
    """Deferred layer construction (reference: pp_layers.py:44)."""

    def __init__(self, layer_cls, *args, **kwargs):
        self.layer_cls = layer_cls
        self.args = args
        self.kwargs = kwargs

    def build_layer(self) -> Layer:
        return self.layer_cls(*self.args, **self.kwargs)


class SharedLayerDesc(LayerDesc):
    """Layer shared between stages, e.g. tied embeddings
    (reference: pp_layers.py:62; weight sync pp_layers.py:180-188)."""

    def __init__(self, key, layer_cls, forward_func=None,
                 shared_weight_attr="weight", *args, **kwargs):
        super().__init__(layer_cls, *args, **kwargs)
        self.layer_name = key
        self.forward_func = forward_func
        self.shared_weight_attr = shared_weight_attr


class PipelineLayer(Layer):
    """Segments a LayerDesc list across pipeline stages
    (reference: pp_layers.py:76 PipelineLayer).

    Eager/forward semantics run the full stack (correct on any device
    count); the SPMD pipelined execution is built by ``spmd_pipeline``
    over the uniform block segment. ``seg_method="layer:<ClassName>"``
    marks which class forms the uniform pipelined body, as in the
    reference's "layer:TransformerBlock" convention.
    """

    def __init__(self, layers: Sequence, num_stages: Optional[int] = None,
                 topology=None, loss_fn=None, seg_method="uniform",
                 recompute_interval: int = 0, num_virtual_pipeline_stages=1):
        super().__init__()
        self.descs = list(layers)
        self.loss_fn = loss_fn
        self.num_stages = num_stages or 1
        self.seg_method = seg_method
        self.recompute_interval = recompute_interval
        self.shared_layers = {}
        built: List[Layer] = []
        for d in self.descs:
            if isinstance(d, SharedLayerDesc):
                if d.layer_name in self.shared_layers:
                    built.append(self.shared_layers[d.layer_name])
                else:
                    layer = d.build_layer()
                    self.shared_layers[d.layer_name] = layer
                    built.append(layer)
            elif isinstance(d, LayerDesc):
                built.append(d.build_layer())
            elif isinstance(d, Layer):
                built.append(d)
            else:  # bare callable (e.g. lambda reshape)
                built.append(d)
        self.run_function = built
        self._layers = LayerList([b for b in built if isinstance(b, Layer)])

    def forward(self, x, **kwargs):
        for fn in self.run_function:
            x = fn(x)
        return x

    def get_stage_layers(self, stage: int, num_stages: Optional[int] = None
                         ) -> List:
        n = num_stages or self.num_stages
        per = (len(self.run_function) + n - 1) // n
        return self.run_function[stage * per:(stage + 1) * per]


def spmd_pipeline(stage_fn: Callable, stage_params: Any, x_micro,
                  axis_name: str = "pp", remat: bool = False):
    """Collective-permute pipeline over the pp mesh axis (call inside
    shard_map).

    stage_fn(params, x) -> y with matching x/y shapes; ``stage_params``
    are THIS device's stage weights (callers shard a stacked
    [n_stages, ...] pytree over the pp axis). x_micro: [n_micro, mb, ...]
    microbatched input (meaningful on stage 0; replicated elsewhere).
    Returns [n_micro, mb, ...] outputs valid on the LAST stage (zeros
    elsewhere); reduce with a pp-psum or mask as needed.
    """
    n_stages = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    n_micro = x_micro.shape[0]
    total_steps = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    fn = jax.checkpoint(stage_fn, policy=_remat_policy()) \
        if remat else stage_fn

    def body(carry, t):
        recv_buf, outputs = carry
        mb_idx = jnp.clip(t - stage, 0, n_micro - 1)
        first_in = jax.lax.dynamic_index_in_dim(x_micro, jnp.clip(
            t, 0, n_micro - 1), keepdims=False)
        inp = jnp.where(stage == 0, first_in, recv_buf)
        out = fn(stage_params, inp)
        active = (t >= stage) & (t - stage < n_micro)
        out = jnp.where(active, out, jnp.zeros_like(out))
        # collect on the last stage
        is_last = stage == n_stages - 1
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(active & is_last, out,
                      jax.lax.dynamic_index_in_dim(outputs, mb_idx,
                                                   keepdims=False)),
            mb_idx, axis=0)
        nxt = jax.lax.ppermute(out, axis_name, perm)
        return (nxt, outputs), None

    recv0 = jnp.zeros_like(x_micro[0])
    outs0 = jnp.zeros_like(x_micro)
    (recv, outputs), _ = jax.lax.scan(body, (recv0, outs0),
                                      jnp.arange(total_steps))
    return outputs


def pipeline_last_stage_value(x, axis_name: str = "pp"):
    """Broadcast the last stage's value to all pp ranks (sum works because
    other stages contribute zeros)."""
    return jax.lax.psum(x, axis_name)


def spmd_pipeline_1f1b(stage_fn: Callable, stage_params: Any, shared: Any,
                       first_fn: Callable, last_fn: Callable, n_micro: int,
                       axis_name: str = "pp", remat: bool = False):
    """True 1F1B microbatch schedule with manual backward (call inside
    shard_map).

    Reference parity: the SectionWorker 1F1B schedule
    (paddle/fluid/framework/section_worker.cc:144-180), where each stage
    interleaves one forward with one backward per slot so in-flight
    activations are bounded by the stage count rather than by the number
    of microbatches (F-then-B via ``spmd_pipeline`` + jax.grad keeps all
    ``n_micro`` activations live unless remat'd).

    SPMD lockstep formulation: all pp ranks run the same scan; at step t

      * stage ``s`` runs the FORWARD of microbatch ``t - s``;
      * stage ``s`` runs the BACKWARD of microbatch ``t - (2L-2-s)``
        (recompute-vjp from the stored stage input);

    both masked to their valid microbatch range. Activations are held in
    a circular buffer of ``2L-1`` slots — O(stages), independent of
    ``n_micro``. Two collective-permutes per step carry activations
    forward (+1) and output-grads backward (-1) around the pp ring.

    Args:
      stage_fn(stage_params, x) -> y: this device's stage (x/y same shape)
      shared: replicated params used by ``first_fn``/``last_fn``
      first_fn(shared, mb_idx) -> x: stage-0 input producer (e.g. embed)
      last_fn(shared, y, mb_idx) -> scalar loss contribution for one
        microbatch — scale by 1/n_micro inside so the sum is the mean
    Returns:
      (loss_sum, d_stage_params, d_shared) — loss/d_shared are partial
      per pp rank (stage-0 holds first_fn grads, last stage holds
      last_fn grads and the loss); psum over the pp axis to combine.
    """
    n_stages = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    fn = jax.checkpoint(stage_fn, policy=_remat_policy()) \
        if remat else stage_fn
    total_steps = n_micro + 2 * (n_stages - 1)
    cap = 2 * n_stages - 1  # circular activation-store slots
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    bwd_perm = [((i + 1) % n_stages, i) for i in range(n_stages)]

    x0 = first_fn(shared, jnp.int32(0))
    zeros_x = jnp.zeros_like(x0)

    def body(carry, t):
        fwd_recv, bwd_recv, store, dp_acc, dsh_acc, loss_sum = carry

        # ---- forward tick: stage s, microbatch t - s -------------------
        mb_f = t - stage
        valid_f = (mb_f >= 0) & (mb_f < n_micro)
        mb_f_c = jnp.clip(mb_f, 0, n_micro - 1)
        x_in = jnp.where(stage == 0, first_fn(shared, mb_f_c), fwd_recv)
        slot_f = jnp.remainder(mb_f_c, cap)
        old = jax.lax.dynamic_index_in_dim(store, slot_f, keepdims=False)
        store = jax.lax.dynamic_update_index_in_dim(
            store, jnp.where(valid_f, x_in, old), slot_f, axis=0)
        y_out = fn(stage_params, x_in)

        # ---- backward tick: stage s, microbatch t - (2L-2-s) -----------
        mb_b = t - (2 * (n_stages - 1) - stage)
        valid_b = (mb_b >= 0) & (mb_b < n_micro)
        mb_b_c = jnp.clip(mb_b, 0, n_micro - 1)
        slot_b = jnp.remainder(mb_b_c, cap)
        x_saved = jax.lax.dynamic_index_in_dim(store, slot_b,
                                               keepdims=False)
        # last stage: seed grad from the loss of the microbatch whose
        # forward just finished here (mb_f == mb_b at the last stage)
        loss_mb, head_vjp = jax.vjp(
            lambda sh, yy: last_fn(sh, yy, mb_b_c), shared, y_out)
        dsh_head, dy_seed = head_vjp(jnp.ones_like(loss_mb))
        is_last = stage == n_stages - 1
        g_in = jnp.where(is_last, dy_seed, bwd_recv)
        _, stage_vjp = jax.vjp(fn, stage_params, x_saved)
        dp_mb, dx = stage_vjp(g_in)
        # stage 0: fold dx into first_fn (embed) grads per microbatch
        _, in_vjp = jax.vjp(lambda sh: first_fn(sh, mb_b_c), shared)
        (dsh_in,) = in_vjp(dx)

        mask = lambda flag, tree: jax.tree_util.tree_map(
            lambda g: jnp.where(flag, g, jnp.zeros_like(g)), tree)
        dp_acc = jax.tree_util.tree_map(
            jnp.add, dp_acc, mask(valid_b, dp_mb))
        dsh_acc = jax.tree_util.tree_map(
            jnp.add, dsh_acc,
            jax.tree_util.tree_map(
                jnp.add, mask(valid_b & is_last, dsh_head),
                mask(valid_b & (stage == 0), dsh_in)))
        loss_sum = loss_sum + jnp.where(valid_b & is_last, loss_mb, 0.0)

        # ---- ring hops (must run on every rank every step) -------------
        fwd_recv = jax.lax.ppermute(
            jnp.where(valid_f, y_out, jnp.zeros_like(y_out)),
            axis_name, fwd_perm)
        bwd_recv = jax.lax.ppermute(
            jnp.where(valid_b, dx, jnp.zeros_like(dx)),
            axis_name, bwd_perm)
        return (fwd_recv, bwd_recv, store, dp_acc, dsh_acc, loss_sum), None

    zeros_like_tree = functools.partial(jax.tree_util.tree_map,
                                        jnp.zeros_like)
    carry0 = (zeros_x, zeros_x,
              jnp.zeros((cap,) + x0.shape, x0.dtype),
              zeros_like_tree(stage_params), zeros_like_tree(shared),
              jnp.zeros((), jnp.float32))
    carry, _ = jax.lax.scan(body, carry0, jnp.arange(total_steps))
    _, _, _, dp_acc, dsh_acc, loss_sum = carry
    return loss_sum, dp_acc, dsh_acc
