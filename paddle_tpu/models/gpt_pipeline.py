"""Pipeline-parallel GPT training step.

The reference trains GPT-class models with static pipeline parallelism
(PipelineOptimizer fluid/optimizer.py:4134 splitting the program into
per-stage sections + SectionWorker microbatch schedules
section_worker.cc:130-180). TPU-native: GPT blocks are uniform, so the
whole stack is ONE stacked [n_layers, ...] params pytree sharded over the
"pp" mesh axis; inside shard_map each device scans its local blocks and
spmd_pipeline rotates microbatch activations around the pp ring. jax.grad
through the loop reverses the permutes (F-then-B). schedule="1f1b"
selects the true 1F1B schedule (spmd_pipeline_1f1b): O(pp) in-flight
activations independent of n_micro, matching section_worker.cc:144-180.

Embedding/head run replicated on every stage (cheap vs the blocks), which
also implements the reference's tied-embedding weight sync
(pp_layers.py:180-188) for free: there is only one copy.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..autograd.engine import no_grad
from ..nn.layer import bind_state, functional_state
from ..tensor import Tensor
from ..distributed.pp import spmd_pipeline
from .gpt import GPTConfig, GPTForCausalLM


def _split_block_params(params: Dict[str, jax.Array], num_layers: int
                        ) -> Tuple[Dict[str, jax.Array],
                                   Dict[str, jax.Array]]:
    """Separate per-block params (stacked over a leading layer dim) from
    the shared embedding/head/final-norm params."""
    block_suffixes = sorted({k.split(".", 3)[3]
                             for k in params if k.startswith("gpt.h.")})
    stacked = {}
    for suffix in block_suffixes:
        leaves = [params[f"gpt.h.{i}.{suffix}"] for i in range(num_layers)]
        if isinstance(leaves[0], jax.ShapeDtypeStruct):  # abstract mode
            stacked[suffix] = jax.ShapeDtypeStruct(
                (num_layers,) + tuple(leaves[0].shape), leaves[0].dtype)
        else:
            stacked[suffix] = jnp.stack(leaves)
    shared = {k: v for k, v in params.items() if not k.startswith("gpt.h.")}
    return stacked, shared


def _param_pspecs(model) -> Dict[str, P]:
    """Tensor-parallel PartitionSpec per param name (P() when dense)."""
    return {n: (getattr(p, "pspec", None) or P())
            for n, p in model.named_parameters()}


def _merge_block_params(stacked: Dict[str, jax.Array],
                        shared: Dict[str, jax.Array], num_layers: int
                        ) -> Dict[str, jax.Array]:
    out = dict(shared)
    for suffix, v in stacked.items():
        for i in range(num_layers):
            out[f"gpt.h.{i}.{suffix}"] = v[i]
    return out


class GPTPipelineTrainStep:
    """shard_map(pp × dp) train step for GPTForCausalLM.

    Two modes:
    - standalone (default): builds its own (pp, dp) mesh, everything
      inside shard_map is fully manual.
    - hybrid (``hcg=`` the fleet HybridCommunicateGroup): runs on the ONE
      global mesh with manual={"pp"} only — tensor parallel (mp) and
      sequence parallel (sep) ride GSPMD constraints inside each stage,
      the batch shards over dp×sharding, and optimizer slots ZeRO-shard
      over ``zero_axis``. This is the reference's hardest composition
      (sharding_optimizer.py:968 _build_groups pp×mp×sharding interplay)
      expressed as one SPMD program.
    """

    def __init__(self, config: GPTConfig, optimizer, pp: int, dp: int = 1,
                 n_micro: int = 2, devices=None, remat: bool = False,
                 seed: int = 0, schedule: str = "fthenb", hcg=None,
                 zero_axis: Optional[str] = None, abstract: bool = False):
        assert config.num_layers % pp == 0, "layers must divide pp"
        assert config.dropout == 0.0 and config.attn_dropout == 0.0, \
            "pipeline step requires dropout=0 (rng is not plumbed per-stage)"
        self.config = config
        self.optimizer = optimizer
        self.n_micro = n_micro
        self.abstract = abstract
        import contextlib
        import paddle_tpu as pt
        from ..nn.initializer import abstract_init
        pt.seed(seed)
        # abstract: params are ShapeDtypeStructs (nothing materializes) so
        # multi-billion-param configs can be AOT-lowered against a target
        # topology (tools/scale_proof.py) without host/device memory.
        with (abstract_init() if abstract else contextlib.nullcontext()):
            self.model = GPTForCausalLM(config)
        self.model.eval()  # dropout off; training math identical
        self.hybrid = hcg is not None
        if self.hybrid:
            self.mesh = hcg.mesh
            assert self.mesh.shape["pp"] == pp, \
                (self.mesh.shape, pp)
        else:
            devices = list(devices if devices is not None
                           else jax.devices())
            dev = np.asarray(devices[:pp * dp]).reshape(pp, dp)
            self.mesh = Mesh(dev, ("pp", "dp"))
        state = functional_state(self.model)
        stacked, shared = _split_block_params(state["params"],
                                              config.num_layers)

        def _place(v, spec):
            sh = NamedSharding(self.mesh, spec)
            if self.abstract:
                return jax.ShapeDtypeStruct(tuple(v.shape), v.dtype,
                                            sharding=sh)
            return jax.device_put(v, sh)
        self._place = _place
        if self.hybrid:
            pspecs = _param_pspecs(self.model)
            # every layer's suffix carries the same TP spec; index layer 0
            stacked_specs = {
                suf: P("pp", *pspecs[f"gpt.h.0.{suf}"])
                for suf in stacked}
            # embed/head/final-norm run replicated on every stage (by
            # design — tied-embedding sync for free); also, a
            # vocab-sharded embedding gather inside a manual-pp subgroup
            # trips XLA's SPMD partitioner, so mp shards block matmuls
            # only.
            shared_specs = {n: P() for n in shared}
            self.stacked = {suf: _place(v, stacked_specs[suf])
                            for suf, v in stacked.items()}
            self.shared = {n: _place(v, shared_specs[n])
                           for n, v in shared.items()}
            self._data_axes = tuple(
                ax for ax in ("dp", "sharding")
                if self.mesh.shape.get(ax, 1) > 1)
        else:
            self.stacked = {suf: _place(v, P("pp"))
                            for suf, v in stacked.items()}
            self.shared = {n: _place(v, P()) for n, v in shared.items()}
            self._data_axes = ("dp",)
        params = {"stacked": self.stacked, "shared": self.shared}
        # slots inherit their param's sharding (stacked slots ride pp)
        if self.abstract:
            self.opt_state = self._abstract_opt_init(params)
        else:
            self.opt_state = optimizer.init(params)
        if self.hybrid and zero_axis and \
                self.mesh.shape.get(zero_axis, 1) > 1:
            self._zero_shard_slots(zero_axis)

        assert schedule in ("fthenb", "1f1b"), schedule
        self.schedule = schedule
        self._step = (self._build(remat) if schedule == "fthenb"
                      else self._build_1f1b(remat))

    def _abstract_opt_init(self, params):
        """optimizer.init without materializing: eval_shape the slot tree,
        then give every slot its param's sharding (shape-matched leaves)
        or replication (scalars/step counters) — the same placements the
        concrete Optimizer.init assigns via place_like."""
        opt_shapes = jax.eval_shape(self.optimizer.init, params)
        flat_p, pdef = jax.tree_util.tree_flatten(params)
        flat_slots = pdef.flatten_up_to(opt_shapes["slots"])

        def attach(p, slot_tree):
            def leaf(s):
                sh = (p.sharding if tuple(s.shape) == tuple(p.shape)
                      else NamedSharding(self.mesh, P()))
                return jax.ShapeDtypeStruct(tuple(s.shape), s.dtype,
                                            sharding=sh)
            return jax.tree_util.tree_map(leaf, slot_tree)

        slots = jax.tree_util.tree_unflatten(
            pdef, [attach(p, s) for p, s in zip(flat_p, flat_slots)])
        step = jax.ShapeDtypeStruct((), jnp.int32,
                                    sharding=NamedSharding(self.mesh, P()))
        return {"slots": slots, "step": step}

    def _zero_shard_slots(self, axis: str) -> None:
        """ZeRO-1: moment slots of the stacked block params shard over
        `axis` on their first free, divisible dim (reference:
        sharding_optimizer.py optimizer-state sharding; the param itself
        stays pp/mp-sharded). Shared embedding/head slots stay replicated:
        they are small, and a sharded slot's spec propagates back onto the
        embedding-gather operand, which XLA's gather partitioner cannot
        handle under manual-pp subgroups."""
        deg = self.mesh.shape[axis]

        def reshard(slot):
            if not isinstance(slot, (jax.Array, jax.ShapeDtypeStruct)) \
                    or slot.ndim == 0:
                return slot
            spec = list(getattr(slot.sharding, "spec", P()) or [])
            spec += [None] * (slot.ndim - len(spec))
            for d in range(slot.ndim):
                if spec[d] is None and slot.shape[d] % deg == 0 \
                        and slot.shape[d] >= deg:
                    spec[d] = axis
                    return self._place(slot, P(*spec))
            return slot

        self.opt_state["slots"]["stacked"] = jax.tree_util.tree_map(
            reshard, self.opt_state["slots"]["stacked"])

    # -- functional pieces ----------------------------------------------------

    def _zigzag_sep(self) -> int:
        """sep degree when the config runs the balanced zigzag ring over
        a sep axis in this mesh; 0 otherwise."""
        sep = dict(self.mesh.shape).get("sep", 1)
        if self.config.seq_parallel_mode != "zigzag" or sep <= 1:
            return 0
        return sep

    def _embed(self, shared, ids):
        model = self.model
        b, s = ids.shape
        sep = self._zigzag_sep()
        import jax.numpy as jnp
        if sep:
            # Zigzag layout from the very first op: chunk-reorder the
            # int ids (split+concat — a sequence-axis GATHER inside the
            # manual-pp region trips the TPU SPMD partitioner), and
            # feed the permuted positions as position ids. The whole
            # block stack then runs in zigzag order (positionwise ops
            # are invariant; attention runs the balanced ring);
            # _head_loss un-permutes before the next-token shift.
            from ..distributed.sp import (zigzag_permutation,
                                          zigzag_reorder)
            ids = zigzag_reorder(ids, sep, axis=1)
            perm, _ = zigzag_permutation(s, sep)
        with bind_state(model, {"params": shared, "buffers": {}}), \
                no_grad():
            import paddle_tpu.dispatch as dispatch
            F = dispatch.wrapped_ops
            if sep:
                pos = jnp.broadcast_to(
                    jnp.asarray(perm, jnp.int32)[None, :], (b, s))
                pos = Tensor(pos)
            else:
                pos = F["arange"](s, dtype="int32")
                pos = F["expand"](F["unsqueeze"](pos, 0), (b, s))
            x = model.gpt.wte(Tensor(ids)) + model.gpt.wpe(pos)
            return x.value

    def _head_loss(self, shared, hidden, labels):
        model = self.model
        sep = self._zigzag_sep()
        if sep:
            # Restore the public order before the next-token shift —
            # chunk-level split+concat (shard-aligned slices lower to
            # collective-permutes; a sharded-S gather trips the TPU
            # SPMD partitioner).
            from ..distributed.sp import zigzag_reorder
            hidden = zigzag_reorder(hidden, sep, axis=1, inverse=True)
        with bind_state(model, {"params": shared, "buffers": {}}), \
                no_grad():
            h = model.gpt.ln_f(Tensor(hidden))
            if model.config.loss_chunk_size:
                # chunked CE: the [mb, S, vocab] logits never materialize
                # (same path as GPTForCausalLM.forward)
                loss = model._chunked_lm_loss(
                    h, Tensor(labels), model.config.loss_chunk_size)
                return loss.value if isinstance(loss, Tensor) else loss
            logits = model.logits(h)
            import paddle_tpu.dispatch as dispatch
            F = dispatch.wrapped_ops
            loss = F["mean"](model.loss_fn(logits[:, :-1],
                                           Tensor(labels)[:, 1:]))
            return loss.value

    def _block_apply(self, blk_params, x):
        """Apply ONE block given its unstacked param dict."""
        block = self.model.gpt.h[0]
        named = {k: v for k, v in blk_params.items()}
        with bind_state(block, {"params": named, "buffers": {}}), \
                no_grad():
            return block(Tensor(x)).value

    def _build(self, remat: bool):
        n_micro = self.n_micro
        layers_per_stage = self.config.num_layers // self.mesh.shape["pp"]
        block_apply = self._block_apply
        embed = self._embed
        head_loss = self._head_loss
        optimizer = self.optimizer
        mesh = self.mesh

        def stage_fn(blocks_local, x):
            # blocks_local: dict of [L/pp, ...]; scan across local layers
            def body(h, blk):
                return block_apply(blk, h), None
            h, _ = jax.lax.scan(body, x, blocks_local)
            return h

        from ..core.offload import remat_policy
        with self._remat_scope():
            sfn = jax.checkpoint(stage_fn, policy=remat_policy()) \
                if remat else stage_fn
        hybrid = self.hybrid
        data_axes = self._data_axes

        def loss_fn(stacked, shared, ids, labels):
            def inner(stacked_l, shared_l, ids_l, labels_l):
                # stacked_l: [L/pp, ...] local blocks; ids_l: dp-local
                # batch (standalone) or the global batch with auto
                # dp/sharding sharding (hybrid)
                if hybrid:
                    # keep the embedding/CE gathers' indices replicated
                    # (XLA's gather partitioner mishandles sharded
                    # indices under manual-pp subgroups), then push the
                    # activations onto the data axes
                    ids_l = jax.lax.with_sharding_constraint(ids_l, P())
                    labels_l = jax.lax.with_sharding_constraint(
                        labels_l, P())
                x = embed(shared_l, ids_l)  # [mb*nm, s, h]
                if hybrid and data_axes:
                    x = jax.lax.with_sharding_constraint(
                        x, P(data_axes if len(data_axes) > 1
                             else data_axes[0]))
                b = x.shape[0]
                mb = b // n_micro
                x_micro = x.reshape(n_micro, mb, *x.shape[1:])
                outs = spmd_pipeline(lambda bp, xm: sfn(bp, xm),
                                     stacked_l, x_micro, axis_name="pp")
                hidden = outs.reshape(b, *x.shape[1:])
                loss = head_loss(shared_l, hidden, labels_l)
                # only the last stage's loss is real; psum broadcasts it
                n_stages = jax.lax.axis_size("pp")
                stage = jax.lax.axis_index("pp")
                loss = jnp.where(stage == n_stages - 1, loss, 0.0)
                loss = jax.lax.psum(loss, "pp")
                if not hybrid:  # hybrid: dp is auto; mean is global
                    loss = jax.lax.pmean(loss, "dp")
                return loss

            data_spec = P() if hybrid else P("dp")
            smapped = shard_map(
                inner, mesh=mesh,
                in_specs=(P("pp"), P(), data_spec, data_spec),
                out_specs=P(), check_vma=False,
                **({"axis_names": frozenset({"pp"})} if hybrid else {}))
            return smapped(stacked, shared, ids, labels)

        def step_impl(params, opt_state, lr, ids, labels):
            from ..distributed.mp_layers import no_sharding_constraints
            import contextlib
            guard = (contextlib.nullcontext() if hybrid
                     else no_sharding_constraints())
            with guard:
                loss, grads = jax.value_and_grad(
                    lambda p: loss_fn(p["stacked"], p["shared"], ids,
                                      labels))(params)
            # check_vma=False skips the automatic replication-sum for
            # grads of replicated/pp-sharded inputs; psums were made
            # explicit in loss_fn, and GSPMD resolves grad shardings here.
            new_params, new_opt = optimizer.apply_gradients(
                params, grads, opt_state, lr=lr)
            return new_params, new_opt, loss

        return jax.jit(step_impl, donate_argnums=(0, 1))

    def _build_1f1b(self, remat: bool):
        """Memory-bounded 1F1B schedule with manual backward composition
        (reference: section_worker.cc:144-180); activations in flight are
        O(pp) instead of O(n_micro)."""
        from ..distributed.pp import spmd_pipeline_1f1b

        n_micro = self.n_micro
        block_apply = self._block_apply
        embed = self._embed
        head_loss = self._head_loss
        optimizer = self.optimizer
        mesh = self.mesh

        def stage_fn(blocks_local, x):
            def body(h, blk):
                return block_apply(blk, h), None
            h, _ = jax.lax.scan(body, x, blocks_local)
            return h

        hybrid = self.hybrid

        def inner(stacked_l, shared_l, ids_l, labels_l):
            b, s = ids_l.shape
            mb = b // n_micro
            ids_m = ids_l.reshape(n_micro, mb, s)
            labels_m = labels_l.reshape(n_micro, mb, s)

            def first_fn(sh, mb_idx):
                return embed(sh, jax.lax.dynamic_index_in_dim(
                    ids_m, mb_idx, keepdims=False))

            def last_fn(sh, y, mb_idx):
                lbl = jax.lax.dynamic_index_in_dim(labels_m, mb_idx,
                                                   keepdims=False)
                return head_loss(sh, y, lbl) / n_micro

            loss_sum, d_stacked, d_shared = spmd_pipeline_1f1b(
                stage_fn, stacked_l, shared_l, first_fn, last_fn,
                n_micro, axis_name="pp", remat=remat)
            loss = jax.lax.psum(loss_sum, "pp")
            d_shared = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(g, "pp"), d_shared)
            if not hybrid:  # hybrid: dp/sharding are auto; GSPMD sums
                loss = jax.lax.pmean(loss, "dp")
                d_stacked = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(g, "dp"), d_stacked)
                d_shared = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(g, "dp"), d_shared)
            return loss, d_stacked, d_shared

        def step_impl(params, opt_state, lr, ids, labels):
            from ..distributed.mp_layers import no_sharding_constraints
            import contextlib
            guard = (contextlib.nullcontext() if hybrid
                     else no_sharding_constraints())
            data_spec = P() if hybrid else P("dp")
            with guard:
                smapped = shard_map(
                    inner, mesh=mesh,
                    in_specs=(P("pp"), P(), data_spec, data_spec),
                    out_specs=(P(), P("pp"), P()), check_vma=False,
                    **({"axis_names": frozenset({"pp"})} if hybrid
                       else {}))
                loss, d_stacked, d_shared = smapped(
                    params["stacked"], params["shared"], ids, labels)
            grads = {"stacked": d_stacked, "shared": d_shared}
            new_params, new_opt = optimizer.apply_gradients(
                params, grads, opt_state, lr=lr)
            return new_params, new_opt, loss

        return jax.jit(step_impl, donate_argnums=(0, 1))

    def _batch_pspec(self) -> P:
        """PartitionSpec for the [batch, seq] token arrays (one source of
        truth for __call__ and lower())."""
        if self.hybrid and self._data_axes:
            return P(self._data_axes if len(self._data_axes) > 1
                     else self._data_axes[0])
        if not self.hybrid:
            return P("dp")
        return P()

    def lower(self, batch_size: int, seq_len: int):
        """AOT-lower one train step with abstract arguments (usable in
        both modes; the point of abstract=True). Returns the jax Lowered —
        .compile() against the mesh's (possibly compile-only) topology
        yields per-device memory analysis without running anything."""
        ids = jax.ShapeDtypeStruct(
            (batch_size, seq_len), jnp.int32,
            sharding=NamedSharding(self.mesh, self._batch_pspec()))
        lr = jax.ShapeDtypeStruct(
            (), jnp.float32, sharding=NamedSharding(self.mesh, P()))
        params = {"stacked": self.stacked, "shared": self.shared}
        with self._remat_scope():
            return self._step.lower(params, self.opt_state, lr, ids, ids)

    def _remat_scope(self):
        """The model's selective-remat selection, scoped (GPTModel
        captures it per-model; the pipeline path never runs
        GPTModel.forward, so the override must wrap every point that
        consults core.offload at build or trace time: remat_policy()
        in _build, spmd_pipeline_1f1b's policy evaluation, and the
        flash kernel's name_activation tagging inside the step trace)."""
        import contextlib
        names = self.model.gpt._remat_names
        if names is None:
            return contextlib.nullcontext()
        from ..core.offload import override_remat_saved_names
        return override_remat_saved_names(names)

    def __call__(self, ids, labels) -> jax.Array:
        assert not self.abstract, \
            "abstract=True builds a compile-only step: use lower()"
        params = {"stacked": self.stacked, "shared": self.shared}
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        ids, labels = jnp.asarray(ids), jnp.asarray(labels)
        if self.hybrid and self._data_axes:
            # batch dim over dp×sharding (the pp split is handled by the
            # manual shard_map in_specs)
            bspec = NamedSharding(self.mesh, self._batch_pspec())
            ids = jax.device_put(ids, bspec)
            labels = jax.device_put(labels, bspec)
        with self._remat_scope():
            params, self.opt_state, loss = self._step(
                params, self.opt_state, lr, ids, labels)
        self.stacked = params["stacked"]
        self.shared = params["shared"]
        return loss

    def merged_params(self) -> Dict[str, jax.Array]:
        return _merge_block_params(self.stacked, self.shared,
                                   self.config.num_layers)
