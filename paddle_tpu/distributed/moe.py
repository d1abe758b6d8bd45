"""Mixture-of-Experts with expert parallelism.

BEYOND-REFERENCE capability (SURVEY §2.3: the reference snapshot has only
the raw alltoall building block, operators/collective/alltoall_op.cc, and
no MoE). TPU-native design: experts carry a leading expert dim sharded
over a mesh axis (default: the "sharding" axis doubles as the expert axis,
the common ep=dp layout).

Dispatch is capacity-based (GShard/Switch): each expert processes at most
C = ceil(top_k * T / E * capacity_factor) tokens, so expert FLOPs are
O(k * T * capacity_factor) — independent of E — with overflow tokens
dropped (their output is the residual path only). The [E, C, H] expert
batch shards over the ep axis; GSPMD turns the scatter/gather dispatch
into the alltoall exchanges a manual implementation would issue. The
dense one-hot formulation (every expert runs every token, unrouted rows
zeroed) is kept as ``dispatch_mode="dense"`` — it is the parity oracle
for the capacity path and occasionally wins at tiny E*T.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import dispatch
from ..nn.initializer import Normal
from ..nn.layer import Layer
from ..tensor import Tensor

F = dispatch.wrapped_ops


def _route(tokens, gate_w, num_experts, top_k):
    """Shared router: top-k gates renormalized, plus the Switch-style
    load-balance aux loss inputs."""
    logits = tokens @ gate_w  # [T, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, top_k)  # [T, k]
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    combine = jnp.zeros((tokens.shape[0], num_experts), jnp.float32)
    combine = jnp.put_along_axis(combine, top_idx, top_vals, axis=-1,
                                 inplace=False)  # [T, E]
    me = jnp.mean(combine, axis=0)  # fraction routed per expert
    ce = jnp.mean(probs, axis=0)
    aux = num_experts * jnp.sum(me * ce)
    return top_vals, top_idx, combine, aux.astype(jnp.float32)


def _expert_ffn(xe, w_in, b_in, w_out, b_out, activation):
    """[E, C, H] -> [E, C, H] batched expert FFN (rides the MXU as E
    batched matmuls; sharded over ep by the params' pspecs)."""
    hmid = jnp.einsum("eth,ehf->etf", xe, w_in) + b_in[:, None, :]
    act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
           "silu": jax.nn.silu}[activation]
    hmid = act(hmid)
    return jnp.einsum("etf,efh->eth", hmid, w_out) + b_out[:, None, :]


def moe_capacity(num_tokens: int, num_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    """Per-expert token capacity C (multiple of 8 for TPU lane tiling)."""
    c = int(np.ceil(top_k * num_tokens * capacity_factor / num_experts))
    c = max(c, top_k)
    return min(-(-c // 8) * 8, num_tokens)


def _moe_ffn(x, gate_w, w_in, b_in, w_out, b_out, num_experts, top_k,
             capacity_factor, activation, expert_axis=None):
    """Pure kernel, capacity dispatch: x [B, S, H] -> [B, S, H].

    GShard-style: token t's j-th choice goes to expert e at the slot
    given by a running per-expert count (choice-major priority: all
    first choices beat all second choices); slots >= C overflow and are
    dropped (output falls back to the residual path). Expert compute is
    [E, C, H] — O(k*T*capacity_factor) FLOPs total, independent of E.
    gate_w: [H, E]; w_in: [E, H, F]; w_out: [E, F, H].
    """
    b, s, h = x.shape
    tokens = x.reshape(b * s, h)
    t = tokens.shape[0]
    cap = moe_capacity(t, num_experts, top_k, capacity_factor)

    top_vals, top_idx, _, aux = _route(tokens, gate_w, num_experts, top_k)

    # choice-major flattening: [k*T] with all 1st choices first
    flat_e = top_idx.T.reshape(-1)
    flat_t = jnp.tile(jnp.arange(t), top_k)
    flat_g = top_vals.T.reshape(-1)
    # position of each (token, choice) within its expert's batch
    onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                              flat_e[:, None], axis=1)[:, 0]  # [kT]
    keep = pos < cap
    safe_pos = jnp.where(keep, pos, cap - 1)

    # scatter tokens into the [E, C, H] expert batch (kept slots are
    # unique, so scatter-add == scatter; dropped rows add zero)
    xe = jnp.zeros((num_experts, cap, h), x.dtype)
    contrib = tokens[flat_t] * keep[:, None].astype(x.dtype)
    xe = xe.at[flat_e, safe_pos].add(contrib)
    if expert_axis is not None:
        # pin the expert batch to the ep axis so the scatter lowers to
        # the alltoall exchange instead of a replicated gather
        from .mp_layers import _constrain
        xe = _constrain(xe, expert_axis)

    ye = _expert_ffn(xe, w_in, b_in, w_out, b_out, activation)
    if expert_axis is not None:
        from .mp_layers import _constrain
        ye = _constrain(ye, expert_axis)

    # gather each choice's output back and combine with its gate
    yg = ye[flat_e, safe_pos]  # [kT, H]
    wgt = (flat_g * keep.astype(jnp.float32)).astype(x.dtype)
    out = jnp.zeros((t, h), x.dtype).at[flat_t].add(yg * wgt[:, None])
    return out.reshape(b, s, h).astype(x.dtype), aux


def _moe_ffn_dense(x, gate_w, w_in, b_in, w_out, b_out, num_experts,
                   top_k, activation):
    """Dense dispatch (no token dropping, O(E*T) expert FLOPs): combine
    weights are zero for unrouted experts. The parity oracle for
    _moe_ffn."""
    b, s, h = x.shape
    tokens = x.reshape(b * s, h)
    _, _, combine, aux = _route(tokens, gate_w, num_experts, top_k)
    # routed mask in, gate out: out[t] = sum_e g_te * FFN_e(x_t). (Gating
    # the INPUT would feed the nonlinear FFN g*x, and summing unmasked
    # outputs would leak every expert's bias-propagated FFN_e(0) into
    # every token once biases train away from zero.)
    mask = (combine > 0).astype(x.dtype)
    xe = jnp.einsum("te,th->eth", mask, tokens)
    out_e = _expert_ffn(xe, w_in, b_in, w_out, b_out, activation)
    out = jnp.einsum("te,eth->th", combine.astype(x.dtype), out_e)
    return out.reshape(b, s, h).astype(x.dtype), aux


class MoELayer(Layer):
    """Switch/top-k MoE FFN (expert-parallel over ``expert_axis``).

    ``dispatch_mode``: "capacity" (default — GShard scatter/gather with
    per-expert capacity, O(k*T) expert FLOPs, overflow drops) or "dense"
    (one-hot einsum oracle, O(E*T) FLOPs, no drops)."""

    def __init__(self, hidden_size: int, ffn_hidden_size: int,
                 num_experts: int, top_k: int = 2,
                 capacity_factor: float = 1.25, activation: str = "gelu",
                 expert_axis: str = "sharding", aux_loss_weight: float =
                 0.01, dispatch_mode: str = "capacity"):
        super().__init__()
        assert dispatch_mode in ("capacity", "dense"), dispatch_mode
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.activation = activation
        self.aux_loss_weight = aux_loss_weight
        self.dispatch_mode = dispatch_mode
        self.expert_axis = expert_axis
        self.last_aux_loss = None
        init = Normal(std=0.02)
        self.gate_weight = self.create_parameter(
            (hidden_size, num_experts), default_initializer=init)
        self.w_in = self.create_parameter(
            (num_experts, hidden_size, ffn_hidden_size),
            default_initializer=init)
        self.b_in = self.create_parameter((num_experts, ffn_hidden_size),
                                          is_bias=True)
        self.w_out = self.create_parameter(
            (num_experts, ffn_hidden_size, hidden_size),
            default_initializer=init)
        self.b_out = self.create_parameter((num_experts, hidden_size),
                                           is_bias=True)
        # expert dim sharded over the ep axis; mp shards the ffn dim
        self.w_in.pspec = P(expert_axis, None, "mp")
        self.b_in.pspec = P(expert_axis, "mp")
        self.w_out.pspec = P(expert_axis, "mp", None)
        self.b_out.pspec = P(expert_axis, None)

    def forward(self, x):
        if self.dispatch_mode == "dense":
            def kernel(xv, gw, wi, bi, wo, bo):
                return _moe_ffn_dense(
                    xv, gw, wi, bi, wo, bo, self.num_experts,
                    self.top_k, self.activation)
        else:
            def kernel(xv, gw, wi, bi, wo, bo):
                return _moe_ffn(
                    xv, gw, wi, bi, wo, bo, self.num_experts,
                    self.top_k, self.capacity_factor, self.activation,
                    self.expert_axis)
        out, aux = dispatch.call_fn(
            kernel, "moe_ffn", True,
            (x, self.gate_weight, self.w_in, self.b_in, self.w_out,
             self.b_out), {})
        self.last_aux_loss = aux
        return out

    def aux_loss(self):
        if self.last_aux_loss is None:
            return None
        return self.last_aux_loss * self.aux_loss_weight


# --------------------------------------------------------------------------
# Dropless routing for serving: every pick is computed, none overflows
# --------------------------------------------------------------------------

def route_top_k(h, w_router, top_k: int):
    """Router of a layer whose gates are a softmax over the picked
    logits (equal to a softmax over all experts renormalised over the
    picks). ``h`` [T, H] is read in float32 against ``w_router`` [H, E]
    at the highest matmul precision: an expert's pick must not turn on
    how the MXU rounds. Returns ``(idx [T, k] int32, gates [T, k]
    f32)``."""
    logits = jnp.matmul(h.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision="highest")
    vals, idx = jax.lax.top_k(logits, top_k)
    return idx.astype(jnp.int32), jax.nn.softmax(vals, axis=-1)


def route_sigmoid_top_k(u, w_router, bias, top_k: int,
                        scaling: float = 1.0):
    """Router of a layer that scores every expert by a sigmoid and
    picks by score plus a bias an expert: ``s = sigmoid(u W_r)`` in
    float32 at the highest matmul precision, the ``k`` largest ``s +
    bias`` picked (the bias balances load and takes no part in the
    gate), gates ``s_e / sum of the picked s`` times ``scaling``.
    Returns ``(idx [T, k] int32, gates [T, k] f32)``."""
    s = jax.nn.sigmoid(jnp.matmul(
        u.astype(jnp.float32), w_router.astype(jnp.float32),
        precision="highest"))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    gates = picked / jnp.sum(picked, axis=-1, keepdims=True) * scaling
    return idx.astype(jnp.int32), gates


def gated_ffn(u, w_gate, w_up, w_down, activation: str = "silu"):
    """``(act(u W_gate) * (u W_up)) W_down``: an expert every token
    goes through (a shared expert), as plain matmuls."""
    from ..ops.pallas.grouped_matmul import ACTIVATIONS
    mid = ACTIVATIONS[activation](
        jnp.matmul(u, w_gate, preferred_element_type=jnp.float32)) * \
        jnp.matmul(u, w_up, preferred_element_type=jnp.float32)
    return jnp.matmul(mid.astype(u.dtype), w_down)


def expert_tile_rows(picks: int, experts: int, itemsize: int) -> int:
    """Rows of one tile of the grouped layout: a power of two near the
    mean rows an expert gets, from one sublane tile (decode: 96 picks
    over 64 experts) to 256 (a long prefill, where the MXU wants tall
    tiles and padding every expert to a tile costs a sixth)."""
    floor = 8 * max(1, 4 // itemsize)
    tm = floor
    while tm < 256 and tm * experts < picks:
        tm *= 2
    return tm


def dropless_experts(u, idx, gates, w_gate, w_up, w_down, *,
                     held=None, valid=None, activation: str = "relu"):
    """The part of ``sum_k gates[t, k] * FFN_{idx[t, k]}(u[t])`` that the
    experts held here give, with ``FFN_e(x) = (act(x @ w_gate[e]) * (x
    @ w_up[e])) @ w_down[e]`` (``activation``: ``relu`` or ``silu``). No
    capacity and no drop: every pick of a held expert is computed.

    u: [T, H]; idx, gates: [T, k] over ALL experts; w_gate, w_up:
    [held, H, F]; w_down: [held, F, H]; ``held`` = (first, count) of the
    expert ids these weights are (default: all of them from 0);
    ``valid`` [T] bool drops whole rows (padding, empty slots) before
    they cost a weight read. Picks of experts not held and rows not
    valid contribute nothing.

    Rows are laid out by expert in tiles of ``tm`` rows, one expert a
    tile (ops/pallas/grouped_matmul.py): the rank of a pick among its
    expert's picks is a cumulative sum over a one-hot, so no sort and no
    scatter-add runs, and the result does not depend on an order of
    additions other than over k. Returns ``(y [T, H] in u's dtype,
    counts [held] int32: picks computed per expert)``."""
    from ..ops.pallas.grouped_matmul import grouped_ffn_in, grouped_matmul

    t, h = u.shape
    k = idx.shape[1]
    n_held = w_gate.shape[0]
    first = 0 if held is None else int(held[0])
    tm = expert_tile_rows(t * k, n_held, u.dtype.itemsize)
    tiles = -(-(t * k) // tm) + n_held
    mp = tiles * tm

    e = idx.reshape(-1) - first  # [T*k], expert id among those held
    keep = (e >= 0) & (e < n_held)
    if valid is not None:
        keep = keep & jnp.repeat(valid, k)
    onehot = (e[:, None] == jnp.arange(n_held, dtype=jnp.int32)[None]) \
        & keep[:, None]
    oh = onehot.astype(jnp.int32)
    counts = jnp.sum(oh, axis=0)  # [held]
    rank = jnp.sum((jnp.cumsum(oh, axis=0) - 1) * oh, axis=1)
    tiles_of = -(-counts // tm)
    tile_end = jnp.cumsum(tiles_of)  # tiles used up to and with expert e
    start_row = (tile_end - tiles_of) * tm
    dest = jnp.where(keep, start_row[jnp.clip(e, 0, n_held - 1)] + rank, mp)
    used = tile_end[-1]
    # each tile's expert; tiles past the last used one repeat its expert
    # so that their weight block is the one already in VMEM
    tile_ids = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32),
                           jnp.maximum(used - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, tile_ids, side="right"),
        n_held - 1).astype(jnp.int32)
    token = jnp.arange(t * k, dtype=jnp.int32) // k
    row_token = jnp.zeros((mp,), jnp.int32).at[dest].set(
        token, mode="drop", unique_indices=True)
    xs = u[row_token]  # [mp, H]; pad rows repeat row 0, never read back
    used1 = used.reshape(1).astype(jnp.int32)
    mid = grouped_ffn_in(xs, w_gate, w_up, tile_expert, used1, tm,
                         activation)
    ys = grouped_matmul(mid, w_down, tile_expert, used1, tm)
    # pick by pick, in k's order: one [T, H] gather at a time, so a long
    # prefill never holds all T * k gathered rows in float32
    at = jnp.minimum(dest, mp - 1).reshape(t, k)
    w = jnp.where(keep.reshape(t, k), gates.astype(jnp.float32), 0.0)
    y = jnp.zeros((t, h), jnp.float32)
    for j in range(k):
        row = jnp.where(w[:, j, None] > 0, ys[at[:, j]], 0)
        y = y + row.astype(jnp.float32) * w[:, j, None]
    return y.astype(u.dtype), counts
