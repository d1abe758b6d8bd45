"""Fused lm_head sampling: stream vocab tiles through the logits
matmul, never materializing [B, vocab] logits in HBM.

The decode-side twin of models/gpt.py's ``_chunked_lm_loss`` trick
(ROADMAP item 3 / the r13 fused decode hot path): at serving batch
sizes the [B, vocab] logits tensor exists only to be argmax'd (greedy)
or top-k'd, yet the unfused path round-trips it through HBM every
step — ~B * 50k * 4 bytes of write+read per token at GPT vocab. Here
the lm_head matmul is tiled over the vocab dimension and the sampling
reduction rides the tiles: a running (max, argmax) carry for greedy, a
running top-k reservoir for top-k sampling. Only the [B]-sized winner
(or [B, k] reservoir) ever leaves the core.

Two implementations with identical semantics, selected at call time
exactly like `paged_attention`:

- a Mosaic kernel (grid over vocab tiles, carry in VMEM scratch, the
  weight streamed tile-by-tile) for the greedy path on TPU;
- a pure-JAX ``lax.scan`` reference that runs everywhere else (the CPU
  fast lane) and also implements the top-k reservoir.

Greedy tie-breaking matches ``jnp.argmax`` (first index of the max):
the running carry only replaces its best on a STRICT improvement, so
the earliest maximal index survives — the property the fused-vs-
unfused bit-identity pins lean on. Those pins hold on the CPU lane,
where the streaming reference computes the exact unfused dots; the
MOSAIC kernel keeps operands in their storage dtype with f32
accumulation (matching the unfused MXU lowering's operand precision),
but on-chip bit-parity against the unfused programs is CHIP-PENDING
validation, not a claimed contract. Both weight layouts — vocab-major
[V, D] (tied embedding) and feature-major [D, V] (untied
ColumnParallelLinear) — are tiled along their vocab axis NATIVELY;
canonicalizing by transpose would materialize a V*D copy inside every
decode program, more HBM traffic than the logits the fusion avoids.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from .naming import named_pallas_call

_NEG_INF = -1e30

# Vocab tile of the streaming reference, and the kernel's upper bound.
DEFAULT_TILE = 2048

# The kernel's streamed weight tile is double-buffered by the Pallas
# pipeline; both buffers get half of the 16 MiB scoped-VMEM limit the
# v5e compiler enforces, the rest stays for the hidden block, the
# [B, tile] logits and their temporaries.
_W_TILE_VMEM_BYTES = 8 * 1024 * 1024


def kernel_tile(vocab: int, d: int, itemsize: int,
                tile: int = DEFAULT_TILE) -> int:
    """Vocab tile the Mosaic kernel streams: the largest multiple of
    128 that is at most ``tile`` and ``vocab`` and whose two weight
    buffers fit `_W_TILE_VMEM_BYTES` (E=2048: 512 rows in f32, 1024 in
    bf16). 0 when no such tile exists (vocab or ``tile`` under 128)."""
    fit = _W_TILE_VMEM_BYTES // (2 * d * itemsize)
    return min(int(tile), vocab, fit) // 128 * 128


def _vocab_dim(transpose_y: bool) -> int:
    """Which weight axis is the vocab: ``transpose_y=True`` is the
    vocab-major [V, D] tied-embedding layout (logits = hidden @ W.T);
    False the feature-major [D, V] untied-head layout (logits =
    hidden @ W). BOTH are tiled along their vocab axis natively — a
    canonicalizing transpose would materialize a full V*D copy inside
    every decode program, more HBM traffic than the [B, V] logits the
    fusion exists to avoid."""
    return 0 if transpose_y else 1


# --------------------------------------------------------------------------
# Pure-JAX streaming reference (CPU fast lane / semantics contract)
# --------------------------------------------------------------------------

def _tile_starts(vocab: int, tile: int):
    """Clamped tile starts covering [0, vocab): the final tile starts
    at vocab - tile when vocab is not a multiple (its leading rows
    re-evaluate the previous tile's tail — the overlap is masked out,
    so no padded weight copy is ever materialized)."""
    n = max(1, -(-vocab // tile))
    return jnp.asarray([min(i * tile, max(0, vocab - tile))
                        for i in range(n)], jnp.int32), \
        jnp.asarray([i * tile for i in range(n)], jnp.int32)


def _scan_tiles(hidden, weight, vdim, bias, tile, body_init, body_step):
    """Shared vocab-tile scan: slices [start:start+tile] along the
    weight's vocab axis ``vdim`` (dynamic_slice, clamped at the edge —
    NO layout-canonicalizing transpose is ever materialized), computes
    the tile logits in the operands' natural dtype (the same promotion
    the unfused matmul applies) and feeds (logits_f32, idx) to
    ``body_step``. Already-covered overlap rows at the clamped edge are
    masked to -inf so every vocab id contributes exactly once."""
    vocab = weight.shape[vdim]
    d = weight.shape[1 - vdim]
    tile = min(tile, vocab)
    starts, fronts = _tile_starts(vocab, tile)

    def step(carry, xs):
        start, front = xs
        if vdim == 0:  # [V, D]: contract dim 1 of both
            wt = jax.lax.dynamic_slice(weight, (start, 0), (tile, d))
            lg = jax.lax.dot_general(
                hidden, wt, (((1,), (1,)), ((), ())))  # [B, tile]
        else:          # [D, V]: contract hidden dim 1 with dim 0
            wt = jax.lax.dynamic_slice(weight, (0, start), (d, tile))
            lg = jax.lax.dot_general(
                hidden, wt, (((1,), (0,)), ((), ())))  # [B, tile]
        idx = start + jnp.arange(tile, dtype=jnp.int32)
        if bias is not None:
            lg = lg + jax.lax.dynamic_slice(bias, (start,), (tile,))
        lg = jnp.where(idx[None, :] >= front, lg.astype(jnp.float32),
                       _NEG_INF)
        return body_step(carry, lg, idx), None

    carry, _ = jax.lax.scan(step, body_init, (starts, fronts))
    return carry


def fused_argmax_reference(hidden, weight, vdim: int, bias=None,
                           tile: int = DEFAULT_TILE):
    """Streaming greedy: argmax of the full logits without the [B, V]
    intermediate; ties resolve to the first index AND NaN contaminates
    exactly like ``jnp.argmax`` (a NaN tile beats any finite carry, an
    earlier NaN beats a later one), so a numerically-blown checkpoint
    produces the SAME tokens fused or unfused — the --no-fused-step
    bisect contract must not misattribute NaN divergence to fusion."""
    b = hidden.shape[0]

    def init():
        return (jnp.full((b,), _NEG_INF, jnp.float32),
                jnp.zeros((b,), jnp.int32))

    def step(carry, lg, idx):
        best_v, best_i = carry
        tmax = jnp.max(lg, axis=1)
        targ = idx[jnp.argmax(lg, axis=1)]  # first-NaN inside the tile
        upd = (tmax > best_v) | (jnp.isnan(tmax) & ~jnp.isnan(best_v))
        return (jnp.where(upd, tmax, best_v),
                jnp.where(upd, targ, best_i))

    _, best_i = _scan_tiles(hidden, weight, vdim, bias, tile, init(),
                            step)
    return best_i.astype(jnp.int32)


def fused_topk_reference(hidden, weight, vdim: int, k: int, bias=None,
                         tile: int = DEFAULT_TILE
                         ) -> Tuple[jax.Array, jax.Array]:
    """Streaming top-k reservoir: returns ``(values [B, k] f32,
    indices [B, k] i32)`` of the k largest logits — the candidate set
    a top-k sampler draws from — again without the [B, V] tensor. The
    reservoir is merged with each tile via one ``lax.top_k`` over
    [carry | tile]."""
    b = hidden.shape[0]
    vocab = weight.shape[vdim]
    k = min(int(k), vocab)

    def init():
        return (jnp.full((b, k), _NEG_INF, jnp.float32),
                jnp.zeros((b, k), jnp.int32))

    def step(carry, lg, idx):
        vals, idxs = carry
        cand_v = jnp.concatenate([vals, lg], axis=1)
        cand_i = jnp.concatenate(
            [idxs, jnp.broadcast_to(idx[None, :], lg.shape)], axis=1)
        top_v, pos = jax.lax.top_k(cand_v, k)
        return top_v, jnp.take_along_axis(cand_i, pos, axis=1)

    vals, idxs = _scan_tiles(hidden, weight, vdim, bias, tile, init(),
                             step)
    return vals, idxs.astype(jnp.int32)


# --------------------------------------------------------------------------
# Mosaic kernel (TPU): greedy streaming argmax over vocab tiles
# --------------------------------------------------------------------------

def _argmax_kernel(h_ref, w_ref, b_ref, o_ref, best_v, best_i, *,
                   tile: int, vocab: int, n_tiles: int, has_bias: bool,
                   vdim: int):
    """Grid step = one vocab tile: tile matmul on the MXU, running
    (max, first-argmax) carry in VMEM scratch, winner written on the
    final step. The trailing partial tile's out-of-range lanes are
    masked to -inf before the reduction; NaN contaminates exactly like
    ``jnp.argmax`` (first NaN index wins, see the reference)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        best_v[...] = jnp.full(best_v.shape, _NEG_INF, best_v.dtype)
        best_i[...] = jnp.zeros(best_i.shape, best_i.dtype)

    # operands stay in their storage dtype (the unfused lm_head matmul
    # feeds bf16 operands to the MXU too); only the accumulation and
    # the running carry are f32, minimizing fused-vs-unfused rounding
    # skew on chip (exact on-chip bit-identity is not claimed — see
    # module docstring)
    if vdim == 0:  # weight tile [tile, D]
        lg = jax.lax.dot_general(
            h_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [B, tile]
    else:          # weight tile [D, tile]
        lg = jax.lax.dot_general(
            h_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [B, tile]
    if has_bias:
        lg = lg + b_ref[...].astype(jnp.float32)
    col = i * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    lg = jnp.where(col < vocab, lg, _NEG_INF)
    nan = jnp.isnan(lg)
    tmax = jnp.max(lg, axis=1, keepdims=True)   # [B, 1]
    # first index achieving the tile max (argmax tie-breaking); with a
    # NaN in the tile, jnp.argmax returns the FIRST NaN index instead
    tile_nan = jnp.any(nan, axis=1, keepdims=True)
    cand = jnp.where(lg == tmax, col, jnp.int32(2 ** 30))
    nan_cand = jnp.where(nan, col, jnp.int32(2 ** 30))
    targ = jnp.where(tile_nan,
                     jnp.min(nan_cand, axis=1, keepdims=True),
                     jnp.min(cand, axis=1, keepdims=True))
    upd = (tmax > best_v[...]) | \
        ((tile_nan | jnp.isnan(tmax)) & ~jnp.isnan(best_v[...]))
    best_i[...] = jnp.where(upd, targ, best_i[...])
    best_v[...] = jnp.where(upd, jnp.where(tile_nan, jnp.nan, tmax),
                            best_v[...])

    @pl.when(i == n_tiles - 1)
    def _():
        o_ref[...] = best_i[...].astype(o_ref.dtype)


def _fused_argmax_pallas(hidden, weight, vdim, bias, tile: int):
    b, d = hidden.shape
    vocab = weight.shape[vdim]
    n_tiles = pl.cdiv(vocab, tile)
    has_bias = bias is not None
    brow = (bias.reshape(1, vocab) if has_bias
            else jnp.zeros((1, 1), jnp.float32))
    if vdim == 0:
        w_spec = pl.BlockSpec((tile, d), lambda i: (i, 0),
                              memory_space=pltpu.VMEM)
    else:
        w_spec = pl.BlockSpec((d, tile), lambda i: (0, i),
                              memory_space=pltpu.VMEM)
    out = named_pallas_call(
        "fused_argmax",
        functools.partial(_argmax_kernel, tile=tile, vocab=vocab,
                          n_tiles=n_tiles, has_bias=has_bias,
                          vdim=vdim),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((b, d), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),      # hidden
            w_spec,                                     # weight tile
            pl.BlockSpec((1, tile) if has_bias else (1, 1),
                         (lambda i: (0, i)) if has_bias
                         else (lambda i: (0, 0)),
                         memory_space=pltpu.VMEM),      # bias tile
        ],
        out_specs=pl.BlockSpec((b, 1), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((b, 1), jnp.float32),
                        pltpu.VMEM((b, 1), jnp.int32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * b * d * vocab,
            bytes_accessed=vocab * d * weight.dtype.itemsize,
            transcendentals=0),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(hidden, weight, brow)
    return out[:, 0]


def fused_sample_supported(hidden_shape, w_shape,
                           backend: Optional[str] = None,
                           transpose_y: bool = True) -> bool:
    """Gate for the Mosaic streaming-argmax kernel: lane-tiling hidden
    width on a TPU backend, either weight layout (everything else —
    CPU, odd widths, top-k — runs the streaming reference, same
    semantics)."""
    from .flash_attention import _FORCE_DEPTH
    if backend is None:
        backend = jax.default_backend()
    if backend != "tpu" and _FORCE_DEPTH == 0:
        return False
    b, d = hidden_shape
    return d % 128 == 0 and w_shape[1 - _vocab_dim(transpose_y)] == d


def fused_sample(hidden, weight, bias=None, transpose_y: bool = False,
                 top_k: Optional[int] = None, tile: int = DEFAULT_TILE):
    """Streaming lm_head sampling primitive.

    ``hidden``: [B, D] final hidden states; ``weight``: the lm_head
    weight — [V, D] with ``transpose_y=True`` (tied-embedding layout,
    logits = hidden @ W.T) or [D, V] with ``transpose_y=False``
    (logits = hidden @ W). ``top_k=None`` returns greedy tokens
    ([B] int32, == argmax of the full logits, first-index ties);
    ``top_k=k`` returns the ``(values [B, k], indices [B, k])``
    reservoir of the k largest logits for a sampler to draw from. The
    [B, V] logits tensor is never materialized either way."""
    vdim = _vocab_dim(transpose_y)
    if top_k is not None:
        return fused_topk_reference(hidden, weight, vdim, top_k,
                                    bias=bias, tile=tile)
    k_tile = kernel_tile(weight.shape[vdim], hidden.shape[1],
                         weight.dtype.itemsize, tile)
    if k_tile and fused_sample_supported(hidden.shape, weight.shape,
                                         transpose_y=transpose_y):
        return _fused_argmax_pallas(hidden, weight, vdim, bias, k_tile)
    return fused_argmax_reference(hidden, weight, vdim, bias=bias,
                                  tile=tile)
