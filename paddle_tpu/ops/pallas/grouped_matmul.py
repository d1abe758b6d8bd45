"""Grouped matmul over rows laid out in tiles of one expert each.

The dropless expert layer (distributed/moe.py ``dropless_experts``)
places the rows that picked expert ``e`` in whole tiles of ``tm`` rows,
so every row tile multiplies ONE expert's weights: ``tile_expert[i]``
names it, scalar-prefetched so the weight block's index map reads it
before the grid body runs. Only the experts that own a tile are read
from HBM (a decode step touches about 51 of 64), consecutive tiles of
one expert re-use the block already in VMEM, and tiles past ``used``
(the static tile count is an upper bound) repeat the last used block
index, so nothing is fetched for them and their bodies are skipped;
their output rows are never read.

``grouped_ffn_in`` computes ``act(x @ w_gate[e]) * (x @ w_up[e])`` in
one pass over ``x`` (``act`` the model's: ``relu`` or ``silu``);
``grouped_matmul`` is the plain product (the down
projection). Both accumulate in float32 on the MXU and store in the
activation's dtype. Off the TPU the same layout runs through a gather
of the tiles' weights (tiny sizes only: the CPU tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .naming import named_pallas_call

# the x tile and two double-buffered weight blocks at tm 256, K 2560,
# tn 256 come to about 9 MB: over the 16 MB default, with room
_VMEM_LIMIT = 48 * 1024 * 1024


def _col_block(n: int, cap: int = 256) -> int:
    """Largest multiple of 128 that divides ``n`` and is at most
    ``cap``; ``n`` itself where it has none (tiny test widths)."""
    best = 0
    for c in range(128, min(cap, n) + 1, 128):
        if n % c == 0:
            best = c
    return best or n


ACTIVATIONS = {"relu": lambda a: jnp.maximum(a, 0.0), "silu": jax.nn.silu}


def _kernel(te_ref, used_ref, x_ref, *refs, act):
    w_refs, o_ref = refs[:-1], refs[-1]

    @pl.when(pl.program_id(0) < used_ref[0])
    def _():
        x = x_ref[...]
        acc = jax.lax.dot_general(
            x, w_refs[0][0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if act is not None:
            up = jax.lax.dot_general(
                x, w_refs[1][0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = act(acc) * up
        o_ref[...] = acc.astype(o_ref.dtype)


def grouped_supported(backend=None) -> bool:
    from .flash_attention import _FORCE_DEPTH
    if backend is None:
        backend = jax.default_backend()
    return backend == "tpu" or _FORCE_DEPTH > 0


def _reference(x, ws, tile_expert, tm: int, act):
    nt = tile_expert.shape[0]
    xt = x.reshape(nt, tm, x.shape[1])
    acc = jnp.einsum("tmk,tkn->tmn", xt, ws[0][tile_expert],
                     preferred_element_type=jnp.float32)
    if act is not None:
        up = jnp.einsum("tmk,tkn->tmn", xt, ws[1][tile_expert],
                        preferred_element_type=jnp.float32)
        acc = act(acc) * up
    return acc.astype(x.dtype).reshape(nt * tm, -1)


def _call(name, x, ws, tile_expert, used, tm: int, act=None):
    """``act``: the gate's activation where ``ws`` is (gate, up), None
    for the plain product."""
    if not grouped_supported():
        return _reference(x, ws, tile_expert, tm, act)
    mp, k = x.shape
    n = ws[0].shape[2]
    tn = _col_block(n)
    nt, nj = mp // tm, n // tn

    def x_map(i, j, te, nu):
        return (jnp.minimum(i, jnp.maximum(nu[0] - 1, 0)), 0)

    def w_map(i, j, te, nu):
        live = i < nu[0]
        return (te[i], 0, jnp.where(live, j, nj - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(nt, nj),
        in_specs=[pl.BlockSpec((tm, k), x_map)]
        + [pl.BlockSpec((1, k, tn), w_map) for _ in ws],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, te, nu: (i, j)))
    flops = 2 * mp * k * n * len(ws)
    call = named_pallas_call(
        name, functools.partial(_kernel, act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, n), x.dtype),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=0,
            bytes_accessed=(x.size + mp * n) * x.dtype.itemsize
            + sum(w.size * w.dtype.itemsize for w in ws)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT))
    return call(tile_expert, used, x, *ws)


def grouped_ffn_in(x, w_gate, w_up, tile_expert, used, tm: int,
                   activation: str = "relu"):
    """``act(x @ w_gate[e]) * (x @ w_up[e])`` per row tile, ``act`` one
    of ``ACTIVATIONS``.

    x: [tiles * tm, K]; w_gate, w_up: [E, K, F]; tile_expert: [tiles]
    int32 (each tile's expert; tiles past ``used`` repeat the last
    used tile's); used: [1] int32. Returns [tiles * tm, F]."""
    return _call("moe_ffn_in", x, (w_gate, w_up), tile_expert, used, tm,
                 ACTIVATIONS[activation])


def grouped_matmul(x, w, tile_expert, used, tm: int):
    """``x @ w[e]`` per row tile: x [tiles * tm, K], w [E, K, N]."""
    return _call("moe_ffn_out", x, (w,), tile_expert, used, tm)
