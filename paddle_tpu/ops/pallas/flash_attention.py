"""Flash attention Pallas/Mosaic kernel for TPU.

The fused-attention hot op (reference analog: the CUDA fusion
paddle/fluid/operators/fused/multihead_matmul_op.cu — rebuilt here as a
proper online-softmax flash kernel instead of a translated fusion).

Forward: grid (B, H, Sq/BQ, Sk/BK); the K/V blocks stream through the
LAST grid axis while running (max, sumexp, acc) state lives in VMEM
scratch — the output block is revisited across the K axis and written on
its final step. Backward: FlashAttention-2 split — one kernel recomputes
p-blocks to build dK/dV (K blocks outer, Q blocks streaming), another
builds dQ (Q outer, K streaming); both use the saved logsumexp and
delta = rowsum(dO * O).

Only BLOCKS ever sit in VMEM (the r3 fix: the previous design mapped the
full [S, D] counterpart operand per (batch, head) into VMEM and
fori_loop'ed over it, capping S*D at the ~16 MB scoped-vmem budget —
S=8192 x D=128 failed to compile), so sequence length is bounded by HBM,
not VMEM. All matmuls run on the MXU in fp32 accumulation
(preferred_element_type=float32); causal runs skip fully-masked blocks
via pl.when on the block indices.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from .naming import named_pallas_call

# Blocks of 512 x 512, auto-clamped to the sequence length. What a grid
# step costs on a v5e (PR 33, tools/flash_report.py: device time of the
# forward call at seven shapes of d 128 bf16, split by least squares): a
# step over a block that holds a visible key 0.93 us (its two matmuls
# need 0.68), a step the causal skip guards off 0.11 us, a row of blocks
# 0.27 us to start and finish. Smaller blocks pay the per-step part more
# often for the same keys.
# PT_FLASH_BLOCK_Q/K override for shape-specific tuning (the analog of
# the reference's per-kernel-key JIT selection, operators/jit/README).
import contextlib as _contextlib
import os as _os

DEFAULT_BLOCK_Q = int(_os.environ.get("PT_FLASH_BLOCK_Q", 512))
DEFAULT_BLOCK_K = int(_os.environ.get("PT_FLASH_BLOCK_K", 512))
_NEG_INF = -1e30

# batch/head/outer-block grid axes carry no cross-iteration state ->
# Mosaic may pipeline them; the LAST axis streams the counterpart blocks
# through scratch accumulators and must run in order ("arbitrary").
_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


_LANES = 128


def _over_lanes(x, n):
    """A lane-wide ``[rows, 128]`` carry laid beside ``n`` columns. Its
    128 lanes hold one value a row, so a multiple of 128 columns is the
    same registers again (no cross-lane move) and fewer are a slice."""
    if n == _LANES:
        return x
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    if n < _LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _lane_sums(p):
    """``[rows, n]`` -> ``[rows, 128]``: the sum of each row's columns
    that share a lane (elementwise adds of whole registers); summing the
    128 lanes gives the row's sum."""
    out = p[:, :_LANES]
    for c in range(_LANES, p.shape[1], _LANES):
        out = out + p[:, c:c + _LANES]
    return out


def _scores(q, k_blk, scale, q0=None, k0=None, window=None):
    """``q k^T * scale`` in f32 (operands stay in the input dtype: bf16
    on the MXU at full rate). With ``q0`` / ``k0``, the positions of
    the block's first query and key, the causal (and window) mask is
    laid over it; without, the block is taken as wholly visible."""
    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [BQ, BK] f32
    if q0 is None:
        return s
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    seen = q_pos >= k_pos
    if window is not None:
        seen = seen & (k_pos > q_pos - window)
    return jnp.where(seen, s, _NEG_INF)


def _softmax_block(s, m):
    """One online-softmax step over a score block: the new running max
    (``[BQ, 128]``, every lane of a row the same value), ``alpha`` that
    rescales what was accumulated under the old one, and ``p``.

    The carries stay 128 lanes wide on purpose. A ``[BQ, 1]`` slice of
    the scratch costs a cross-lane permute for every register of scores
    it is subtracted from. The compiler's schedule of a 512 x 512 step
    (PR 33, compiled for a v5e) held 2,534 slots of the cross-lane unit
    in 2,025 bundles that way and holds 570 in 1,083 this way, against
    1,024 cycles of MXU work; on the chip the call at B2 H16 S2048 d128
    went from 733 to 351 us (tools/flash_report.py)."""
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - _over_lanes(m_new, s.shape[1]))
    return m_new, alpha, p


def _normalised(acc, l_lanes, m):
    """(o, lse rows) from the accumulator, the per-lane partial sums
    and the running max, all still lane-wide."""
    denom = jnp.maximum(jnp.broadcast_to(
        jnp.sum(l_lanes, axis=1, keepdims=True), l_lanes.shape), 1e-30)
    return (acc / _over_lanes(denom, acc.shape[1]),
            (m + jnp.log(denom))[:, :1])


def _relevant(qb, kb, block_q, block_k, window, live=None):
    """Does the K block hold a key that some query of the Q block sees:
    not wholly above the diagonal, nor wholly behind the window.
    ``live`` (``_live_blocks`` of a sequence): nor does it start at or
    past the sequence's true length, queries or keys (no LIVE query sees
    such a key: one at p < length sees keys <= p)."""
    relevant = kb * block_k <= (qb + 1) * block_q - 1
    if window is not None:
        # a query at p sees keys p - window + 1 .. p
        relevant = relevant & (
            (kb + 1) * block_k - 1 >= qb * block_q - (window - 1))
    if live is not None:
        q_blocks, last_k = live
        relevant = relevant & (qb < q_blocks) & (kb <= last_k)
    return relevant


def _live_blocks(lengths, block_q, block_k):
    """What the kernel reads of the true lengths, a pair a sequence
    ([2, B] int32): the Q blocks that start before the length (``qb <
    it`` is ``qb * block_q < length``) and the last K block that does
    (``kb <= it`` is ``kb * block_k < length``; 0 for an empty
    sequence, whose Q blocks are all past the end). Worked out once a
    call and not once a grid step: the compiler schedules 6 scalar
    bundles a step for the pair, 12 for the length itself (PR 35)."""
    return jnp.stack([-(-lengths // block_q),
                      jnp.maximum(lengths - 1, 0) // block_k])


def _fwd_kernel_ragged(live_ref, q_ref, k_ref, v_ref, o_ref, *scratch,
                       **static):
    """``_fwd_kernel`` under a scalar-prefetch grid: ``_live_blocks``
    arrives first, in scalar memory, and no log-sum-exp rows leave (the
    serving prefill keeps none, and their ``[block_q, 1]`` store and
    copy cost 1.2 us a row of blocks at d 256, 0.4 at d 128: 1.5 ms of
    a 32,768 bucket's call, PR 35)."""
    b = pl.program_id(0)
    _fwd_kernel(q_ref, k_ref, v_ref, o_ref, None, *scratch,
                live=(live_ref[0, b], live_ref[1, b]), **static)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, acc_ref, m_ref, l_run_ref,
                *, scale, causal, block_q, block_k, nk, window=None,
                live=None):
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_run_ref[...] = jnp.zeros_like(l_run_ref)

    # causal: K blocks wholly above the diagonal (or behind the window)
    # contribute nothing. Every other block takes the ONE masked body,
    # also those the diagonal does not cut: the iota / compare / select
    # chain hides under the MXU. Measured (PR 33, device time of the
    # call): B2 H16 S2048 351 us with this body, 360 with a second,
    # unmasked body for the wholly visible blocks; [1, 64, 8192] over 8
    # KV heads 8.99 against 9.35 ms (the compiler schedules the masked
    # step in 1,083 bundles, the unmasked one in 1,137). Walking the
    # diagonal block in sub-tiles and skipping those above it did not
    # pay either: no change with 256-wide sub-tiles, 6 % slower with
    # 128-wide ones (narrow bands reload the MXU's weights as often as
    # they stream rows); folding ``scale`` into the exponent: 0.4 %.
    # With ``live`` a Q block past the sequence's end runs no step at
    # all: _init and _finish alone, which write zeros (0 / 1e-30), so
    # the padded rows that flow on through the row-wise matmuls and
    # norms after the attention are finite and never uninitialised.
    @pl.when(_relevant(qb, kb, block_q, block_k, window, live)
             if causal else True)
    def _step():
        v_blk = v_ref[0, 0]
        where = (qb * block_q, kb * block_k, window) if causal else ()
        s = _scores(q_ref[0, 0], k_ref[0, 0], scale, *where)
        m_new, alpha, p = _softmax_block(s, m_ref[...])
        # the running sum is kept a partial sum a lane (no cross-lane
        # reduce a step): _finish adds the 128 lanes once a row of blocks
        l_run_ref[...] = l_run_ref[...] * alpha + _lane_sums(p)
        acc_ref[...] = (
            acc_ref[...] * _over_lanes(alpha, acc_ref.shape[1])
            + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(kb == nk - 1)
    def _finish():
        o, lse = _normalised(acc_ref[...], l_run_ref[...], m_ref[...])
        o_ref[0, 0] = o.astype(o_ref.dtype)
        # logsumexp per row, stored [BQ, 1] (lane-1 layout keeps the
        # block spec legal on TPU: last dim equals the array dim)
        if l_ref is not None:
            l_ref[0, 0] = lse


def _fwd_single_block_kernel(q_ref, k_ref, v_ref, o_ref, l_ref,
                             *, scale, causal, block_q, block_k,
                             window=None):
    """Forward for the nk == 1 case (the whole K axis is one block,
    e.g. S=512 at the default 512 block): the same chain with one step,
    so no scratch, no rescale and every grid dim parallel."""
    v_blk = v_ref[0, 0]
    where = (pl.program_id(2) * block_q, 0, window) if causal else ()
    s = _scores(q_ref[0, 0], k_ref[0, 0], scale, *where)
    m, _, p = _softmax_block(
        s, jnp.full((s.shape[0], _LANES), _NEG_INF, jnp.float32))
    acc = jax.lax.dot_general(
        p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o, lse = _normalised(acc, _lane_sums(p), m)
    o_ref[0, 0] = o.astype(o_ref.dtype)
    l_ref[0, 0] = lse


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale, causal, block_q, block_k, nk):
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    relevant = (kb * block_k <= (qb + 1) * block_q - 1) if causal else True

    @pl.when(relevant)
    def _step():
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # [BQ, 1]
        delta = delta_ref[0, 0]  # [BQ, 1]
        k_blk = k_ref[0, 0]
        v_blk = v_ref[0, 0]
        where = (qb * block_q, kb * block_k) if causal else ()
        s = _scores(q, k_blk, scale, *where)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _finish():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc_ref,
                      *, scale, causal, block_q, block_k, nk):
    """Single-pass backward for the nq == 1 case (the whole Q axis is
    one block, e.g. S=512 at the default 512 block): grid (B, H, nk)
    streams K blocks, dQ accumulates in scratch over the LAST grid axis
    (the one revisiting Pallas TPU allows), dK/dV are per-block
    outputs. Computes the score block and its exp ONCE per (q,k) pair
    — the general two-kernel FlashAttention-2 backward recomputes them
    in both passes (7 matmuls + 2 exps vs 5 matmuls + 1 exp here)."""
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    # causal with sk > sq (nq == 1): K blocks entirely past the last Q
    # row are fully masked — p would underflow to exact zero, so skip
    # the matmuls/DMA-consumption and zero-fill their dk/dv outputs
    # (dq accumulates nothing from them). The K/V input specs clamp the
    # block index for these steps so the HBM fetch is skipped too.
    relevant = (kb * block_k <= block_q - 1) if causal else True

    @pl.when(relevant)
    def _step():
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # [BQ, 1]
        delta = delta_ref[0, 0]  # [BQ, 1]
        k_blk = k_ref[0, 0]
        v_blk = v_ref[0, 0]
        where = (0, kb * block_k) if causal else ()
        s = _scores(q, k_blk, scale, *where)
        p = jnp.exp(s - lse)  # [BQ, BK]
        dv_ref[0, 0] = jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dv_ref.dtype)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_ref[0, 0] = jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dk_ref.dtype)
        dq_acc_ref[...] = dq_acc_ref[...] + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(jnp.logical_not(relevant))
        def _masked_block():
            dk_ref[0, 0] = jnp.zeros_like(dk_ref[0, 0])
            dv_ref[0, 0] = jnp.zeros_like(dv_ref[0, 0])

    @pl.when(kb == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                    *, scale, causal, block_q, block_k, nq):
    kb = pl.program_id(2)
    qb = pl.program_id(3)

    @pl.when(qb == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    # causal: Q blocks fully above the diagonal see none of this K block
    relevant = ((qb + 1) * block_q - 1 >= kb * block_k) if causal else True

    @pl.when(relevant)
    def _step():
        k_blk = k_ref[0, 0]  # [BK, D]
        v_blk = v_ref[0, 0]
        q = q_ref[0, 0]  # [BQ, D]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # [BQ, 1]
        delta = delta_ref[0, 0]
        where = (qb * block_q, kb * block_k) if causal else ()
        s = _scores(q, k_blk, scale, *where)
        p = jnp.exp(s - lse)  # [BQ, BK]
        dv_acc_ref[...] = dv_acc_ref[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc_ref[...] = dk_acc_ref[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qb == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _spec_outer(block, d):
    """Block indexed by the OUTER block axis (grid dim 2), constant over
    the streaming axis (grid dim 3).

    Note (r4, measured): a "packed" variant of these specs that kept
    heads as d-wide column blocks over the natural [B, S, H*D] layout —
    eliminating the [B,S,H,D]->[B,H,S,D] transpose round-trip — was
    tried and REMOVED: Mosaic cannot lower d=64 column blocks (the last
    block dim must divide 128 or span the array), and at d=128 the
    strided block DMA cost more than the transposes it saved (GPT-1.3B
    step 254.0 vs 251.7 ms)."""
    return pl.BlockSpec((1, 1, block, d),
                        lambda b, h, i, j, *_: (b, h, i, 0),
                        memory_space=pltpu.VMEM)


def _spec_inner(block, d, clamp=None, group=1):
    """Block streamed by the INNER grid axis (grid dim 3). ``clamp(i, j)``
    maps the stream index per outer block — causal kernels clamp masked
    steps to the last/first relevant block, so Pallas sees a repeated
    block index and skips the HBM re-fetch for steps pl.when guards off.
    ``group`` > 1: grouped heads, query head h streams KV head
    h // group.
    """
    if group > 1:
        keep = clamp or (lambda i, j: j)
        return pl.BlockSpec((1, 1, block, d),
                            lambda b, h, i, j: (b, h // group, keep(i, j), 0),
                            memory_space=pltpu.VMEM)
    if clamp is None:
        return pl.BlockSpec((1, 1, block, d),
                            lambda b, h, i, j: (b, h, j, 0),
                            memory_space=pltpu.VMEM)
    return pl.BlockSpec((1, 1, block, d),
                        lambda b, h, i, j: (b, h, clamp(i, j), 0),
                        memory_space=pltpu.VMEM)


def _spec_lane1_outer(block):
    return pl.BlockSpec((1, 1, block, 1),
                        lambda b, h, i, j, *_: (b, h, i, 0),
                        memory_space=pltpu.VMEM)


def _spec_lane1_inner(block, clamp=None):
    if clamp is None:
        return pl.BlockSpec((1, 1, block, 1),
                            lambda b, h, i, j: (b, h, j, 0),
                            memory_space=pltpu.VMEM)
    return pl.BlockSpec((1, 1, block, 1),
                        lambda b, h, i, j: (b, h, clamp(i, j), 0),
                        memory_space=pltpu.VMEM)


def _spec3_indexed(block, d, lim=None):
    """3-dim-grid spec: block selected by the grid's third axis.
    ``lim`` clamps the index (causal fused-bwd: K blocks past the last
    Q row repeat the last relevant block so Pallas skips the fetch)."""
    if lim is None:
        return pl.BlockSpec((1, 1, block, d),
                            lambda b, h, i: (b, h, i, 0),
                            memory_space=pltpu.VMEM)
    return pl.BlockSpec((1, 1, block, d),
                        lambda b, h, i: (b, h, jnp.minimum(i, lim), 0),
                        memory_space=pltpu.VMEM)


def _spec3_pinned(block, d, group=1):
    """3-dim-grid spec: the same (b, h) block regardless of the third
    grid axis (the single outer block of an nq==1/nk==1 kernel);
    ``group`` > 1 pins KV head h // group."""
    if group > 1:
        return pl.BlockSpec((1, 1, block, d),
                            lambda b, h, i: (b, h // group, 0, 0),
                            memory_space=pltpu.VMEM)
    return pl.BlockSpec((1, 1, block, d),
                        lambda b, h, i: (b, h, 0, 0),
                        memory_space=pltpu.VMEM)


def _kv_clamp(causal, block_q, block_k, window=None):
    """For Q-outer kernels: the last K block visible to Q block i and,
    under a window, the first."""
    if not causal:
        return None
    if window is None:
        return lambda i, j: jnp.minimum(
            j, ((i + 1) * block_q - 1) // block_k)
    return lambda i, j: jnp.clip(
        j, jnp.maximum(i * block_q - (window - 1), 0) // block_k,
        ((i + 1) * block_q - 1) // block_k)


def _spec_inner_ragged(block_q, block_k, d, window, group):
    """``_spec_inner`` of a causal Q-outer kernel that was handed
    ``_live_blocks`` (the last argument of a scalar-prefetch index map).
    A live Q block streams what it streams without, capped by the last
    K block that holds a live key; a Q block past its sequence's end
    maps EVERY step to that one block, which the edge Q block before it
    left in VMEM: nothing is fetched for it."""
    visible = _kv_clamp(True, block_q, block_k, window)

    def index(b, h, i, j, live):
        last_k = live[1, b]
        j = jnp.where(i < live[0, b],
                      jnp.minimum(visible(i, j), last_k), last_k)
        return (b, h // group, j, 0)

    return pl.BlockSpec((1, 1, block_k, d), index, memory_space=pltpu.VMEM)


def _q_clamp(causal, block_q, block_k):
    """For K-outer kernels: the first Q block that sees K block i."""
    if not causal:
        return None
    return lambda i, j: jnp.maximum(j, (i * block_k) // block_q)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, group=1,
               window=None, lengths=None):
    """``group`` > 1: k, v are [B, H // group, S, D] and query head h
    reads KV head h // group. ``window``: with ``causal``, a query at p
    sees keys p - window + 1 .. p; blocks behind the window are skipped
    (neither fetched nor computed), the edge block is masked.
    ``lengths`` ([B] int32, with ``causal``): positions from a
    sequence's length on are padding. They reach the kernel as
    prefetched scalars (``_live_blocks``), and a Q block that starts at
    or past the length runs no step, fetches no K or V block and writes
    zeros; no key is masked for it (a live query at p sees keys <= p <
    length), so every live row runs the blocks, in the order, it runs
    without. The log-sum-exp rows are not written then (None comes back
    in their place): forward only. On a v5e (PR 35,
    tools/flash_report.py) a [1, 20, 32768, 256] call takes 76.1 ms
    without lengths, 76.9 at a length of 32,768, 49.5 at 24,576 and
    30.8 at 16,896: the steps of the rows past the length are still
    grid steps, 0.1-0.2 us each. All three default to the plain kernel,
    whose program they leave untouched."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nk = sk // block_k
    extra = {} if window is None else {"window": int(window)}
    if nk == 1:
        # one K block: plain softmax kernel, no streaming axis — every
        # grid dim is parallel and the online-softmax scratch vanishes
        # (a prompt of the shortest bucket: ``lengths`` has no block to
        # save there)
        out, lse = named_pallas_call(
            "flash_fwd_single",
            functools.partial(_fwd_single_block_kernel, scale=scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k, **extra),
            grid=(b, h, sq // block_q),
            in_specs=[_spec3_indexed(block_q, d),
                      _spec3_pinned(block_k, d, group),
                      _spec3_pinned(block_k, d, group)],
            out_specs=[_spec3_indexed(block_q, d),
                       _spec3_indexed(block_q, 1)],
            out_shape=[
                jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
                jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
            ],
            cost_estimate=pl.CostEstimate(
                flops=4 * b * h * sq * sk * d,
                bytes_accessed=(q.size + k.size + v.size) *
                q.dtype.itemsize,
                transcendentals=b * h * sq * sk),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel",
                                     "parallel")),
        )(q, k, v)
        return out, lse
    static = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, nk=nk, **extra)
    grid = (b, h, sq // block_q, nk)
    out_specs = [_spec_outer(block_q, d), _spec_lane1_outer(block_q)]
    out_shape = [
        jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
    ]
    scratch_shapes = [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q, 128), jnp.float32),
        pltpu.VMEM((block_q, 128), jnp.float32),
    ]
    if lengths is None:
        kvc = _kv_clamp(causal, block_q, block_k, window)
        kernel = functools.partial(_fwd_kernel, **static)
        layout = dict(grid=grid,
                      in_specs=[_spec_outer(block_q, d),
                                _spec_inner(block_k, d, kvc, group),
                                _spec_inner(block_k, d, kvc, group)],
                      out_specs=out_specs, scratch_shapes=scratch_shapes)
        args = (q, k, v)
    else:
        if not causal:
            raise ValueError("lengths: the causal forward alone takes them")
        kernel = functools.partial(_fwd_kernel_ragged, **static)
        kv = _spec_inner_ragged(block_q, block_k, d, window, group)
        out_specs, out_shape = out_specs[:1], out_shape[:1]
        layout = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[_spec_outer(block_q, d), kv, kv],
            out_specs=out_specs, scratch_shapes=scratch_shapes))
        args = (_live_blocks(lengths.astype(jnp.int32), block_q, block_k),
                q, k, v)
    out, *lse = named_pallas_call(
        "flash_fwd", kernel,
        out_shape=out_shape,
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * sq * sk * d,
            bytes_accessed=(q.size + k.size + v.size) * q.dtype.itemsize,
            transcendentals=b * h * sq * sk),
        compiler_params=_GRID_SEMANTICS,
        **layout,
    )(*args)
    return out, (lse[0] if lse else None)


def _flash_bwd(q, k, v, out, lse, do, scale, causal, block_q, block_k,
               g_lse=None, delta=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq, nk = sq // block_q, sk // block_k
    if delta is None:
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1, keepdims=True)  # [B,H,Sq,1]
    if g_lse is not None:
        # lse cotangent folds into delta: d lse/d s_j = p_j, so the lse
        # contribution to ds is p * g_lse — i.e. ds = p*(dp - (delta -
        # g_lse)). No kernel change needed.
        delta = delta - g_lse.astype(jnp.float32)

    if nq == 1:
        # the whole Q axis is one block: a single fused pass computes
        # dQ/dK/dV together (one score recompute instead of two).
        # Measured v5e: neutral on the isolated scanned microbench but
        # -14.5 ms (-6.7%) on the full BERT-base body step, where the
        # halved launch count composes with XLA's surrounding schedule.
        kv_lim = ((block_q - 1) // block_k) if causal else None
        dq, dk, dv = named_pallas_call(
            "flash_bwd_fused",
            functools.partial(_bwd_fused_kernel, scale=scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k, nk=nk),
            grid=(b, h, nk),
            in_specs=[_spec3_pinned(block_q, d),
                      _spec3_indexed(block_k, d, kv_lim),
                      _spec3_indexed(block_k, d, kv_lim),
                      _spec3_pinned(block_q, d),
                      _spec3_pinned(block_q, 1),
                      _spec3_pinned(block_q, 1)],
            out_specs=[_spec3_pinned(block_q, d),
                       _spec3_indexed(block_k, d),
                       _spec3_indexed(block_k, d)],
            out_shape=[
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            cost_estimate=pl.CostEstimate(
                # 5 matmuls over every (q, k) pair: dv, dp, dk, dq, s
                flops=10 * b * h * sq * sk * d,
                bytes_accessed=(2 * q.size + 2 * do.size + 2 * k.size +
                                2 * v.size) * q.dtype.itemsize,
                transcendentals=b * h * sq * sk),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel",
                                     "arbitrary")),
        )(q, k, v, do, lse, delta)
        return dq, dk, dv

    # dQ: Q blocks outer (parallel), K/V blocks stream on the last axis
    kvc = _kv_clamp(causal, block_q, block_k)
    dq = named_pallas_call(
        "flash_bwd_dq",
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk),
        grid=(b, h, nq, nk),
        in_specs=[
            _spec_outer(block_q, d),
            _spec_inner(block_k, d, kvc),
            _spec_inner(block_k, d, kvc),
            _spec_outer(block_q, d),
            _spec_lane1_outer(block_q), _spec_lane1_outer(block_q),
        ],
        out_specs=_spec_outer(block_q, d),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_GRID_SEMANTICS,
    )(q, k, v, do, lse, delta)

    # dK/dV: K blocks outer (parallel), Q/dO/lse/delta stream
    qc = _q_clamp(causal, block_q, block_k)
    dk, dv = named_pallas_call(
        "flash_bwd_dkv",
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq),
        grid=(b, h, nk, nq),
        in_specs=[
            _spec_inner(block_q, d, qc),
            _spec_outer(block_k, d),
            _spec_outer(block_k, d),
            _spec_inner(block_q, d, qc),
            _spec_lane1_inner(block_q, qc), _spec_lane1_inner(block_q, qc),
        ],
        out_specs=[_spec_outer(block_k, d),
                   _spec_outer(block_k, d)],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_GRID_SEMANTICS,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_bhsd_lse(q, k, v, scale, causal, block_q, block_k):
    """(out, lse) with lse DIFFERENTIABLE — the building block for
    blockwise/ring merging, where gradients flow through the logsumexp
    merge weights as well as the block outputs."""
    return _flash_fwd(q, k, v, scale, causal, block_q, block_k)


def _flash_lse_vjp_fwd(q, k, v, scale, causal, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    # selective-remat hook: when ATTN_OUT_NAME is an active saved name
    # (core.offload.set_remat_saved_names, e.g. via
    # GPTConfig.remat_save_attention), tag BOTH backward residuals this
    # kernel produces — out alone is not enough, the FlashAttention-2
    # backward also consumes lse, and an unsaved lse forces the whole
    # flash forward to recompute under jax.checkpoint
    from ...core.offload import ATTN_OUT_NAME, name_activation
    out = name_activation(out, ATTN_OUT_NAME)
    lse = name_activation(lse, ATTN_OUT_NAME)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_vjp_bwd(scale, causal, block_q, block_k, res, g):
    q, k, v, out, lse = res
    g_out, g_lse = g
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, g_out, scale, causal,
                            block_q, block_k, g_lse=g_lse)
    return dq, dk, dv


_flash_attention_bhsd_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def flash_attention_lse(q, k, v, causal: bool = False,
                        scale: Optional[float] = None,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K):
    """flash_attention that also returns the per-row logsumexp
    ([B, S, H] f32), both differentiable. Layout [B, S, H, D]."""
    b, sq, h, d = q.shape
    block_q, block_k = _resolve_blocks(sq, k.shape[1], block_q, block_k)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qT = jnp.swapaxes(q, 1, 2)
    kT = jnp.swapaxes(k, 1, 2)
    vT = jnp.swapaxes(v, 1, 2)
    out, lse = _flash_attention_bhsd_lse(qT, kT, vT, float(scale),
                                         bool(causal), block_q, block_k)
    return jnp.swapaxes(out, 1, 2), jnp.swapaxes(lse[..., 0], 1, 2)


def flash_attention_grouped(q, k, v, window: Optional[int] = None,
                            scale: Optional[float] = None,
                            block_q: int = DEFAULT_BLOCK_Q,
                            block_k: int = DEFAULT_BLOCK_K,
                            lengths=None):
    """Causal forward for serving prefill, layout [B, S, H, D] with
    k, v [B, S, KVH, D], KVH dividing H: query head h reads KV head
    h // (H // KVH) through the K/V block index maps (K and V are not
    repeated). ``window``: a query at p sees keys p - window + 1 .. p.
    ``lengths`` ([B] int32): the right-padded sequences' true lengths;
    the rows of a Q block wholly past one come back zero and run no
    attention (``_flash_fwd``), every row before one is what it is
    without them.
    Forward only (no vjp): the same kernels as ``flash_attention``."""
    b, sq, h, d = q.shape
    group = h // k.shape[2]
    block_q, block_k = _resolve_blocks(sq, k.shape[1], block_q, block_k)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    out, _ = _flash_fwd(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                        jnp.swapaxes(v, 1, 2), float(scale), True,
                        block_q, block_k, group=group, window=window,
                        lengths=lengths)
    return jnp.swapaxes(out, 1, 2)


def _resolve_blocks(sq, sk, block_q, block_k):
    """Largest 128-multiple block that divides the sequence length, capped
    at the requested block — so S=640 runs with 128-blocks rather than
    falling off the flash path entirely."""
    def best(s, cap):
        pick = 0
        m = 128
        while m <= min(cap, s):
            if s % m == 0:
                pick = m
            m += 128
        return pick or cap
    return best(sq, block_q), best(sk, block_k)


_FORCE_DEPTH = 0


@_contextlib.contextmanager
def force_flash_for_aot():
    """Treat the flash kernel as supported while compiling FOR a TPU
    topology ON a CPU host (jax.default_backend() reports the host, not
    the compile target). Scoped — unlike a leftover env var, it cannot
    leak into a real CPU/GPU execution and fail at Mosaic lowering.
    Used by tools/scale_proof.py around its AOT lower+compile."""
    global _FORCE_DEPTH
    _FORCE_DEPTH += 1
    try:
        yield
    finally:
        _FORCE_DEPTH -= 1


def flash_attention_supported(q_shape, k_shape, backend: Optional[str] =
                              None, block_q=DEFAULT_BLOCK_Q,
                              block_k=DEFAULT_BLOCK_K) -> bool:
    if backend is None:
        backend = jax.default_backend()
    if backend != "tpu" and _FORCE_DEPTH == 0:
        return False
    b, sq, h, d = q_shape
    sk = k_shape[1]
    block_q, block_k = _resolve_blocks(sq, sk, block_q, block_k)
    return (sq % block_q == 0 and sk % block_k == 0 and
            block_q % 128 == 0 and block_k % 128 == 0 and
            d in (64, 128, 256))


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K):
    """Public entry, layout [B, S, H, D] (matching
    scaled_dot_product_attention). One vjp stack for both entries:
    this is flash_attention_lse with the lse dropped (its unused
    cotangent arrives as zeros, so delta is unchanged)."""
    out, _ = flash_attention_lse(q, k, v, causal=causal, scale=scale,
                                 block_q=block_q, block_k=block_k)
    return out
