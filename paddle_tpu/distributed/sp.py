"""Sequence/context parallelism: ring attention + Ulysses.

BEYOND-REFERENCE capability (SURVEY §5: the reference has no sequence
parallelism — only the raw alltoall op, operators/collective/
alltoall_op.cc). Long-context training shards the sequence axis over a
mesh axis ("sep"):

- ring_attention: K/V blocks rotate around the ring via
  lax.ppermute while each device holds its Q shard; online-softmax
  (flash-style) accumulation keeps memory O(seq/N). On TPU each hop is
  the Pallas flash kernel with an O(S_local) custom-vjp backward.
- zigzag causal schedule: the lockstep contiguous ring leaves ~2x on
  the table for causal runs (each scan step waits for whichever device
  drew a fully-visible hop). With the sequence split into 2n half-chunks
  and device i holding chunks (i, 2n-1-i), EVERY hop does exactly two
  half-chunk-pairs of work: the local hop is plain local-causal flash,
  a hop from an earlier device attends full-q x first-half-k, a hop
  from a later device attends second-half-q x full-k. ``ring_attention
  (layout="zigzag")`` implements it; ``zigzag_permutation`` gives the
  global reorder (applied once at the model boundary by models.gpt when
  seq_parallel_mode="zigzag").
- ulysses_attention: all_to_all exchanges seq-shards for head-shards so
  each device runs full-sequence attention on a head subset, then
  exchanges back (DeepSpeed-Ulysses pattern on the alltoall primitive).

Both are written for shard_map over the hybrid mesh's "sep" axis and are
used by models.gpt when sep_degree > 1.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def _block_attn(q, k, v, scale, causal_mask=None):
    """One block's contribution: returns (unnormalized out, row-max,
    row-sumexp) in fp32 for online-softmax accumulation.
    q: [B,Sq,H,D], k/v: [B,Sk,H,D]."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal_mask is not None:
        logits = jnp.where(causal_mask, logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)  # [B,H,Sq]
    # guard fully-masked rows
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    l = jnp.sum(p, axis=-1)  # [B,H,Sq]
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return out.astype(jnp.float32), m_safe, l


def merge_attention_blocks(acc, lse_run, out_b, lse_b):
    """Fold one block's NORMALIZED attention result (out_b, lse_b) into
    the running (acc f32 normalized, lse_run): the logsumexp merge
    out = acc*e^(lse_run-lse') + out_b*e^(lse_b-lse'). A fully-masked
    block is lse_b = -inf (weight 0). Shapes: out [..., D], lse [...]."""
    lse_new = jnp.logaddexp(lse_run, lse_b)
    # guard -inf - -inf (no mass seen yet anywhere)
    w_run = jnp.where(jnp.isneginf(lse_new), 0.0,
                      jnp.exp(lse_run - lse_new))
    w_b = jnp.where(jnp.isneginf(lse_new), 0.0, jnp.exp(lse_b - lse_new))
    acc = acc * w_run[..., None] + \
        out_b.astype(jnp.float32) * w_b[..., None]
    return acc, lse_new


def _ring_case(kv_idx, idx):
    """0 = fully visible hop, 1 = diagonal (local causal), 2 = masked."""
    return jnp.where(kv_idx < idx, 0, jnp.where(kv_idx == idx, 1, 2))


def zigzag_permutation(seq_len: int, n: int):
    """(perm, inv) index arrays for the zigzag layout over ``n`` ring
    devices: ``x[:, perm]`` puts the sequence in zigzag order (device i's
    contiguous shard holds original half-chunks i and 2n-1-i);
    ``x[:, inv]`` undoes it. n=1 is the identity."""
    if seq_len % (2 * n):
        raise ValueError(f"seq_len {seq_len} must divide 2*n ({2 * n})")
    c = seq_len // (2 * n)
    parts = []
    for i in range(n):
        parts.append(np.arange(i * c, (i + 1) * c))
        j = 2 * n - 1 - i
        parts.append(np.arange(j * c, (j + 1) * c))
    perm = np.concatenate(parts)
    inv = np.argsort(perm)
    return perm, inv


def zigzag_chunk_order(n: int, inverse: bool = False):
    """Chunk-level zigzag order over 2n half-chunks (chunk i of the
    permuted layout = chunk order[i] of the original)."""
    order = []
    for i in range(n):
        order.extend((i, 2 * n - 1 - i))
    if inverse:
        order = list(np.argsort(order))
    return order


def zigzag_reorder(x, n: int, axis: int = 1, inverse: bool = False):
    """Apply the zigzag layout as SPLIT + CONCAT of 2n chunks instead of
    a gather: static slices with shard-aligned boundaries lower to
    collective-permutes under GSPMD, where a sequence-axis gather trips
    the TPU SPMD partitioner (CHECK failure in spmd_partitioner_util)
    inside partial-manual regions. n=1 is the identity."""
    if n <= 1:
        return x
    chunks = jnp.split(x, 2 * n, axis=axis)
    order = zigzag_chunk_order(n, inverse=inverse)
    return jnp.concatenate([chunks[j] for j in order], axis=axis)


def zigzag_positions(idx, n: int, s_loc: int):
    """Global sequence positions of a device's zigzag-local rows
    (traced-friendly in the device index ``idx``)."""
    c = s_loc // 2
    r = jnp.arange(c)
    return jnp.concatenate([idx * c + r, (2 * n - 1 - idx) * c + r])


def _ring_flash_forward(q, k, v, axis_name, causal, scale):
    """Returns (normalized acc f32, global lse) — the flash residuals."""
    from ..ops.pallas.flash_attention import flash_attention_lse

    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_loc, h, _ = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def hop(k_cur, v_cur, kv_idx):
        def full(_):
            return flash_attention_lse(q, k_cur, v_cur, causal=False,
                                       scale=scale)

        def diag(_):
            # same global offset on both sides: local causal mask IS the
            # global one
            return flash_attention_lse(q, k_cur, v_cur, causal=True,
                                       scale=scale)

        def skip(_):
            return (jnp.zeros(q.shape, q.dtype),
                    jnp.full((b, s_loc, h), -jnp.inf, jnp.float32))

        if not causal:
            return full(None)
        return jax.lax.switch(_ring_case(kv_idx, idx),
                              [full, diag, skip], None)

    def body(carry, t):
        k_cur, v_cur, kv_idx, acc, lse_run = carry
        out_b, lse_b = hop(k_cur, v_cur, kv_idx)
        acc, lse_run = merge_attention_blocks(acc, lse_run, out_b, lse_b)
        # the final hop's rotation feeds nobody: skip its comm volume
        # (t is uniform across devices, so the cond's collectives agree)
        k_nxt, v_nxt = jax.lax.cond(
            t < n - 1,
            lambda kv: (jax.lax.ppermute(kv[0], axis_name, perm),
                        jax.lax.ppermute(kv[1], axis_name, perm)),
            lambda kv: kv, (k_cur, v_cur))
        return (k_nxt, v_nxt, (kv_idx - 1) % n, acc, lse_run), None

    acc0 = jnp.zeros(q.shape, jnp.float32)
    lse0 = jnp.full((b, s_loc, h), -jnp.inf, jnp.float32)
    (_, _, _, acc, lse_run), _ = jax.lax.scan(
        body, (k, v, idx, acc0, lse0), jnp.arange(n))
    return acc, lse_run


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_attention_flash(q, k, v, axis_name, causal, scale):
    """Ring attention whose per-hop block attention is the Pallas flash
    kernel: no [S_loc, S_loc] score tensor ever materializes, and the
    custom vjp keeps backward residuals at O(S_local) — only
    (q, k, v, out, global lse) are saved; the backward RE-ROTATES K/V
    around the ring and runs the flash backward per hop with the global
    lse (plain autodiff through the forward scan would have stored every
    rotated K/V shard, O(S_global) per device, defeating the point).
    dK/dV partials travel around the ring with their shard and arrive
    home after the full rotation."""
    acc, _ = _ring_flash_forward(q, k, v, axis_name, causal, scale)
    return acc.astype(q.dtype)


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, scale):
    acc, lse = _ring_flash_forward(q, k, v, axis_name, causal, scale)
    out = acc.astype(q.dtype)
    return out, (q, k, v, out, lse)


def _ring_flash_vjp_bwd(axis_name, causal, scale, res, do):
    from ..ops.pallas.flash_attention import (DEFAULT_BLOCK_K,
                                              DEFAULT_BLOCK_Q, _flash_bwd,
                                              _resolve_blocks)

    q, k, v, out, lse = res
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_loc = q.shape[1]
    perm = [(i, (i + 1) % n) for i in range(n)]
    bq, bk = _resolve_blocks(s_loc, s_loc, DEFAULT_BLOCK_Q,
                             DEFAULT_BLOCK_K)
    # bhsd layouts for the kernels; lse [B,H,S,1]
    qT = jnp.swapaxes(q, 1, 2)
    outT = jnp.swapaxes(out, 1, 2)
    doT = jnp.swapaxes(do, 1, 2)
    lseT = jnp.swapaxes(lse, 1, 2)[..., None]
    # delta is hop-invariant: compute it once, not n times in the scan
    deltaT = jnp.sum(doT.astype(jnp.float32) * outT.astype(jnp.float32),
                     axis=-1, keepdims=True)

    def hop_bwd(k_cur, v_cur, kv_idx):
        kT = jnp.swapaxes(k_cur, 1, 2)
        vT = jnp.swapaxes(v_cur, 1, 2)

        def run(is_causal):
            def f(_):
                return _flash_bwd(qT, kT, vT, outT, lseT, doT, scale,
                                  is_causal, bq, bk, delta=deltaT)
            return f

        def skip(_):
            return (jnp.zeros_like(qT), jnp.zeros_like(kT),
                    jnp.zeros_like(vT))

        if not causal:
            return run(False)(None)
        return jax.lax.switch(_ring_case(kv_idx, idx),
                              [run(False), run(True), skip], None)

    def body(carry, t):
        k_cur, v_cur, dk_t, dv_t, kv_idx, dq_acc = carry
        dq_p, dk_b, dv_b = hop_bwd(k_cur, v_cur, kv_idx)
        dq_acc = dq_acc + jnp.swapaxes(dq_p, 1, 2).astype(jnp.float32)
        dk_t = dk_t + jnp.swapaxes(dk_b, 1, 2).astype(jnp.float32)
        dv_t = dv_t + jnp.swapaxes(dv_b, 1, 2).astype(jnp.float32)
        # the dK/dV partial buffers travel WITH their K/V shard and need
        # the FULL n rotations to arrive home (device i holds shard
        # (i - t) mod n; only after the n-th hop is every shard back at
        # its owner). The K/V operands themselves are done after the
        # last hop, so their final rotation is skipped.
        dk_nxt = jax.lax.ppermute(dk_t, axis_name, perm)
        dv_nxt = jax.lax.ppermute(dv_t, axis_name, perm)
        k_nxt, v_nxt = jax.lax.cond(
            t < n - 1,
            lambda kv: (jax.lax.ppermute(kv[0], axis_name, perm),
                        jax.lax.ppermute(kv[1], axis_name, perm)),
            lambda kv: kv, (k_cur, v_cur))
        return (k_nxt, v_nxt, dk_nxt, dv_nxt, (kv_idx - 1) % n,
                dq_acc), None

    carry0 = (k, v, jnp.zeros(k.shape, jnp.float32),
              jnp.zeros(v.shape, jnp.float32), idx,
              jnp.zeros(q.shape, jnp.float32))
    (_, _, dk_f, dv_f, _, dq_f), _ = jax.lax.scan(body, carry0,
                                                  jnp.arange(n))
    return (dq_f.astype(q.dtype), dk_f.astype(k.dtype),
            dv_f.astype(v.dtype))


_ring_attention_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def _zigzag_ring_flash_forward(q, k, v, axis_name, scale):
    """Causal ring forward over zigzag-laid-out shards: every hop costs
    exactly two half-chunk-pairs, so the lockstep scan is balanced (the
    contiguous layout's ~2x causal wait disappears)."""
    from ..ops.pallas.flash_attention import flash_attention_lse

    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_loc, h, _ = q.shape
    c = s_loc // 2
    perm = [(i, (i + 1) % n) for i in range(n)]

    def hop(k_cur, v_cur, kv_idx):
        def earlier(_):
            # kv from an earlier device: its first half-chunk is fully
            # visible to all local rows, its second fully masked.
            out_b, lse_b = flash_attention_lse(
                q, k_cur[:, :c], v_cur[:, :c], causal=False, scale=scale)
            return out_b, lse_b

        def local(_):
            # zigzag-local causal IS plain local causal: qa•ka and qb•kb
            # sit on the global diagonal, qb•ka is fully visible,
            # qa•kb fully masked — exactly the row>=col local mask.
            return flash_attention_lse(q, k_cur, v_cur, causal=True,
                                       scale=scale)

        def later(_):
            # kv from a later device: only local second-half rows see it
            # (both its half-chunks precede chunk 2n-1-idx).
            out_b, lse_b = flash_attention_lse(
                q[:, c:], k_cur, v_cur, causal=False, scale=scale)
            return (jnp.concatenate(
                        [jnp.zeros((b, c, h, q.shape[-1]), q.dtype),
                         out_b], axis=1),
                    jnp.concatenate(
                        [jnp.full((b, c, h), -jnp.inf, jnp.float32),
                         lse_b], axis=1))

        return jax.lax.switch(_ring_case(kv_idx, idx),
                              [earlier, local, later], None)

    def body(carry, t):
        k_cur, v_cur, kv_idx, acc, lse_run = carry
        out_b, lse_b = hop(k_cur, v_cur, kv_idx)
        acc, lse_run = merge_attention_blocks(acc, lse_run, out_b, lse_b)
        k_nxt, v_nxt = jax.lax.cond(
            t < n - 1,
            lambda kv: (jax.lax.ppermute(kv[0], axis_name, perm),
                        jax.lax.ppermute(kv[1], axis_name, perm)),
            lambda kv: kv, (k_cur, v_cur))
        return (k_nxt, v_nxt, (kv_idx - 1) % n, acc, lse_run), None

    acc0 = jnp.zeros(q.shape, jnp.float32)
    lse0 = jnp.full((b, s_loc, h), -jnp.inf, jnp.float32)
    (_, _, _, acc, lse_run), _ = jax.lax.scan(
        body, (k, v, idx, acc0, lse0), jnp.arange(n))
    return acc, lse_run


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _zigzag_ring_attention_flash(q, k, v, axis_name, scale):
    """Balanced causal ring attention (zigzag layout) on the Pallas
    flash kernel; same O(S_local) residual contract as
    _ring_attention_flash."""
    acc, _ = _zigzag_ring_flash_forward(q, k, v, axis_name, scale)
    return acc.astype(q.dtype)


def _zigzag_flash_vjp_fwd(q, k, v, axis_name, scale):
    acc, lse = _zigzag_ring_flash_forward(q, k, v, axis_name, scale)
    out = acc.astype(q.dtype)
    return out, (q, k, v, out, lse)


def _zigzag_flash_vjp_bwd(axis_name, scale, res, do):
    from ..ops.pallas.flash_attention import (DEFAULT_BLOCK_K,
                                              DEFAULT_BLOCK_Q, _flash_bwd,
                                              _resolve_blocks)

    q, k, v, out, lse = res
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_loc = q.shape[1]
    c = s_loc // 2
    perm = [(i, (i + 1) % n) for i in range(n)]
    # bhsd layouts; lse [B,H,S,1]
    qT = jnp.swapaxes(q, 1, 2)
    outT = jnp.swapaxes(out, 1, 2)
    doT = jnp.swapaxes(do, 1, 2)
    lseT = jnp.swapaxes(lse, 1, 2)[..., None]
    deltaT = jnp.sum(doT.astype(jnp.float32) * outT.astype(jnp.float32),
                     axis=-1, keepdims=True)

    def hop_bwd(k_cur, v_cur, kv_idx):
        kT = jnp.swapaxes(k_cur, 1, 2)
        vT = jnp.swapaxes(v_cur, 1, 2)

        def earlier(_):
            bq, bk = _resolve_blocks(s_loc, c, DEFAULT_BLOCK_Q,
                                     DEFAULT_BLOCK_K)
            dq_p, dk_h, dv_h = _flash_bwd(
                qT, kT[:, :, :c], vT[:, :, :c], outT, lseT, doT, scale,
                False, bq, bk, delta=deltaT)
            return (dq_p,
                    jnp.concatenate([dk_h, jnp.zeros_like(dk_h)], axis=2),
                    jnp.concatenate([dv_h, jnp.zeros_like(dv_h)], axis=2))

        def local(_):
            bq, bk = _resolve_blocks(s_loc, s_loc, DEFAULT_BLOCK_Q,
                                     DEFAULT_BLOCK_K)
            return _flash_bwd(qT, kT, vT, outT, lseT, doT, scale, True,
                              bq, bk, delta=deltaT)

        def later(_):
            bq, bk = _resolve_blocks(c, s_loc, DEFAULT_BLOCK_Q,
                                     DEFAULT_BLOCK_K)
            dq_h, dk_b, dv_b = _flash_bwd(
                qT[:, :, c:], kT, vT, outT[:, :, c:], lseT[:, :, c:],
                doT[:, :, c:], scale, False, bq, bk,
                delta=deltaT[:, :, c:])
            dq_p = jnp.concatenate([jnp.zeros_like(dq_h), dq_h], axis=2)
            return dq_p, dk_b, dv_b

        return jax.lax.switch(_ring_case(kv_idx, idx),
                              [earlier, local, later], None)

    def body(carry, t):
        k_cur, v_cur, dk_t, dv_t, kv_idx, dq_acc = carry
        dq_p, dk_b, dv_b = hop_bwd(k_cur, v_cur, kv_idx)
        dq_acc = dq_acc + jnp.swapaxes(dq_p, 1, 2).astype(jnp.float32)
        dk_t = dk_t + jnp.swapaxes(dk_b, 1, 2).astype(jnp.float32)
        dv_t = dv_t + jnp.swapaxes(dv_b, 1, 2).astype(jnp.float32)
        dk_nxt = jax.lax.ppermute(dk_t, axis_name, perm)
        dv_nxt = jax.lax.ppermute(dv_t, axis_name, perm)
        k_nxt, v_nxt = jax.lax.cond(
            t < n - 1,
            lambda kv: (jax.lax.ppermute(kv[0], axis_name, perm),
                        jax.lax.ppermute(kv[1], axis_name, perm)),
            lambda kv: kv, (k_cur, v_cur))
        return (k_nxt, v_nxt, dk_nxt, dv_nxt, (kv_idx - 1) % n,
                dq_acc), None

    carry0 = (k, v, jnp.zeros(k.shape, jnp.float32),
              jnp.zeros(v.shape, jnp.float32), idx,
              jnp.zeros(q.shape, jnp.float32))
    (_, _, dk_f, dv_f, _, dq_f), _ = jax.lax.scan(body, carry0,
                                                  jnp.arange(n))
    return (dq_f.astype(q.dtype), dk_f.astype(k.dtype),
            dv_f.astype(v.dtype))


_zigzag_ring_attention_flash.defvjp(_zigzag_flash_vjp_fwd,
                                    _zigzag_flash_vjp_bwd)


def ring_attention(q, k, v, axis_name: str = "sep", causal: bool = False,
                   scale: Optional[float] = None,
                   use_flash: Optional[bool] = None,
                   layout: str = "contiguous"):
    """Blockwise ring attention inside shard_map.

    q,k,v: [B, S_local, H, D] — the local sequence shard. Rotates K/V
    around ``axis_name`` with ppermute; one hop per step overlaps with the
    block matmuls (XLA schedules the permute concurrently). On TPU each
    hop runs the Pallas flash kernel with a logsumexp block merge
    (``use_flash=None`` auto-detects; the jnp online-softmax path remains
    for CPU/unsupported shapes).

    ``layout="zigzag"`` (causal only): shards are in the zigzag order of
    ``zigzag_permutation`` — balanced causal schedule, every hop does
    equal work.
    """
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown ring layout {layout!r}")
    zigzag = layout == "zigzag" and causal
    if use_flash is None:
        from ..ops.pallas.flash_attention import flash_attention_supported
        use_flash = flash_attention_supported(q.shape, k.shape)
        if zigzag and use_flash:
            # zigzag hops dispatch HALF-chunk kernels (q x k[:c] etc.):
            # the half length must itself block-align or the jnp path
            # takes over (e.g. S_local=384: 384 is a 128-multiple but
            # 192 is not)
            c = q.shape[1] // 2
            half = (q.shape[0], c, *q.shape[2:])
            use_flash = (q.shape[1] % 2 == 0 and
                         flash_attention_supported(half, half))
    if use_flash:
        scale_f = float(scale if scale is not None
                        else 1.0 / np.sqrt(q.shape[-1]))
        if zigzag:
            return _zigzag_ring_attention_flash(q, k, v, axis_name,
                                                scale_f)
        return _ring_attention_flash(q, k, v, axis_name, causal, scale_f)
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    perm = [(i, (i + 1) % n) for i in range(n)]

    if zigzag:
        q_pos = zigzag_positions(idx, n, s_loc)
        pos_of = lambda kv_index: zigzag_positions(kv_index, n, s_loc)  # noqa: E731
    else:
        q_pos = idx * s_loc + jnp.arange(s_loc)  # global positions
        pos_of = lambda kv_index: kv_index * s_loc + jnp.arange(s_loc)  # noqa: E731

    def causal_mask_for(kv_index):
        k_pos = pos_of(kv_index)
        return (q_pos[:, None] >= k_pos[None, :])[None, None]  # [1,1,Sq,Sk]

    def body(carry, t):
        k_cur, v_cur, kv_idx, acc, m_run, l_run = carry
        mask = causal_mask_for(kv_idx) if causal else None
        out_b, m_b, l_b = _block_attn(q, k_cur, v_cur, scale, mask)
        # online softmax merge
        m_new = jnp.maximum(m_run, m_b)
        alpha = jnp.exp(m_run - m_new)
        beta = jnp.exp(m_b - m_new)
        l_new = l_run * alpha + l_b * beta
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + \
            out_b * beta.transpose(0, 2, 1)[..., None]
        # rotate kv to the next device (the final hop's rotation feeds
        # nobody; t is uniform so the cond's collectives agree)
        k_nxt, v_nxt = jax.lax.cond(
            t < n - 1,
            lambda kv: (jax.lax.ppermute(kv[0], axis_name, perm),
                        jax.lax.ppermute(kv[1], axis_name, perm)),
            lambda kv: kv, (k_cur, v_cur))
        kv_nxt = (kv_idx - 1) % n
        return (k_nxt, v_nxt, kv_nxt, acc, m_new, l_new), None

    acc0 = jnp.zeros((b, s_loc, h, d), jnp.float32)
    m0 = jnp.full((b, h, s_loc), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    carry0 = (k, v, idx, acc0, m0, l0)
    (kf, vf, _, acc, m_run, l_run), _ = jax.lax.scan(
        body, carry0, jnp.arange(n))
    denom = jnp.maximum(l_run, 1e-20).transpose(0, 2, 1)[..., None]
    return (acc / denom).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str = "sep",
                      causal: bool = False,
                      scale: Optional[float] = None,
                      attn_fn=None):
    """Ulysses: alltoall seq<->head re-shard inside shard_map.

    q,k,v: [B, S_local, H, D] with H divisible by the axis size. After the
    exchange each device holds [B, S_full, H/N, D] and runs ordinary
    (flash) attention, then exchanges back.
    """
    n = jax.lax.axis_size(axis_name)

    def seq_to_head(x):
        # [B, S/N, H, D] -> [B, S, H/N, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def head_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qf, kf, vf = seq_to_head(q), seq_to_head(k), seq_to_head(v)
    if attn_fn is None:
        from ..ops.nn_functional import scaled_dot_product_attention
        out = scaled_dot_product_attention(qf, kf, vf, is_causal=causal,
                                           scale=scale, dropout_p=0.0)
    else:
        out = attn_fn(qf, kf, vf)
    return head_to_seq(out)


def ring_schedule_work(n: int, layout: str = "contiguous"):
    """Analytic causal-ring work profile: work[t][i] = half-chunk-pair
    units device i computes at hop t (full shard-pair = 4 units, local
    causal = 2, masked = 0; zigzag hops = 2 by construction). The
    lockstep scan's step time is max over i per hop; summing the maxes
    gives the schedule's critical path — the measurement behind the
    contiguous layout's ~2x causal imbalance and the zigzag fix.
    Mirrors the hop case structure of ring_attention exactly."""
    work = []
    for t in range(n):
        row = []
        for i in range(n):
            kv = (i - t) % n
            if layout == "zigzag":
                row.append(2)
            elif kv < i:
                row.append(4)
            elif kv == i:
                row.append(2)
            else:
                row.append(0)
        work.append(row)
    return work


def _axis_bound(axis_name: str) -> bool:
    try:
        jax.lax.axis_size(axis_name)
        return True
    except NameError:
        return False


def sequence_parallel_attention(q, k, v, mode: str = "ring",
                                axis_name: str = "sep",
                                causal: bool = False):
    """Three calling contexts, one entry point:

    - inside shard_map with ``axis_name`` bound: run the sharded
      algorithm directly (the op-level usage);
    - under jit with a live hybrid mesh whose sep degree > 1: enter a
      shard_map region here, sharding batch over (dp, sharding) and
      sequence over sep — this is what the model-level
      ``seq_parallel_mode`` config reaches through GSPMD-jitted steps;
    - anywhere else (eager single device, sep degree 1): dense
      attention fallback with identical semantics.
    """
    if _axis_bound(axis_name):
        if mode == "ring":
            return ring_attention(q, k, v, axis_name, causal)
        if mode == "zigzag":
            return ring_attention(q, k, v, axis_name, causal,
                                  layout="zigzag")
        return ulysses_attention(q, k, v, axis_name, causal)

    from .topology import get_hybrid_communicate_group
    hcg = get_hybrid_communicate_group()
    from jax._src import core as _jax_core
    in_trace = not _jax_core.trace_state_clean()
    dims = dict(hcg.mesh.shape) if hcg is not None else {}
    sep = dims.get(axis_name, 1)
    if hcg is not None and in_trace and sep > 1:
        mp = dims.get("mp", 1)
        if q.shape[1] % sep:
            raise ValueError(
                f"sequence length {q.shape[1]} must divide the sep "
                f"degree {sep} for seq_parallel_mode")
        if mp > 1 and q.shape[2] % mp:
            raise ValueError(
                f"num_heads {q.shape[2]} must be divisible by the mp "
                f"degree {mp}")
        local_heads = q.shape[2] // mp
        if mode == "ulysses" and local_heads % sep:
            raise ValueError(
                "ulysses redistributes heads over sep: per-mp-shard "
                f"heads {local_heads} must be divisible by the sep "
                f"degree {sep}")
        from jax import shard_map
        head_axis = "mp" if mp > 1 else None

        def sharded(qq, kk, vv):
            # ring rotates K/V over sep; heads are a pure batch dim, so
            # an mp head-shard composes for free. Ulysses exchanges its
            # (mp-local) head shard against the sequence shard.
            if mode == "ring":
                return ring_attention(qq, kk, vv, axis_name, causal)
            if mode == "zigzag":
                # the caller (models.gpt boundary permutation) already
                # laid the sequence out in zigzag order, so contiguous
                # sep-sharding hands each device its zigzag shard
                return ring_attention(qq, kk, vv, axis_name, causal,
                                      layout="zigzag")
            return ulysses_attention(qq, kk, vv, axis_name, causal)

        try:
            manual = set(jax.sharding.get_abstract_mesh().manual_axes)
        except Exception:
            manual = set()
        if manual:
            # already inside a manual region (the pipeline's shard_map
            # over "pp"): nest a partial-manual shard_map over sep (+mp,
            # + the batch axes) on the CONTEXT abstract mesh (pp stays
            # manual outside). The batch axes join the manual set because
            # a Pallas (flash) hop requires every mesh axis around it to
            # be manual — attention is purely data-parallel in batch, so
            # the split is semantically free.
            amesh = jax.sharding.get_abstract_mesh()
            # manual over EVERY remaining axis (degree-1 ones are free):
            # Mosaic refuses to lower a Pallas call inside any auto-axis
            # context. The batch dim stays OUT of the specs (replicated
            # along dp/sharding in the manual region): marking an axis
            # manual does not require splitting data over it, and a
            # batch split would add a new divisibility precondition on
            # the per-stage microbatch.
            names = set(amesh.axis_names) - set(amesh.manual_axes)
            spec = P(None, axis_name, head_axis)
            return shard_map(sharded, mesh=amesh,
                             in_specs=spec, out_specs=spec,
                             check_vma=False,
                             axis_names=frozenset(names))(q, k, v)
        batch_axes = tuple(a for a in ("dp", "sharding")
                           if dims.get(a, 1) > 1) or None
        spec = P(batch_axes, axis_name, head_axis)
        return shard_map(sharded, mesh=hcg.mesh, in_specs=spec,
                         out_specs=spec, check_vma=False)(q, k, v)

    from ..ops.nn_functional import scaled_dot_product_attention
    if mode == "zigzag" and sep > 1:
        # The caller (models.gpt) hands zigzag-ordered tensors whenever
        # sep > 1; the dense fallback (eager path) must un-permute
        # before masking causally and re-permute the result, or the
        # row>=col mask would apply to reordered tokens.
        perm, inv = zigzag_permutation(q.shape[1], sep)
        out = scaled_dot_product_attention(
            q[:, inv], k[:, inv], v[:, inv], is_causal=causal,
            use_flash=False)
        return out[:, perm]
    return scaled_dot_product_attention(q, k, v, is_causal=causal,
                                        use_flash=False)
