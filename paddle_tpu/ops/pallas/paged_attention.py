"""Ragged paged-attention decode kernel over a block-paged KV pool.

The decode-shaped attention kernel the r5 verdict asked for (weak #1 /
top_next): a pre-round decode trace put b128 GPT-1.3B decode at 13.1
ms/step against an 8.0 ms weights+KV streaming floor, with the KV
prefix (the "loop fusion" category: 5.5 GB/step at 641 GB/s) dominating
— the dense `StaticKVCache` pays full-prefix bandwidth for EVERY
sequence in the batch regardless of its real length. Paper basis:
*Ragged Paged Attention: A High-Performance and Flexible LLM Inference
Kernel for TPU* (PAPERS.md) — KV lives in fixed-size pages indexed by a
per-sequence page table, and the kernel walks only the pages a
sequence actually owns, so a ragged mixed-length batch streams
sum(len_i) tokens of KV instead of B * max(len_i).

Design (house style: lane-native layout, online softmax, ragged skip):

- KV pool: ``[num_pages, page_size, H, D]`` — one page is a contiguous
  block, so the per-page DMA is a single copy in the pool's own tiling
  (heads on sublanes, D on lanes); the kernel computes in that tiling,
  never via a materialized transpose or an in-VMEM relayout of a page.
- Page table: ``[B, max_pages]`` int32 + ``seq_lens [B]`` int32, fed
  through `PrefetchScalarGridSpec` scalar prefetch so the kernel can
  compute page addresses before the grid body runs.
- Grid ``(B,)``; per sequence the kernel walks ``ceil(len/page)``
  pages with a double-buffered async copy HBM->VMEM and an online
  softmax (m, l, acc) carry — pages past the ragged length are never
  fetched, which is the entire bandwidth win. Decode has ONE query row
  per head, so QK^T and PV are matrix-vector products on the VPU
  (multiply + reduce), not MXU dots.
- int8 KV: pages may be int8 with a per-(page, position, head) abs-max
  scale (layout ``[num_pages, page_size, H]``, quantization/quant.py
  convention ``deq = q * s / 127``); the int8 page is widened on its
  VMEM copy so HBM page traffic is halved, and the scale factors are
  applied to the scores (they leave the D sum). The scale rows are not
  DMA'd per page — see `_gather_scales`.

- Fused epilogue (`paged_attention_fused`, the GPT decode step's op):
  the same page walk a slot, then ONE output projection a call — each
  grid step leaves its context row in a VMEM scratch laid out by head,
  the last grid step pushes all slots' rows through the o-projection
  weight in one pass, and the weight reaches VMEM by one async copy
  that runs under the walks (`_decode_fused_kernel`). What a call costs,
  fixed and per page, is `tools/paged_decode_report.py`'s reading
  (PERF.md §6).

A pure-JAX reference (`paged_attention_reference`) implements identical
semantics by gathering pages densely — the CPU fast lane and the
numeric tests run it, and the public entry `paged_attention` routes to
it wherever the Mosaic kernel can't run, so both lanes share one
contract (the "CanBeUsed" runtime-selection pattern of
`folded_attention.folded_attention_supported`).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from .naming import named_pallas_call

_NEG_INF = -1e30

# Decode pages are streamed once and never revisited, so the page size
# only has to amortize DMA issue overhead; 64 rows x E lanes keeps the
# double-buffered live set (2 pages x K+V) under ~1 MB of VMEM at
# E=2048 bf16 while giving the allocator fine-grained recycling.
DEFAULT_PAGE_SIZE = 64


def _col(x):
    """[1, H] (heads on lanes) -> [H, 1] (heads on sublanes)."""
    return x[..., None][0]


def _pad_to_sublane_tile(rows: int, dtype) -> int:
    """``rows`` rounded up to whole sublane tiles of ``dtype`` (8 rows
    of 32 bits, 16 of bf16, 32 of int8)."""
    tile = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    return -(-rows // tile) * tile


# --------------------------------------------------------------------------
# Pallas kernel (TPU): ragged page walk, double-buffered DMA
# --------------------------------------------------------------------------

def _walk_pages(pt_ref, len_ref, q_ref, kp_ref, vp_ref, ks_ref, vs_ref,
                k_buf, v_buf, sems, *,
                page: int, scale: float, quantized: bool):
    """Shared ragged page walk: one grid step = one sequence; walks its
    pages with double-buffered DMA and an online softmax, returning the
    NORMALIZED per-head context [H, D] fp32 (the `_decode_kernel` body,
    factored out so the fused-epilogue kernel reuses the exact same
    arithmetic — the bit-identical-per-head property both lean on).

    One query row per head makes QK^T and PV matrix-VECTOR products, so
    both run on the VPU in the pool's own [page, H, D] tiling (heads on
    sublanes, D on lanes): multiply and reduce. Mosaic takes no
    dot_general whose lhs has only batch and contracting dims, and
    regrouping a page to [H, page, D] for the MXU would relayout every
    page. Scores live as [page, H] (heads on lanes).

    Scratch: ``k_buf``/``v_buf`` [2, page, H, D] double buffers;
    ``sems`` [2, 2] DMA semaphores (k, v) x (slot0, slot1). int8 page
    scales are not walked: ``ks_ref``/``vs_ref`` are this sequence's
    [1, max_pages, H, page] block, already in VMEM (see
    `_gather_scales`)."""
    b = pl.program_id(0)
    seq_len = len_ref[b]
    n_pages = pl.cdiv(seq_len, page)
    q = q_ref[0].astype(jnp.float32)  # [H, D]
    h, d = q.shape

    def copies(i, slot):
        idx = pt_ref[b, i]
        return [pltpu.make_async_copy(kp_ref.at[idx], k_buf.at[slot],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(vp_ref.at[idx], v_buf.at[slot],
                                      sems.at[1, slot])]

    @pl.when(n_pages > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    def body(i, carry):
        m, l, acc = carry  # noqa: E741
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_pages)
        def _():
            for c in copies(i + 1, jax.lax.rem(i + 1, 2)):
                c.start()

        for c in copies(i, slot):
            c.wait()
        k = k_buf[slot].astype(jnp.float32)  # [page, H, D]
        v = v_buf[slot].astype(jnp.float32)
        # scores[p, h] = q[h, :] . k[p, h, :]
        s = jnp.sum(k * q[None], axis=2)  # [page, H]
        if quantized:
            # quant.py convention deq = q * scale / 127, per (row,
            # head): the factor leaves the D sum
            s = s * (ks_ref[0, i].astype(jnp.float32).T / 127.0)
        s = s * scale
        kpos = i * page + jax.lax.broadcasted_iota(jnp.int32, (page, 1), 0)
        s = jnp.where(kpos < seq_len, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=0, keepdims=True)  # noqa: E741
        if quantized:
            p = p * (vs_ref[0, i].astype(jnp.float32).T / 127.0)
        acc = acc * _col(alpha) + jnp.sum(p[..., None] * v, axis=0)
        return m_new, l, acc

    m0 = jnp.full((1, h), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((1, h), jnp.float32)
    a0 = jnp.zeros((h, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, a0))
    # empty sequences (len 0) produce defined zeros, not NaN — the
    # continuous-batching engine parks inactive slots at len 0
    return acc / _col(jnp.maximum(l, 1e-30))


def _decode_kernel(pt_ref, len_ref, q_ref, kp_ref, vp_ref, ks_ref, vs_ref,
                   o_ref, k_buf, v_buf, sems, *,
                   page: int, scale: float, quantized: bool):
    """Raw per-head context output (the pre-r13 kernel contract)."""
    ctx = _walk_pages(pt_ref, len_ref, q_ref, kp_ref, vp_ref, ks_ref,
                      vs_ref, k_buf, v_buf, sems,
                      page=page, scale=scale, quantized=quantized)
    o_ref[0] = ctx.astype(o_ref.dtype)


def _decode_fused_kernel(pt_ref, len_ref, q_ref, kp_ref, vp_ref, ks_ref,
                         vs_ref, w_hbm, b_ref, o_ref, k_buf, v_buf, sems,
                         ctx_buf, w_buf, w_sem, *, page: int, scale: float,
                         quantized: bool, has_bias: bool):
    """Fused attention epilogue: the softmax-normalized per-head context
    never leaves VMEM — the kernel emits the attention BLOCK's output
    rows (head-concat, output projection, bias) instead of raw per-head
    context. One launch where the unfused path runs attention + reshape
    + matmul + bias-add.

    The projection runs ONCE A CALL, not once a slot. Every grid step
    walks its slot's pages and leaves the context row in ``ctx_buf``
    ``[H, Bp, D]`` (head ``hh`` of all slots is one ``[Bp, D]`` tile, so
    nothing moves heads from sublanes to lanes); the last grid step
    pushes all slots' rows through the weight in one pass, one
    ``[Bp, D] @ [D, E_out]`` dot a head, and writes the one
    ``[B, E_out]`` output block that stays resident over the grid. The
    weight ``w_hbm`` [E, E_out] stays in HBM as an operand; one async
    copy into ``w_buf`` starts in the first grid step and is waited for
    just before the projection, so it passes under the page walks."""
    i = pl.program_id(0)
    w_copy = pltpu.make_async_copy(w_hbm, w_buf, w_sem.at[0])

    @pl.when(i == 0)
    def _():
        w_copy.start()

    ctx = _walk_pages(pt_ref, len_ref, q_ref, kp_ref, vp_ref, ks_ref,
                      vs_ref, k_buf, v_buf, sems,
                      page=page, scale=scale, quantized=quantized)
    h, d = ctx.shape
    bp = ctx_buf.shape[1]
    # mimic the unfused lowering's rounding: the standalone kernel
    # rounds the context to the output dtype (bf16 in bf16 serving)
    # BEFORE the model's out-projection matmul, whose MXU dot then
    # accumulates in f32 — round here the same way so fused-vs-unfused
    # on-chip divergence is limited to XLA tiling, not operand
    # precision. (Exact on-chip bit-identity is NOT claimed — see
    # `paged_attention_fused`; the CPU-lane references are bit-equal.)
    # The scratch keeps the rounded values as f32 (exact), so a row is
    # placed with a 32-bit select and no packed sublane is addressed.
    ctx = ctx.astype(o_ref.dtype).astype(jnp.float32)
    mine = jax.lax.broadcasted_iota(jnp.int32, (h, bp, d), 1) == i
    ctx_buf[...] = jnp.where(mine, ctx[:, None, :], ctx_buf[...])

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        w_copy.wait()
        # rows @ W with a row = ctx flattened head-major (the [H*D]
        # order the model's reshape produces), as one dot per head over
        # that head's D weight rows. Rows past B are never written and
        # never read back: a row of a matmul depends on no other row.
        rows = ctx_buf[...].astype(o_ref.dtype)
        out = jnp.zeros((bp, o_ref.shape[-1]), jnp.float32)
        for hh in range(h):
            out = out + jax.lax.dot_general(
                rows[hh], w_buf[hh * d:(hh + 1) * d, :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [Bp, E_out]
        if has_bias:
            out = out + b_ref[...].astype(jnp.float32)
        o_ref[...] = out[:o_ref.shape[0]].astype(o_ref.dtype)


def _gather_scales(scales, page_table):
    """int8 page scales for the kernel: each sequence's own pages,
    [B, max_pages, H, page]. The pool keeps them [P, page, H], whose
    16-wide minor dim Mosaic cannot slice for a per-page DMA (slices
    must be lane-aligned), so XLA gathers the batch's rows once per
    call and the kernel reads them through an ordinary VMEM block; page
    rows go last to keep that block's lane padding at 2x."""
    return jnp.swapaxes(scales[page_table], 2, 3)


def _decode_call(name, kernel, q, k_pages, v_pages, page_table, seq_lens,
                 k_scale, v_scale, *, out_shape, out_spec, extra=(),
                 extra_scratch=(), extra_flops=0, extra_bytes=0):
    """The pallas_call both decode kernels share, under the kernel's
    ``name``: grid (B,), page table and lengths scalar-prefetched, KV
    pools left in HBM. ``extra``: (array, BlockSpec) pairs appended to
    the kernel's inputs; ``extra_scratch``: scratch shapes appended to
    the page walk's."""
    b, h, d = q.shape
    n_pool, page = k_pages.shape[:2]
    mp = page_table.shape[1]
    if k_scale is not None:
        ks = _gather_scales(k_scale, page_table)
        vs = _gather_scales(v_scale, page_table)
        s_spec = pl.BlockSpec((1, mp, h, page), lambda i, *_: (i, 0, 0, 0),
                              memory_space=pltpu.VMEM)
    else:
        ks = vs = jnp.zeros((1, 1), jnp.float32)
        s_spec = pl.BlockSpec((1, 1), lambda i, *_: (0, 0),
                              memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda i, *_: (i, 0, 0),
                         memory_space=pltpu.VMEM),     # q
            pl.BlockSpec(memory_space=pl.ANY),         # k pages (HBM)
            pl.BlockSpec(memory_space=pl.ANY),         # v pages (HBM)
            s_spec,                                    # k scales
            s_spec,                                    # v scales
            *(spec for _, spec in extra),
        ],
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((2, page, h, d), k_pages.dtype),
            pltpu.VMEM((2, page, h, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            *extra_scratch,
        ],
    )
    return named_pallas_call(
        name, kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        cost_estimate=pl.CostEstimate(
            # ragged: the average sequence reads its own prefix once
            flops=4 * int(b) * h * page * d * mp + extra_flops,
            bytes_accessed=(2 * n_pool * page * h * d
                            * k_pages.dtype.itemsize + extra_bytes),
            transcendentals=b * h * page * mp),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(page_table, seq_lens, q, k_pages, v_pages, ks, vs,
      *(arg for arg, _ in extra))


def _paged_decode_pallas(q, k_pages, v_pages, page_table, seq_lens,
                         k_scale, v_scale, scale):
    b, h, d = q.shape
    return _decode_call(
        "paged_decode",
        functools.partial(_decode_kernel, page=k_pages.shape[1],
                          scale=scale, quantized=k_scale is not None),
        q, k_pages, v_pages, page_table, seq_lens, k_scale, v_scale,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        out_spec=pl.BlockSpec((1, h, d), lambda i, *_: (i, 0, 0),
                              memory_space=pltpu.VMEM))


def _paged_decode_fused_pallas(q, k_pages, v_pages, page_table, seq_lens,
                               k_scale, v_scale, scale, w, bias):
    """Fused-epilogue variant of :func:`_paged_decode_pallas`: the same
    grid and page-walk scratch, plus the projection weight as an HBM
    operand with a VMEM scratch of its size (one copy a call, started
    in the first grid step: `_decode_fused_kernel`), the contexts'
    scratch ``[H, Bp, D]`` (B padded to a sublane tile of the output
    dtype) and one ``[B, E_out]`` output block for the whole grid."""
    b, h, d = q.shape
    e_out = w.shape[1]
    has_bias = bias is not None
    brow = (bias.reshape(1, e_out) if has_bias
            else jnp.zeros((1, e_out), jnp.float32))
    bp = _pad_to_sublane_tile(b, q.dtype)
    return _decode_call(
        "paged_decode_fused",
        functools.partial(_decode_fused_kernel, page=k_pages.shape[1],
                          scale=scale, quantized=k_scale is not None,
                          has_bias=has_bias),
        q, k_pages, v_pages, page_table, seq_lens, k_scale, v_scale,
        extra=[
            (w, pl.BlockSpec(memory_space=pl.ANY)),
            (brow, pl.BlockSpec((1, e_out), lambda i, *_: (0, 0),
                                memory_space=pltpu.VMEM))],
        extra_scratch=[
            pltpu.VMEM((h, bp, d), jnp.float32),
            pltpu.VMEM((h * d, e_out), w.dtype),
            pltpu.SemaphoreType.DMA((1,))],
        out_shape=jax.ShapeDtypeStruct((b, e_out), q.dtype),
        out_spec=pl.BlockSpec((b, e_out), lambda i, *_: (0, 0),
                              memory_space=pltpu.VMEM),
        extra_flops=2 * int(b) * h * d * e_out,
        extra_bytes=h * d * e_out * w.dtype.itemsize)


# --------------------------------------------------------------------------
# Pure-JAX reference (CPU fast lane / semantics contract)
# --------------------------------------------------------------------------

def paged_attention_reference(q, k_pages, v_pages, page_table, seq_lens,
                              k_scale=None, v_scale=None,
                              scale: Optional[float] = None,
                              q_offsets=None):
    """Dense-gather reference with identical semantics to the kernel.

    ``q``: [B, Sq, H, D] — query tokens are the LAST Sq positions of
    each sequence unless ``q_offsets`` ([B], absolute position of the
    first query token) overrides it (the ragged-prefill case, where a
    right-padded chunk's true length is shorter than Sq). Positions at
    or beyond ``seq_lens`` are masked; fully-masked rows return zeros
    (not NaN), so empty slots in a fixed-slot batch stay inert.

    Exists for semantics, not bandwidth: the gather materializes the
    padded [B, max_pages*page, H, D] KV — the kernel never does."""
    b, sq, h, d = q.shape
    page = k_pages.shape[1]
    mp = page_table.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    def gather(pages, scales):
        g = pages[page_table]  # [B, mp, page, H, D]
        if scales is not None:
            from ...quantization.quant import dequantize_kv
            g = dequantize_kv(g, scales[page_table], jnp.float32)
        else:
            g = g.astype(jnp.float32)
        return g.reshape(b, mp * page, h, d)

    k = gather(k_pages, k_scale)
    v = gather(v_pages, v_scale)
    qf = q.astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, k,
                        preferred_element_type=jnp.float32) * scale
    if q_offsets is None:
        q_offsets = seq_lens - sq
    kpos = jnp.arange(mp * page, dtype=jnp.int32)
    qpos = q_offsets[:, None] + jnp.arange(sq, dtype=jnp.int32)[None]
    mask = kpos[None, None, :] <= qpos[:, :, None]  # [B, Sq, T]
    logits = jnp.where(mask[:, None], logits, _NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)  # noqa: E741
    out = jnp.einsum("bhqk,bkhd->bqhd", p / jnp.maximum(l, 1e-30), v)
    any_valid = mask.any(-1)  # [B, Sq]
    out = jnp.where(any_valid[..., None, None], out, 0.0)
    return out.astype(q.dtype)


# --------------------------------------------------------------------------
# Grouped heads and a lower bound on the keys (MXU page walk)
# --------------------------------------------------------------------------
#
# Query heads that share KV heads (KV head = query head // group) and
# keys bounded below (a window layer: keys before ``kv_start`` are not
# seen). With a group of G > 1 query rows a KV head, QK^T and PV are
# [G, D] x [page, D] products and go to the MXU, which wants each KV
# head's page as a [page, D] tile: these pools are HEADS-MAJOR inside a
# page, ``[P, KVH, page, D]`` (models/cache_layout.py ``heads_major``),
# so a page is still one DMA, which lays KV head ``j``'s rows under
# ``buf[j]``, no relayout. (A ``[P, page, KVH, D]`` pool with KVH = 4
# is tiled (4, 128): regrouping it costs a copy of the pool a call.)
# Pages are fetched a block at a time, as the latent walk's are, and
# the next sequence's first block during this one's last: fetched one
# 128 KB page (K and V) a loop turn the walk read 251 GB/s of 819 at 4
# KV heads and 293 at 8, and a live slot cost 0.5-0.7 us; blocks of 2
# MB read 757 at both, and a slot's first block no longer waits in the
# open (1.3-2.8 us a live slot without the look-ahead, about 0 with it;
# tools/grouped_decode_report.py, PERF.md section 5, PR 37).

GROUPED_BLOCK_PAGES = 16
GROUPED_BLOCK_BYTES = 2 << 20


def grouped_block_pages(kvh: int, page: int, d: int, itemsize: int,
                        table_width: int) -> int:
    """Pages a turn of the grouped walk: as many as hold
    ``GROUPED_BLOCK_BYTES`` of K and V (16 pages of 4 KV heads, 8 of 8:
    two such buffers are the walk's VMEM), at most 16 and at most a
    quarter of the table (a sequence's last, partly filled block costs
    a whole one's products)."""
    page_bytes = 2 * kvh * page * d * itemsize
    return max(1, min(GROUPED_BLOCK_PAGES, table_width // 4,
                      GROUPED_BLOCK_BYTES // page_bytes))


def _decode_grouped_kernel(pt_ref, len_ref, lo_ref, q_ref, kp_ref, vp_ref,
                           o_ref, k_buf, v_buf, sems, turn, *,
                           page: int, scale: float, kvh: int, block: int):
    """One grid step = one sequence. ``q_ref`` is ``[1, KVH, Gp, D]``:
    the group padded to a sublane tile with zero rows, whose outputs
    the caller drops. The walk starts at the page that holds
    ``kv_start`` and masks inside it; pages behind the bound are never
    fetched. A loop turn takes a block of ``block`` pages (their copies
    in flight together) into one buffer ``[KVH, block * page, D]`` and
    runs one pair of products a KV head over its rows. What a block's
    copies do not fill keeps older rows, which the mask hides; the
    buffers are zeroed once a call so that nothing read from them was
    never written. A parked slot (length 0) walks nothing and returns
    zeros.

    The next sequence's first block is started during this one's last
    turn, so only the first sequence of a call waits for its first
    block in the open. ``turn`` (SMEM) carries across grid steps the
    count of blocks walked so far (a block's buffer is that count's
    parity) and whether this step's first block is already in flight."""
    b = pl.program_id(0)
    n_seq = pt_ref.shape[0]
    gp, d = q_ref.shape[2], q_ref.shape[3]
    rows = block * page

    def walk(s):
        first = lo_ref[s] // page
        n_pages = pl.cdiv(len_ref[s], page)
        return first, n_pages, pl.cdiv(jnp.maximum(n_pages - first, 0), block)

    def each_copy(s, i, slot, fn):
        """``fn`` on the K and V copies of block ``i`` of sequence ``s``
        into buffer ``slot``: its pages up to the sequence's last."""
        first, n_pages, _ = walk(s)
        start = first + i * block

        def one(j, carry):
            idx = pt_ref[s, start + j]
            dst = (slot, slice(None),
                   pl.ds(pl.multiple_of(j * page, page), page))
            fn(pltpu.make_async_copy(kp_ref.at[idx], k_buf.at[dst],
                                     sems.at[0, slot, j]))
            fn(pltpu.make_async_copy(vp_ref.at[idx], v_buf.at[dst],
                                     sems.at[1, slot, j]))
            return carry

        jax.lax.fori_loop(0, jnp.clip(n_pages - start, 0, block), one, 0)

    @pl.when(b == 0)
    def _():
        k_buf[...] = jnp.zeros(k_buf.shape, k_buf.dtype)
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
        turn[0] = 0
        turn[1] = 0

    seq_len = len_ref[b]
    lo = lo_ref[b]
    first, _, n_blocks = walk(b)
    base = turn[0]
    nxt = jnp.minimum(b + 1, n_seq - 1)
    ahead = (b + 1 < n_seq) & (walk(nxt)[2] > 0)

    @pl.when((n_blocks > 0) & (turn[1] == 0))
    def _():
        each_copy(b, 0, jax.lax.rem(base, 2), lambda c: c.start())

    def body(i, carry):
        slot = jax.lax.rem(base + i, 2)

        @pl.when(i + 1 < n_blocks)
        def _():
            each_copy(b, i + 1, 1 - slot, lambda c: c.start())

        @pl.when((i + 1 == n_blocks) & ahead)
        def _():
            each_copy(nxt, 0, 1 - slot, lambda c: c.start())

        each_copy(b, i, slot, lambda c: c.wait())
        kpos = (first * page + i * rows
                + jax.lax.broadcasted_iota(jnp.int32, (gp, rows), 1))
        seen = (kpos < seq_len) & (kpos >= lo)
        out = []
        for j in range(kvh):
            m, l, acc = carry[j]  # noqa: E741
            s = jax.lax.dot_general(
                q_ref[0, j], k_buf[slot, j], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [Gp, rows]
            s = jnp.where(seen, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)  # noqa: E741
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(v_buf.dtype), v_buf[slot, j],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [Gp, D]
            out.append((m_new, l, acc))
        return tuple(out)

    init = tuple((jnp.full((gp, 1), _NEG_INF, jnp.float32),
                  jnp.zeros((gp, 1), jnp.float32),
                  jnp.zeros((gp, d), jnp.float32)) for _ in range(kvh))
    final = jax.lax.fori_loop(0, n_blocks, body, init)
    turn[0] = base + n_blocks
    turn[1] = ((n_blocks > 0) & ahead).astype(jnp.int32)
    for j in range(kvh):
        _, l, acc = final[j]  # noqa: E741
        o_ref[0, j] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_decode_grouped_pallas(q, k_pages, v_pages, page_table, seq_lens,
                                 kv_start, scale, block):
    """q: [B, Hq, D]; pools [P, KVH, page, D]; returns [B, Hq, D]."""
    b, hq, d = q.shape
    kvh, page = k_pages.shape[1:3]
    group = hq // kvh
    gp = _pad_to_sublane_tile(group, k_pages.dtype)
    qg = q.reshape(b, kvh, group, d).astype(k_pages.dtype)
    if gp != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - group), (0, 0)))
    mp = page_table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, kvh, gp, d), lambda i, *_: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, kvh, gp, d), lambda i, *_: (i, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, kvh, block * page, d), k_pages.dtype),
            pltpu.VMEM((2, kvh, block * page, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2, block)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    out = named_pallas_call(
        "paged_decode_grouped",
        functools.partial(_decode_grouped_kernel, page=page, scale=scale,
                          kvh=kvh, block=block),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, gp, d), q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=4 * int(b) * hq * page * d * mp,
            bytes_accessed=(2 * int(b) * mp * page * kvh * d
                            * k_pages.dtype.itemsize),
            transcendentals=b * hq * page * mp),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(page_table, seq_lens, kv_start, qg, k_pages, v_pages)
    return out[:, :, :group].reshape(b, hq, d)


def paged_attention_grouped_reference(q, k_pages, v_pages, page_table,
                                      seq_lens, kv_start=None,
                                      scale: Optional[float] = None):
    """Dense-gather reference of :func:`paged_attention_grouped`: one
    query token a sequence (the LAST position), heads-major pools."""
    b, sq, h, d = q.shape
    kvh, page = k_pages.shape[1:3]
    mp = page_table.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    def gather(pages):
        g = pages[page_table].astype(jnp.float32)  # [B, mp, KVH, page, D]
        return jnp.swapaxes(g, 2, 3).reshape(b, mp * page, kvh, d)

    k, v = gather(k_pages), gather(v_pages)
    qf = q.astype(jnp.float32).reshape(b, sq, kvh, h // kvh, d)
    logits = jnp.einsum("bqjgd,bkjd->bjgqk", qf, k) * scale
    kpos = jnp.arange(mp * page, dtype=jnp.int32)[None]
    seen = kpos < seq_lens[:, None]
    if kv_start is not None:
        seen = seen & (kpos >= kv_start[:, None])
    logits = jnp.where(seen[:, None, None, None], logits, _NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.where(seen[:, None, None, None], jnp.exp(logits - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)  # noqa: E741
    out = jnp.einsum("bjgqk,bkjd->bqjgd", p / jnp.maximum(l, 1e-30), v)
    return out.reshape(b, sq, h, d).astype(q.dtype)


def paged_grouped_supported(q_shape, kp_shape,
                            backend: Optional[str] = None) -> bool:
    """Gate for the grouped kernel: single-token decode, head size one
    lane tile, whole sublane tiles a page."""
    from .flash_attention import _FORCE_DEPTH
    if backend is None:
        backend = jax.default_backend()
    if backend != "tpu" and _FORCE_DEPTH == 0:
        return False
    b, sq, h, d = q_shape
    kvh, page = kp_shape[1:3]
    return sq == 1 and d == 128 and page % 16 == 0 and h % kvh == 0


def paged_attention_grouped(q, k_pages, v_pages, page_table, seq_lens,
                            kv_start=None, scale: Optional[float] = None):
    """Single-token paged attention with grouped heads over heads-major
    pools. q: [B, 1, H, D]; pools ``[P, KVH, page, D]`` with KVH
    dividing H (query head h reads KV head h // (H // KVH)); seq_lens
    [B] lengths INCLUDING the appended token; ``kv_start`` [B] hides
    the keys before it (a window layer passes ``len - window``, floored
    at 0). Returns [B, 1, H, D]. Not under head sharding."""
    if get_head_sharding() is not None:
        raise NotImplementedError("grouped heads under head sharding")
    b, sq, h, d = q.shape
    scale = float(1.0 / math.sqrt(d) if scale is None else scale)
    if paged_grouped_supported(q.shape, k_pages.shape):
        lo = jnp.zeros_like(seq_lens) if kv_start is None else kv_start
        kvh, page = k_pages.shape[1:3]
        block = grouped_block_pages(kvh, page, d, k_pages.dtype.itemsize,
                                    page_table.shape[1])
        out = _paged_decode_grouped_pallas(
            q.reshape(b, h, d), k_pages, v_pages,
            page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
            lo.astype(jnp.int32), scale, block)
        return out.reshape(b, sq, h, d)
    return paged_attention_grouped_reference(
        q, k_pages, v_pages, page_table, seq_lens, kv_start=kv_start,
        scale=scale)


# --------------------------------------------------------------------------
# Latent pages (multi-head latent attention, the absorbed form)
# --------------------------------------------------------------------------
#
# A latent layer's pool is ``[P, page, W]`` (models/cache_layout.py
# ``latent``): a position's row holds the normed latent ``c`` (``rank``
# values), the ONE rotated key all heads share (``rope`` values) and
# zeros up to ``W`` (whole lane tiles). In the absorbed form a head's
# query is ``[q_nope W_UK^T | q_rope]``, also ``rank + rope`` long, so
# every head scores against the page's rows as they lie, and the
# output is the probability-weighted sum of the SAME rows' first
# ``rank`` columns: a page is fetched once and is the keys and the
# values of all heads. ``H`` query rows against ``[page, W]``: both
# products go to the MXU. Pages are fetched ``block`` at a time into
# one buffer (their copies in flight together, one pair of products
# over ``block * page`` rows), because one page's 80 KB arrive faster
# than a loop iteration turns round: at 48 slots of 8,192 positions a
# call reads its required bytes at 35 % of the chip's bandwidth with 2
# pages a block, 52 % with 4, 69 % with 8, 75 % with 16 and no more
# with 32, while a sequence's last, partly filled block costs a whole
# one (0.5, 0.9, 1.3, 2.0 us a live slot; tools/latent_decode_report.py,
# PERF.md section 6, PR 34). So a block is 16 pages where sequences can
# be long, and an eighth of the table where they cannot.

LATENT_BLOCK_PAGES = 16


def _decode_latent_kernel(pt_ref, len_ref, q_ref, *refs, page: int,
                          scale: float, rank: int, block: int, masked: bool):
    """One grid step = one sequence; a parked slot (length 0) walks no
    page and returns zeros. ``q_ref`` is ``[1, Hp, W]``: the heads
    padded to a sublane tile with zero rows, whose outputs the caller
    drops. ``buf`` ``[2, block * page, W]``: what a block's copies do
    not fill (pages past the sequence's last) keeps older rows, which
    the mask hides; it is zeroed once a call so that nothing read from
    it was never written. ``masked``: ``refs`` begin with ``sel_ref``
    ``[1, 1, positions]`` int32, and only the positions it marks
    nonzero are attended to."""
    sel_ref, (pool_ref, o_ref, buf, sems) = \
        (refs[0], refs[1:]) if masked else (None, refs)
    b = pl.program_id(0)
    seq_len = len_ref[b]
    n_pages = pl.cdiv(seq_len, page)
    n_blocks = pl.cdiv(n_pages, block)
    mp = pt_ref.shape[1]
    hp = q_ref.shape[1]
    rows = block * page

    @pl.when(b == 0)
    def _():
        buf[...] = jnp.zeros(buf.shape, buf.dtype)

    def each_copy(i, slot, fn):
        for j in range(block):
            pg = i * block + j

            @pl.when(pg < n_pages)
            def _():
                fn(pltpu.make_async_copy(
                    pool_ref.at[pt_ref[b, jnp.minimum(pg, mp - 1)]],
                    buf.at[slot, pl.ds(j * page, page)],
                    sems.at[slot, j]))

    @pl.when(n_blocks > 0)
    def _():
        each_copy(0, 0, lambda c: c.start())

    def body(i, carry):
        m, l, acc = carry  # noqa: E741
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blocks)
        def _():
            each_copy(i + 1, 1 - slot, lambda c: c.start())

        each_copy(i, slot, lambda c: c.wait())
        s = jax.lax.dot_general(
            q_ref[0], buf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [Hp, rows]
        kpos = i * rows + jax.lax.broadcasted_iota(jnp.int32, (hp, rows), 1)
        seen = kpos < seq_len
        if masked:
            sel = sel_ref[0, :, pl.ds(pl.multiple_of(i * rows, rows), rows)]
            seen = seen & (sel > 0)
        s = jnp.where(seen, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)  # noqa: E741
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(buf.dtype), buf[slot, :, :rank],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [Hp, rank]
        return m_new, l, acc

    init = (jnp.full((hp, 1), _NEG_INF, jnp.float32),
            jnp.zeros((hp, 1), jnp.float32),
            jnp.zeros((hp, rank), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)  # noqa: E741
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_decode_latent_pallas(q, pages, page_table, seq_lens, rank, scale,
                                block, interpret=False, sel=None):
    """q: [B, H, <= W]; pages [P, page, W]; returns [B, H, rank]. With
    ``sel`` [B, >= table positions] the call is ``sparse_decode_latent``
    (ops/pallas/sparse_attention.py), without it ``paged_decode_latent``."""
    b, h, qw = q.shape
    page, w = pages.shape[1:]
    hp = _pad_to_sublane_tile(h, pages.dtype)
    qp = jnp.pad(q.astype(pages.dtype),
                 ((0, 0), (0, hp - h), (0, w - qw)))
    mp = page_table.shape[1]
    operands, mask_spec = (qp, pages), []
    if sel is not None:
        # the mask a position a sequence, padded to whole blocks of rows
        n_pos = -(-mp * page // (block * page)) * block * page
        sel = jnp.pad(sel.astype(jnp.int32),
                      ((0, 0), (0, n_pos - sel.shape[1])))[:, None]
        operands = (qp, sel, pages)
        mask_spec = [pl.BlockSpec((1, 1, n_pos), lambda i, *_: (i, 0, 0),
                                  memory_space=pltpu.VMEM)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hp, w), lambda i, *_: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            *mask_spec,
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hp, rank), lambda i, *_: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, block * page, w), pages.dtype),
            pltpu.SemaphoreType.DMA((2, block)),
        ],
    )
    out = named_pallas_call(
        "paged_decode_latent" if sel is None else "sparse_decode_latent",
        functools.partial(_decode_latent_kernel, page=page, scale=scale,
                          rank=rank, block=block, masked=sel is not None),
        grid_spec=grid_spec, interpret=interpret,
        out_shape=jax.ShapeDtypeStruct((b, hp, rank), q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * int(b) * h * page * mp * (w + rank),
            bytes_accessed=int(b) * mp * page * w * pages.dtype.itemsize,
            transcendentals=int(b) * h * page * mp),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(page_table, seq_lens, *operands)
    return out[:, :h]


def paged_attention_latent_reference(q, pages, page_table, seq_lens,
                                     rank: int, scale: float, sel=None):
    """Dense-gather reference of :func:`paged_attention_latent`."""
    b, h, qw = q.shape
    page = pages.shape[1]
    mp = page_table.shape[1]
    rows = pages[page_table].astype(jnp.float32).reshape(
        b, mp * page, -1)
    logits = jnp.einsum("bhw,btw->bht", q.astype(jnp.float32),
                        rows[..., :qw]) * scale
    seen = (jnp.arange(mp * page, dtype=jnp.int32)[None]
            < seq_lens[:, None])
    if sel is not None:
        seen = seen & (sel[:, :mp * page] > 0)
    seen = seen[:, None]
    logits = jnp.where(seen, logits, _NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.where(seen, jnp.exp(logits - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)  # noqa: E741
    out = jnp.einsum("bht,btr->bhr", p / jnp.maximum(l, 1e-30),
                     rows[..., :rank])
    return out.astype(q.dtype)


def paged_latent_supported(pool_shape, rank: int,
                           backend: Optional[str] = None) -> bool:
    """Gate for the latent kernel: rows and their value part in whole
    lane tiles, whole sublane tiles a page."""
    from .flash_attention import _FORCE_DEPTH
    if backend is None:
        backend = jax.default_backend()
    if backend != "tpu" and _FORCE_DEPTH == 0:
        return False
    page, w = pool_shape[1:]
    return w % 128 == 0 and rank % 128 == 0 and page % 16 == 0


def paged_attention_latent(q, pages, page_table, seq_lens, rank: int,
                           scale: float, block: Optional[int] = None,
                           interpret: bool = False, sel=None):
    """Single-token absorbed latent attention over a latent pool. q:
    ``[B, H, rank + rope]``, a head's ``[q_nope W_UK^T | rotated
    q_rope]``; pages ``[P, page, W]``, a position's row ``[latent
    (rank) | rotated shared key (rope) | zeros]``; seq_lens [B] lengths
    INCLUDING the appended token, 0 for a parked slot (zeros out).
    Returns ``[B, H, rank]``: softmax over the context of ``q . row *
    scale``, times the rows' first ``rank`` columns. With ``sel`` [B,
    >= table positions], nonzero at the positions a sequence selected
    (ops/pallas/sparse_attention.py), the softmax is over those alone;
    the walk still reads every page of the context. Not under head
    sharding (one latent serves every head)."""
    if get_head_sharding() is not None:
        raise NotImplementedError("latent pages under head sharding")
    if block is None:
        block = min(LATENT_BLOCK_PAGES, max(1, page_table.shape[1] // 8))
    if interpret or paged_latent_supported(pages.shape, rank):
        return _paged_decode_latent_pallas(
            q, pages, page_table.astype(jnp.int32),
            seq_lens.astype(jnp.int32), int(rank), float(scale), int(block),
            interpret, sel)
    return paged_attention_latent_reference(q, pages, page_table, seq_lens,
                                            int(rank), float(scale), sel)


# --------------------------------------------------------------------------
# Head sharding (tensor-parallel serving over a `model` mesh axis)
# --------------------------------------------------------------------------

# Trace-time routing state for the mesh-sharded decode engine
# (inference/continuous_batching.py `mesh=`): while a (mesh, axis) pair
# is active, the public entry runs head-sharded under shard_map. The
# head dimension is embarrassingly parallel in attention — every head
# attends its own K/V columns — so the per-device body is exactly the
# single-device kernel on 1/N of the heads, with no collectives and
# therefore BIT-IDENTICAL per-head arithmetic (the property the
# mesh-vs-single-device greedy pins lean on). THREAD-LOCAL: jit traces
# run on the calling thread, and one process may trace a mesh engine
# and a single-device engine concurrently (two server threads); a
# process-global switch would reroute the other thread's trace.
import threading as _threading

_HEAD_SHARDING = _threading.local()


def _default_axis() -> str:
    # topology.SERVING_MODEL_AXIS is the single source of truth for
    # the serving mesh's axis name; imported lazily (ops.pallas must
    # not pull the distributed package at module import)
    from ...distributed.topology import SERVING_MODEL_AXIS
    return SERVING_MODEL_AXIS


@contextlib.contextmanager
def head_sharding(mesh, axis: Optional[str] = None):
    """Route `paged_attention` through the head-sharded shard_map
    dispatch for the duration (a trace-time switch: wrap the jit-traced
    call, not the runtime one). ``axis=None`` = the serving model axis
    (topology.SERVING_MODEL_AXIS)."""
    prev = getattr(_HEAD_SHARDING, "value", None)
    _HEAD_SHARDING.value = (mesh, axis or _default_axis())
    try:
        yield
    finally:
        _HEAD_SHARDING.value = prev


def get_head_sharding() -> Optional[tuple]:
    return getattr(_HEAD_SHARDING, "value", None)


def paged_attention_head_sharded(q, k_pages, v_pages, page_table,
                                 seq_lens, mesh,
                                 axis: Optional[str] = None,
                                 k_scale=None, v_scale=None,
                                 scale: Optional[float] = None,
                                 q_offsets=None):
    """Ragged paged attention with heads sharded over ``mesh[axis]``.

    shard_map over the head dim of q and the KV pools (page table,
    seq_lens and q_offsets replicate — they are host scheduler state);
    each device runs the standard kernel-selection path on its own
    H/N-head slice, so on TPU every shard dispatches the Mosaic
    page-walk kernel and on CPU the dense-gather reference. No
    inter-device communication: attention is head-local. Requires
    ``num_heads % mesh.shape[axis] == 0``."""
    from jax import shard_map

    if axis is None:
        axis = _default_axis()
    b, sq, h, d = q.shape
    n = mesh.shape[axis]
    if h % n != 0:
        raise ValueError(
            f"num_heads {h} not divisible by mesh axis {axis!r}={n}")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    scale = float(scale)
    p4 = jax.sharding.PartitionSpec(None, None, axis)
    p3 = jax.sharding.PartitionSpec(None, None, axis)
    rep = jax.sharding.PartitionSpec()
    args = [q, k_pages, v_pages, page_table, seq_lens]
    specs = [p4, p4, p4, rep, rep]
    has_scale = k_scale is not None
    if has_scale:
        args += [k_scale, v_scale]
        specs += [p3, p3]
    has_qo = q_offsets is not None
    if has_qo:
        args += [q_offsets]
        specs += [rep]

    def local(*a):
        it = iter(a)
        qq, kp, vp, pt, sl = (next(it) for _ in range(5))
        ks = next(it) if has_scale else None
        vs = next(it) if has_scale else None
        qo = next(it) if has_qo else None
        return _paged_attention_local(qq, kp, vp, pt, sl, k_scale=ks,
                                      v_scale=vs, scale=scale,
                                      q_offsets=qo)

    fn = shard_map(local, mesh=mesh, in_specs=tuple(specs),
                   out_specs=p4, check_vma=False)
    return fn(*args)


# --------------------------------------------------------------------------
# Public entry — runtime kernel selection
# --------------------------------------------------------------------------

def paged_attention_supported(q_shape, kp_shape,
                              backend: Optional[str] = None) -> bool:
    """Gate for the Mosaic kernel: single-token decode over lane-tiling
    head groups. Everything else (ragged prefill chunks, odd head
    widths, CPU/GPU) takes the reference path."""
    from .flash_attention import _FORCE_DEPTH
    if backend is None:
        backend = jax.default_backend()
    if backend != "tpu" and _FORCE_DEPTH == 0:
        return False
    b, sq, h, d = q_shape
    page = kp_shape[1]
    return (sq == 1 and d in (64, 128) and (h * d) % 128 == 0 and
            page % 8 == 0)


def _paged_attention_local(q, k_pages, v_pages, page_table, seq_lens,
                           k_scale=None, v_scale=None,
                           scale: Optional[float] = None,
                           q_offsets=None):
    """Single-device kernel selection (the pre-mesh public entry): the
    Mosaic page-walk kernel where the shape gate admits, the
    dense-gather reference elsewhere. Also the per-shard body of the
    head-sharded dispatch."""
    b, sq, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    scale = float(scale)
    if q_offsets is None and paged_attention_supported(
            q.shape, k_pages.shape):
        out = _paged_decode_pallas(
            q.reshape(b, h, d), k_pages, v_pages,
            page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
            k_scale, v_scale, scale)
        return out.reshape(b, sq, h, d)
    return paged_attention_reference(
        q, k_pages, v_pages, page_table, seq_lens,
        k_scale=k_scale, v_scale=v_scale, scale=scale,
        q_offsets=q_offsets)


def paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                    k_scale=None, v_scale=None,
                    scale: Optional[float] = None, q_offsets=None):
    """Ragged paged attention over a block-paged KV pool.

    q: [B, Sq, H, D]; k_pages/v_pages: [P, page, H, D] (float or int8
    with k_scale/v_scale [P, page, H]); page_table: [B, max_pages]
    int32; seq_lens: [B] int32 lengths INCLUDING the already-appended
    query tokens. Returns [B, Sq, H, D].

    Under an active :func:`head_sharding` context (the mesh-sharded
    decode engine wraps its jit traces in one) the call runs
    head-sharded via shard_map; otherwise single-device kernel
    selection."""
    hs = get_head_sharding()
    if hs is not None:
        mesh, axis = hs
        return paged_attention_head_sharded(
            q, k_pages, v_pages, page_table, seq_lens, mesh, axis=axis,
            k_scale=k_scale, v_scale=v_scale, scale=scale,
            q_offsets=q_offsets)
    return _paged_attention_local(
        q, k_pages, v_pages, page_table, seq_lens, k_scale=k_scale,
        v_scale=v_scale, scale=scale, q_offsets=q_offsets)


# --------------------------------------------------------------------------
# Fused attention epilogue (r13): attention + out-projection, one launch
# --------------------------------------------------------------------------

# VMEM budget for the o-projection weight's scratch: the fused kernel
# copies W [E, E_out] whole into VMEM next to the double-buffered page
# set, so the gate admits only weights that fit comfortably (v4/v5 cores
# carry 16 MB VMEM; 8 MB leaves the page buffers + q + output headroom).
_FUSED_W_VMEM_BYTES = 8 * 1024 * 1024


def fused_epilogue_supported(q_shape, kp_shape, w_shape,
                             backend: Optional[str] = None,
                             w_itemsize: int = 4) -> bool:
    """Gate for the Mosaic fused-epilogue kernel: everything
    :func:`paged_attention_supported` requires, plus a lane-tiling
    projection whose weight block fits the VMEM budget
    (``w_itemsize``: the weight's storage bytes/element — the kernel
    keeps W in storage dtype, so a bf16 [2048, 2048] head fits where
    an fp32 one does not)."""
    if not paged_attention_supported(q_shape, kp_shape, backend):
        return False
    e_in, e_out = w_shape
    _, _, h, d = q_shape
    return (e_in == h * d and e_out % 128 == 0 and
            e_in * e_out * int(w_itemsize) <= _FUSED_W_VMEM_BYTES)


def paged_attention_fused_reference(q, k_pages, v_pages, page_table,
                                    seq_lens, w, bias=None,
                                    k_scale=None, v_scale=None,
                                    scale: Optional[float] = None,
                                    q_offsets=None):
    """Dense-gather reference for the fused epilogue: EXACTLY the
    unfused model math — :func:`paged_attention_reference`, the
    head-concat reshape, ``x @ W`` (ops.nn_functional.linear semantics)
    and the bias add — composed inside one op, so the fused engine's
    greedy tokens are bit-identical to the unfused engine on the CPU
    lane (the jaxpr the trace emits is the same one the unfused layers
    emit; only the launch/op count differs)."""
    ctx = paged_attention_reference(
        q, k_pages, v_pages, page_table, seq_lens, k_scale=k_scale,
        v_scale=v_scale, scale=scale, q_offsets=q_offsets)
    b, sq, h, d = ctx.shape
    out = jnp.matmul(ctx.reshape(b, sq, h * d), w)
    if bias is not None:
        out = out + bias
    return out


def paged_attention_fused(q, k_pages, v_pages, page_table, seq_lens,
                          w, bias=None, k_scale=None, v_scale=None,
                          scale: Optional[float] = None, q_offsets=None):
    """Ragged paged attention with the output-projection epilogue fused
    in: returns the attention BLOCK's output ``[B, Sq, E_out]`` instead
    of raw per-head context (``w``: [H*D, E_out] o-projection weight,
    ``bias``: optional [E_out]).

    Kernel selection mirrors :func:`paged_attention`: under an active
    :func:`head_sharding` context the attention runs head-sharded and
    the projection stays in the same traced program (GSPMD partitions
    the contraction over the head-grouped rows exactly as the unfused
    RowParallelLinear would — no separate launch, identical math);
    single-device, the Mosaic fused-epilogue kernel runs where
    :func:`fused_epilogue_supported` admits, the dense-gather fused
    reference elsewhere.

    Bit-identity contract: the REFERENCE composes the exact unfused
    jnp ops, so fused-vs-unfused greedy outputs are bit-equal wherever
    it runs (the CPU CI lane). The Mosaic kernel mimics the unfused
    lowering's rounding (context rounded to the output dtype before
    the epilogue dot, f32 accumulation) but on-chip bit-parity with
    the separately-launched unfused programs is chip-pending
    validation — validate with the fused_decode A/B on a chip-attached
    host before relying on cross-mode determinism there."""
    b, sq, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    scale = float(scale)
    hs = get_head_sharding()
    if hs is not None:
        mesh, axis = hs
        ctx = paged_attention_head_sharded(
            q, k_pages, v_pages, page_table, seq_lens, mesh, axis=axis,
            k_scale=k_scale, v_scale=v_scale, scale=scale,
            q_offsets=q_offsets)
        out = jnp.matmul(ctx.reshape(b, sq, h * d), w)
        if bias is not None:
            out = out + bias
        return out
    if q_offsets is None and fused_epilogue_supported(
            q.shape, k_pages.shape, w.shape,
            w_itemsize=w.dtype.itemsize):
        out = _paged_decode_fused_pallas(
            q.reshape(b, h, d), k_pages, v_pages,
            page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
            k_scale, v_scale, scale, w, bias)
        return out.reshape(b, sq, w.shape[1])
    # epilogue not in-kernel: compose the STANDARD kernel-selected
    # attention (_paged_attention_local — the Mosaic page-walk kernel
    # on TPU where its gate admits, the dense-gather reference on the
    # CPU lane) with the same epilogue ops, still as one dispatch op.
    # Falling back to the dense reference here would silently hand the
    # big-E decode hot path (e.g. a 1.3B head over the VMEM budget)
    # the worst kernel on exactly the backend the fusion targets.
    ctx = _paged_attention_local(
        q, k_pages, v_pages, page_table, seq_lens, k_scale=k_scale,
        v_scale=v_scale, scale=scale, q_offsets=q_offsets)
    out = jnp.matmul(ctx.reshape(b, sq, h * d), w)
    if bias is not None:
        out = out + bias
    return out
