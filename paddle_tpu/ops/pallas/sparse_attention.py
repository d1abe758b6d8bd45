"""Learned sparse attention over latent pages (DeepSeek Sparse Attention
of DeepSeek-V3.2, as GLM-5.3 runs it without positions).

A small indexer scores, for a query at position ``t``, every complete
block of ``pool`` positions before its own (``b < t // pool``) against
the block's pooled key ``kbar_b``:

    I[t, b] = sum_j w[t, j] relu(q_j(t) . kbar_b)

and the query attends to the positions of its ``k`` best blocks (every
block whose score is at least the ``k``-th largest: ties are kept) and
of its own block ``t // pool`` up to ``t`` (the tail). Where a query has
``k`` complete blocks or fewer, it attends to everything before it.

The attention itself is the absorbed latent form (one row of ``W``
values a position, read as the keys and the values of every head,
ops/pallas/paged_attention.py ``paged_attention_latent``) restricted to
the selected positions. Its kernels:

- ``sparse_decode_latent``: one token a live slot over its pages, the
  latent walk itself (``paged_attention_latent`` handed ``sel``, the
  selected positions) with positions not selected masked out. A page of
  64 positions holds 16 blocks of 4;
  where 2,048 of about 6,000 blocks are selected, a page holds no
  selected block with a chance of (2/3)^16 (0.15 %), so a walk of only
  the selected blocks would still fetch nearly every page, in 4 KB
  copies whose issue would bound it. The walk reads every page of the
  context instead, and the mask costs nothing.
- ``sparse_prefill_latent``: a prompt's queries against its latent rows
  in dense tiles (a flash kernel, exact), the selection a mask from the
  indexer's scores and each query's threshold. Inside a tile of keys
  the rows are laid out by position within a block, then by block, so
  that a tile of block scores, repeated once for each position of a
  block, is the tile's mask. Handed the prompts' true lengths, it
  computes no tile of queries or of keys past a prompt's end.
- ``dsa_index_prefill``: the indexer's scores of a prompt's queries over
  its pooled keys, a tile of queries' heads against a tile of blocks,
  the heads summed in the kernel: ``[T, heads, blocks]`` is never
  stored.

The threshold is exact: :func:`kth_largest` finds the ``k``-th largest
score of a row bit by bit over an order-preserving integer image of
the floats (32 passes of a compare and a count). Off the TPU every
kernel runs as plain ``jax.numpy`` that computes the same.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .naming import named_pallas_call

F32 = jnp.float32
_NEG_INF = -1e30
_VMEM_LIMIT = 64 * 1024 * 1024
# the prompt kernel's tiles: 16 queries of every head against 512 keys
# (128 blocks of 4: one lane tile of block scores)
Q_TILE = 16
K_TILE = 512
# the indexer's tiles: 32 queries of every index head against 512 blocks
IQ_TILE = 32
IB_TILE = 512


def _on_tpu(interpret: bool) -> bool:
    from .flash_attention import _FORCE_DEPTH
    return interpret or jax.default_backend() == "tpu" or _FORCE_DEPTH > 0


def _pow2(n: int) -> bool:
    return n > 0 and n & (n - 1) == 0


# -- the threshold ------------------------------------------------------------

def kth_largest(scores, k: int):
    """The ``k``-th largest value along the last axis of float32
    ``scores`` (``-inf`` where a row has fewer than ``k`` finite ones),
    exactly: bit by bit from the top over the floats' order-preserving
    unsigned image. ``-0.0`` counts as ``+0.0``. Returns
    ``scores.shape[:-1]``."""
    if scores.shape[-1] < k:
        return jnp.full(scores.shape[:-1], -jnp.inf, F32)
    s = scores.astype(F32) + 0.0  # -0.0 -> +0.0
    i = jax.lax.bitcast_convert_type(s, jnp.int32)
    u = jax.lax.bitcast_convert_type(
        jnp.where(i >= 0, i ^ jnp.int32(-2 ** 31), ~i), jnp.uint32)

    def bit(j, ans):
        cand = ans | (jnp.uint32(1) << (31 - j).astype(jnp.uint32))
        enough = jnp.sum((u >= cand[..., None]).astype(jnp.int32),
                         axis=-1) >= k
        return jnp.where(enough, cand, ans)

    ans = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    a = jax.lax.bitcast_convert_type(ans, jnp.int32)
    back = jnp.where(a < 0, a ^ jnp.int32(-2 ** 31), ~a)
    return jax.lax.bitcast_convert_type(back, F32)


def select_blocks(scores, tau, qpos, pool: int):
    """Which blocks a query at ``qpos`` attends to: its complete blocks
    scored at least ``tau`` and its own block. ``scores`` [..., N];
    ``tau``, ``qpos`` [...]. Returns bool [..., N]."""
    blk = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    own = (qpos // pool)[..., None]
    return ((blk < own) & (scores + 0.0 >= tau[..., None])) | (blk == own)


# -- the indexer --------------------------------------------------------------

def index_scores_reference(qi, w, keys, qpos, pool: int):
    """``I[t, b] = sum_j w[t, j] relu(q_j(t) . kbar_b)`` over the blocks
    ``b < qpos // pool``, ``-inf`` elsewhere. qi [B, S, Hi, Di]; w [B, S,
    Hi] float32; keys [B, N, Di]; qpos [B, S]. Returns [B, S, N]
    float32."""
    dots = jnp.einsum("bshd,bnd->bshn", qi, keys, preferred_element_type=F32)
    s = jnp.einsum("bsh,bshn->bsn", w.astype(F32), jnp.maximum(dots, 0.0))
    blk = jnp.arange(keys.shape[1], dtype=jnp.int32)
    return jnp.where(blk < (qpos // pool)[..., None], s, -jnp.inf)


def _index_kernel(q_ref, w_ref, k_ref, o_ref, *, lo: int, tq: int, tb: int,
                  heads: int, pool: int):
    qi, bj = pl.program_id(1), pl.program_id(2)
    first = lo + qi * tq
    # blocks a query of the tile can score: b < (first + tq - 1) // pool
    live = bj * tb < (first + tq - 1) // pool

    @pl.when(live)
    def _():
        d = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=F32)  # [tq * heads, tb]
        d = jnp.maximum(d, 0.0) * w_ref[0]
        s = jnp.sum(d.reshape(tq, heads, tb), axis=1)  # [tq, tb]
        qpos = first + jax.lax.broadcasted_iota(jnp.int32, (tq, tb), 0)
        blk = bj * tb + jax.lax.broadcasted_iota(jnp.int32, (tq, tb), 1)
        own = jnp.right_shift(qpos, pool.bit_length() - 1)
        o_ref[0] = jnp.where(blk < own, s, -jnp.inf)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)


def _index_pallas(qi, w, keys, lo: int, pool: int, interpret: bool):
    b, s, hi, di = qi.shape
    n = keys.shape[1]
    tq, tb = min(IQ_TILE, s), min(IB_TILE, n)
    kern = functools.partial(_index_kernel, lo=lo, tq=tq, tb=tb, heads=hi,
                             pool=pool)

    def last(q):  # the last tile of blocks a tile of queries scores
        return jnp.maximum((lo + q * tq + tq - 1) // pool - 1, 0) // tb

    return named_pallas_call(
        "dsa_index_prefill", kern,
        grid=(b, s // tq, n // tb),
        in_specs=[
            pl.BlockSpec((1, tq * hi, di), lambda i, q, j: (i, q, 0)),
            pl.BlockSpec((1, tq * hi, 1), lambda i, q, j: (i, q, 0)),
            pl.BlockSpec((1, tb, di),
                         lambda i, q, j: (i, jnp.minimum(j, last(q)), 0)),
        ],
        out_specs=pl.BlockSpec((1, tq, tb), lambda i, q, j: (i, q, j)),
        out_shape=jax.ShapeDtypeStruct((b, s, n), F32),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * b * s * hi * di * n // 2, transcendentals=0,
            bytes_accessed=qi.size * qi.dtype.itemsize
            + keys.size * keys.dtype.itemsize * (s // tq) + b * s * n * 4),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(qi.reshape(b, s * hi, di), w.astype(F32).reshape(b, s * hi, 1), keys)


def index_scores(qi, w, keys, lo: int, pool: int, interpret: bool = False):
    """The indexer's scores of a prompt's queries at positions ``lo ..
    lo + S - 1`` over its pooled keys: qi [B, S, Hi, Di] (bf16), w [B,
    S, Hi] float32 (the head weights with their scales), keys [B, N, Di].
    Returns [B, S, N] float32, ``-inf`` past each query's complete
    blocks."""
    b, s, hi, di = qi.shape
    n = keys.shape[1]
    tq, tb = min(IQ_TILE, s), min(IB_TILE, n)
    if _on_tpu(interpret) and s % tq == 0 and n % tb == 0 and \
            di % 128 == 0 and tb % 128 == 0 and _pow2(pool):
        return _index_pallas(qi, w, keys, lo, pool, interpret)
    qpos = jnp.broadcast_to(lo + jnp.arange(s, dtype=jnp.int32), (b, s))
    return index_scores_reference(qi, w, keys, qpos, pool)


def index_scores_paged(qi, w, pool_pages, page_table, qpos, pool: int):
    """A decoded token's indexer scores over its slot's pooled keys in a
    pool of ``[P, page / pool, Di]`` rows under the page table: qi [B,
    Hi, Di], w [B, Hi], qpos [B]. Returns [B, N] float32 (N: the
    table's blocks)."""
    b, _, di = qi.shape
    keys = pool_pages[page_table][..., :di].reshape(b, -1, di)
    return index_scores_reference(qi[:, None], w[:, None], keys,
                                  qpos[:, None], pool)[:, 0]


# -- a prompt -----------------------------------------------------------------

def block_major(rows, tk: int, pool: int):
    """The prompt kernel's layout of latent rows [B, T, W] (T a multiple
    of ``tk``): inside each tile of ``tk`` positions, position ``pool *
    b + r`` of the tile goes to row ``r * tk / pool + b``."""
    b, t, w = rows.shape
    return rows.reshape(b, t // tk, tk // pool, pool, w).swapaxes(2, 3) \
        .reshape(b, t, w)


def _prefill_live(lengths, lo: int, s: int):
    """What the prompt kernel reads of the true lengths for a segment of
    ``s`` queries at ``lo``, a pair a sequence ([2, B] int32): the query
    tiles of the segment that start before the length (``q < it``) and
    the key tile of the last position (0 for an empty sequence), where
    the query tiles past the length park their reads. Worked out once a
    call, as flash_attention's ``_live_blocks``."""
    n = lengths.astype(jnp.int32)
    # shifts of non-negative values: no floor division's sign fix-ups
    tiles = jnp.right_shift(jnp.maximum(n - lo, 0) + Q_TILE - 1,
                            Q_TILE.bit_length() - 1)
    return jnp.stack([jnp.minimum(tiles, s // Q_TILE),
                      jnp.right_shift(jnp.maximum(n - 1, 0),
                                      K_TILE.bit_length() - 1)])


def prefill_tile_pairs(lengths, lo: int, s: int):
    """The (query tile, key tile) pairs the prompt kernel computes for a
    segment of ``s`` queries at ``lo``: with the true ``lengths`` ([B]),
    and without them (every query tile up to its diagonal, the padding's
    too). Returns ``(run, grid)``, int32 scalars."""
    first = lo + np.arange(-(-s // Q_TILE)) * Q_TILE
    diag = (first + Q_TILE - 1) // K_TILE
    run = jnp.where(first < lengths.astype(jnp.int32)[:, None], diag + 1, 0)
    return (jnp.sum(run).astype(jnp.int32),
            jnp.int32(lengths.shape[0] * int(np.sum(diag + 1))))


def _prefill_kernel(q_ref, k_ref, sc_ref, tau_ref, o_ref, m_sc, l_sc, acc_sc,
                    *, lo: int, tq: int, tk: int, heads: int, pool: int,
                    scale: float, live=None):
    qi, kt = pl.program_id(1), pl.program_id(2)
    first = lo + qi * tq
    nb = tk // pool
    rows = heads * tq

    @pl.when(kt == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, F32)
        l_sc[...] = jnp.zeros(l_sc.shape, F32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, F32)

    # a key tile past the diagonal holds no key a query of the tile
    # sees. With ``live`` (``_prefill_live`` of the sequence) a query
    # tile past the length runs no step: the init and the last write
    # alone, which write zeros (0 / 1e-30). A live tile's diagonal never
    # passes the length's key tile (16 positions never straddle a tile
    # of 512), so a live row runs the tiles, in the order, it runs
    # without.
    run = kt * tk <= first + tq - 1
    if live is not None:
        run = run & (qi < live[0])

    @pl.when(run)
    def _():
        w = q_ref.shape[-1]
        q = q_ref[0].reshape(rows, w)
        kv = k_ref[0]  # [tk, W], block-major inside the tile
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
        qpos_b = first + jax.lax.broadcasted_iota(jnp.int32, (tq, nb), 0)
        blk = kt * nb + jax.lax.broadcasted_iota(jnp.int32, (tq, nb), 1)
        own = jnp.right_shift(qpos_b, pool.bit_length() - 1)
        picked = ((blk < own) & (sc_ref[0] + 0.0 >= tau_ref[0])) | \
            (blk == own)
        sel = jnp.concatenate([picked.astype(F32)] * pool, axis=1)
        col = jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        kpos = kt * tk + pool * jnp.bitwise_and(col, nb - 1) + \
            jnp.right_shift(col, nb.bit_length() - 1)
        qpos = first + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        seen = jnp.where(kpos <= qpos, sel, 0.0)
        seen = jnp.broadcast_to(seen[None], (heads, tq, tk)).reshape(rows, tk)
        s = jnp.where(seen > 0, s, _NEG_INF)
        m = m_sc[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(seen > 0, jnp.exp(s - m_new), 0.0)
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p.astype(kv.dtype), kv, (((1,), (0,)), ((), ())),
            preferred_element_type=F32)
        m_sc[...] = m_new

    @pl.when(kt == pl.num_programs(2) - 1)
    def _():
        out = acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = out.reshape(heads, tq, -1).astype(o_ref.dtype)


def _prefill_kernel_ragged(live_ref, *refs, **static):
    """``_prefill_kernel`` under a scalar-prefetch grid: ``_prefill_live``
    arrives first, in scalar memory."""
    b = pl.program_id(0)
    _prefill_kernel(*refs, live=(live_ref[0, b], live_ref[1, b]), **static)


def _prefill_pallas(qa, rows, scores, tau, lo, pool, scale, interpret,
                    lengths=None):
    b, h, s, w = qa.shape
    t = rows.shape[1]
    tq, tk = Q_TILE, K_TILE
    nb = tk // pool
    lat = block_major(rows, tk, pool)

    def tiles(i, q, k, live=None):
        """The query tile and the key tile a grid step reads: a query
        tile reads up to the last key tile it sees. One past the length
        reads, every step, the tiles the step before it read (the last
        live query tile and the length's key tile, that tile's
        diagonal): nothing is fetched for it."""
        kt = jnp.minimum(k, (lo + q * tq + tq - 1) // tk)
        if live is None:
            return q, kt
        n, last_k = live[0, i], live[1, i]
        return (jnp.minimum(q, jnp.maximum(n - 1, 0)),
                jnp.where(q < n, kt, last_k))

    static = dict(lo=lo, tq=tq, tk=tk, heads=h, pool=pool, scale=scale)
    in_specs = [
        pl.BlockSpec((1, h, tq, w),
                     lambda i, q, k, *lv: (i, 0, tiles(i, q, k, *lv)[0], 0)),
        pl.BlockSpec((1, tk, w),
                     lambda i, q, k, *lv: (i, tiles(i, q, k, *lv)[1], 0)),
        pl.BlockSpec((1, tq, nb),
                     lambda i, q, k, *lv: (i, *tiles(i, q, k, *lv))),
        pl.BlockSpec((1, tq, 1),
                     lambda i, q, k, *lv: (i, tiles(i, q, k, *lv)[0], 0)),
    ]
    layout = dict(
        # the key axis ends at the segment's last key tile
        grid=(b, s // tq, -(-(lo + s) // tk)), in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, tq, w),
                               lambda i, q, k, *lv: (i, 0, q, 0)),
        scratch_shapes=[pltpu.VMEM((h * tq, 1), F32),
                        pltpu.VMEM((h * tq, 1), F32),
                        pltpu.VMEM((h * tq, w), F32)])
    args = (qa, lat, scores, tau.astype(F32)[..., None])
    if lengths is None:
        kern = functools.partial(_prefill_kernel, **static)
    else:
        kern = functools.partial(_prefill_kernel_ragged, **static)
        layout = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **layout))
        args = (_prefill_live(lengths, lo, s),) + args
    pairs = min(lo + s // 2, t)  # keys a query sees, the mean
    return named_pallas_call(
        "sparse_prefill_latent", kern,
        out_shape=jax.ShapeDtypeStruct((b, h, s, w), qa.dtype),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * s * w * pairs, transcendentals=b * h * s * pairs,
            bytes_accessed=2 * qa.size * qa.dtype.itemsize
            + rows.size * rows.dtype.itemsize * (s // tq) + scores.size * 4),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        **layout,
    )(*args)


def sparse_prefill_reference(qa, rows, scores, tau, lo: int, pool: int,
                             scale: float, lengths=None):
    """Dense reference of :func:`sparse_prefill_latent`; with
    ``lengths``, zeros at the queries from each length on."""
    b, h, s, w = qa.shape
    t = rows.shape[1]
    qpos = lo + jnp.arange(s, dtype=jnp.int32)
    picked = select_blocks(scores, tau, jnp.broadcast_to(qpos, (b, s)), pool)
    kpos = jnp.arange(t, dtype=jnp.int32)
    sel = jnp.take(picked, jnp.minimum(kpos // pool, picked.shape[-1] - 1),
                   axis=-1)
    seen = (sel & (kpos[None, None] <= qpos[None, :, None]))[:, None]
    logits = jnp.einsum("bhsw,btw->bhst", qa.astype(F32),
                        rows.astype(F32)) * scale
    logits = jnp.where(seen, logits, _NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.where(seen, jnp.exp(logits - m), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhst,btw->bhsw", p, rows.astype(F32))
    if lengths is not None:
        live = qpos[None] < lengths.astype(jnp.int32)[:, None]
        out = jnp.where(live[:, None, :, None], out, 0.0)
    return out.astype(qa.dtype)


def sparse_prefill_latent(qa, rows, scores, tau, lo: int, pool: int,
                          scale: float, interpret: bool = False,
                          lengths=None):
    """A prompt's queries at positions ``lo .. lo + S - 1`` against its
    latent rows, each query over the positions of the blocks it
    selects (:func:`select_blocks`) and no later one. qa [B, H, S, W]
    (absorbed queries); rows [B, T, W]; scores [B, S, T / pool] (the
    indexer's); tau [B, S] (each query's threshold). Returns [B, H, S,
    W]: the softmax over the selected positions of ``qa . row * scale``,
    times the rows.

    ``lengths`` ([B] int32): positions from a sequence's length on are
    padding. The kernel then computes no tile of queries that starts at
    or past the length (zeros come out) and no tile of keys that does;
    a live query's row is the one it gets without. The rows of padded
    queries inside the tile that holds the length are not defined."""
    b, h, s, w = qa.shape
    t = rows.shape[1]
    if _on_tpu(interpret) and s % Q_TILE == 0 and t % K_TILE == 0 and \
            w % 128 == 0 and scores.shape[-1] == t // pool and _pow2(pool):
        return _prefill_pallas(qa, rows, scores, tau, int(lo), int(pool),
                               float(scale), interpret, lengths)
    return sparse_prefill_reference(qa, rows, scores, tau, int(lo), int(pool),
                                    float(scale), lengths)
