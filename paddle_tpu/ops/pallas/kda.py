"""The gated delta rule with a decay a key channel (KDA, Kimi Linear,
arXiv:2510.26692), as serving runs it.

A head keeps a state ``S [d_k, d_v]`` in float32. A token with query
``q``, key ``k`` (both L2-normalised here, ``q`` scaled), value ``v``,
log-decay ``g [d_k] <= 0`` and ``beta`` moves it by

    S' = Diag(exp g) S;  u = beta (v - S'^T k);  S = S' + k u^T;  o = S^T q

``kda_decode`` is that step for one token a live slot, on the state
pool in place. ``kda_chunk_fwd`` is the same recurrence over a prompt
in chunks of ``C`` tokens, everything inside a chunk as matrix products
(the chunkwise-parallel form). With ``G_r`` the running sum of ``g``
inside the chunk and ``S_0`` the state the chunk starts from:

    A[r, i] = sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c])     (i <  r)
    B[r, i] = sum_c q_r[c] k_i[c] exp(G_r[c] - G_i[c])     (i <= r)
    (I + Diag(beta) A) U = Diag(beta) (V - (K * exp G) S_0)
    O = (Q * exp G) S_0 + B U
    S_C = Diag(exp G_C) S_0 + (K * exp(G_C - G))^T U

``exp(-G_i)`` alone overflows where a chunk decays hard, so A and B are
built a block of ``SUB`` rows at a time against the running sum at the
block's start: every exponent is then at most ``SUB`` steps of decay.
The triangular system is solved by forward substitution (a block of
rows at a time, the rows of a block one after another): a series in
powers of the strictly lower part loses every digit where keys repeat
and ``beta`` nears 2. Inside a block it runs in column form: once a row
is final, its outer product with its column of ``N = Diag(beta) A`` is
taken from the rows below it. The columns do not depend on the solve,
so a row's serial chain is a sublane broadcast, a product and a
difference; the row form (each row a sum over the rows solved so far,
reduced across sublanes, from a transposed ``N`` made by a product with
the identity) compiled to 2,616 bundles a grid step for a v5e at 64
heads of 128, the column form to 1,885. Inverting the blocks' diagonals
together and merging them by products saved 4 %: the float32 products
it adds run one after another. Rows past a sequence's length get
``beta = 0`` and ``g = 0``: they leave the state alone.

Off the TPU both run the same mathematics through plain ``jax.numpy``
(the chunk's body is one function, used by the kernel and by the
fallback).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .naming import named_pallas_call

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
CHUNK = 64
SUB = 16
L2_EPS = 1e-6
_VMEM_LIMIT = 48 * 1024 * 1024


def kda_supported(d_k: int, d_v: int, backend=None) -> bool:
    from .flash_attention import _FORCE_DEPTH
    if backend is None:
        backend = jax.default_backend()
    return (backend == "tpu" or _FORCE_DEPTH > 0) and \
        d_k % 128 == 0 and d_v % 128 == 0


def _mm(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=F32)


def _dot(a, b):      # a @ b
    return _mm(a, b, ((1,), (0,)))


def _dot_nt(a, b):   # a @ b.T
    return _mm(a, b, ((1,), (1,)))


def _dot_tn(a, b):   # a.T @ b
    return _mm(a, b, ((0,), (0,)))


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _eye(n):
    return _iota((n, n), 0) == _iota((n, n), 1)


def _col(row):
    """``[1, n] -> [n, 1]`` as a masked sum over lanes (no relayout)."""
    return jnp.sum(jnp.where(_eye(row.shape[1]), row, 0.0), axis=1,
                   keepdims=True)


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + L2_EPS)


def chunk_body(q, k, v, g, beta_row, s, n_valid, scale: float):
    """One chunk of one head. q, k, g: [C, d_k]; v: [C, d_v]; beta_row:
    [1, C]; s: [d_k, d_v]; ``n_valid``: rows of the chunk inside the
    sequence (a scalar, C or more where the chunk is whole). All
    float32. Returns ``(o [C, d_v], s_new)``."""
    c, dk = q.shape
    dv = v.shape[1]
    sub = SUB if c % SUB == 0 else 8
    rows = _iota((c, 1), 0)
    g = jnp.where(rows < n_valid, g, 0.0)
    beta_row = jnp.where(_iota((1, c), 1) < n_valid, beta_row, 0.0)
    beta_col = _col(beta_row)
    q = l2norm(q) * scale
    k = l2norm(k)
    lower = _iota((c, c), 0) >= _iota((c, c), 1)
    gam = _dot(lower.astype(F32), g)  # running sum of g, [C, d_k]
    gam_end = gam[c - 1:c]
    a_rows, b_rows = [], []
    for i in range(c // sub):
        lo, hi = i * sub, (i + 1) * sub
        ref = gam[lo - 1:lo] if i else jnp.zeros((1, dk), F32)
        er = jnp.exp(gam[lo:hi] - ref)
        # columns past this block of rows are masked below: any finite
        # exponent does there
        kc = k * jnp.exp(jnp.where(rows < hi, ref - gam, 0.0))
        ab = _dot_nt(jnp.concatenate([k[lo:hi] * er, q[lo:hi] * er], 0), kc)
        a_rows.append(ab[:sub])
        b_rows.append(ab[sub:])
    a = jnp.where(lower & ~_eye(c), jnp.concatenate(a_rows, 0), 0.0)
    b = jnp.where(lower, jnp.concatenate(b_rows, 0), 0.0)
    n = a * beta_col                                     # N[r, i], i < r
    rhs = beta_col * (v - _dot(k * jnp.exp(gam), s))
    u_blocks = []
    for i in range(c // sub):
        lo, hi = i * sub, (i + 1) * sub
        blk = rhs[lo:hi]
        if i:
            # the rows solved so far, zeros below them: N's entries
            # inside this block meet zeros
            so_far = jnp.concatenate(
                u_blocks + [jnp.zeros((c - lo, dv), F32)], 0)
            blk = blk - _dot(n[lo:hi], so_far)
        # column form: once row r is final it leaves the rows below it
        # (N is zero on and above the diagonal, so the rows above stay);
        # a row touches only the tiles of 8 rows that hold rows below it
        tiles = [blk[j:j + 8] for j in range(0, sub, 8)]
        for r in range(sub - 1):
            row = tiles[r // 8][r % 8:r % 8 + 1]
            for t in range((r + 1) // 8, len(tiles)):
                at = lo + 8 * t
                tiles[t] = tiles[t] - n[at:at + 8, lo + r:lo + r + 1] * row
        u_blocks.append(jnp.concatenate(tiles, 0))
    u = jnp.concatenate(u_blocks, 0)
    o = _dot(q * jnp.exp(gam), s) + _dot(b, u)
    s_new = _col(jnp.exp(gam_end)) * s + _dot_tn(
        k * jnp.exp(gam_end - gam), u)
    return o, s_new


def decode_body(q, k, v, g, beta, s, scale: float):
    """The recurrence's one step, a batch of heads at once: q, k, g
    [..., d_k]; v [..., d_v]; beta [..., 1]; s [..., d_k, d_v]."""
    q = l2norm(q) * scale
    k = l2norm(k)
    s = s * jnp.exp(g)[..., None]
    u = beta * (v - jnp.sum(k[..., None] * s, axis=-2))
    s = s + k[..., None] * u[..., None, :]
    return jnp.sum(q[..., None] * s, axis=-2), s


# -- the prompt: chunks ------------------------------------------------------

def _chunk_kernel(lens_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref,
                  o_ref, s_ref, *, chunk: int, scale: float):
    b, c = pl.program_id(0), pl.program_id(2)
    left = lens_ref[b] - c * chunk

    @pl.when(c == 0)
    def _():
        s_ref[0, 0] = s0_ref[0, 0]

    @pl.when(left > 0)
    def _():
        o, s_new = chunk_body(
            q_ref[0].astype(F32), k_ref[0].astype(F32),
            v_ref[0].astype(F32), g_ref[0],
            beta_ref[0, 0, pl.ds(c, 1), :], s_ref[0, 0], left, scale)
        o_ref[0] = o.astype(o_ref.dtype)
        s_ref[0, 0] = s_new

    @pl.when(left <= 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _chunk_pallas(q, k, v, g, beta, s0, lens, heads: int, chunk: int,
                  scale: float, interpret: bool = False):
    bsz, t, _ = q.shape
    dk, dv = q.shape[2] // heads, v.shape[2] // heads
    nc = t // chunk
    beta = beta.astype(F32).transpose(0, 2, 1).reshape(bsz, heads, nc,
                                                       chunk)

    def tok(b, h, c, lens):
        # chunks past the sequence's end re-read its last chunk: no DMA
        last = jnp.maximum(lens[b] - 1, 0) // chunk
        return (b, jnp.minimum(c, last), h)

    def head(b, h, c, lens):
        return (b, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(bsz, heads, nc),
        in_specs=[pl.BlockSpec((1, chunk, dk), tok),
                  pl.BlockSpec((1, chunk, dk), tok),
                  pl.BlockSpec((1, chunk, dv), tok),
                  pl.BlockSpec((1, chunk, dk), tok),
                  pl.BlockSpec((1, 1, nc, chunk), head),
                  pl.BlockSpec((1, 1, dk, dv), head)],
        out_specs=[pl.BlockSpec((1, chunk, dv),
                                lambda b, h, c, lens: (b, c, h)),
                   pl.BlockSpec((1, 1, dk, dv), head)])
    tokens = bsz * t * heads
    call = named_pallas_call(
        "kda_chunk_fwd",
        functools.partial(_chunk_kernel, chunk=chunk, scale=scale),
        grid_spec=grid_spec, interpret=interpret,
        out_shape=[jax.ShapeDtypeStruct((bsz, t, heads * dv), v.dtype),
                   jax.ShapeDtypeStruct((bsz, heads, dk, dv), F32)],
        cost_estimate=pl.CostEstimate(
            flops=tokens * (6 * dk * dv + 4 * chunk * (dk + dv)),
            transcendentals=tokens * 6 * dk,
            bytes_accessed=(q.size + k.size + 2 * v.size)
            * q.dtype.itemsize + 4 * (g.size + beta.size + 2 * s0.size)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT))
    o, s = call(lens.astype(jnp.int32), q, k, v, g, beta, s0)
    return o, s


def _chunk_jnp(q, k, v, g, beta, s0, lens, heads: int, chunk: int,
               scale: float):
    bsz, t, _ = q.shape
    nc = t // chunk

    def split(x):  # [B, T, H * d] -> [NC, B, H, C, d]
        return x.astype(F32).reshape(bsz, nc, chunk, heads, -1).transpose(
            1, 0, 3, 2, 4)

    beta = beta.astype(F32).reshape(bsz, nc, 1, chunk, heads).transpose(
        1, 0, 4, 2, 3)  # [NC, B, H, 1, C]
    left = lens.astype(jnp.int32)[None] - \
        jnp.arange(nc, dtype=jnp.int32)[:, None] * chunk  # [NC, B]
    body = jax.vmap(jax.vmap(functools.partial(chunk_body, scale=scale),
                             in_axes=(0, 0, 0, 0, 0, 0, None)))  # B, H

    def step(s, xs):
        qc, kc, vc, gc, bc, n = xs
        o, s_new = body(qc, kc, vc, gc, bc, s, n)
        live = (n > 0)[:, None, None, None]
        return jnp.where(live, s_new, s), jnp.where(live, o, 0.0)

    s, o = jax.lax.scan(step, s0.astype(F32),
                        (split(q), split(k), split(v), split(g), beta,
                         left))
    o = o.transpose(1, 0, 3, 2, 4).reshape(bsz, t, -1)
    return o.astype(v.dtype), s


def kda_chunk_fwd(q, k, v, g, beta, state, lens, *, heads: int,
                  chunk: int = CHUNK, scale=None, interpret: bool = False):
    """The recurrence over right-padded prompts. q, k, g: [B, T, H *
    d_k]; v: [B, T, H * d_v]; beta: [B, T, H]; ``state`` [B, H, d_k,
    d_v] float32 is what each row starts from; ``lens`` [B]: rows past
    it leave the state alone and their outputs are zero or unread. T is
    padded to whole chunks here. Returns ``(o [B, T, H * d_v] in v's
    dtype, final state)``."""
    bsz, t, _ = q.shape
    dk, dv = q.shape[2] // heads, v.shape[2] // heads
    scale = float(1.0 / math.sqrt(dk) if scale is None else scale)
    chunk = min(chunk, -(-t // 8) * 8)
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                            for x in (q, k, v, g, beta))
    lens = jnp.minimum(lens.astype(jnp.int32), t)
    g = g.astype(F32)
    if interpret or kda_supported(dk, dv):
        o, s = _chunk_pallas(q, k, v, g, beta, state, lens, heads, chunk,
                             scale, interpret)
    else:
        o, s = _chunk_jnp(q, k, v, g, beta, state, lens, heads, chunk,
                          scale)
    return o[:, :t], s


# -- one token a live slot ---------------------------------------------------

def _decode_kernel(src_ref, live_ref, q_ref, k_ref, v_ref, g_ref, beta_ref,
                   s_ref, o_ref, so_ref, *, scale: float):
    b = pl.program_id(0)
    hb, dk = q_ref.shape[1:]

    @pl.when(live_ref[b] > 0)
    def _():
        q = l2norm(q_ref[0].astype(F32)) * scale
        k = l2norm(k_ref[0].astype(F32))
        v = v_ref[0].astype(F32)
        beta = beta_ref[0]
        # the vectors that scale the state's rows, a head a column: a
        # product with the identity puts them there (exact in float32)
        eye = _eye(dk).astype(F32)
        kc, qc = _dot_nt(eye, k), _dot_nt(eye, q)
        ac = _dot_nt(eye, jnp.exp(g_ref[0]))
        for j in range(hb):
            s = s_ref[0, j] * ac[:, j:j + 1]
            u = beta[j:j + 1] * (v[j:j + 1] - jnp.sum(
                kc[:, j:j + 1] * s, axis=0, keepdims=True))
            s = s + kc[:, j:j + 1] * u
            so_ref[0, j] = s
            o_ref[0, j:j + 1, :] = jnp.sum(
                qc[:, j:j + 1] * s, axis=0, keepdims=True
            ).astype(o_ref.dtype)

    @pl.when(live_ref[b] == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _head_block(heads: int) -> int:
    for hb in (16, 8):
        if heads % hb == 0:
            return hb
    return heads


def _decode_pallas(q, k, v, g, beta, pool, src, live, scale: float,
                   interpret: bool = False):
    bsz, heads, dk = q.shape
    dv = v.shape[2]
    hb = _head_block(heads)
    nhb = heads // hb

    def vec(b, h, src, live):
        return (b, h, 0)

    def row(b, h, src, live):
        # a parked slot stays on the block the step before it held:
        # nothing is fetched and nothing written for it
        return (src[b], jnp.where(live[b] > 0, h, nhb - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(bsz, nhb),
        in_specs=[pl.BlockSpec((1, hb, dk), vec),
                  pl.BlockSpec((1, hb, dk), vec),
                  pl.BlockSpec((1, hb, dv), vec),
                  pl.BlockSpec((1, hb, dk), vec),
                  pl.BlockSpec((1, hb, 1), vec),
                  pl.BlockSpec((1, hb, dk, dv), row)],
        out_specs=[pl.BlockSpec((1, hb, dv), vec),
                   pl.BlockSpec((1, hb, dk, dv), row)])
    call = named_pallas_call(
        "kda_decode", functools.partial(_decode_kernel, scale=scale),
        grid_spec=grid_spec, interpret=interpret,
        out_shape=[jax.ShapeDtypeStruct((bsz, heads, dv), v.dtype),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the state pool is updated in place (operand 7 counts the two
        # prefetched scalars)
        input_output_aliases={7: 1},
        cost_estimate=pl.CostEstimate(
            flops=6 * bsz * heads * dk * dv,
            transcendentals=bsz * heads * dk,
            bytes_accessed=8 * bsz * heads * dk * dv),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT))
    return call(src, live, q, k, v, g, beta, pool)


def kda_decode(q, k, v, g, beta, pool, rows, live, *, scale=None,
               interpret: bool = False):
    """One token a sequence against the state pool, in place. q, k, g:
    [B, H, d_k]; v: [B, H, d_v]; beta: [B, H]; ``pool`` [R + 1, H, d_k,
    d_v] float32, its last row scratch; ``rows`` [B]: each batch row's
    pool row; ``live`` [B] bool: a row that is not live (a parked slot)
    is skipped and its pool row is left as it is. Returns ``(o [B, H,
    d_v] in v's dtype, pool)``."""
    dk = q.shape[2]
    scale = float(1.0 / math.sqrt(dk) if scale is None else scale)
    g = g.astype(F32)
    beta = beta.astype(F32)[..., None]
    rows = rows.astype(jnp.int32)
    if interpret or kda_supported(dk, v.shape[2]):
        # a parked slot's block index: the newest live slot's before it,
        # the scratch row where there is none
        at = jax.lax.cummax(jnp.where(
            live, jnp.arange(rows.shape[0], dtype=jnp.int32), -1))
        src = jnp.where(at >= 0, rows[jnp.maximum(at, 0)],
                        pool.shape[0] - 1).astype(jnp.int32)
        return _decode_pallas(q, k, v, g, beta, pool, src,
                              live.astype(jnp.int32), scale, interpret)
    o, s = decode_body(q.astype(F32), k.astype(F32), v.astype(F32), g,
                       beta, pool[rows], scale)
    at = jnp.where(live, rows, pool.shape[0] - 1)
    o = jnp.where(live[:, None, None], o, 0.0).astype(v.dtype)
    return o, pool.at[at].set(s)
