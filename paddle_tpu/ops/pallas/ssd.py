"""Mamba-2's state-space duality (SSD, Dao & Gu 2024, arXiv:2405.21060),
as serving runs it.

A head ``h`` keeps a state ``S [N, P]`` in float32 (``N`` the state
size, ``P`` the head size). A position with input ``x [P]``, step ``dt
> 0`` and its group's ``B, C [N]`` (a group's heads share them) moves it
by

    S = exp(dt A_h) S + dt B x^T;   y = S^T C

with ``A_h < 0`` one scalar a head: linear attention with C the query, B
the key, ``dt x`` the value and one scalar decay a position. There is no
delta correction, so nothing needs solving.

``ssd_decode`` is that step for one token a live slot, on the state pool
in place, a group's heads in one grid step. ``ssd_chunk_fwd`` is the
recurrence over a prompt in chunks of ``C`` positions, a chunk as
matrix products. With ``a_r = dt_r A_h``, ``L_r`` their running sum
inside the chunk and ``S_0`` the state the chunk starts from:

    Y   = (M * C B^T) (dt x) + exp(L) * (C S_0),  M[r, i] = exp(L_r - L_i), i <= r
    S_C = exp(L_C) S_0 + (B * exp(L_C - L) dt)^T x

``C B^T`` is a group's and is made once for its heads. Every exponent
is at most zero. Rows past a sequence's length get ``dt = 0``: no
decay and no input, so they leave the state alone.

Off the TPU both run the same mathematics through plain ``jax.numpy``
(a chunk's body is one function, used by the kernel and by the
fallback). Neither adds the ``D x`` skip: the model does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kda import _VMEM_LIMIT, _dot, _dot_nt, _dot_tn, _eye, _iota
from .naming import named_pallas_call

F32 = jnp.float32
CHUNK = 128


def ssd_supported(n: int, p: int, heads_per_group: int,
                  backend=None) -> bool:
    from .flash_attention import _FORCE_DEPTH
    if backend is None:
        backend = jax.default_backend()
    return (backend == "tpu" or _FORCE_DEPTH > 0) and \
        n % 128 == 0 and p % 128 == 0 and heads_per_group % 8 == 0


def chunk_body(x, dt, a, b, c, s, n_valid, put):
    """One chunk of one group. x: [C, hb * P]; dt: [hb, C] (a head a
    row); a: [hb, 1]; b, c: [C, N]; s: [hb, N, P]; ``n_valid``: rows of
    the chunk inside the sequence (a scalar, C or more where the chunk
    is whole). All float32 but x, b, c. ``x`` and ``s`` are read a head
    at a time (arrays or a kernel's refs); ``put(j, y_j [C, P], s_j [N,
    P])`` takes head ``j``'s output and the state after the chunk."""
    hb, ch = dt.shape
    p = x.shape[1] // hb
    b, c = b.astype(F32), c.astype(F32)
    dt = jnp.where(_iota((1, ch), 1) < n_valid, dt, 0.0)
    upper = (_iota((ch, ch), 0) <= _iota((ch, ch), 1)).astype(F32)
    lam = _dot(dt * a, upper)                  # running sums, [hb, C]
    eye = _eye(ch).astype(F32)
    lam_t, dt_t = _dot_nt(eye, lam), _dot_nt(eye, dt)  # [C, hb] each
    lower = _iota((ch, ch), 0) >= _iota((ch, ch), 1)
    cb = _dot_nt(c, b)                         # the group's C B^T
    # exp(L_C) as a row, by a product that picks the last column (Mosaic
    # cannot broadcast a [1, 1] value over both axes)
    last = (_iota((ch, p), 0) == ch - 1).astype(F32)
    for j in range(hb):
        col, row = lam_t[:, j:j + 1], lam[j:j + 1]
        m = jnp.where(lower, jnp.exp(jnp.where(lower, col - row, 0.0)),
                      0.0)
        xj = x[:, j * p:(j + 1) * p].astype(F32)
        sj = s[j]
        end = row[:, ch - 1:ch]
        put(j, _dot(m * cb, xj * dt_t[:, j:j + 1])
            + jnp.exp(col) * _dot(c, sj),
            _dot(jnp.exp(row), last) * sj + _dot_tn(
                b, xj * (jnp.exp(end - col) * dt_t[:, j:j + 1])))


def decode_body(x, dt, a, b, c, s):
    """The recurrence's one step, every head at once: x [..., H, P]; dt
    [..., H]; a [H]; b, c [..., G, N] (head ``h`` reads group ``h //
    (H / G)``); s [..., H, N, P]. Returns ``(y [..., H, P], s)``."""
    heads, groups = x.shape[-2], b.shape[-2]
    b = jnp.repeat(b, heads // groups, axis=-2)
    c = jnp.repeat(c, heads // groups, axis=-2)
    s = s * jnp.exp(dt * a)[..., None, None] + \
        (dt[..., None] * b)[..., :, None] * x[..., None, :]
    return jnp.sum(c[..., :, None] * s, axis=-2), s


# -- the prompt: chunks ------------------------------------------------------

def _chunk_kernel(lens_ref, a_ref, x_ref, dt_ref, b_ref, c_ref, s0_ref,
                  y_ref, s_ref, *, chunk: int):
    bi, ci = pl.program_id(0), pl.program_id(2)
    left = lens_ref[bi] - ci * chunk

    @pl.when(ci == 0)
    def _():
        s_ref[0] = s0_ref[0]

    @pl.when(left > 0)
    def _():
        p = s_ref.shape[3]

        def put(j, y, s):
            y_ref[0, :, j * p:(j + 1) * p] = y.astype(y_ref.dtype)
            s_ref[0, j] = s

        chunk_body(x_ref.at[0], dt_ref[0], a_ref[...], b_ref[0], c_ref[0],
                   s_ref.at[0], left, put)

    @pl.when(left <= 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)


def _chunk_pallas(x, dt, a, b, c, s0, lens, groups: int, chunk: int,
                  interpret: bool = False):
    bsz, t, _ = x.shape
    heads, n, p = s0.shape[1:]
    hb = heads // groups
    nc = t // chunk
    dt = dt.transpose(0, 2, 1)  # [B, H, T]: a head a row

    def tok(bi, g, ci, lens):
        # chunks past the sequence's end re-read its last chunk: no DMA
        last = jnp.maximum(lens[bi] - 1, 0) // chunk
        return (bi, jnp.minimum(ci, last), g)

    def dt_map(bi, g, ci, lens):
        last = jnp.maximum(lens[bi] - 1, 0) // chunk
        return (bi, g, jnp.minimum(ci, last))

    def state(bi, g, ci, lens):
        return (bi, g, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(bsz, groups, nc),
        in_specs=[pl.BlockSpec((hb, 1), lambda bi, g, ci, lens: (g, 0)),
                  pl.BlockSpec((1, chunk, hb * p), tok),
                  pl.BlockSpec((1, hb, chunk), dt_map),
                  pl.BlockSpec((1, chunk, n), tok),
                  pl.BlockSpec((1, chunk, n), tok),
                  pl.BlockSpec((1, hb, n, p), state)],
        out_specs=[pl.BlockSpec((1, chunk, hb * p),
                                lambda bi, g, ci, lens: (bi, ci, g)),
                   pl.BlockSpec((1, hb, n, p), state)])
    rows = bsz * t
    call = named_pallas_call(
        "ssd_chunk_fwd", functools.partial(_chunk_kernel, chunk=chunk),
        grid_spec=grid_spec, interpret=interpret,
        out_shape=[jax.ShapeDtypeStruct((bsz, t, heads * p), x.dtype),
                   jax.ShapeDtypeStruct((bsz, heads, n, p), F32)],
        cost_estimate=pl.CostEstimate(
            flops=rows * (2 * chunk * n * groups
                          + heads * (2 * chunk * p + 4 * n * p)),
            transcendentals=rows * heads * (chunk + 2),
            bytes_accessed=(2 * x.size + b.size + c.size) * x.dtype.itemsize
            + 4 * (dt.size + 2 * s0.size)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT))
    return call(lens.astype(jnp.int32), a.reshape(heads, 1), x, dt, b, c,
                s0)


def _chunk_jnp(x, dt, a, b, c, s0, lens, groups: int, chunk: int):
    bsz, t, _ = x.shape
    heads = s0.shape[1]
    hb = heads // groups
    nc = t // chunk

    def split(v):  # [B, T, G * w] -> [NC, B, G, C, w]
        return v.reshape(bsz, nc, chunk, groups, -1).transpose(1, 0, 3, 2, 4)

    dts = dt.reshape(bsz, nc, chunk, groups, hb).transpose(1, 0, 3, 4, 2)
    left = lens.astype(jnp.int32)[None] - \
        jnp.arange(nc, dtype=jnp.int32)[:, None] * chunk  # [NC, B]
    a = a.reshape(groups, hb, 1)

    def group(x, dt, a, b, c, s, n_valid):
        ys, ss = [None] * hb, [None] * hb

        def put(j, y, s_new):
            ys[j], ss[j] = y, s_new

        chunk_body(x, dt, a, b, c, s, n_valid, put)
        return jnp.concatenate(ys, axis=1), jnp.stack(ss)

    body = jax.vmap(jax.vmap(group, in_axes=(0, 0, 0, 0, 0, 0, None)),
                    in_axes=(0, 0, None, 0, 0, 0, 0))  # B, G

    def step(s, xs):
        xc, dc, bc, cc, n_ok = xs
        y, s_new = body(xc, dc, a, bc, cc, s, n_ok)
        live = (n_ok > 0)[:, None, None, None, None]
        return jnp.where(live, s_new, s), jnp.where(live[..., 0], y, 0.0)

    s, y = jax.lax.scan(step, s0.reshape(bsz, groups, hb, *s0.shape[2:]),
                        (split(x), dts, split(b), split(c), left))
    y = y.transpose(1, 0, 3, 2, 4).reshape(bsz, t, -1)
    return y.astype(x.dtype), s.reshape(s0.shape)


def ssd_chunk_fwd(x, dt, a, b, c, state, lens, *, groups: int,
                  chunk: int = CHUNK, interpret: bool = False):
    """The recurrence over right-padded prompts. x: [B, T, H * P]; dt:
    [B, T, H] (after its softplus); a: [H] (``A``, negative); b, c: [B,
    T, G * N]; ``state`` [B, H, N, P] float32 is what each row starts
    from; ``lens`` [B]: rows past it leave the state alone and their
    outputs are zero or unread. T is padded to whole chunks here.
    Returns ``(y [B, T, H * P] in x's dtype, final state)``."""
    bsz, t, _ = x.shape
    heads, n, p = state.shape[1:]
    chunk = min(chunk, -(-t // 8) * 8)
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                       for v in (x, dt, b, c))
    lens = jnp.minimum(lens.astype(jnp.int32), t)
    dt, a, state = dt.astype(F32), a.astype(F32), state.astype(F32)
    if interpret or ssd_supported(n, p, heads // groups):
        y, s = _chunk_pallas(x, dt, a, b, c, state, lens, groups, chunk,
                             interpret)
    else:
        y, s = _chunk_jnp(x, dt, a, b, c, state, lens, groups, chunk)
    return y[:, :t], s


# -- one token a live slot ---------------------------------------------------

def _decode_kernel(src_ref, live_ref, dtx_ref, dec_ref, b_ref, c_ref, s_ref,
                   y_ref, so_ref):
    bi = pl.program_id(0)
    hb, n = s_ref.shape[1:3]

    @pl.when(live_ref[bi] > 0)
    def _():
        dtx, dec = dtx_ref[0], dec_ref[0]
        # the group's B and C a column each: a product with the identity
        # puts them there (exact in float32)
        eye = _eye(n).astype(F32)
        b_col = _dot_nt(eye, b_ref[0, 0].astype(F32))
        c_col = _dot_nt(eye, c_ref[0, 0].astype(F32))
        for j in range(hb):
            s = s_ref[0, j] * dec[j:j + 1] + b_col * dtx[j:j + 1]
            so_ref[0, j] = s
            y_ref[0, j:j + 1, :] = jnp.sum(
                c_col * s, axis=0, keepdims=True).astype(y_ref.dtype)

    @pl.when(live_ref[bi] == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)


def _decode_pallas(dtx, dec, b, c, pool, src, live, out_dtype,
                   interpret=False):
    bsz, heads, p = dtx.shape
    groups, n = b.shape[1:]
    hb = heads // groups
    b = b.reshape(bsz, groups, 1, n)
    c = c.reshape(bsz, groups, 1, n)

    def vec(bi, g, src, live):
        return (bi, g, 0)

    def grp(bi, g, src, live):
        return (bi, g, 0, 0)

    def row(bi, g, src, live):
        # a parked slot stays on the block the step before it held:
        # nothing is fetched and nothing written for it
        return (src[bi], jnp.where(live[bi] > 0, g, groups - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(bsz, groups),
        in_specs=[pl.BlockSpec((1, hb, p), vec),
                  pl.BlockSpec((1, hb, p), vec),
                  pl.BlockSpec((1, 1, 1, n), grp),
                  pl.BlockSpec((1, 1, 1, n), grp),
                  pl.BlockSpec((1, hb, n, p), row)],
        out_specs=[pl.BlockSpec((1, hb, p), vec),
                   pl.BlockSpec((1, hb, n, p), row)])
    call = named_pallas_call(
        "ssd_decode", _decode_kernel, grid_spec=grid_spec,
        interpret=interpret,
        out_shape=[jax.ShapeDtypeStruct((bsz, heads, p), out_dtype),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the state pool is updated in place (operand 6 counts the two
        # prefetched scalars)
        input_output_aliases={6: 1},
        cost_estimate=pl.CostEstimate(
            flops=5 * bsz * heads * n * p, transcendentals=0,
            bytes_accessed=8 * bsz * heads * n * p),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT))
    return call(src, live, dtx, dec, b, c, pool)


def ssd_decode(x, dt, a, b, c, pool, rows, live, *, interpret: bool = False):
    """One token a sequence against the state pool, in place. x: [B, H,
    P]; dt: [B, H] (after its softplus); a: [H]; b, c: [B, G, N];
    ``pool`` [R + 1, H, N, P] float32, its last row scratch; ``rows``
    [B]: each batch row's pool row; ``live`` [B] bool: a row that is not
    live (a parked slot) is skipped and its pool row is left as it is.
    Returns ``(y [B, H, P] in x's dtype, pool)``."""
    heads, n, p = pool.shape[1:]
    dt, a = dt.astype(F32), a.astype(F32)
    rows = rows.astype(jnp.int32)
    if interpret or ssd_supported(n, p, heads // b.shape[1]):
        # a parked slot's block index: the newest live slot's before it,
        # the scratch row where there is none
        at = jax.lax.cummax(jnp.where(
            live, jnp.arange(rows.shape[0], dtype=jnp.int32), -1))
        src = jnp.where(at >= 0, rows[jnp.maximum(at, 0)],
                        pool.shape[0] - 1).astype(jnp.int32)
        # dt x and the decay a row a head: the kernel scales the state
        # by rows (Mosaic cannot broadcast a [1, 1] value over both axes)
        dtx = dt[..., None] * x.astype(F32)
        dec = jnp.broadcast_to(jnp.exp(dt * a)[..., None], dtx.shape)
        return _decode_pallas(dtx, dec, b, c, pool, src,
                              live.astype(jnp.int32), x.dtype, interpret)
    y, s = decode_body(x.astype(F32), dt, a, b.astype(F32), c.astype(F32),
                       pool[rows])
    at = jnp.where(live, rows, pool.shape[0] - 1)
    y = jnp.where(live[:, None, None], y, 0.0).astype(x.dtype)
    return y, pool.at[at].set(s)
