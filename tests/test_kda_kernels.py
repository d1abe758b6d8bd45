"""The two KDA kernels (ops/pallas/kda.py) in interpret mode, and the
plain-jnp path that runs the same mathematics off the TPU, against the
token-by-token recurrence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import kda


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _recurrence(q, k, v, g, beta, s0, lens, heads):
    """o [B, T, H * d_v] and the final state, a token at a time."""
    b, t, _ = q.shape

    def split(x):
        return x.reshape(b, t, heads, -1).swapaxes(0, 1)

    scale = (q.shape[2] // heads) ** -0.5

    def step(s, xs):
        qt, kt, vt, gt, bt, i = xs
        o, s2 = kda.decode_body(qt, kt, vt, gt, bt[..., None], s, scale)
        return jnp.where((i < lens)[:, None, None, None], s2, s), o

    s, o = jax.lax.scan(step, s0, (split(q), split(k), split(v), split(g),
                                   beta.swapaxes(0, 1), jnp.arange(t)))
    return o.swapaxes(0, 1).reshape(b, t, -1), s


def _inputs(b, t, h, dk, dv, seed, beta_lo=0.0, decay=0.3, same_key=False):
    rng = np.random.default_rng(seed)

    def n(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    k = n(b, t, h * dk)
    if same_key:  # every position the same key: the worst system
        k = jnp.tile(k[:, :1], (1, t, 1))
    g = -jnp.asarray(rng.uniform(1e-3, decay, (b, t, h * dk)), jnp.float32)
    beta = jnp.asarray(rng.uniform(beta_lo, 2.0, (b, t, h)), jnp.float32)
    return n(b, t, h * dk), k, n(b, t, h * dv), g, beta, n(b, h, dk, dv)


def _check(got, want, lens, atol):
    (o, s), (o_ref, s_ref) = got, want
    t = o.shape[1]
    seen = (np.arange(t)[None] < np.asarray(lens)[:, None])[..., None]
    np.testing.assert_allclose(np.where(seen, np.asarray(o), 0.0),
                               np.where(seen, np.asarray(o_ref), 0.0),
                               atol=atol, rtol=0)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=atol,
                               rtol=0)


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("t,lens", [
    (64, (64,)),          # one whole chunk
    (70, (70, 9)),        # not a multiple of the chunk; a row inside one
    (200, (130, 200)),    # padding past a row's length over whole chunks
    (5, (5, 2)),          # shorter than a chunk of any size
])
def test_chunked_scan_equals_the_recurrence(t, lens, interpret):
    h, d = (1, 128) if interpret else (2, 16)
    q, k, v, g, beta, s0 = _inputs(len(lens), t, h, d, d, seed=t)
    lens = jnp.asarray(lens, jnp.int32)
    got = kda.kda_chunk_fwd(q, k, v, g, beta, s0, lens, heads=h,
                            interpret=interpret)
    _check(got, _recurrence(q, k, v, g, beta, s0, lens, h), lens, 5e-6)


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "kernel"])
def test_beta_near_two_over_repeated_keys_and_hard_decay(interpret):
    """``I - beta k k^T`` nearly a reflection at every position, every
    key the same, and a decay whose running sum leaves float32's range
    inside a chunk (64 x 1.6 = 102 > 88): the blocked exponents and the
    forward substitution hold."""
    h, d = (1, 128) if interpret else (2, 16)
    q, k, v, g, beta, s0 = _inputs(1, 96, h, d, d, seed=1, beta_lo=1.9,
                                   decay=0.02, same_key=True)
    lens = jnp.asarray([96], jnp.int32)
    got = kda.kda_chunk_fwd(q, k, v, g, beta, s0, lens, heads=h,
                            interpret=interpret)
    _check(got, _recurrence(q, k, v, g, beta, s0, lens, h), lens, 2e-4)
    q, k, v, g, beta, s0 = _inputs(1, 96, h, d, d, seed=2, decay=1.6)
    g = jnp.full_like(g, -1.6)
    got = kda.kda_chunk_fwd(q, k, v, g, beta, s0, lens, heads=h,
                            interpret=interpret)
    assert np.isfinite(np.asarray(got[0])).all()
    _check(got, _recurrence(q, k, v, g, beta, s0, lens, h), lens, 5e-6)


def test_a_prompt_in_segments_that_pass_the_state_on():
    h, d, t = 2, 16, 150
    q, k, v, g, beta, s0 = _inputs(2, t, h, d, d, seed=7)
    lens = jnp.asarray([150, 77], jnp.int32)
    whole = kda.kda_chunk_fwd(q, k, v, g, beta, s0, lens, heads=h)
    s, outs = s0, []
    for lo in range(0, t, 48):  # segments of 48: chunks of 48 inside
        sl = slice(lo, lo + 48)
        left = jnp.clip(lens - lo, 0, 48)
        o, s = kda.kda_chunk_fwd(q[:, sl], k[:, sl], v[:, sl], g[:, sl],
                                 beta[:, sl], s, left, heads=h)
        outs.append(o)
    _check((jnp.concatenate(outs, 1), s), whole, lens, 5e-6)


@pytest.mark.parametrize("live", [
    (True, True, True, True, True),
    (False, True, False, True, True),   # the first slot parked
    (True, False, False, True, False),  # parked slots behind a live one
    (False, False, False, False, False),
], ids=["all", "first_parked", "parked_behind", "none"])
def test_decode_moves_live_rows_in_place_and_skips_parked(live):
    rng = np.random.default_rng(3)
    b, h, d = 5, 16, 128

    def n(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    pool = n(b + 1, h, d, d)
    q, k, v = n(b, h, d), n(b, h, d), n(b, h, d)
    g = -jnp.asarray(rng.uniform(0, 1, (b, h, d)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 2, (b, h)), jnp.float32)
    rows = jnp.arange(b, dtype=jnp.int32)
    live = jnp.asarray(live)
    o_want, s_want = kda.decode_body(q, k, v, g, beta[..., None], pool[:b],
                                     d ** -0.5)
    keep = np.asarray(live)
    for interpret in (False, True):
        o, out = kda.kda_decode(q, k, v, g, beta, pool, rows, live,
                                interpret=interpret)
        np.testing.assert_allclose(np.asarray(o)[keep],
                                   np.asarray(o_want)[keep], atol=2e-6)
        np.testing.assert_allclose(np.asarray(out[:b])[keep],
                                   np.asarray(s_want)[keep], atol=2e-6)
        # a parked slot's row is what it was, to the bit
        assert (np.asarray(out[:b])[~keep]
                == np.asarray(pool[:b])[~keep]).all()
        assert not np.asarray(o)[~keep].any()


def test_decode_rows_need_not_be_the_pool_first_rows():
    rng = np.random.default_rng(4)
    h, d = 8, 128
    pool = jnp.asarray(rng.standard_normal((7, h, d, d)), jnp.float32)
    q, k, v = (jnp.asarray(rng.standard_normal((2, h, d)), jnp.float32)
               for _ in range(3))
    g = -jnp.asarray(rng.uniform(0, 1, (2, h, d)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 2, (2, h)), jnp.float32)
    rows = jnp.asarray([4, 1], jnp.int32)
    live = jnp.asarray([True, True])
    a = kda.kda_decode(q, k, v, g, beta, pool, rows, live)
    b = kda.kda_decode(q, k, v, g, beta, pool, rows, live, interpret=True)
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]), atol=2e-6)
    np.testing.assert_allclose(np.asarray(a[1][:6]), np.asarray(b[1][:6]),
                               atol=2e-6)
    assert (np.asarray(b[1])[[0, 2, 3, 5]]
            == np.asarray(pool)[[0, 2, 3, 5]]).all()


def test_the_kernels_keep_their_names():
    from paddle_tpu.ops.pallas.naming import KERNEL_SCOPE
    h, d = 1, 128
    q, k, v, g, beta, s0 = _inputs(1, 64, h, d, d, seed=0)
    text = jax.jit(lambda *a: kda.kda_chunk_fwd(
        *a, heads=h, interpret=True)).lower(
        q, k, v, g, beta, s0, jnp.asarray([64], jnp.int32)).as_text(
        debug_info=True)
    assert KERNEL_SCOPE + "kda_chunk_fwd" in text
