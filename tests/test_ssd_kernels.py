"""The two SSD kernels (ops/pallas/ssd.py) in interpret mode, and the
plain-jnp path that runs the same mathematics off the TPU, against the
position-by-position recurrence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import ssd


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _recurrence(x, dt, a, b, c, s0, lens, groups):
    """y [B, T, H * P] and the final state, a position at a time."""
    bsz, t, _ = x.shape
    heads, n, p = s0.shape[1:]

    def step(s, xs):
        xt, dtt, bt, ct, i = xs
        y, s2 = ssd.decode_body(xt.reshape(bsz, heads, p), dtt, a,
                                bt.reshape(bsz, groups, n),
                                ct.reshape(bsz, groups, n), s)
        return jnp.where((i < lens)[:, None, None, None], s2, s), y

    s, y = jax.lax.scan(step, s0, (x.swapaxes(0, 1), dt.swapaxes(0, 1),
                                   b.swapaxes(0, 1), c.swapaxes(0, 1),
                                   jnp.arange(t)))
    return y.swapaxes(0, 1).reshape(bsz, t, -1), s


def _inputs(bsz, t, heads, groups, n, p, seed, dt_hi=0.1):
    rng = np.random.default_rng(seed)

    def r(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    dt = jnp.asarray(rng.uniform(1e-3, dt_hi, (bsz, t, heads)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (heads,)), jnp.float32)
    return (r(bsz, t, heads * p), dt, a, r(bsz, t, groups * n),
            r(bsz, t, groups * n), r(bsz, heads, n, p))


def _check(got, want, lens, atol):
    (y, s), (y_ref, s_ref) = got, want
    seen = (np.arange(y.shape[1])[None] < np.asarray(lens)[:, None])[..., None]
    np.testing.assert_allclose(np.where(seen, np.asarray(y), 0.0),
                               np.where(seen, np.asarray(y_ref), 0.0),
                               atol=atol, rtol=0)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=atol,
                               rtol=0)


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("t,lens,chunk", [
    (32, (32,), 16),          # two whole chunks: the boundary between them
    (40, (40, 9), 16),        # not a multiple of the chunk; a row in one
    (70, (33, 70), 16),       # padding past a row's length, whole chunks
    (5, (5, 2), 128),         # shorter than a chunk of any size
])
def test_chunked_scan_equals_the_recurrence(t, lens, chunk, interpret):
    """Two groups of heads that share their B and C, a starting state
    that is not zero."""
    x, dt, a, b, c, s0 = _inputs(len(lens), t, 4, 2, 16, 8, seed=t)
    lens = jnp.asarray(lens, jnp.int32)
    got = ssd.ssd_chunk_fwd(x, dt, a, b, c, s0, lens, groups=2, chunk=chunk,
                            interpret=interpret)
    _check(got, _recurrence(x, dt, a, b, c, s0, lens, 2), lens, 2e-5)


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "kernel"])
def test_hard_decay_stays_finite(interpret):
    """``dt A`` near -30 a position: the running sum leaves float32's
    range inside a chunk, every exponent the kernel takes does not."""
    x, dt, a, b, c, s0 = _inputs(1, 48, 2, 1, 16, 8, seed=3, dt_hi=2.0)
    lens = jnp.asarray([48], jnp.int32)
    got = ssd.ssd_chunk_fwd(x, dt, a, b, c, s0, lens, groups=1, chunk=16,
                            interpret=interpret)
    assert np.isfinite(np.asarray(got[0])).all()
    _check(got, _recurrence(x, dt, a, b, c, s0, lens, 1), lens, 2e-5)


def test_a_prompt_in_segments_that_pass_the_state_on():
    x, dt, a, b, c, s0 = _inputs(2, 75, 4, 2, 16, 8, seed=7)
    lens = jnp.asarray([75, 31], jnp.int32)
    whole = ssd.ssd_chunk_fwd(x, dt, a, b, c, s0, lens, groups=2, chunk=16)
    s, outs = s0, []
    for lo in range(0, 75, 24):  # segments of 24: chunks of 16 inside
        sl = slice(lo, lo + 24)
        left = jnp.clip(lens - lo, 0, 24)
        y, s = ssd.ssd_chunk_fwd(x[:, sl], dt[:, sl], a, b[:, sl], c[:, sl],
                                 s, left, groups=2, chunk=16)
        outs.append(y)
    _check((jnp.concatenate(outs, 1), s), whole, lens, 2e-5)


@pytest.mark.parametrize("live", [
    (True, True, True, True),
    (False, True, False, True),   # the first slot parked
    (True, False, False, True),   # parked slots behind a live one
    (False, False, False, False),
], ids=["all", "first_parked", "parked_behind", "none"])
def test_decode_moves_live_rows_in_place_and_skips_parked(live):
    rng = np.random.default_rng(3)
    bsz, heads, groups, n, p = 4, 16, 2, 128, 128

    def r(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    pool = r(bsz + 2, heads, n, p)
    x, b, c = r(bsz, heads, p), r(bsz, groups, n), r(bsz, groups, n)
    dt = jnp.asarray(rng.uniform(1e-3, 0.1, (bsz, heads)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, (heads,)), jnp.float32)
    rows = jnp.asarray([4, 0, 2, 1], jnp.int32)  # not the pool's order
    live = jnp.asarray(live)
    y_want, s_want = ssd.decode_body(x, dt, a, b, c, pool[rows])
    keep = np.asarray(live)
    for interpret in (False, True):
        y, out = ssd.ssd_decode(x, dt, a, b, c, pool, rows, live,
                                interpret=interpret)
        got = np.asarray(out)[np.asarray(rows)]
        np.testing.assert_allclose(np.asarray(y)[keep],
                                   np.asarray(y_want)[keep], atol=2e-5)
        np.testing.assert_allclose(got[keep], np.asarray(s_want)[keep],
                                   atol=2e-6)
        # a parked slot's row, and the rows no slot holds, are what they
        # were, to the bit
        assert (got[~keep] == np.asarray(pool)[np.asarray(rows)][~keep]).all()
        assert (np.asarray(out)[3] == np.asarray(pool)[3]).all()
        assert not np.asarray(y)[~keep].any()


def test_the_kernels_keep_their_names():
    from paddle_tpu.ops.pallas.naming import KERNEL_SCOPE
    x, dt, a, b, c, s0 = _inputs(1, 16, 2, 1, 16, 8, seed=0)
    text = jax.jit(lambda *v: ssd.ssd_chunk_fwd(
        *v, groups=1, interpret=True)).lower(
        x, dt, a, b, c, s0, jnp.asarray([16], jnp.int32)).as_text(
        debug_info=True)
    assert KERNEL_SCOPE + "ssd_chunk_fwd" in text
    pool = jnp.zeros((3, 2, 16, 8), jnp.float32)
    text = jax.jit(lambda *v: ssd.ssd_decode(*v, interpret=True)).lower(
        x[:, 0].reshape(1, 2, 8), dt[:, 0], a, b[:, 0].reshape(1, 1, 16),
        c[:, 0].reshape(1, 1, 16), pool, jnp.asarray([0], jnp.int32),
        jnp.asarray([True])).as_text(debug_info=True)
    assert KERNEL_SCOPE + "ssd_decode" in text
