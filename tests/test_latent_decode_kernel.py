"""The absorbed latent decode kernel (ops/pallas/paged_attention.py
``paged_decode_latent``) in interpret mode against a gather-and-softmax
over the pages written here, and the routing of the public entry."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import paged_attention as pa

RANK, ROPE, W, PAGE = 128, 32, 256, 16


def _case(lens, heads=5, max_pages=9, seed=0, dtype=jnp.float32):
    """A pool whose pages a permutation hands out, rows ``[latent |
    rotary key | zeros]``; pages nobody owns hold large values, so a
    walk past a sequence's end shows."""
    rng = np.random.default_rng(seed)
    b = len(lens)
    n_pool = b * max_pages + 3
    pool = rng.standard_normal((n_pool, PAGE, W)).astype(np.float32)
    pool[..., RANK + ROPE:] = 0.0
    table = rng.permutation(n_pool - 1)[:b * max_pages] \
        .reshape(b, max_pages).astype(np.int32)
    owned = np.zeros(n_pool, bool)
    for row, n in zip(table, lens):
        owned[row[:-(-n // PAGE)]] = True
    pool[~owned] = 1e4
    q = rng.standard_normal((b, heads, RANK + ROPE)).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(table), jnp.asarray(lens, jnp.int32))


def _by_hand(q, pool, table, lens, scale):
    """Gather a slot's rows, softmax over its context, weigh the rows'
    first RANK columns: float64 numpy, a slot at a time."""
    q, pool = np.asarray(q, np.float64), np.asarray(pool, np.float64)
    out = np.zeros(q.shape[:2] + (RANK,))
    for i, n in enumerate(np.asarray(lens)):
        if n == 0:
            continue
        rows = pool[np.asarray(table)[i]].reshape(-1, W)[:n]
        s = q[i] @ rows[:, :RANK + ROPE].T * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        out[i] = (p / p.sum(-1, keepdims=True)) @ rows[:, :RANK]
    return out


@pytest.mark.parametrize("block", [1, 3, 4])
@pytest.mark.parametrize("lens", [
    (1, 37, 128, 16),    # one position; not multiples of the page; whole
    (0, 90, 0, 0),       # one live slot among parked ones
    (0, 0, 0),           # every slot parked
    (144, 143, 17, 15),  # the table's last page; a page boundary's sides
], ids=["ragged", "one_live", "all_parked", "boundaries"])
def test_kernel_equals_gather_and_softmax(lens, block):
    q, pool, table, n = _case(lens, seed=len(lens) + block)
    got = pa.paged_attention_latent(q, pool, table, n, RANK, 0.11,
                                    block=block, interpret=True)
    want = _by_hand(q, pool, table, n, 0.11)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=0)
    for i, length in enumerate(lens):
        if length == 0:  # a parked slot walks no page: zeros out
            assert not np.asarray(got[i]).any()


def test_reference_equals_gather_and_softmax():
    q, pool, table, n = _case((1, 0, 77, 144), seed=5)
    got = pa.paged_attention_latent_reference(q, pool, table, n, RANK, 0.2)
    np.testing.assert_allclose(np.asarray(got),
                               _by_hand(q, pool, table, n, 0.2),
                               atol=2e-5, rtol=0)


def test_bf16_pages_and_twenty_heads():
    """The served types: 20 heads padded to two bf16 sublane tiles."""
    q, pool, table, n = _case((40, 0, 129), heads=20, seed=7,
                              dtype=jnp.bfloat16)
    got = pa.paged_attention_latent(q, pool, table, n, RANK, 0.1,
                                    interpret=True)
    assert got.shape == (3, 20, RANK) and got.dtype == jnp.bfloat16
    want = _by_hand(q.astype(jnp.float32), pool.astype(jnp.float32),
                    table, n, 0.1)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=0.03, rtol=0)


def test_entry_routes_to_the_reference_off_the_chip_and_gates_shapes():
    q, pool, table, n = _case((9, 30), seed=2)
    assert not pa.paged_latent_supported(pool.shape, RANK)  # the CPU
    got = pa.paged_attention_latent(q, pool, table, n, RANK, 0.3)
    np.testing.assert_allclose(np.asarray(got),
                               _by_hand(q, pool, table, n, 0.3),
                               atol=2e-5, rtol=0)
    assert pa.paged_latent_supported((10, 64, 640), 512, backend="tpu")
    # a row that is not whole lane tiles, a page that is not whole
    # sublane tiles, a value part that ends inside a tile
    assert not pa.paged_latent_supported((10, 64, 576), 512, backend="tpu")
    assert not pa.paged_latent_supported((10, 8, 640), 512, backend="tpu")
    assert not pa.paged_latent_supported((10, 64, 640), 500, backend="tpu")
    with pa.head_sharding(object()):
        with pytest.raises(NotImplementedError, match="head sharding"):
            pa.paged_attention_latent(q, pool, table, n, RANK, 0.3)
