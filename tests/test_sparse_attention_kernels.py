"""The sparse attention kernels (ops/pallas/sparse_attention.py), each
through its ``jax.numpy`` path and through the Pallas interpreter,
against a dense-gather reference written here: the indexer's scores,
the exact k-th largest, a decoded token's walk over its selected
positions (the latent walk of ops/pallas/paged_attention.py handed the
selection) and a prompt's sparse attention. Contexts with fewer complete
blocks than ``k`` (everything before a query is attended to) and with
more, prompts that end inside a block, and parked slots.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import sparse_attention as sa

POOL = 4


def _dense_scores(qi, w, keys, qpos):
    """``sum_j w_j relu(q_j . kbar_b)`` over every block before the
    query's own, in float64."""
    qi, w, keys = (np.asarray(x, np.float64) for x in (qi, w, keys))
    dots = np.maximum(np.einsum("bshd,bnd->bshn", qi, keys), 0.0)
    s = np.einsum("bsh,bshn->bsn", w, dots)
    blk = np.arange(keys.shape[1])
    return np.where(blk[None, None] < (np.asarray(qpos) // POOL)[..., None],
                    s, -np.inf)


def _dense_selected(scores, qpos, k):
    """The positions each query attends to: its k best complete blocks
    (ties kept) and its own block up to itself, or everything before it
    where it has k complete blocks or fewer."""
    b, s, n = scores.shape
    out = np.zeros((b, s, n * POOL), bool)
    for i in range(b):
        for j in range(s):
            t = int(qpos[i, j])
            own = t // POOL
            if own <= k:
                out[i, j, :t + 1] = True
                continue
            kth = np.sort(scores[i, j, :own])[-k]
            for blk in np.nonzero(scores[i, j, :own] >= kth)[0]:
                out[i, j, blk * POOL:(blk + 1) * POOL] = True
            out[i, j, own * POOL:t + 1] = True
    return out


def _attend(q, rows, seen, scale):
    """Softmax over the seen positions of ``q . row * scale``, times the
    rows (the absorbed form: the rows are keys and values)."""
    q, rows = np.asarray(q, np.float64), np.asarray(rows, np.float64)
    logits = np.einsum("hw,tw->ht", q, rows) * scale
    logits = np.where(seen[None], logits, -np.inf)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p = np.where(seen[None], p, 0.0)
    return np.einsum("ht,tw->hw", p / p.sum(-1, keepdims=True), rows)


def test_kth_largest_is_exact_with_ties_negatives_and_infinities():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((5, 300)).astype(np.float32)
    x[1, :40] = 0.25  # a tie at the threshold
    x[2] = -np.abs(x[2])
    x[3, 100:] = -np.inf  # 100 finite
    x[4, :7] = -0.0
    for k in (1, 17, 100, 300):
        got = np.asarray(sa.kth_largest(jnp.asarray(x), k))
        want = np.sort(x, -1)[:, -k]
        np.testing.assert_array_equal(got, want + 0.0)
    assert np.isneginf(np.asarray(sa.kth_largest(jnp.asarray(x), 301))).all()


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("lo", [0, 1984], ids=["first_segment", "later"])
def test_index_scores_equal_the_dense_ones(interpret, lo):
    """32 queries a tile, 512 blocks a tile: a segment of 64 queries at
    positions ``lo ..`` over 512 pooled keys."""
    rng = np.random.default_rng(lo)
    b, s, hi, di, n = 2, 64, 4, 128, 512
    qi = jnp.asarray(rng.standard_normal((b, s, hi, di)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((b, s, hi)), jnp.float32)
    keys = jnp.asarray(rng.standard_normal((b, n, di)), jnp.float32)
    got = np.asarray(sa.index_scores(qi, w, keys, lo, POOL,
                                     interpret=interpret))
    qpos = np.broadcast_to(lo + np.arange(s), (b, s))
    want = _dense_scores(qi, w, keys, qpos)
    np.testing.assert_array_equal(np.isneginf(got), np.isneginf(want))
    fin = np.isfinite(want)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-4, atol=1e-3)


def _pages(rng, b, mp, page, width, rank):
    n_pool = b * mp + 1
    pages = np.zeros((n_pool, page, width), np.float32)
    pages[..., :rank] = rng.standard_normal((n_pool, page, rank))
    table = rng.permutation(b * mp).reshape(b, mp).astype(np.int32)
    return jnp.asarray(pages), jnp.asarray(table)


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("k", [4, 64], ids=["selects", "sees_all"])
def test_a_decoded_token_attends_to_its_selected_positions(interpret, k):
    """Slots with 57, 0 (parked), 200 and 33 positions over pages of 16
    (a block of two pages a loop turn); the selection from the indexer's
    scores over the slot's pooled keys, the tail included."""
    rng = np.random.default_rng(k)
    b, mp, page, width, rank, heads, di = 4, 16, 16, 128, 128, 8, 16
    pages, table = _pages(rng, b, mp, page, width, rank)
    lens = np.asarray([57, 0, 200, 33], np.int32)
    pos = np.maximum(lens - 1, 0)
    n = mp * page // POOL
    keys = rng.standard_normal((b, n, di)).astype(np.float32)
    qi = rng.standard_normal((b, 2, di)).astype(np.float32)
    w = np.abs(rng.standard_normal((b, 2))).astype(np.float32)
    scores = sa.index_scores_reference(
        jnp.asarray(qi)[:, None], jnp.asarray(w)[:, None],
        jnp.asarray(keys), jnp.asarray(pos)[:, None], POOL)[:, 0]
    tau = sa.kth_largest(scores, k)
    picked = sa.select_blocks(scores, tau, jnp.asarray(pos), POOL)
    sel = jnp.repeat(picked, POOL, axis=1)
    q = jnp.asarray(rng.standard_normal((b, heads, rank)), jnp.float32)
    got = np.asarray(pa.paged_attention_latent(
        q, pages, table, jnp.asarray(lens), rank, 0.1, block=2,
        interpret=interpret, sel=sel))
    want_sel = _dense_selected(_dense_scores(qi[:, None], w[:, None], keys,
                                             pos[:, None]), pos[:, None], k)
    rows = np.asarray(pages)[np.asarray(table)].reshape(b, mp * page, width)
    for i in range(b):
        if lens[i] == 0:
            assert not got[i].any()  # a parked slot: zeros out
            continue
        seen = want_sel[i, 0, :mp * page]
        assert seen[:lens[i]].sum() == (lens[i] if (lens[i] - 1) // POOL <= k
                                        else k * POOL + (lens[i] - 1) % POOL
                                        + 1)
        want = _attend(q[i], rows[i, :, :rank], seen, 0.1)
        np.testing.assert_allclose(got[i], want, rtol=2e-5, atol=2e-5)


def _prompt_case(lo, s, k, b, seed):
    """A segment of ``s`` queries at ``lo ..`` over a prompt of 1024 rows
    (two key tiles of 512), 4 heads, the selection from the indexer's
    scores and the ``k``-th largest."""
    rng = np.random.default_rng(seed)
    t, w, heads, hi, di = 1024, 128, 4, 2, 16
    rows = jnp.asarray(rng.standard_normal((b, t, w)), jnp.float32)
    qa = jnp.asarray(rng.standard_normal((b, heads, s, w)), jnp.float32)
    qi = jnp.asarray(rng.standard_normal((b, s, hi, di)), jnp.float32)
    wi = jnp.asarray(np.abs(rng.standard_normal((b, s, hi))), jnp.float32)
    keys = jnp.asarray(rng.standard_normal((b, t // POOL, di)), jnp.float32)
    scores = sa.index_scores(qi, wi, keys, lo, POOL)
    return qa, rows, scores, sa.kth_largest(scores, k)


# each a function of the segment's first position and size
_PROMPT_LENGTHS = {
    "none": lambda lo, s: None,
    "in_a_tile": lambda lo, s: [lo + s // 2 + 5],
    "key_tile_edge": lambda lo, s: [512],
    "past_the_end": lambda lo, s: [max(lo - 3, 0)],
    "two_unequal": lambda lo, s: [lo + 21, 1024],
}


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("lo,s,k", [(0, 64, 8), (960, 64, 16),
                                    (960, 64, 300), (480, 64, 8)],
                         ids=["first_segment", "later", "sees_all",
                              "across_key_tiles"])
@pytest.mark.parametrize("lengths", list(_PROMPT_LENGTHS),
                         ids=list(_PROMPT_LENGTHS))
def test_a_prompt_attends_to_its_selected_positions(interpret, lo, s, k,
                                                    lengths):
    """Live queries (all of them without lengths) attend to what they
    select; a tile of 16 queries that starts at or past its sequence's
    length comes out zero."""
    lengths = _PROMPT_LENGTHS[lengths](lo, s)
    b = 1 if lengths is None else len(lengths)
    qa, rows, scores, tau = _prompt_case(lo, s, k, b, lo + k)
    got = np.asarray(sa.sparse_prefill_latent(
        qa, rows, scores, tau, lo, POOL, 0.05, interpret=interpret,
        lengths=None if lengths is None else jnp.asarray(lengths, jnp.int32)))
    qpos = lo + np.arange(s)
    seen = _dense_selected(np.asarray(scores), np.broadcast_to(qpos, (b, s)),
                           k)
    for i in range(b):
        n = 1024 if lengths is None else lengths[i]
        live = [j for j in (0, 1, s // 2, s - 1, n - 1 - lo) if 0 <= j < s
                and qpos[j] < n]
        for j in live:
            want = _attend(np.asarray(qa)[i, :, j], np.asarray(rows)[i],
                           seen[i, j, :1024], 0.05)
            np.testing.assert_allclose(got[i, :, j], want, rtol=2e-5,
                                       atol=2e-5)
        past = lo + np.arange(s) // sa.Q_TILE * sa.Q_TILE >= n
        assert not got[i][:, past].any()


def test_live_rows_are_the_same_bits_with_and_without_lengths():
    """The kernel (interpreted) handed lengths computes a live query's row
    from the same tiles in the same order as without them."""
    lo, s = 480, 64
    qa, rows, scores, tau = _prompt_case(lo, s, 8, 3, 7)
    lengths = np.asarray([500, 1024, 517], np.int32)

    def call(n):
        return np.asarray(sa.sparse_prefill_latent(
            qa, rows, scores, tau, lo, POOL, 0.05, interpret=True,
            lengths=n))

    plain, ragged = call(None), call(jnp.asarray(lengths))
    for i, n in enumerate(lengths):
        live = lo + np.arange(s) < n
        np.testing.assert_array_equal(ragged[i][:, live], plain[i][:, live])
    # and the tiles counted: the segment's 4 query tiles each see key
    # tiles 0 and 1 (its diagonal lies in the second) but the first two
    # (0 only); with lengths a tile past its sequence's end sees none
    run, grid = sa.prefill_tile_pairs(jnp.asarray(lengths), lo, s)
    assert int(grid) == 3 * (1 + 1 + 2 + 2)
    assert int(run) == (1 + 1 + 0 + 0) + (1 + 1 + 2 + 2) + (1 + 1 + 2 + 0)


def test_the_prompt_kernels_layout_of_a_key_tile():
    """Inside a tile of 512 keys, position 4 b + r of the tile goes to
    row 128 r + b: a tile of block scores repeated once for each
    position of a block is the tile's mask."""
    rows = jnp.arange(1024, dtype=jnp.float32)[None, :, None]
    lay = np.asarray(sa.block_major(rows, 512, POOL))[0, :, 0]
    for tile in (0, 512):
        for r in range(POOL):
            np.testing.assert_array_equal(
                lay[tile + 128 * r: tile + 128 * (r + 1)],
                tile + POOL * np.arange(128) + r)


def test_the_decode_walk_reads_a_prompts_rows_through_the_page_table():
    """`jax.jit` of the decode kernel's plain path over a pool larger
    than the table: positions past a slot's length never count."""
    rng = np.random.default_rng(3)
    pages, table = _pages(rng, 2, 4, 16, 128, 64)
    sel = jnp.ones((2, 64), jnp.int32)
    q = jnp.asarray(rng.standard_normal((2, 4, 64)), jnp.float32)
    lens = jnp.asarray([10, 64], jnp.int32)
    out = jax.jit(lambda q, p, t, n, s: pa.paged_attention_latent(
        q, p, t, n, 64, 0.125, sel=s))(q, pages, table, lens, sel)
    rows = np.asarray(pages)[np.asarray(table)].reshape(2, 64, 128)[..., :64]
    for i, n in enumerate((10, 64)):
        seen = np.arange(64) < n
        np.testing.assert_allclose(np.asarray(out[i]),
                                   _attend(q[i], rows[i], seen, 0.125),
                                   rtol=2e-5, atol=2e-5)


def test_the_masked_walk_is_the_latent_walk_under_another_name():
    """Every position selected, the masked walk computes what the
    unmasked one does; each is one custom call under its own name."""
    rng = np.random.default_rng(5)
    pages, table = _pages(rng, 2, 4, 16, 128, 128)
    q = jnp.asarray(rng.standard_normal((2, 8, 128)), jnp.float32)
    lens = jnp.asarray([23, 64], jnp.int32)
    sel = jnp.ones((2, 64), jnp.int32)

    def walk(mask):
        return jax.jit(lambda q, p, t, n, s: pa.paged_attention_latent(
            q, p, t, n, 128, 0.2, block=2, interpret=True,
            sel=s if mask else None))

    got = walk(True)(q, pages, table, lens, sel)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(walk(False)(q, pages, table, lens,
                                                      sel)),
                               rtol=1e-6, atol=1e-6)
    for mask, name, other in ((True, "sparse_decode_latent",
                               "paged_decode_latent"),
                              (False, "paged_decode_latent",
                               "sparse_decode_latent")):
        text = str(jax.make_jaxpr(walk(mask))(q, pages, table, lens, sel))
        assert name in text and other not in text
