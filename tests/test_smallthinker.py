"""SmallThinker through the serving engine against the plain reference
(benchmarks/references/smallthinker.py), at the tiny preset on the CPU:
window and global layers with a cache each, grouped heads, rotary by
layer, dropless experts routed before attention, an untied head.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import smallthinker as ref
from paddle_tpu.distributed import moe
from paddle_tpu.inference.continuous_batching import ContinuousBatchingEngine
from paddle_tpu.models import (SmallThinkerForCausalLM, UnsupportedCacheLayout,
                               ring_pages, smallthinker_tiny)
from paddle_tpu.models.cache_layout import create_pools, ring_table
from paddle_tpu.models.gpt import PagedKVCache
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import grouped_matmul as gm
from paddle_tpu.ops.pallas import paged_attention as pa

PAGE = 4  # window 8 -> a ring of 4 pages, 16 positions


@pytest.fixture(scope="module")
def tiny():
    cfg = smallthinker_tiny()
    model = SmallThinkerForCausalLM(cfg, seed=3)
    params = {n: p.value for n, p in model.named_parameters()}
    cd = dict(dataclasses.asdict(cfg), engine={"page_size": PAGE})
    return cfg, model, params, cd


def _engine(model, **kw):
    base = dict(num_slots=3, page_size=PAGE, max_seq_len=128, num_pages=64,
                prompt_buckets=(8, 16, 32, 64))
    base.update(kw)
    return ContinuousBatchingEngine(model, **base)


def _drain(eng):
    while eng.num_active or eng.num_queued:
        eng.step()


# -- the cached path against the reference, on logits --------------------------

@pytest.mark.parametrize("plen,new", [
    (5, 6),     # shorter than the window
    (8, 6),     # the window exactly
    (13, 8),    # longer: the prefill sees keys that never reach the ring
    (40, 44),   # the ring of 16 positions wraps twice while decoding
])
def test_prefill_then_decode_equals_the_reference_on_logits(tiny, plen, new):
    cfg, model, params, cd = tiny
    rng = np.random.default_rng(plen)
    ids = rng.integers(0, cfg.vocab_size, plen + new)
    want = np.asarray(ref.forward_logits(cd, params, ids))
    layout = model.cache_layout()
    ring = ring_pages(cfg.sliding_window_size, PAGE)
    max_pages = 32
    pools = [create_pools(lc, max_pages if lc.window is None else ring, PAGE)
             for lc in layout]
    table = jnp.arange(max_pages, dtype=jnp.int32)[None]
    rows = jnp.zeros((1,), jnp.int32)

    def caches(lens):
        return [PagedKVCache(p[0], p[1], None, None,
                             table if lc.window is None
                             else ring_table(rows, ring), lens)
                for p, lc in zip(pools, layout)]

    bucket = -(-plen // 8) * 8  # a right-padded prompt, as the engine's
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :plen] = ids[:plen]
    logits, nc = model.forward(jnp.asarray(padded),
                               caches=caches(jnp.zeros((1,), jnp.int32)),
                               prefill_lens=jnp.asarray([plen], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits)[0, :plen], want[:plen],
                               atol=2e-5)
    for t in range(plen, plen + new):
        pools = [(c.k_pages, c.v_pages) for c in nc]
        logits, nc = model.forward(
            jnp.asarray(ids[t:t + 1][None], jnp.int32),
            caches=caches(jnp.asarray([t], jnp.int32)))
        np.testing.assert_allclose(np.asarray(logits)[0, 0], want[t],
                                   atol=2e-5, err_msg=f"position {t}")


def test_engine_serves_the_references_greedy_tokens(tiny):
    """Mixed lengths through admission, prefill buckets, the resident
    decode step and slot reuse: every served token is the reference's
    best at its position."""
    cfg, model, params, cd = tiny
    eng = _engine(model)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (5, 8, 13, 40, 3)]
    ids = [eng.submit(p, 40) for p in prompts]
    _drain(eng)
    for p, i in zip(prompts, ids):
        seq = eng.result(i)
        want = np.asarray(ref.forward_logits(cd, params, seq))
        best = want.argmax(-1)[len(p) - 1:-1]
        assert (best == seq[len(p):]).all()


# -- the window bound and the counters ----------------------------------------

def test_window_layers_hold_a_bounded_ring_at_every_step(tiny):
    cfg, model, _, _ = tiny
    eng = _engine(model)
    ring = ring_pages(cfg.sliding_window_size, PAGE)
    assert ring == 4
    for lc, r, k in zip(eng._layout, eng._rings, eng._pools["k"]):
        if lc.window is None:
            assert r is None and k.shape[0] == eng.num_pages + 1
        else:  # the rings and the scratch page, whatever num_pages is
            assert r == ring and k.shape[0] == eng.num_slots * ring + 1
    rng = np.random.default_rng(1)
    for n in (40, 9, 30):
        eng.submit(rng.integers(0, cfg.vocab_size, n), 70)
    seen = 0
    while eng.num_active or eng.num_queued:
        eng.step()
        rec = eng.step_timeline()[-1]
        assert rec["kv_pages"]["window"] <= ring * rec["slots_active"]
        assert rec["kv_pages"]["global"] == \
            eng.num_pages - rec["free_pages"]
        seen = max(seen, rec["kv_pages"]["global"])
        if rec.get("decode_ahead") and rec["slots_decoding"]:
            # a call that launched ahead settled the step before it, and
            # its record carries that step's counters: distinct experts
            # hit over 4 layers of 8; the most picks one got
            assert 1 <= rec["moe"]["touched"] <= 4 * 8
            assert 1 <= rec["moe"]["max_load"] <= rec["slots_decoding"]
        if rec["programs"].get("prefill"):
            assert rec["moe"]["max_over_mean"] >= 1.0
    # the global layers grew past what a ring may hold: 110 positions
    assert seen > 3 * ring
    totals = eng.flight_summary()
    assert totals["window_ring_pages"] == ring
    assert {"moe.touched", "moe.max_load", "moe.max_over_mean"} <= \
        set(totals["model_counters"])


def test_gpt_answers_a_uniform_layout_and_packs_nothing():
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    model = GPTForCausalLM(gpt_tiny())
    layout = model.cache_layout()
    assert len(layout) == model.config.num_layers
    assert all(lc.plain and lc.kv_heads == model.config.num_heads
               for lc in layout)
    eng = ContinuousBatchingEngine(model, num_slots=2, page_size=16,
                                   max_seq_len=64)
    eng.submit(np.arange(5), 3)
    _drain(eng)
    rec = eng.step_timeline()[-1]
    assert "kv_pages" not in rec and "moe" not in rec
    assert eng.flight_summary()["model_counters"] == {}


# -- what is not supported yet refuses typed, at construction -----------------

@pytest.mark.parametrize("option", [
    "prefix_cache", "mesh", "kv_int8", "speculative",
    "prefill_chunk_tokens"])
def test_unsupported_options_refuse_typed_at_construction(tiny, option):
    _, model, _, _ = tiny
    if option == "prefix_cache":
        from paddle_tpu.serving.prefix_cache import PrefixCache
        kw = {"prefix_cache": PrefixCache(PAGE)}
    elif option == "mesh":
        from jax.sharding import Mesh
        kw = {"mesh": Mesh(np.array(jax.devices()[:1]), ("model",))}
    else:
        kw = {"kv_int8": {"kv_int8": True},
              "speculative": {"speculative": 2},
              "prefill_chunk_tokens": {"prefill_chunk_tokens": 8}}[option]
    with pytest.raises(UnsupportedCacheLayout):
        _engine(model, **kw)


def test_server_refuses_the_prefix_cache_and_serves_without(tiny):
    from paddle_tpu.serving.server import ServingServer, _build_model
    model = _build_model("smallthinker_tiny")
    with pytest.raises(UnsupportedCacheLayout):
        ServingServer(model, port=0, page_size=PAGE, num_slots=2)
    server = ServingServer(model, port=0, prefix_cache=False,
                           page_size=PAGE, num_slots=2, max_seq_len=64)
    from benchmarks.drivers.serve import rpc
    port = server.start()
    try:
        rep = rpc(port, {"op": "generate", "prompt": list(range(11)),
                         "max_new_tokens": 5})
    finally:
        server.stop()
    assert len(rep["generated"]) == 5


# -- the expert layer -----------------------------------------------------------

def _layer_weights(rng, e=8, h=64, f=32):
    return (jnp.asarray(rng.standard_normal((e, h, f)) * 0.1, jnp.float32),
            jnp.asarray(rng.standard_normal((e, h, f)) * 0.1, jnp.float32),
            jnp.asarray(rng.standard_normal((e, f, h)) * 0.1, jnp.float32))


def _dense_experts(u, idx, gates, wg, wu, wd):
    """Every expert on every token, the picks' gates applied: O(E T)."""
    out = jnp.zeros_like(u)
    for e in range(wg.shape[0]):
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=1)
        out = out + (jnp.maximum(u @ wg[e], 0) * (u @ wu[e])) @ wd[e] \
            * g[:, None]
    return out


def test_four_shares_of_two_experts_add_up_to_the_uncut_layer():
    """The guide's share test: a chip that holds experts [first, first
    + 2) routes over all 8 and computes its own experts' part; the four
    parts add up to the whole layer (nothing is computed alike by every
    share: the residual is added once, outside)."""
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((21, 64)), jnp.float32)
    w_r = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
    wg, wu, wd = _layer_weights(rng)
    idx, gates = moe.route_top_k(u, w_r, 2)
    whole, counts = moe.dropless_experts(u, idx, gates, wg, wu, wd)
    assert int(counts.sum()) == 21 * 2
    np.testing.assert_allclose(
        np.asarray(whole), np.asarray(_dense_experts(u, idx, gates, wg, wu, wd)),
        atol=1e-5)
    parts, picks = 0, 0
    for first in range(0, 8, 2):
        sl = slice(first, first + 2)
        part, cnt = moe.dropless_experts(u, idx, gates, wg[sl], wu[sl],
                                         wd[sl], held=(first, 2))
        np.testing.assert_array_equal(np.asarray(cnt),
                                      np.asarray(counts)[sl])
        parts, picks = parts + part, picks + int(cnt.sum())
    assert picks == 21 * 2
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=1e-5)


def test_the_reference_computes_the_same_share(tiny):
    """experts_held in the model and in the reference: the same partial
    result goes on to the next layer in both."""
    cfg, _, params, cd = tiny
    held = (2, 4)
    model = SmallThinkerForCausalLM(
        dataclasses.replace(cfg, experts_held=held), abstract=True)
    cut = {n: (v[held[0]:held[0] + held[1]]
               if n.rsplit(".", 1)[-1] in ref.EXPERT_LEAVES else v)
           for n, v in params.items()}
    model.load_weights(cut)
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, 19)
    want = ref.forward_logits(dict(cd, experts_held=list(held)), cut, ids)
    got = model.forward(jnp.asarray(ids[None]))
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want),
                               atol=2e-5)
    whole = ref.forward_logits(cd, params, ids)
    assert np.abs(np.asarray(whole) - np.asarray(want)).max() > 1e-3


@pytest.mark.parametrize("rows", [7, 64])
def test_no_token_is_dropped_under_a_skewed_router(rows):
    """Every row picks expert 0 first: a capacity dispatch would drop
    most of them; here expert 0 computes all of its picks."""
    rng = np.random.default_rng(rows)
    u = jnp.asarray(rng.standard_normal((rows, 64)), jnp.float32)
    w_r = jnp.asarray(rng.standard_normal((64, 8)) * 0.01, jnp.float32)
    w_r = w_r.at[:, 0].set(0.0)
    logits_bias = jnp.zeros((8,)).at[0].set(100.0)
    wg, wu, wd = _layer_weights(rng)
    logits = u @ w_r + logits_bias
    vals, idx = jax.lax.top_k(logits, 2)
    gates = jax.nn.softmax(vals, -1)
    out, counts = moe.dropless_experts(u, idx.astype(jnp.int32), gates,
                                       wg, wu, wd)
    assert int(counts[0]) == rows and int(counts.sum()) == 2 * rows
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_dense_experts(u, idx, gates, wg, wu, wd)),
        atol=1e-5)
    # rows that are not live cost nothing and add nothing
    valid = jnp.arange(rows) % 2 == 0
    out2, counts2 = moe.dropless_experts(u, idx.astype(jnp.int32), gates,
                                         wg, wu, wd, valid=valid)
    assert int(counts2.sum()) == 2 * int(valid.sum())
    np.testing.assert_allclose(np.asarray(out2)[::2], np.asarray(out)[::2],
                               atol=1e-6)
    assert not np.asarray(out2)[1::2].any()


def test_a_padded_prompt_hands_flash_its_true_lengths(
        check_padded_prefill_through_flash):
    """Head size 64 and a bucket of 640 take the flash kernel in blocks
    of 128: every layer, window or global, hands it ``prefill_lens``,
    and the logits at a prompt's last position are the dense path's
    (the Q blocks past the shorter prompt's end come back zero and run
    on through the matmuls and norms after: finite everywhere)."""
    cfg = smallthinker_tiny(head_dim=64, max_position_embeddings=1024)
    check_padded_prefill_through_flash(SmallThinkerForCausalLM(cfg, seed=11),
                                       flash_calls=cfg.num_hidden_layers)


# -- the kernels against jax.numpy, in interpret mode --------------------------

@pytest.fixture
def interpret(monkeypatch):
    orig = fa.pl.pallas_call
    monkeypatch.setattr(fa.pl, "pallas_call",
                        functools.partial(orig, interpret=True))
    with fa.force_flash_for_aot():
        yield


@pytest.mark.parametrize("window", [None, 40, 64])
def test_paged_decode_kernel_group_7_window_edge_inside_a_page(
        interpret, window):
    """7 query heads a KV head (not a power of two); a window whose
    first key lies inside a page (40 over pages of 16) or on its edge."""
    rng = np.random.default_rng(0)
    kvh, g, d, page, n_pages = 2, 7, 128, 16, 12
    kp = jnp.asarray(rng.standard_normal((n_pages + 1, kvh, page, d)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages + 1, kvh, page, d)),
                     jnp.float32)
    table = jnp.asarray(rng.permutation(n_pages).reshape(2, 6), jnp.int32)
    lens = jnp.asarray([77, 23], jnp.int32)
    lo = None if window is None else jnp.maximum(lens - window, 0)
    q = jnp.asarray(rng.standard_normal((2, 1, kvh * g, d)), jnp.float32)
    assert pa.paged_grouped_supported(q.shape, kp.shape)
    out = pa.paged_attention_grouped(q, kp, vp, table, lens, kv_start=lo)
    want = pa.paged_attention_grouped_reference(q, kp, vp, table, lens,
                                                kv_start=lo)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kvh,g,width,block,lens,window", [
    (2, 7, 16, 4, [77, 130], None),
    (2, 7, 16, 4, [200, 150], 90),
    (2, 7, 22, 5, [345, 17, 160], None),
    (2, 7, 16, 4, [70, 0, 120, 0, 33], None),
    (8, 8, 16, 4, [100, 0, 64, 250], None),
    (2, 7, 16, 4, [1, 16, 0, 2], None),
], ids=["ends_mid_block_mid_page", "window_bound_in_an_unaligned_block",
        "width_not_a_multiple_of_the_block", "parked_between_live",
        "group_8_over_8_kv_heads", "single_page"])
def test_paged_decode_kernel_walks_a_block_of_pages_a_turn(
        interpret, kvh, g, width, block, lens, window):
    """The walk takes ``block`` pages a turn (the rule's choice for the
    table's width) and starts the next sequence's first block during
    this one's last: a block that is partly past a sequence's end or
    past the table's, a window's first page that does not start a
    block, parked slots between live ones (zeros out), pages of 8 KV
    heads."""
    rng = np.random.default_rng(len(lens) * width + kvh)
    d, page = 128, 16
    assert pa.grouped_block_pages(kvh, page, d, 4, width) == block
    b = len(lens)
    n_pages = b * width
    kp = jnp.asarray(rng.standard_normal((n_pages + 1, kvh, page, d)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages + 1, kvh, page, d)),
                     jnp.float32)
    table = jnp.asarray(rng.permutation(n_pages).reshape(b, width),
                        jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    lo = None if window is None else jnp.maximum(lens - window, 0)
    q = jnp.asarray(rng.standard_normal((b, 1, kvh * g, d)), jnp.float32)
    out = pa.paged_attention_grouped(q, kp, vp, table, lens, kv_start=lo)
    want = pa.paged_attention_grouped_reference(q, kp, vp, table, lens,
                                                kv_start=lo)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    assert not np.asarray(out)[np.asarray(lens) == 0].any()


@pytest.mark.parametrize("s,window", [(256, None), (256, 100), (512, 130),
                                      (128, 40)])
def test_flash_prefill_kernel_group_7_and_a_window(interpret, s, window):
    """The window's edge inside a K block; blocks behind it skipped."""
    from paddle_tpu.models.served import dense_attention
    rng = np.random.default_rng(s)
    kvh, g, d = 2, 7, 64
    q = jnp.asarray(rng.standard_normal((1, s, kvh * g, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, s, kvh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, s, kvh, d)), jnp.float32)
    assert fa.flash_attention_supported(q.shape, k.shape)
    out = fa.flash_attention_grouped(q, k, v, window=window, block_q=128,
                                     block_k=128)
    want = dense_attention(q, k, v, window, 1.0 / d ** 0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("act", ["relu", "silu", "silu_limit1"])
@pytest.mark.parametrize("rows,tm", [(12, 8), (300, 64)])
def test_grouped_matmul_kernels_match_the_gathered_product(interpret, rows,
                                                           tm, act):
    rng = np.random.default_rng(rows)
    e, h, f = 5, 128, 256
    wg, wu, wd = _layer_weights(rng, e, h, f)
    tiles = -(-rows // tm) + e
    tile_expert = jnp.asarray(np.sort(rng.integers(0, e, tiles)), jnp.int32)
    used = jnp.asarray([tiles - 2], jnp.int32)
    x = jnp.asarray(rng.standard_normal((tiles * tm, h)), jnp.float32)
    live = (tiles - 2) * tm
    mid = gm.grouped_ffn_in(x, wg, wu, tile_expert, used, tm, act)
    want = gm._reference(x, (wg, wu), tile_expert, tm, gm.glu(act))
    np.testing.assert_allclose(np.asarray(mid)[:live], np.asarray(want)[:live],
                               rtol=1e-4, atol=1e-4)
    out = gm.grouped_matmul(want, wd, tile_expert, used, tm)
    want = gm._reference(want, (wd,), tile_expert, tm, None)
    np.testing.assert_allclose(np.asarray(out)[:live], np.asarray(want)[:live],
                               rtol=1e-4, atol=1e-4)
