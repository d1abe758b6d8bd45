"""GLM-4 MoE Lite through the serving engine against the plain
reference (benchmarks/references/glm4_moe_lite.py, the expanded form
only), at the tiny preset on the CPU: latent attention over pages of one
row a position, absorbed at decode and expanded at prefill, a dense
layer, a sigmoid router with a selection bias, a shared expert, an
untied head.
"""

import dataclasses
import math

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import spec
from benchmarks.references import glm4_moe_lite as ref
from paddle_tpu.inference.continuous_batching import ContinuousBatchingEngine
from paddle_tpu.inference.speculative import SpeculativeConfig
from paddle_tpu.models import (Glm4MoeLiteConfig, Glm4MoeLiteForCausalLM,
                               LatentCache, UnsupportedCacheLayout,
                               glm4_moe_lite_tiny)
from paddle_tpu.models.cache_layout import create_pools
from paddle_tpu.models.glm4_moe_lite import split_kv_b
from paddle_tpu.models.served import latent_append, rotate
from paddle_tpu.serving.prefix_cache import PrefixCache

PAGE = 4
CELL = "serve-glm47flash-longctx-sat"


@pytest.fixture(scope="module")
def tiny():
    cfg = glm4_moe_lite_tiny()
    model = Glm4MoeLiteForCausalLM(cfg, seed=3)
    params = {n: p.value for n, p in model.named_parameters()}
    return cfg, model, params, dataclasses.asdict(cfg)


def _engine(model, **kw):
    base = dict(num_slots=3, page_size=PAGE, max_seq_len=128, num_pages=64,
                prompt_buckets=(8, 16, 32, 64))
    base.update(kw)
    return ContinuousBatchingEngine(model, **base)


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


def _latent_caches(layout, table, lens, pools=None, n_pages=64):
    pools = pools or [create_pools(lc, n_pages, PAGE)[0] for lc in layout]
    return [LatentCache(p, table, lens) for p in pools]


# -- the cached path against the reference, on logits --------------------------

@pytest.mark.parametrize("plens,new", [
    ((5,), 6),          # shorter than a segment, ends inside a page
    ((19, 3), 5),       # ragged: a row shorter than a page
    ((40, 17, 33), 7),  # three segments of 16, the rows end in each
])
def test_prefill_then_decode_equals_the_reference_on_logits(tiny, plens,
                                                            new):
    cfg, model, params, cd = tiny
    b = len(plens)
    seqs = _prompts(cfg, [n + new for n in plens], seed=sum(plens))
    want = [np.asarray(ref.forward_logits(cd, params, s)) for s in seqs]
    layout = model.cache_layout()
    max_pages = 16
    # every row's pages from the pool's middle, not its first
    table = ((jnp.arange(b, dtype=jnp.int32)[:, None] + 1) * max_pages
             + jnp.arange(max_pages, dtype=jnp.int32)[None])
    bucket = -(-max(plens) // 8) * 8  # right-padded, as the engine's
    padded = np.zeros((b, bucket), np.int32)
    for i, n in enumerate(plens):
        padded[i, :n] = seqs[i][:n]
    lens = jnp.asarray(plens, jnp.int32)
    caches = _latent_caches(layout, table, jnp.zeros_like(lens),
                            n_pages=(b + 2) * max_pages)
    logits, nc = model.forward(jnp.asarray(padded), caches=caches,
                               prefill_lens=lens)
    for i, n in enumerate(plens):
        np.testing.assert_allclose(np.asarray(logits[i, :n]), want[i][:n],
                                   atol=2e-5, rtol=0)
    for j in range(new):
        tok = jnp.asarray([[seqs[i][n + j]] for i, n in enumerate(plens)],
                          jnp.int32)
        caches = _latent_caches(layout, table, lens + j,
                                pools=[c.pages for c in nc])
        logits, nc = model.forward(tok, caches=caches)
        for i, n in enumerate(plens):
            np.testing.assert_allclose(np.asarray(logits[i, 0]),
                                       want[i][n + j], atol=2e-5, rtol=0)
    # page 0.. of the pool, which no row's table names, stays zero
    for c in nc:
        assert not np.asarray(c.pages[:max_pages]).any()


def test_the_absorbed_form_equals_the_expanded_one(tiny):
    """One layer: scores and outputs of the absorbed form (the query
    through W_UK, the pages as keys and values, the output through
    W_UV) equal the expanded definition's to float32 rounding, for
    positions on both sides of a page boundary."""
    cfg, model, _, _ = tiny
    blk = model.model.layers[1]
    rng = np.random.default_rng(11)
    s = 3 * PAGE + 2  # positions past three page boundaries
    h = jnp.asarray(rng.standard_normal((1, s, cfg.hidden_size)),
                    jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    q_nope, q_rope = model._queries(blk, h, pos)
    rows = model._latent_rows(blk, h, pos)[0]  # [S, rank + rope]
    lat, k_rope = rows[:, :cfg.kv_lora_rank], rows[:, cfg.kv_lora_rank:]
    w_uk, w_uv = blk.w_uk.value, blk.w_uv.value
    scale = 1.0 / math.sqrt(cfg.qk_head_dim)
    seen = np.tril(np.ones((s, s), bool))
    # expanded: keys and values of every head from the latent
    k = jnp.concatenate([
        jnp.einsum("sr,hrn->shn", lat, w_uk),
        jnp.broadcast_to(k_rope[:, None], (s, cfg.num_heads,
                                           cfg.qk_rope_head_dim))], -1)
    v = jnp.einsum("sr,hrv->shv", lat, w_uv)
    q = jnp.concatenate([q_nope, q_rope], -1)[0]
    sc_e = np.asarray(jnp.einsum("qhd,khd->hqk", q, k)) * scale
    p_e = np.where(seen, np.exp(sc_e - sc_e.max(-1, keepdims=True)), 0)
    p_e /= p_e.sum(-1, keepdims=True)
    sc_e = np.where(seen, sc_e, 0)
    o_e = np.einsum("hqk,khv->qhv", p_e, np.asarray(v))
    # absorbed: the cache rows alone
    qa = jnp.concatenate([jnp.einsum("qhn,hrn->qhr", q_nope[0], w_uk),
                          q_rope[0]], -1)
    sc_a = np.where(seen, np.asarray(
        jnp.einsum("qhw,kw->hqk", qa, rows)) * scale, 0)
    np.testing.assert_allclose(sc_a, sc_e, atol=2e-6, rtol=0)
    o_a = np.einsum("qhr,hrv->qhv",
                    np.einsum("hqk,kr->qhr", p_e, np.asarray(lat)),
                    np.asarray(w_uv))
    np.testing.assert_allclose(o_a, o_e, atol=2e-6, rtol=0)
    # and the model's two paths: a prompt of s positions against one
    # token at a time through the pages
    table = jnp.arange(8, dtype=jnp.int32)[None] + 3
    lc = model.cache_layout()[1]
    fresh = LatentCache(create_pools(lc, 16, PAGE)[0], table,
                        jnp.zeros((1,), jnp.int32))
    lens = jnp.asarray([s], jnp.int32)
    whole, nc = model._mla(blk, h, fresh, pos, lens)
    stored = np.asarray(nc.pages[3:8]).reshape(-1, lc.latent_width)
    np.testing.assert_array_equal(stored[:s, :rows.shape[1]],
                                  np.asarray(rows))
    assert not stored[:, rows.shape[1]:].any()
    cache = LatentCache(nc.pages, table, jnp.asarray([PAGE - 1], jnp.int32))
    for t in range(PAGE - 1, s):  # from a page's last row over the next
        one, cache = model._mla(blk, h[:, t:t + 1], cache,
                                jnp.asarray([[t]], jnp.int32), None)
        np.testing.assert_allclose(np.asarray(one[0, 0]),
                                   np.asarray(whole[0, t]), atol=2e-6,
                                   rtol=0)
        assert int(cache.seq_lens[0]) == t + 1


def test_one_rotary_key_serves_every_head_at_its_own_position(tiny):
    cfg, model, _, _ = tiny
    blk = model.model.layers[0]
    h = jnp.asarray(np.random.default_rng(2).standard_normal(
        (2, 1, cfg.hidden_size)), jnp.float32)
    pos = jnp.asarray([[5], [70]], jnp.int32)
    rows = model._latent_rows(blk, h, pos)
    raw = jnp.matmul(h, blk.wkv_a.value)[..., cfg.kv_lora_rank:]
    want = rotate(raw[:, :, None], pos, cfg.rope_theta)[:, :, 0]
    np.testing.assert_array_equal(np.asarray(rows[..., cfg.kv_lora_rank:]),
                                  np.asarray(want))
    assert rows.shape == (2, 1, cfg.kv_lora_rank + cfg.qk_rope_head_dim)


def test_a_parked_slot_and_a_full_table_write_to_the_scratch_page(tiny):
    _, model, _, _ = tiny
    lc = model.cache_layout()[0]
    pool = create_pools(lc, 8, PAGE)[0]
    table = jnp.asarray([[0, 1], [2, 3], [4, 5]], jnp.int32)
    lens = jnp.asarray([0, 5, 2 * PAGE], jnp.int32)  # parked, live, full
    rows = jnp.ones((3, 1, sum(lc.latent)), jnp.float32)
    nc = latent_append(LatentCache(pool, table, lens), rows)
    pages = np.array(nc.pages)
    assert pages[3, 1, :sum(lc.latent)].all()  # position 5: page 3, row 1
    assert not pages[3, 1, sum(lc.latent):].any()  # zeros behind a row
    pages[3, 1] = 0
    assert not pages[:8].any() and pages[8].any()  # the rest: scratch
    assert np.asarray(nc.seq_lens).tolist() == [1, 6, 2 * PAGE]


def test_split_kv_b_gives_the_leaves_of_the_published_matrix():
    heads, rank, nope, v = 3, 4, 2, 5
    w = jnp.arange(rank * heads * (nope + v), dtype=jnp.float32).reshape(
        rank, heads * (nope + v))
    w_uk, w_uv = split_kv_b(w, heads, nope, v)
    assert w_uk.shape == (heads, rank, nope) and w_uv.shape == (heads, rank, v)
    c = jnp.asarray(np.random.default_rng(0).standard_normal((6, rank)),
                    jnp.float32)
    exp = (c @ w).reshape(6, heads, nope + v)
    np.testing.assert_allclose(jnp.einsum("sr,hrn->shn", c, w_uk),
                               exp[..., :nope], rtol=1e-6)
    np.testing.assert_allclose(jnp.einsum("sr,hrv->shv", c, w_uv),
                               exp[..., nope:], rtol=1e-6)


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "yarn"}), ("partial_rotary_factor", 0.5),
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("n_group", 2), ("norm_topk_prob", False), ("n_shared_experts", 2),
    ("hidden_act", "gelu"), ("topk_method", "greedy"),
    ("num_key_value_heads", 2), ("v_head_dim", 16)])
def test_what_the_config_cannot_build_is_refused(key, value):
    with pytest.raises(NotImplementedError):
        glm4_moe_lite_tiny(**{key: value})


def test_the_published_defaults_are_the_sources():
    c = Glm4MoeLiteConfig()
    assert (c.num_hidden_layers, c.hidden_size, c.num_attention_heads) == \
        (47, 2048, 20)
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim) == (768, 512, 192, 64, 256)
    assert (c.n_routed_experts, c.num_experts_per_tok,
            c.routed_scaling_factor, c.first_k_dense_replace) == \
        (64, 4, 1.8, 1)
    assert (c.vocab_size, c.intermediate_size, c.moe_intermediate_size) == \
        (154880, 10240, 1536)


# -- the engine ----------------------------------------------------------------

def _serve(eng, prompts, news):
    ids = [eng.submit(p, n) for p, n in zip(prompts, news)]
    out = eng.run()
    return [out[i][-n:].tolist() for i, n in zip(ids, news)]


def test_a_latent_layer_has_one_pool_and_the_engine_no_v_pool(tiny):
    cfg, model, _, _ = tiny
    eng = _engine(model)
    width = -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128
    assert all(lc.latent == (cfg.kv_lora_rank, cfg.qk_rope_head_dim)
               and not lc.plain and lc.latent_width == width
               for lc in eng._layout)
    assert [p.shape for p in eng._pools["k"]] == \
        [(64 + 1, PAGE, width)] * cfg.num_hidden_layers
    for kind in ("v", "ks", "vs", "state", "tail"):
        assert eng._pools[kind] == [None] * cfg.num_hidden_layers
    card = eng.flight_summary()
    assert card["latent_pool_bytes"] == \
        cfg.num_hidden_layers * 65 * PAGE * width * 4
    assert card["state_pool_bytes"] == 0
    _serve(eng, _prompts(cfg, (9,)), (5,))
    # the programs hand back the same one pool a layer
    assert [p.shape for p in eng._pools["k"]] == \
        [(65, PAGE, width)] * cfg.num_hidden_layers
    assert eng._pools["v"] == [None] * cfg.num_hidden_layers
    # and what lies behind a row's rank + rope values stays zero
    assert not np.asarray(eng._pools["k"][0])[
        ..., cfg.kv_lora_rank + cfg.qk_rope_head_dim:].any()


def test_continuous_batching_equals_one_at_a_time_and_the_reference(tiny):
    cfg, model, params, cd = tiny
    prompts = _prompts(cfg, (5, 19, 40, 7, 33))
    news = (6, 9, 12, 5, 8)
    eng = _engine(model)
    got = _serve(eng, prompts, news)
    card = eng.flight_summary()
    seen = [e for e in eng.step_timeline() if "kv_pages" in e]
    assert seen and all(e["kv_pages"].keys() == {"global"} for e in seen)
    assert max(e["kv_pages"]["global"] for e in seen) > 0
    assert not any("state_slots" in e for e in eng.step_timeline())
    assert {"moe.touched", "moe.max_load", "moe.max_over_mean"} <= \
        set(card["model_counters"])
    # two expert layers of eight experts: a step touches at most 16
    assert card["model_counters"]["moe.touched"]["max"] <= 16
    for p, n, g in zip(prompts, news, got):
        assert _serve(_engine(model, num_slots=1), [p], [n]) == [g]
        # greedy against the reference's logits: every served token is
        # the reference's best (ties aside: none at this size)
        full = np.concatenate([p, np.asarray(g, np.int32)])
        logits = np.asarray(ref.forward_logits(cd, params, full))
        at = np.arange(len(p) - 1, len(full) - 1)
        gap = logits[at].max(-1) - logits[at, full[at + 1]]
        assert float(gap.max()) <= 2e-5


def test_a_slot_reused_at_once_after_a_finish_under_the_look_ahead(tiny):
    """A count-known finish rides the look-ahead: the step launched
    ahead appends the finished slot's row once more, to a page the
    slot has given back or to scratch. The next admission into that
    slot must serve what a fresh engine serves."""
    cfg, model, _, _ = tiny
    long, first, second = _prompts(cfg, (14, 9, 21), seed=4)
    eng = _engine(model, num_slots=2)
    eng.submit(long, 40)       # keeps the engine stepping throughout
    a = eng.submit(first, 7)
    b = eng.submit(second, 6)  # waits for the slot `first` frees
    out = eng.run()
    assert eng.decode_rows_dropped >= 1 and eng.decode_steps_ahead > 0
    assert eng.flight_summary()["state_rows_overwritten"] == 0
    fresh = _engine(model, num_slots=2)
    c = fresh.submit(second, 6)
    assert out[b].tolist() == fresh.run()[c].tolist()
    assert len(out[a]) == len(first) + 7


@pytest.mark.parametrize("option", [
    {"prefix_cache": PrefixCache(PAGE)},
    {"prefill_chunk_tokens": 2 * PAGE},
    {"kv_int8": True},
    {"speculative": SpeculativeConfig(k=2, draft="ngram")},
    {"mesh": object()},
], ids=["prefix_cache", "chunked_prefill", "int8_kv", "speculation", "mesh"])
def test_what_a_latent_layout_cannot_serve_is_refused_typed(tiny, option):
    _, model, _, _ = tiny
    with pytest.raises(UnsupportedCacheLayout, match="latent pages"):
        _engine(model, **option)


def test_a_prompt_that_would_attend_to_pages_is_refused(tiny):
    _, model, _, _ = tiny
    with pytest.raises(NotImplementedError, match="absorbed form"):
        model.decode_hidden(jnp.zeros((1, 4), jnp.int32), None,
                            prefill_lens=jnp.asarray([4]),
                            prefill_chained=True)
    with pytest.raises(NotImplementedError, match="several tokens"):
        model.decode_hidden(jnp.zeros((1, 2), jnp.int32), [])


def test_the_server_builds_the_presets_and_serves_one():
    from benchmarks.drivers.serve import rpc
    from paddle_tpu.serving.server import ServingServer, _build_model
    model = _build_model("glm4_moe_lite_tiny")
    assert isinstance(model, Glm4MoeLiteForCausalLM)
    server = ServingServer(model, port=0, prefix_cache=False, num_slots=2,
                           page_size=PAGE, max_seq_len=64)
    port = server.start()
    try:
        rep = rpc(port, {"op": "generate", "prompt": [1, 2, 3, 4, 5],
                         "max_new_tokens": 4})
        assert len(rep["generated"]) == 4
    finally:
        server.stop()
    with pytest.raises(UnsupportedCacheLayout):
        ServingServer(model, port=0, prefix_cache=True, num_slots=2,
                      page_size=PAGE, max_seq_len=64)


# -- the rehearsal's limit against the reference's planted faults --------------

@pytest.fixture(scope="module")
def rehearsed():
    """The cell's rehearsal configuration with the benchmark's weights,
    and what the engine served of a few prompts."""
    cell = spec.Cell(CELL, rehearsal=True)
    builder = cell.load_module("builders", cell.config["builder"])
    cfg = cell.config
    model = builder.build(cfg, 2**31 + 9)
    model.eval()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for n in (12, 30, 21, 44, 9, 17)]
    eng = ContinuousBatchingEngine(model, num_slots=4, page_size=PAGE,
                                   max_seq_len=128, num_pages=96)
    served = _serve(eng, prompts, (24,) * len(prompts))
    seqs = [p.tolist() + g for p, g in zip(prompts, served)]
    weights = {n: p.value for n, p in model.named_parameters()}
    return cell, cfg, weights, seqs, [len(p) for p in prompts]


@pytest.mark.parametrize("fault", (None,) + ref.FAULTS,
                         ids=lambda f: f or "program")
def test_a_planted_fault_fails_the_rehearsals_limit(rehearsed, fault):
    cell, cfg, weights, seqs, plens = rehearsed
    res = ref.served_token_gaps(cfg, weights, seqs, plens, fault=fault)
    mean = float(np.mean(np.concatenate(res["gaps"])))
    limit = cell.traffic["limits"]["served_gap_mean"]
    if fault is None:
        assert mean <= limit / 10
    else:
        assert mean > 2 * limit, (fault, mean)


def test_a_padded_prompt_hands_flash_its_true_lengths(
        check_padded_prefill_through_flash):
    """Latent attention's expanded prefill (keys of 48 + 16, values of
    64, a bucket of 640: the flash kernel in blocks of 128) hands it
    ``prefill_lens`` in every layer, and the logits at a prompt's last
    position are the dense path's."""
    cfg = glm4_moe_lite_tiny(qk_nope_head_dim=48, qk_rope_head_dim=16,
                             v_head_dim=64, max_position_embeddings=1024,
                             prefill_segment=1024)
    check_padded_prefill_through_flash(Glm4MoeLiteForCausalLM(cfg, seed=11),
                                       flash_calls=cfg.num_hidden_layers)
