"""GLM-5.3-Flash through the serving engine against the plain reference
(benchmarks/references/glm5_next.py), at the tiny preset on the CPU: a
residual of four streams mixed by Sinkhorn-normalised hyper-connections,
KDA state layers, one layer of learned sparse attention over a latent
cache and the indexer's pooled keys, routed experts with a shared one.

Tolerance on logits: 5e-5 absolute, in float32, on logits whose spread
is about 1. The program and the reference differ in summation order
alone (the chunked scan against the position-by-position recurrence,
the absorbed latent form against expanded keys and values, a norm's
weight folded into ``Phi``), which moves float32 results by a few units
of their last place in each of five layers and four streams: 1e-5 at
most, measured. The selection is the same in both: the indexer's
scores differ by as little, and a block that swapped places at the
threshold would move a logit by far more than the tolerance. Selection
is real in every case below: a query past 20 positions has more
complete blocks than ``index_topk`` (4). Leaving out the indexer's
ReLU, the tail block, the Sinkhorn iterations or ``H_post``'s factor 2
moves the logits by 0.1 or more.
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import glm5_next as ref
from paddle_tpu.distributed import moe
from paddle_tpu.inference.continuous_batching import ContinuousBatchingEngine
from paddle_tpu.inference.speculative import SpeculativeConfig
from paddle_tpu.models import (Glm5NextForCausalLM, LatentCache, StateCache,
                               UnsupportedCacheLayout, glm5_3_flash,
                               glm5_next_tiny)
from paddle_tpu.models import glm5_next as g5
from paddle_tpu.models.cache_layout import create_pools, create_state_pools
from paddle_tpu.ops.pallas import sparse_attention as sa
from paddle_tpu.serving.prefix_cache import PrefixCache

PAGE = 4
ATOL = 5e-5
REF_LEN = 80
REPO = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def tiny():
    cfg = glm5_next_tiny()
    model = Glm5NextForCausalLM(cfg, seed=3)
    params = {n: p.value for n, p in model.named_parameters()}
    return cfg, model, params, dataclasses.asdict(cfg)


def _engine(model, **kw):
    base = dict(num_slots=3, page_size=PAGE, max_seq_len=128, num_pages=96,
                prompt_buckets=(8, 16, 32, 64))
    base.update(kw)
    return ContinuousBatchingEngine(model, **base)


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


def _caches(layout, pools, rows, table, lens):
    return [StateCache(p[0], p[1], rows, lens) if lc.state is not None
            else LatentCache(p[0], table, lens)
            for p, lc in zip(pools, layout)]


def _keep(nc):
    return [(c.state, c.tail) if isinstance(c, StateCache) else (c.pages,)
            for c in nc]


def _ref_logits(cd, params, seq, fault=None):
    """The reference's logits of ``seq``: computed over ``REF_LEN``
    positions (the sequence, then zeros: causal, so the rows of the
    sequence are its own) so that every call has one shape."""
    ids = np.zeros((REF_LEN,), np.int32)
    ids[:len(seq)] = seq
    return np.asarray(ref.forward_logits(cd, params, ids,
                                         fault=fault))[:len(seq)]


def _jitted(model):
    """The cached forward as one program a shape (the weights closed
    over: this size only)."""
    return jax.jit(lambda ids, caches, lens: model.forward(
        ids, caches=caches, prefill_lens=lens))


def _pools(layout, slots, pages):
    return [create_state_pools(lc, slots) if lc.state is not None
            else create_pools(lc, pages, PAGE)[:1] for lc in layout]


# -- the cached path against the reference, on logits --------------------------

def test_the_sparse_layer_gives_the_engine_a_latent_and_an_index_pool(tiny):
    cfg, model, _, _ = tiny
    layout = model.cache_layout()
    # layers 0, 3, 4, 5, 6: KDA, DSA (two entries), KDA, KDA, KDA
    assert len(layout) == 6
    assert [lc.state is not None for lc in layout] == \
        [True, False, False, True, True, True]
    lat, idx = layout[1:3]
    assert lat.latent == (32, 0) and lat.pooled == 1
    assert idx.latent == (16, 0) and idx.pooled == 4
    # a page of 4 positions holds one pooled row; a row is whole lanes
    assert create_pools(idx, 7, PAGE)[0].shape == (8, 1, 128)
    assert create_pools(lat, 7, PAGE)[0].shape == (8, 4, 128)
    big = Glm5NextForCausalLM(glm5_3_flash(dtype="bfloat16"),
                              abstract=True).cache_layout()
    # 512 latent values are four lane tiles: no padding is stored
    assert big[1].latent == (512, 0) and big[1].latent_width == 512
    assert big[2].latent_width == 128 and big[2].pooled == 4


@pytest.mark.parametrize("plens,new", [
    ((23,), 6),          # selection from position 20 on, not a multiple of 4
    ((45, 9), 5),        # three segments of 16; a row that never selects
    ((64, 30, 37), 7),   # a whole bucket; rows ending inside a block
])
def test_prefill_then_decode_equals_the_reference_on_logits(tiny, plens,
                                                            new):
    cfg, model, params, cd = tiny
    b = len(plens)
    seqs = _prompts(cfg, [n + new for n in plens], seed=sum(plens))
    want = [_ref_logits(cd, params, s) for s in seqs]
    layout = model.cache_layout()
    fwd = _jitted(model)
    max_pages = 32
    # slots 1.. of a pool with one more row than rows in the batch: the
    # batch's rows are not the pool's first
    rows = jnp.arange(1, b + 1, dtype=jnp.int32)
    table = (rows[:, None] * max_pages
             + jnp.arange(max_pages, dtype=jnp.int32)[None])
    pools = _pools(layout, b + 1, (b + 2) * max_pages)
    bucket = max(16, 1 << (max(plens) - 1).bit_length())
    padded = np.zeros((b, bucket), np.int32)
    for i, n in enumerate(plens):
        padded[i, :n] = seqs[i][:n]
    lens = jnp.asarray(plens, jnp.int32)
    logits, nc = fwd(
        jnp.asarray(padded),
        _caches(layout, pools, rows, table, jnp.zeros_like(lens)), lens)
    for i, n in enumerate(plens):
        np.testing.assert_allclose(np.asarray(logits[i, :n]), want[i][:n],
                                   atol=ATOL, rtol=0)
    pools = _keep(nc)
    for j in range(new):
        tok = jnp.asarray([[seqs[i][n + j]] for i, n in enumerate(plens)],
                          jnp.int32)
        logits, nc = fwd(
            tok, _caches(layout, pools, rows, table, lens + j), None)
        pools = _keep(nc)
        for i, n in enumerate(plens):
            np.testing.assert_allclose(np.asarray(logits[i, 0]),
                                       want[i][n + j], atol=ATOL, rtol=0)
    # a block's pooled key in the pool is the mean of its positions' k^I
    blk = model.model.layers[1]
    seq = seqs[0][:plens[0] + new]
    x = _hidden_in(model, params, cd, seq)
    _, ki = model._dsa_keys(blk, x[None])
    full = (plens[0] + new) // 4
    want_kbar = np.asarray(ki[0, :4 * full].astype(jnp.float32)).reshape(
        full, 4, -1).mean(1)
    idx_pool = pools[2][0]
    got = np.asarray(idx_pool[np.asarray(table[0, :full])][:, 0, :16])
    np.testing.assert_allclose(got, want_kbar, atol=1e-5, rtol=0)


def _hidden_in(model, params, cd, seq):
    """The DSA layer's normed input ``RMSNorm_1(H_pre X)`` of a whole
    sequence, from the reference's first layer."""
    sz = ref.sizes_of(cd)
    ids = np.zeros((REF_LEN,), np.int32)
    ids[:len(seq)] = seq
    e = params["model.embed"][ids].astype(jnp.float32)
    x = jnp.array(jnp.broadcast_to(e[:, None], (e.shape[0], 4, e.shape[1])))
    p = ref._layer(params, 0)
    x = ref.sublayer(x, p, "a", sz, None,
                     lambda h: ref.kda_mix(p, h, sz, False, None))
    x = ref.sublayer(x, p, "f", sz, None, lambda h: ref.dense_ffn(
        p, h, eps=sz["eps"], limit=sz["limit"], lowp=False))
    p = ref._layer(params, 1)
    h, _, _ = ref.hc_maps(x, p["hc_norm_a"], p["hc_phi_a"], p["hc_alpha_a"],
                          p["hc_bias_a"], n=4, eps=sz["eps"],
                          hc_eps=sz["hc_eps"], iters=sz["iters"], fault=None)
    return ref._rms(h, p["ln1"], sz["eps"])[:len(seq)]


def test_a_parked_slot_keeps_its_state_latent_and_index_rows(tiny):
    cfg, model, _, _ = tiny
    layout = model.cache_layout()
    rng = np.random.default_rng(1)
    rows = jnp.arange(3, dtype=jnp.int32)
    table = rows[:, None] * 8 + jnp.arange(8, dtype=jnp.int32)[None]
    pools = [tuple(jnp.asarray(rng.standard_normal(x.shape), x.dtype)
                   for x in p) for p in _pools(layout, 3, 32)]
    lens = jnp.asarray([6, 0, 9], jnp.int32)  # slot 1 parked
    _, nc = model.forward(jnp.asarray([[3], [4], [5]], jnp.int32),
                          caches=_caches(layout, pools, rows, table, lens))
    for c, p, lc in zip(nc, pools, layout):
        if lc.state is not None:
            for old, new in ((p[0], c.state), (p[1], c.tail)):
                old, new = np.asarray(old), np.asarray(new)
                assert (new[1] == old[1]).all()
                assert (new[0] != old[0]).any() and (new[2] != old[2]).any()
            continue
        old, new = np.asarray(p[0]), np.asarray(c.pages)
        slot1 = np.asarray(table[1])
        assert (new[slot1] == old[slot1]).all()
        # slot 0's position 6 and slot 2's 9: a row each, in its block
        per = PAGE // lc.pooled
        for s, pos in ((0, 6), (2, 9)):
            page = int(table[s, pos // lc.pooled // per])
            assert (new[page] != old[page]).any()


def test_the_eight_shares_and_the_shared_expert_add_up_to_the_layer(tiny):
    """The routed parts that the 8 chips of a layer compute (2 of the 16
    experts each, the clamped SwiGLU), with the shared expert counted
    once, are the uncut reference's expert layer."""
    cfg, model, params, cd = tiny
    rng = np.random.default_rng(5)
    y = jnp.asarray(rng.standard_normal((37, cfg.hidden_size)), jnp.float32)
    layer = 2
    want = np.asarray(ref.moe_layer(cd, params, layer, y))
    blk = model.model.layers[layer]
    u32 = ref._rms(y, blk.ln2.value, cfg.rms_norm_eps)
    idx, gates = moe.route_sigmoid_top_k(
        u32, blk.router.value, blk.router_bias.value,
        cfg.num_experts_per_tok, cfg.routed_scaling_factor)
    total = moe.gated_ffn(u32, blk.ws_gate.value, blk.ws_up.value,
                          blk.ws_down.value, cfg.activation)
    picks = 0
    for first in range(0, cfg.n_routed_experts, 2):
        sl = slice(first, first + 2)
        part, cnt = moe.dropless_experts(
            u32, idx, gates, blk.w_gate.value[sl], blk.w_up.value[sl],
            blk.w_down.value[sl], held=(first, 2), activation=cfg.activation)
        total = total + part
        picks += int(np.asarray(cnt).sum())
    assert picks == 37 * cfg.num_experts_per_tok
    np.testing.assert_allclose(np.asarray(total), want, atol=2e-5, rtol=0)
    # and a model that holds one share computes that share's reference
    held = dataclasses.replace(cfg, experts_held=(4, 2))
    part = Glm5NextForCausalLM(held, abstract=True)
    part.load_weights({
        n: (v[4:6] if n.rsplit(".", 1)[-1] in ref.EXPERT_LEAVES else v)
        for n, v in params.items()})
    ids = rng.integers(0, cfg.vocab_size, 29)
    cut = dict(dataclasses.asdict(held), n_routed_experts=2,
               published={"n_routed_experts": 16})
    weights = {n: p.value for n, p in part.named_parameters()}
    np.testing.assert_allclose(
        np.asarray(part.forward(jnp.asarray(ids[None]))[0]),
        np.asarray(ref.forward_logits(cut, weights, ids)), atol=ATOL, rtol=0)


def test_the_clamp_binds_where_a_gate_or_an_up_passes_the_limit():
    from paddle_tpu.ops.pallas.grouped_matmul import glu
    g = jnp.asarray([-20.0, -3.0, 5.0, 12.0])
    u = jnp.asarray([-15.0, 2.0, 11.0, 4.0])
    got = np.asarray(glu("silu_limit10")(g, u))
    a = np.minimum(np.asarray(g), 10.0)
    want = a / (1 + np.exp(-a)) * np.clip(np.asarray(u), -10, 10)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = np.asarray(glu("silu")(g, u))
    assert got[3] != plain[3] and got[2] != plain[2]


# -- every part of the sparse layer and of the residual is there ---------------

def _no_relu(qi, w, keys, qpos, pool):
    dots = jnp.einsum("bshd,bnd->bshn", qi, keys,
                      preferred_element_type=jnp.float32)
    s = jnp.einsum("bsh,bshn->bsn", w.astype(jnp.float32), dots)
    blk = jnp.arange(keys.shape[1], dtype=jnp.int32)
    return jnp.where(blk < (qpos // pool)[..., None], s, -jnp.inf)


def _no_tail(scores, tau, qpos, pool):
    blk = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    own = (qpos // pool)[..., None]
    before = blk < own
    picked = before & (scores + 0.0 >= tau[..., None])
    # the own block only where nothing before it can be picked
    return picked | ((blk == own) & ~jnp.any(before, -1, keepdims=True))


@pytest.mark.parametrize("what", ["relu", "tail", "sinkhorn", "post_factor"])
def test_a_program_without_any_one_part_fails_the_comparison(tiny, what,
                                                             monkeypatch):
    cfg, _, params, cd = tiny
    ids = _prompts(cfg, (45,), seed=2)[0]
    want = _ref_logits(cd, params, ids)
    dcfg = cfg
    if what == "relu":
        monkeypatch.setattr(sa, "index_scores_reference", _no_relu)
    elif what == "tail":
        monkeypatch.setattr(sa, "select_blocks", _no_tail)
    elif what == "sinkhorn":
        dcfg = dataclasses.replace(cfg, hc_sinkhorn_iters=1)
    else:
        real = g5.hc_maps

        def unit_post(*a, **k):
            h, post, res = real(*a, **k)
            return h, post / 2.0, res
        monkeypatch.setattr(g5, "hc_maps", unit_post)
    model = Glm5NextForCausalLM(dcfg, abstract=True)
    model.load_weights(params)
    got = np.asarray(model.forward(jnp.asarray(ids[None]))[0])
    assert np.abs(got - want).max() > 1000 * ATOL


# -- the engine ----------------------------------------------------------------

def _serve(eng, prompts, news):
    ids = [eng.submit(p, n) for p, n in zip(prompts, news)]
    out = eng.run()
    return [out[i][-n:].tolist() for i, n in zip(ids, news)]


def test_continuous_batching_equals_one_at_a_time_and_the_reference(tiny):
    cfg, model, params, cd = tiny
    prompts = _prompts(cfg, (5, 23, 44, 7, 33))
    news = (6, 9, 12, 5, 8)
    eng = _engine(model)
    got = _serve(eng, prompts, news)
    card = eng.flight_summary()
    sel = card["model_counters"]["dsa_positions.selected"]["sum"]
    causal = card["model_counters"]["dsa_positions.causal"]["sum"]
    assert 0 < sel < causal
    seen = [e for e in eng.step_timeline() if "state_slots" in e]
    assert seen and max(e["state_slots"] for e in seen) == 3
    alone = _engine(model, num_slots=1)
    for p, n, g in zip(prompts, news, got):
        assert _serve(alone, [p], [n]) == [g]
        # greedy against the reference's logits: every served token is
        # the reference's best
        full = np.concatenate([p, np.asarray(g, np.int32)])
        logits = _ref_logits(cd, params, full)
        at = np.arange(len(p) - 1, len(full) - 1)
        gap = logits[at].max(-1) - logits[at, full[at + 1]]
        assert float(gap.max()) <= ATOL


def test_the_counters_count_the_positions_each_query_attends_to(tiny):
    """A prompt of 30: queries 0..19 see every position before them,
    a query at t > 19 its 4 best blocks and its own block up to t."""
    cfg, model, _, _ = tiny
    ids = _prompts(cfg, (30,), seed=6)[0]
    layout = model.cache_layout()
    rows = jnp.arange(1, dtype=jnp.int32)
    table = jnp.arange(16, dtype=jnp.int32)[None]
    model.forward(
        jnp.asarray(np.pad(ids, (0, 2))[None]),
        caches=_caches(layout, _pools(layout, 1, 16), rows, table,
                       jnp.zeros((1,), jnp.int32)),
        prefill_lens=jnp.asarray([30], jnp.int32))
    got = model.pop_step_stats()["dsa_positions"]
    want = sum(t + 1 if t // 4 <= 4 else 16 + t % 4 + 1 for t in range(30))
    assert int(got["causal"]) == 30 * 31 // 2
    assert int(got["selected"]) == want


def test_the_prompt_kernel_handed_the_lengths_computes_the_plain_path(
        monkeypatch):
    """The rehearsal widths but a latent row of 128 (the prompt kernel's
    lane tile), the DSA layer alone (id 3), over a bucket of 1,024 in
    two segments of 512: the kernel (interpreted) gives the plain
    path's logits at a prompt's last position; it runs fewer tiles than
    the bucket's causal square for a prompt shorter than the bucket, and
    all of them for one that fills it."""
    cfg = glm5_next_tiny(kv_lora_rank=128, prefill_segment=512,
                         num_hidden_layers=1, layer_ids=(3,))
    model = Glm5NextForCausalLM(cfg, seed=5)
    fwd = jax.jit(lambda ids, lens: _last_logits(model, ids, lens))
    ids = jnp.asarray(_prompts(cfg, (1024,), seed=8)[0][None])
    want = {n: fwd(ids, jnp.asarray([n], jnp.int32))[0] for n in (700, 1024)}
    monkeypatch.setattr(sa, "sparse_prefill_latent", functools.partial(
        sa.sparse_prefill_latent, interpret=True))
    fwd = jax.jit(lambda ids, lens: _last_logits(model, ids, lens))
    for n in (700, 1024):
        got, tiles = fwd(ids, jnp.asarray([n], jnp.int32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want[n]),
                                   atol=ATOL, rtol=0)
        # a query tile of 16 sees the key tiles up to its diagonal: 32
        # tiles see one, 32 see two
        assert int(tiles["grid"]) == 32 + 64
        if n == 1024:
            assert int(tiles["run"]) == int(tiles["grid"])
        else:  # 44 query tiles start before 700
            assert int(tiles["run"]) == 32 + 2 * 12 < int(tiles["grid"])


def _last_logits(model, ids, lens):
    hidden, _ = model.decode_hidden(ids, None, prefill_lens=lens)
    last = jnp.take_along_axis(hidden, (lens - 1)[:, None, None], axis=1)
    return (model.logits(last)[:, 0],
            model.pop_step_stats()["dsa_prefill_tiles"])


@pytest.mark.parametrize("option", [
    {"prefix_cache": PrefixCache(PAGE)},
    {"prefill_chunk_tokens": 2 * PAGE},
    {"kv_int8": True},
    {"speculative": SpeculativeConfig(k=2, draft="ngram")},
    {"mesh": object()},
], ids=["prefix_cache", "chunked_prefill", "int8_kv", "speculation", "mesh"])
def test_what_the_layout_cannot_serve_is_refused_typed(tiny, option):
    _, model, _, _ = tiny
    with pytest.raises(UnsupportedCacheLayout, match="latent pages"):
        _engine(model, **option)


def test_the_server_builds_the_presets_and_serves_one():
    from benchmarks.drivers.serve import rpc
    from paddle_tpu.serving.server import ServingServer, _build_model
    model = _build_model("glm5_next_tiny")
    assert isinstance(model, Glm5NextForCausalLM)
    server = ServingServer(model, port=0, prefix_cache=False, num_slots=2,
                           page_size=PAGE, max_seq_len=64)
    port = server.start()
    try:
        rep = rpc(port, {"op": "generate", "prompt": list(range(1, 30)),
                         "max_new_tokens": 4})
        assert len(rep["generated"]) == 4
    finally:
        server.stop()


# -- the configuration file ----------------------------------------------------

def _config_file():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "glm-5.3-flash-serve.json")) as f:
        return json.load(f)


def test_the_configuration_file_is_the_published_config_cut_in_depth():
    """The published widths (the program's defaults are the config's),
    cut in depth, experts held and vocabulary alone, and its `assumed`
    equations name each reading the config does not pin."""
    from benchmarks.builders import glm5_next as builder
    cfg = _config_file()
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 45,
                                "n_routed_experts": 288,
                                "vocab_size": 154880}
    assert cfg["layer_ids"] == [0, 3, 4, 5, 6]
    assert cfg["experts_held"] == [0, 36]
    got = builder.program_config(cfg)
    # the per-layer lists stand in the file as published (45 entries)
    want = glm5_3_flash(experts_held=(0, 36), vocab_size=19360,
                        dtype="bfloat16",
                        layer_types=tuple(cfg["layer_types"]),
                        mlp_layer_types=tuple(cfg["mlp_layer_types"]),
                        linear_attn_config=cfg["linear_attn_config"])
    assert got == want
    assert got.layer_types == glm5_3_flash(layer_ids=range(45)).layer_types
    assert [got.kind(j) for j in range(5)] == [
        (g5.KDA, "dense"), (g5.DSA, "sparse"), (g5.KDA, "sparse"),
        (g5.KDA, "sparse"), (g5.KDA, "sparse")]
    for key, value in dataclasses.asdict(glm5_3_flash()).items():
        if key in cfg and key not in cfg["reduced"] + [
                "dtype", "num_hidden_layers", "layer_ids", "layer_types",
                "mlp_layer_types", "linear_attn_config", "experts_held"]:
            want = list(value) if isinstance(value, tuple) else value
            assert cfg[key] == want, key
    lin = glm5_3_flash().linear_attn_config
    assert {k: cfg["linear_attn_config"][k] for k in lin} == lin
    text = " ".join(cfg["assumed"].values())
    for reading in ("gate_lower_bound", "swiglu_limit", "index_kpool",
                    "summed", "Sinkhorn", "LayerNorm", "mean"):
        assert reading in text, reading


def test_the_kernel_readers_count_four_kda_layers_and_the_held_experts():
    """What the cell's KDA and expert readers count at the cut: four
    KDA layers of 64 float32 states of 128 x 128 (read and written a
    live slot a decode step), one DSA layer, and an expert of three
    4096 x 2048 matrices in bf16."""
    from benchmarks import flops_glm5_next as fl
    cfg = _config_file()
    assert (fl.count(cfg, "kda"), fl.count(cfg, "dsa")) == (4, 1)
    assert fl.state_bytes(cfg) == 4 * 64 * 128 * 128
    assert fl.kda_decode_bytes(cfg, 3) == 2 * 4 * 3 * 4 * 64 * 128 * 128
    assert fl.expert_bytes(cfg, 5) == 5 * 3 * 4096 * 2048 * 2
    per_token = 4 * 64 * 128 * 2 + 4 * 64 * 128 + 4 * 64
    assert fl.kda_prefill_bytes(cfg, 100) == \
        4 * (100 * per_token + 4 * 64 * 128 * 128)
    assert fl.kda_prefill_flops(cfg, 100) == 4 * 6 * 128 * 128 * 64 * 100
