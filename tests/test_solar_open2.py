"""Solar Open 2 through the serving engine against the plain reference
(benchmarks/references/solar_open2.py), at the tiny preset on the CPU:
KDA layers with a state a slot beside the paged cache of the one
softmax layer, a sigmoid router with a selection bias, a shared expert,
an untied head.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import solar_open2 as ref
from paddle_tpu.distributed import fault_inject as fi
from paddle_tpu.distributed import moe
from paddle_tpu.inference.continuous_batching import ContinuousBatchingEngine
from paddle_tpu.inference.speculative import SpeculativeConfig
from paddle_tpu.models import (SolarOpen2ForCausalLM, StateCache,
                               UnsupportedCacheLayout, solar_open2_tiny)
from paddle_tpu.models.cache_layout import create_pools, create_state_pools
from paddle_tpu.models.gpt import PagedKVCache
from paddle_tpu.serving.prefix_cache import PrefixCache

PAGE = 4


@pytest.fixture(scope="module")
def tiny():
    cfg = solar_open2_tiny()
    model = SolarOpen2ForCausalLM(cfg, seed=3)
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


# -- the cached path against the reference, on logits --------------------------

@pytest.mark.parametrize("plens,new", [
    ((5,), 6),         # shorter than a chunk and than a segment
    ((19, 3), 5),      # ragged: a row shorter than the convolution
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
    # slots 1.. of a pool with one more row than rows in the batch: the
    # batch's rows are not the pool's first
    rows = jnp.arange(1, b + 1, dtype=jnp.int32)
    table = (rows[:, None] * max_pages
             + jnp.arange(max_pages, dtype=jnp.int32)[None])
    pools = [create_state_pools(lc, b + 1) if lc.state is not None
             else create_pools(lc, (b + 2) * max_pages, PAGE)[:2]
             for lc in layout]

    def caches(pools, lens):
        return [StateCache(p[0], p[1], rows, lens) if lc.state is not None
                else PagedKVCache(p[0], p[1], None, None, table, lens)
                for p, lc in zip(pools, layout)]

    def keep(nc):
        return [(c.state, c.tail) if isinstance(c, StateCache)
                else (c.k_pages, c.v_pages) for c in nc]

    bucket = -(-max(plens) // 8) * 8  # right-padded, as the engine's
    padded = np.zeros((b, bucket), np.int32)
    for i, n in enumerate(plens):
        padded[i, :n] = seqs[i][:n]
    lens = jnp.asarray(plens, jnp.int32)
    logits, nc = model.forward(jnp.asarray(padded),
                               caches=caches(pools, jnp.zeros_like(lens)),
                               prefill_lens=lens)
    for i, n in enumerate(plens):
        np.testing.assert_allclose(np.asarray(logits[i, :n]), want[i][:n],
                                   atol=2e-5, rtol=0)
    pools = keep(nc)
    for j in range(new):
        tok = jnp.asarray([[seqs[i][n + j]] for i, n in enumerate(plens)],
                          jnp.int32)
        logits, nc = model.forward(tok, caches=caches(pools, lens + j))
        pools = keep(nc)
        for i, n in enumerate(plens):
            np.testing.assert_allclose(np.asarray(logits[i, 0]),
                                       want[i][n + j], atol=2e-5, rtol=0)
    # the pool's scratch row and the row no sequence held stay zero
    for (state, tail), lc in zip(pools, layout):
        if lc.state is not None:
            assert not np.asarray(state[0]).any()
            assert not np.asarray(tail[0]).any()


def test_a_parked_row_is_left_alone_by_a_single_token_step(tiny):
    cfg, model, _, _ = tiny
    layout = model.cache_layout()
    rng = np.random.default_rng(1)
    rows = jnp.arange(3, dtype=jnp.int32)
    table = rows[:, None] * 8 + jnp.arange(8, dtype=jnp.int32)[None]
    pools = []
    for lc in layout:
        if lc.state is None:
            pools.append(create_pools(lc, 32, PAGE)[:2])
            continue
        s, t = create_state_pools(lc, 3)
        pools.append((jnp.asarray(rng.standard_normal(s.shape), s.dtype),
                      jnp.asarray(rng.standard_normal(t.shape), t.dtype)))
    lens = jnp.asarray([6, 0, 9], jnp.int32)  # slot 1 parked
    caches = [StateCache(p[0], p[1], rows, lens) if lc.state is not None
              else PagedKVCache(p[0], p[1], None, None, table, lens)
              for p, lc in zip(pools, layout)]
    _, nc = model.forward(jnp.asarray([[3], [4], [5]], jnp.int32),
                          caches=caches)
    for c, p, lc in zip(nc, pools, layout):
        if lc.state is None:
            continue
        for old, new in ((p[0], c.state), (p[1], c.tail)):
            old, new = np.asarray(old), np.asarray(new)
            assert (new[1] == old[1]).all()
            assert (new[0] != old[0]).any() and (new[2] != old[2]).any()


# -- the share of a deployment ------------------------------------------------

def test_the_eight_shares_and_the_shared_expert_add_up_to_the_layer(tiny):
    """The routed parts that the 8 chips of a layer compute (2 of the 16
    experts each), with the shared expert counted once, are the uncut
    reference's MoE layer."""
    cfg, model, params, cd = tiny
    rng = np.random.default_rng(5)
    y = jnp.asarray(rng.standard_normal((37, cfg.hidden_size)), jnp.float32)
    layer = 1
    want = np.asarray(ref.moe_layer(cd, params, layer, y))
    blk = model.model.layers[layer]
    u32 = ref._rms(y, blk.ln2.value, cfg.rms_norm_eps)
    idx, gates = moe.route_sigmoid_top_k(
        u32, blk.router.value, blk.router_bias.value,
        cfg.num_experts_per_tok, cfg.routed_scaling_factor)
    total = moe.gated_ffn(u32, blk.ws_gate.value, blk.ws_up.value,
                          blk.ws_down.value, "silu")
    picks = 0
    for first in range(0, cfg.n_routed_experts, 2):
        sl = slice(first, first + 2)
        part, cnt = moe.dropless_experts(
            u32, idx, gates, blk.w_gate.value[sl], blk.w_up.value[sl],
            blk.w_down.value[sl], held=(first, 2), activation="silu")
        total = total + part
        picks += int(np.asarray(cnt).sum())
    assert picks == 37 * cfg.num_experts_per_tok
    np.testing.assert_allclose(np.asarray(total), want, atol=2e-5, rtol=0)
    # and a model that holds one share computes that share's reference
    held = dataclasses.replace(cfg, experts_held=(4, 2))
    part = SolarOpen2ForCausalLM(held, abstract=True)
    part.load_weights({
        n: (v[4:6] if n.rsplit(".", 1)[-1] in ref.EXPERT_LEAVES else v)
        for n, v in params.items()})
    ids = rng.integers(0, cfg.vocab_size, 21)
    cut = dict(dataclasses.asdict(held), n_routed_experts=2,
               published={"n_routed_experts": 16})
    weights = {n: p.value for n, p in part.named_parameters()}
    np.testing.assert_allclose(
        np.asarray(part.forward(jnp.asarray(ids[None]))[0]),
        np.asarray(ref.forward_logits(cut, weights, ids)), atol=2e-5, rtol=0)


def test_the_router_picks_by_score_plus_bias_and_gates_by_score():
    u = jnp.eye(4, dtype=jnp.float32)[:2] * 3.0
    w = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 0.0, 0.0],
                     [0, 0, 0, 0], [0, 0, 0, 0]], jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.6, 0.0], jnp.float32)
    idx, gates = moe.route_sigmoid_top_k(u, w, bias, 2, scaling=1.5)
    s = 1 / (1 + np.exp(-np.asarray([6.0, 3.0, 0.0, -3.0])))
    # row 0: scores + bias = (.998, .953, 1.1, .047): experts 2 and 0
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 2]
    got = dict(zip(np.asarray(idx[0]).tolist(), np.asarray(gates[0])))
    assert got[0] == pytest.approx(1.5 * s[0] / (s[0] + s[2]), rel=1e-6)
    assert got[2] == pytest.approx(1.5 * s[2] / (s[0] + s[2]), rel=1e-6)


# -- the engine ----------------------------------------------------------------

def _serve(eng, prompts, news):
    ids = [eng.submit(p, n) for p, n in zip(prompts, news)]
    out = eng.run()
    return [out[i][-n:].tolist() for i, n in zip(ids, news)]


def test_continuous_batching_equals_one_at_a_time_and_the_reference(tiny):
    cfg, model, params, cd = tiny
    prompts = _prompts(cfg, (5, 19, 40, 7, 33))
    news = (6, 9, 12, 5, 8)
    eng = _engine(model)
    got = _serve(eng, prompts, news)
    card = eng.flight_summary()
    assert card["state_pool_bytes"] == sum(
        4 * 4 * 4 * 16 * 16 + 4 * 4 * 3 * 192 for _ in range(3))
    seen = [e for e in eng.step_timeline() if "state_slots" in e]
    assert seen and max(e["state_slots"] for e in seen) == 3
    assert all(e["kv_pages"].keys() == {"global"} for e in seen)
    assert {"moe.touched", "moe.max_load", "moe.max_over_mean"} <= \
        set(card["model_counters"])
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
    ahead moves the finished slot's state row once more. The next
    admission into that slot must serve what a fresh engine serves: its
    prompt writes the row from zero."""
    cfg, model, _, _ = tiny
    long, first, second = _prompts(cfg, (14, 9, 21), seed=4)
    eng = _engine(model, num_slots=2)
    eng.submit(long, 40)       # keeps the engine stepping throughout
    a = eng.submit(first, 7)
    b = eng.submit(second, 6)  # waits for the slot `first` frees
    out = eng.run()
    assert eng.decode_rows_dropped >= 1
    assert eng.flight_summary()["state_rows_overwritten"] == \
        eng.decode_rows_dropped
    assert eng.decode_steps_ahead > 0
    fresh = _engine(model, num_slots=2)
    c = fresh.submit(second, 6)
    assert out[b].tolist() == fresh.run()[c].tolist()
    assert len(out[a]) == len(first) + 7


@pytest.mark.parametrize("site", ["engine.step", "launch"])
def test_a_failed_step_does_not_move_a_state_twice(tiny, site):
    """A step in flight has already moved its rows' states when the
    call after it fails: it is settled, not computed again."""
    cfg, model, _, _ = tiny
    prompts = _prompts(cfg, (11, 6), seed=9)
    want = _serve(_engine(model), prompts, (12, 12))
    eng = _engine(model)
    ids = [eng.submit(p, 12) for p in prompts]
    for _ in range(4):
        eng.step()
    assert eng._inflight is not None
    if site == "engine.step":
        fi.get_injector().arm("engine.step", at_calls=[1])
        with pytest.raises(fi.InjectedFault):
            eng.step()
        fi.reset()
    else:
        real = eng._decode_jit

        def broken(*a):
            raise RuntimeError("launch failed")

        eng._decode_jit = broken
        with pytest.raises(RuntimeError, match="launch failed"):
            eng.step()
        eng._decode_jit = real
    assert eng._inflight is None and eng._resident is None
    out = eng.run()
    assert [out[i][-12:].tolist() for i in ids] == want


@pytest.mark.parametrize("option", [
    {"prefix_cache": PrefixCache(PAGE)},
    {"prefill_chunk_tokens": 2 * PAGE},
    {"kv_int8": True},
    {"speculative": SpeculativeConfig(k=2, draft="ngram")},
    {"mesh": object()},
], ids=["prefix_cache", "chunked_prefill", "int8_kv", "speculation", "mesh"])
def test_what_a_state_layout_cannot_serve_is_refused_typed(tiny, option):
    _, model, _, _ = tiny
    with pytest.raises(UnsupportedCacheLayout, match="state layers"):
        _engine(model, **option)


def test_the_server_builds_the_presets_and_serves_one():
    from benchmarks.drivers.serve import rpc
    from paddle_tpu.serving.server import ServingServer, _build_model
    model = _build_model("solar_open2_tiny")
    assert isinstance(model, SolarOpen2ForCausalLM)
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


def test_a_padded_prompt_hands_flash_its_true_lengths(
        check_padded_prefill_through_flash):
    """The softmax layer of a period (head size 64, a bucket of 640: the
    flash kernel in blocks of 128) hands it ``prefill_lens``; the KDA
    layers after it read the rows of the skipped Q blocks as zeros and
    the logits at a prompt's last position are the dense path's."""
    cfg = solar_open2_tiny(head_dim=64, max_position_embeddings=1024,
                           prefill_segment=1024)
    check_padded_prefill_through_flash(SolarOpen2ForCausalLM(cfg, seed=11),
                                       flash_calls=1)
