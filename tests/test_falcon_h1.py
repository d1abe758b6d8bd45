"""Falcon-H1 through the serving engine against the plain reference
(benchmarks/references/falcon_h1.py), at the tiny preset on the CPU: an
attention branch over the paged cache and a Mamba-2 branch over a state
a slot side by side in every block, the µP multipliers, an untied head.

Tolerance on logits: 1e-6 absolute, in float32. The program and the
reference differ in summation order alone (the chunked scan against the
position-by-position recurrence, flash or paged attention against a
dense softmax, a multiplier folded into a norm), which moves float32
results by a few units of their last place: about 1e-8 on logits whose
spread is 8e-3 (the head multiplier's 1/128 in them). Dropping any one
multiplier, the gated norm, the D skip or the convolution's bias moves
them by 1e-4 or more.
"""

import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import falcon_h1 as ref
from paddle_tpu.distributed import fault_inject as fi
from paddle_tpu.inference.continuous_batching import ContinuousBatchingEngine
from paddle_tpu.inference.speculative import SpeculativeConfig
from paddle_tpu.models import (FalconH1ForCausalLM, StateCache,
                               UnsupportedCacheLayout, falcon_h1_34b,
                               falcon_h1_tiny)
from paddle_tpu.models import falcon_h1 as fh
from paddle_tpu.models.cache_layout import create_pools, create_state_pools
from paddle_tpu.models.gpt import PagedKVCache
from paddle_tpu.serving.prefix_cache import PrefixCache

PAGE = 4
ATOL = 1e-6
REPO = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def tiny():
    cfg = falcon_h1_tiny()
    model = FalconH1ForCausalLM(cfg, seed=3)
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


def _caches(layout, pools, rows, table, lens):
    return [StateCache(p[0], p[1], rows, lens) if lc.state is not None
            else PagedKVCache(p[0], p[1], None, None, table, lens)
            for p, lc in zip(pools, layout)]


def _keep(nc):
    return [(c.state, c.tail) if isinstance(c, StateCache)
            else (c.k_pages, c.v_pages) for c in nc]


# -- the cached path against the reference, on logits --------------------------

def test_a_block_gives_the_engine_two_memories(tiny):
    _, model, _, _ = tiny
    layout = model.cache_layout()
    assert len(layout) == 4
    kv, st = layout[:2]
    assert kv.state is None and kv.heads_major and kv.kv_heads == 2
    assert st.state == (4, 16, 8) and st.conv == (3, 32 + 2 * 2 * 16)


@pytest.mark.parametrize("plens,new", [
    ((5,), 6),          # shorter than a chunk and than a segment
    ((19, 3), 5),       # ragged: a row shorter than the convolution
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
    bucket = -(-max(plens) // 8) * 8  # right-padded, as the engine's
    padded = np.zeros((b, bucket), np.int32)
    for i, n in enumerate(plens):
        padded[i, :n] = seqs[i][:n]
    lens = jnp.asarray(plens, jnp.int32)
    logits, nc = model.forward(
        jnp.asarray(padded),
        caches=_caches(layout, pools, rows, table, jnp.zeros_like(lens)),
        prefill_lens=lens)
    for i, n in enumerate(plens):
        np.testing.assert_allclose(np.asarray(logits[i, :n]), want[i][:n],
                                   atol=ATOL, rtol=0)
    pools = _keep(nc)
    for j in range(new):
        tok = jnp.asarray([[seqs[i][n + j]] for i, n in enumerate(plens)],
                          jnp.int32)
        logits, nc = model.forward(
            tok, caches=_caches(layout, pools, rows, table, lens + j))
        pools = _keep(nc)
        for i, n in enumerate(plens):
            np.testing.assert_allclose(np.asarray(logits[i, 0]),
                                       want[i][n + j], atol=ATOL, rtol=0)
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
    _, nc = model.forward(jnp.asarray([[3], [4], [5]], jnp.int32),
                          caches=_caches(layout, pools, rows, table, lens))
    for c, p, lc in zip(nc, pools, layout):
        if lc.state is None:
            continue
        for old, new in ((p[0], c.state), (p[1], c.tail)):
            old, new = np.asarray(old), np.asarray(new)
            assert (new[1] == old[1]).all()
            assert (new[0] != old[0]).any() and (new[2] != old[2]).any()


# -- every multiplier and every part of the SSM's output is there --------------

def _dropped(cfg, what):
    """``(config, weights edit, patch)`` of a program that leaves
    ``what`` out."""
    if what.startswith("ssm_multipliers") or \
            what.startswith("mlp_multipliers"):
        key, i = what.rsplit(".", 1)
        ms = list(getattr(cfg, key))
        ms[int(i)] = 1.0
        return dataclasses.replace(cfg, **{key: tuple(ms)}), None, None
    if what == "gated_norm":
        def no_norm(y, z, w, groups, eps):  # the gate alone
            return y * (z / (1 + jnp.exp(-z))) * w
        return cfg, None, no_norm
    if what in ("d_skip", "conv_bias"):
        return cfg, what, None
    return dataclasses.replace(cfg, **{what: 1.0}), None, None


@pytest.mark.parametrize("what", [
    "embedding_multiplier", "key_multiplier", "attention_out_multiplier",
    "ssm_in_multiplier", "ssm_out_multiplier", "lm_head_multiplier",
    "ssm_multipliers.0", "ssm_multipliers.1", "ssm_multipliers.2",
    "ssm_multipliers.3", "ssm_multipliers.4", "mlp_multipliers.0",
    "mlp_multipliers.1", "gated_norm", "d_skip", "conv_bias"])
def test_a_program_without_any_one_part_fails_the_comparison(tiny, what,
                                                             monkeypatch):
    cfg, _, params, cd = tiny
    ids = _prompts(cfg, (24,), seed=2)[0]
    want = np.asarray(ref.forward_logits(cd, params, ids))
    dcfg, zero, patch = _dropped(cfg, what)
    if patch is not None:
        monkeypatch.setattr(fh, "gated_group_norm", patch)
    model = FalconH1ForCausalLM(dcfg, abstract=True)
    model.load_weights({n: (jnp.zeros_like(v) if zero and n.endswith(zero)
                            else v) for n, v in params.items()})
    got = np.asarray(model.forward(jnp.asarray(ids[None]))[0])
    assert np.abs(got - want).max() > 100 * ATOL


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
    # 2 blocks x 4 rows x (a state of 4 x 16 x 8 and a tail of 3 x 96),
    # float32 at this preset
    assert card["state_pool_bytes"] == 2 * 4 * 4 * (4 * 16 * 8 + 3 * 96)
    seen = [e for e in eng.step_timeline() if "state_slots" in e]
    assert seen and max(e["state_slots"] for e in seen) == 3
    assert all(e["kv_pages"].keys() == {"global"} for e in seen)
    # chunks of 16 in segments of 16: a prompt of 40 in a bucket of 64
    # visits 4 chunks and needs 3
    chunks = card["model_counters"]
    assert chunks["ssd_prefill_chunks.needed"]["sum"] == sum(
        -(-n // 16) for n in (5, 19, 40, 7, 33))
    assert chunks["ssd_prefill_chunks.scanned"]["sum"] == sum(
        -(-b // 16) for b in (8, 32, 64, 8, 64))
    for p, n, g in zip(prompts, news, got):
        assert _serve(_engine(model, num_slots=1), [p], [n]) == [g]
        # greedy against the reference's logits: every served token is
        # the reference's best (ties aside: none at this size)
        full = np.concatenate([p, np.asarray(g, np.int32)])
        logits = np.asarray(ref.forward_logits(cd, params, full))
        at = np.arange(len(p) - 1, len(full) - 1)
        gap = logits[at].max(-1) - logits[at, full[at + 1]]
        assert float(gap.max()) <= ATOL


def test_a_slot_reused_at_once_after_a_finish_under_the_look_ahead(tiny):
    """The step launched ahead moves a finished slot's state row once
    more; the next prompt into that slot writes the row from zero."""
    cfg, model, _, _ = tiny
    long, first, second = _prompts(cfg, (14, 9, 21), seed=4)
    eng = _engine(model, num_slots=2)
    eng.submit(long, 40)
    a = eng.submit(first, 7)
    b = eng.submit(second, 6)
    out = eng.run()
    assert eng.decode_rows_dropped >= 1
    assert eng.flight_summary()["state_rows_overwritten"] == \
        eng.decode_rows_dropped
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


def test_the_server_builds_the_preset_and_serves_one():
    from benchmarks.drivers.serve import rpc
    from paddle_tpu.serving.server import ServingServer, _build_model
    model = _build_model("falcon_h1_tiny")
    assert isinstance(model, FalconH1ForCausalLM)
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


def test_a_theta_written_as_an_integer_past_int32_is_served(tiny):
    """As the configuration file writes the published 1e11."""
    cfg, _, params, _ = tiny
    model = FalconH1ForCausalLM(dataclasses.replace(cfg,
                                                    rope_theta=10 ** 11),
                                abstract=True)
    model.load_weights(params)
    ids = jnp.asarray(_prompts(cfg, (9,))[0][None])
    assert np.isfinite(np.asarray(model.forward(ids))).all()


def test_a_padded_prompt_hands_flash_its_true_lengths(
        check_padded_prefill_through_flash):
    """Each block's attention (head size 64, a bucket of 640: the flash
    kernel in blocks of 128) hands it ``prefill_lens``; the SSM beside
    it and the blocks after read the rows of the skipped Q blocks as
    zeros and the logits at a prompt's last position are the dense
    path's."""
    cfg = falcon_h1_tiny(head_dim=64, max_position_embeddings=1024,
                         prefill_segment=1024)
    check_padded_prefill_through_flash(FalconH1ForCausalLM(cfg, seed=11),
                                       flash_calls=2)


# -- the configuration file ----------------------------------------------------

def test_the_configuration_file_is_the_published_config_cut_in_depth():
    """What the benchmark serves: the published widths and multipliers
    (the program's defaults are the config's), cut in depth and
    vocabulary alone, and its `assumed` equations name each multiplier
    at its value."""
    from benchmarks.builders import falcon_h1 as builder
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "falcon-h1-34b-serve.json")) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 72,
                                "vocab_size": 261120}
    got = builder.program_config(cfg)
    want = falcon_h1_34b(6, vocab_size=32640, prefill_segment=2048,
                         dtype="bfloat16")
    assert got == want
    # the file's theta is the integer 100000000000, over int32's range
    assert cfg["rope_theta"] == 10 ** 11 and type(got.rope_theta) is float
    text = " ".join(cfg["assumed"].values())
    for m in (0.0375, 0.01105, 0.25, 0.0884, 5.657, 0.0078125, 0.1768,
              0.01116, 0.354, 0.177, 0.5):
        assert str(m) in text, m
    for key, value in dataclasses.asdict(falcon_h1_34b()).items():
        if key in cfg and key not in cfg["reduced"] + ["dtype"]:
            assert cfg[key] == (list(value) if isinstance(value, tuple)
                                else value), key


def test_the_cells_arithmetic_from_its_shapes():
    """`flops_falcon_h1.py` at the cell's configuration: the numbers its
    `note` and the readers rest on (430.08 M parameters a block, a state
    of 4.19 MB a block, 5.19 GFLOP a prompt token)."""
    from benchmarks import flops_falcon_h1 as fl
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "falcon-h1-34b-serve.json")) as f:
        cfg = json.load(f)
    assert fl.matmul_params_per_layer(cfg) == 430_080_000
    assert fl.state_bytes(cfg) == 32 * 256 * 128 * 4
    # a decode step's state traffic at 128 live streams: 6.44 GB
    assert fl.ssd_decode_bytes(cfg, 128) == pytest.approx(
        128 * 6 * (2 * 4_194_304 + (2 * 4096 + 1024) * 2 + 128))
    per_token = fl.prefill_flops(cfg, 1) - 2 * 5120 * 32640
    assert per_token == pytest.approx(2 * 430.08e6 * 6 + 4 * 256 * 128
                                      * 32 * 6 + 4 * 20 * 128 * 6)
    # causal keys: a prompt of n sees n (n + 1) / 2 of them a block
    assert fl.attention_flops(cfg, 0, 4) == 4 * 20 * 128 * 10 * 6
    assert fl.decode_flops(cfg, [5]) == fl.forward_flops(cfg, 4, 1, 1)
    assert fl.ssd_prefill_bytes(cfg, 100) == 6 * (
        100 * ((2 * 4096 + 1024) * 2 + 128) + 4_194_304)
