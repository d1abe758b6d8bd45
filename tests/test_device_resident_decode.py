"""The decode step's inputs stay on the device (ISSUE 26).

The single-step decode program takes ONE packed int32 array
``[num_slots, max_pages + 2]`` (page table | length | current token)
and returns the next step's. The engine holds that output
(``_resident``) and feeds it back; the host mirrors stay the truth and
are sent, in one transfer, only on the decode step after a host write
(``_write_slot`` drops the copy) or a failed step. What is pinned here:

- tokens are bit-identical to an engine whose copy is dropped before
  every step, through the same writer, on the plain, prefix-cache,
  chunked-prefill, int8, speculative and mesh engines, chunked prefill
  on a mesh and a decoder with window rings and grouped heads, with
  one compiled decode program either way;
- a clean step makes no host-to-device transfer and one device-to-host
  fetch (the tokens), and records ``decode_h2d`` 0; a stale one records
  1; a step with a half-prefilled slot is always stale;
- admission, finish, eviction, a deadline that passes inside a prefill,
  an ``engine.step`` fault and a failed launch each leave the next
  decode step stale, and the tokens what they were;
- the device's lengths equal the host's own ``+ 1`` arithmetic while
  slots fill and empty, and an empty slot's length stays 0 there;
- ``flight_summary()``'s totals add up to the steps that decoded.

One decode step stays in flight ahead of the host (ISSUE 29): a call
that finds a step in flight and no slot write due launches the next
step from the device's outputs before it fetches that one's tokens.
Pinned below the older cases:

- tokens, and the order of the ``on_token`` calls, are those of an
  engine whose step in flight is settled after every call (it never
  runs ahead), on every engine variant;
- an ``eos_token`` finish under a step in flight: the extra row is
  never handed out, nothing leaks, and the request admitted into the
  freed slot yields its reference tokens;
- every event above, a stall eviction (the engine has no cancel call:
  the watchdog's typed eviction stands in), ``dump_inflight``,
  ``swap_weights`` and ``close`` arriving with a step in flight;
- a step launched ahead makes no host-to-device transfer and is
  launched before the fetch of the step before it; each call fetches
  once;
- ``decode_steps_ahead`` <= ``decode_steps_resident``, and the totals
  add up to the records.
"""

import time

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.distributed import fault_inject as fi
from paddle_tpu.distributed.topology import make_serving_mesh
from paddle_tpu.inference import SpeculativeConfig, create_decode_engine
from paddle_tpu.inference import continuous_batching as cb
from paddle_tpu.models import (FalconH1ForCausalLM, Glm4MoeLiteForCausalLM,
                               SmallThinkerForCausalLM,
                               SolarOpen2ForCausalLM, falcon_h1_tiny,
                               glm4_moe_lite_tiny, smallthinker_tiny,
                               solar_open2_tiny)
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving.prefix_cache import PrefixCache

PAGE = 8
ENGINE_KW = dict(num_slots=2, page_size=PAGE, max_seq_len=64,
                 timeline_steps=4096)

# engine variants; a chunked engine's masked steps have no copy to hold,
# and the speculative one builds its own arguments and never runs the
# single-step program: they must come out the same all the more.
# `rings` is the second decoder (window rings, grouped heads), `state`
# the third (a state a slot updated in place beside the pages),
# `latent` the fourth (one latent row a position in the allocator's
# pages, one pool a layer), `hybrid` the fifth (every block a state AND
# pages: two cache entries a block), at the tiny size
VARIANTS = {
    "plain": lambda: {},
    "prefix_cache": lambda: {"prefix_cache": PrefixCache(PAGE)},
    "mesh": lambda: {"mesh": make_serving_mesh(2)},
    "chunked": lambda: {"prefill_chunk_tokens": PAGE},
    "chunked_mesh": lambda: {"prefill_chunk_tokens": PAGE,
                             "mesh": make_serving_mesh(2)},
    "int8": lambda: {"kv_int8": True},
    "rings": lambda: {},
    "state": lambda: {},
    "latent": lambda: {},
    "hybrid": lambda: {},
    "speculative": lambda: {
        "speculative": SpeculativeConfig(k=2, draft="ngram")},
}
SINGLE_STEP = tuple(v for v in VARIANTS if v != "speculative")


@pytest.fixture(autouse=True)
def _clean_injector():
    fi.reset()
    yield
    fi.reset()


@pytest.fixture(scope="module", autouse=True)
def _compile_cache(module_compile_cache):
    yield


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def rings_model():
    return SmallThinkerForCausalLM(smallthinker_tiny(), seed=3)


@pytest.fixture(scope="module")
def state_model():
    return SolarOpen2ForCausalLM(solar_open2_tiny(), seed=3)


@pytest.fixture(scope="module")
def latent_model():
    return Glm4MoeLiteForCausalLM(glm4_moe_lite_tiny(), seed=3)


@pytest.fixture(scope="module")
def hybrid_model():
    return FalconH1ForCausalLM(falcon_h1_tiny(), seed=3)


@pytest.fixture
def build(model, rings_model, state_model, latent_model, hybrid_model):
    """An engine of a variant, on the model the variant serves."""
    def make(variant, **kw):
        m = {"rings": rings_model, "state": state_model,
             "latent": latent_model, "hybrid": hybrid_model}.get(variant,
                                                                 model)
        return _engine(m, **VARIANTS[variant](), **kw)
    return make


def _engine(m, **kw):
    return create_decode_engine(m, **{**ENGINE_KW, **kw})


def _prompts():
    """Six prompts over two slots: slots are refilled mid-flight, and
    the last two repeat the first two (whole pages for a prefix cache
    to hit)."""
    rng = np.random.default_rng(0)
    first = [rng.integers(0, 1024, n).astype(np.int32)
             for n in (17, 9, 13, 20)]
    return first + [first[0].copy(), first[3].copy()]


def _serve(eng, always_stale=False, never_ahead=False, calls=None,
           new_tokens=(9, 5, 12, 7, 6, 8)):
    """Run the prompts to the end; ``always_stale`` drops the device's
    copy before every step through the engine's own writer,
    ``never_ahead`` settles the step in flight after every call, so no
    step is launched over another; ``calls`` collects every
    ``on_token`` call in order."""
    on_token = None if calls is None else \
        (lambda r, t, d: calls.append((r, t, d)))
    rids = [eng.submit(p % eng.cfg.vocab_size, n, on_token=on_token)
            for p, n in zip(_prompts(), new_tokens)]
    while eng.num_queued or eng.num_active:
        if always_stale:
            eng._write_slot(0)
        eng.step()
        if never_ahead:
            eng._settle_inflight()
    return [eng.result(r).tolist() for r in rids]


def _h2d(eng):
    """``decode_h2d`` of the records that carry it, oldest first."""
    return [e["decode_h2d"] for e in eng.timeline if "decode_h2d" in e]


def _decode_until_clean(eng, limit=16):
    """Step until a step decoded without an upload."""
    for _ in range(limit):
        eng.step()
        if _h2d(eng)[-1:] == [0]:
            return
    raise AssertionError(f"no clean step in {limit}: {_h2d(eng)}")


class _CountingNumpy:
    """``numpy`` for the engine module, counting its fetches of device
    arrays (``np.asarray`` is how the engine reads a result)."""

    def __init__(self):
        self.fetched = []

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, x, *a, **k):
        if isinstance(x, jax.Array):
            self.fetched.append(tuple(x.shape))
        return np.asarray(x, *a, **k)


# ---------------------------------------------------------------------------
# Bit-identity against the always-stale engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_tokens_identical_to_an_always_stale_engine(build, variant):
    resident = build(variant)
    got = _serve(resident)
    stale = build(variant)
    want = _serve(stale, always_stale=True)
    assert got == want
    if variant in SINGLE_STEP:
        # one compiled decode program, whichever way its input came
        assert resident._decode_jit._cache_size() == 1
        assert stale._decode_jit._cache_size() == 1
        assert set(_h2d(stale)) == {1}
        assert 0 in _h2d(resident)
    else:
        assert _h2d(resident) == [] and resident._decode_jit is None
    resident.close()
    stale.close()


# ---------------------------------------------------------------------------
# Transfers of a clean and of a stale step
# ---------------------------------------------------------------------------

def test_clean_step_uploads_nothing_and_fetches_once(model, monkeypatch):
    eng = _engine(model)
    eng.submit(_prompts()[0], 12)
    eng.submit(_prompts()[1], 12)
    eng.step()  # admits both: this step's decode uploads
    assert _h2d(eng) == [1] and eng._resident is not None
    counting = _CountingNumpy()
    monkeypatch.setattr(cb, "np", counting)
    for _ in range(3):
        with jax.transfer_guard_host_to_device("disallow_explicit"):
            eng.step()
    assert _h2d(eng) == [1, 0, 0, 0]
    # one fetch a step: the tokens, and nothing else
    assert counting.fetched == [(eng.num_slots,)] * 3


def test_stale_step_is_one_transfer(model, monkeypatch):
    eng = _engine(model)
    eng.submit(_prompts()[0], 12)
    _decode_until_clean(eng)
    puts = []
    real = eng._place_resident
    monkeypatch.setattr(eng, "_place_resident",
                        lambda a: puts.append(a.shape) or real(a))
    eng._write_slot(1)  # a host write that changes nothing
    assert eng._resident is None
    # the guard lets the one explicit transfer through and nothing
    # implicit beside it
    with jax.transfer_guard_host_to_device("disallow"):
        eng.step()
    assert _h2d(eng)[-1] == 1
    assert puts == [(eng.num_slots, eng.max_pages + 2)]
    with jax.transfer_guard_host_to_device("disallow_explicit"):
        eng.step()
    assert _h2d(eng)[-1] == 0 and len(puts) == 1


@pytest.mark.parametrize("variant", ["plain", "mesh", "chunked_mesh"])
def test_an_upload_never_hands_the_device_the_mirrors_themselves(
        build, variant, monkeypatch):
    """The mirrors are written in place, a transfer may alias host
    memory or copy it late, and on a mesh the step's one fetch waits for
    the first device alone: an upload of ``_packed`` itself let the
    second device read lengths the host had already advanced (the
    ``[mesh]`` flake of the bit-identity test under load, ROADMAP D8).
    What is uploaded is a copy nobody writes again: the packed inputs
    of a decode step, and the table row of a prefill chunk."""
    eng = build(variant)
    sent = []
    real = eng._place_resident

    def place(a):
        assert not np.shares_memory(a, eng._packed)
        sent.append((a, a.copy()))
        return real(a)

    monkeypatch.setattr(eng, "_place_resident", place)
    rows = []
    real_asarray = eng._jnp.asarray

    class _Jnp:
        """``jnp`` for this engine, keeping what a chunk uploads."""

        def __getattr__(self, name):
            return getattr(jax.numpy, name)

        def asarray(self, a, *args, **kw):
            if isinstance(a, np.ndarray) and a.shape == (1, eng.max_pages):
                assert not np.shares_memory(a, eng._packed)
                rows.append((a, a.copy()))
            return real_asarray(a, *args, **kw)

    eng._jnp = _Jnp()
    _serve(eng)
    assert len(sent) == eng.decode_steps_uploaded > 0
    assert all((a == was).all() for a, was in sent)
    # a row a prefill, whole or a chunk (a chunk's ids have that shape
    # here too: they are the chunk's own array)
    assert len(rows) >= sum(eng.programs_launched.get(k, 0) for k in
                            ("prefill", "prefill_chained")) > 0
    assert all((a == was).all() for a, was in rows)
    eng.close()


def test_a_half_prefilled_slot_keeps_every_step_stale(model):
    eng = _engine(model, prefill_chunk_tokens=PAGE)
    eng.submit(_prompts()[1], 30)  # 9 tokens: two chunks
    while not any(r is not None and r.state == "decoding"
                  for r in eng._slots):
        eng.step()
    _decode_until_clean(eng)
    eng.submit(_prompts()[3], 4)  # 20 tokens: three chunks
    seen = []
    for _ in range(3):
        eng.step()
        partial = any(r is not None and r.state == "prefill_partial"
                      for r in eng._slots)
        seen.append((partial, eng.timeline[-1]["decode_h2d"],
                     eng._resident is None))
    # masked steps upload and leave nothing behind to reuse
    assert (True, 1, True) in seen
    assert all(h2d == 1 and gone for partial, h2d, gone in seen if partial)


# ---------------------------------------------------------------------------
# What makes the next step stale
# ---------------------------------------------------------------------------

def _reference_tokens(model, new_tokens, prompt=0):
    eng = _engine(model)
    rid = eng.submit(_prompts()[prompt], new_tokens)
    return eng.run()[rid].tolist()


def _admission(eng):
    eng.submit(_prompts()[1], 3)
    return "queued"  # the next step's admission is the host write


def _finish(eng):
    # the short request of the pair is one token from its end
    short = eng._slots[1]
    while len(short.generated) < short.max_new_tokens - 1:
        eng.step()
    assert _h2d(eng)[-1] == 0
    eng.step()
    assert short.done and _h2d(eng)[-1] == 0  # it finished after its step


def _eviction(eng):
    victim = eng._slots[1]
    victim.deadline_t = time.monotonic() - 1.0
    assert eng.expire_deadlines() == [victim]
    assert victim.state == "deadline"


def _deadline_in_prefill(eng):
    """The deadline passes while the prefill runs: the admission is
    unwound after the pools were adopted."""
    real = eng._get_prefill(False)

    def slow(*a):
        time.sleep(0.3)
        return real(*a)

    eng._prefill_jits[False] = slow
    eng.submit(_prompts()[2], 5, deadline_t=time.monotonic() + 0.15)
    late = eng._queue[-1]
    eng.step()
    eng._prefill_jits[False] = real
    assert late.state == "deadline" and eng.num_active == 1
    return "this_step"


def _step_fault(eng):
    fi.get_injector().arm("engine.step", at_calls=[1])
    with pytest.raises(fi.InjectedFault):
        eng.step()
    fi.reset()


def _launch_fault(eng):
    real = eng._decode_jit

    def broken(*a):
        raise RuntimeError("launch failed")

    eng._decode_jit = broken
    with pytest.raises(RuntimeError, match="launch failed"):
        eng.step()
    eng._decode_jit = real


EVENTS = {"admission": (_admission, False), "finish": (_finish, True),
          "eviction": (_eviction, True),
          "deadline_in_prefill": (_deadline_in_prefill, False),
          "engine_step_fault": (_step_fault, False),
          "launch_fault": (_launch_fault, False)}


@pytest.mark.parametrize("event", sorted(EVENTS))
def test_event_leaves_the_next_step_stale_and_correct(model, event):
    happen, second_slot = EVENTS[event]
    eng = _engine(model)
    rid = eng.submit(_prompts()[0], 24)
    if second_slot:
        eng.submit(_prompts()[1], 6)
    _decode_until_clean(eng)
    when = happen(eng)
    if when is None:
        assert eng._resident is None
    if when != "this_step":
        eng.step()
    assert eng.timeline[-1]["decode_h2d"] == 1
    eng.step()
    assert eng.timeline[-1]["decode_h2d"] == 0
    assert eng.run()[rid].tolist() == _reference_tokens(model, 24)


def test_a_rebuilt_engine_starts_stale_and_continues_the_stream(model):
    """Resurrection: what was in flight is replayed (prompt plus the
    tokens already out) on a new engine, whose first decode step has
    nothing on the device to reuse."""
    eng = _engine(model)
    eng.submit(_prompts()[0], 24)
    _decode_until_clean(eng)
    (req,) = eng.dump_inflight()
    done = list(req.generated)
    assert 0 < len(done) < 24
    eng.close()
    fresh = _engine(model)
    assert fresh._resident is None
    rid = fresh.submit(np.concatenate([req.prompt, done]).astype(np.int32),
                       24 - len(done))
    fresh.step()
    assert _h2d(fresh) == [1]
    # a result is the prompt and everything generated after it
    assert fresh.run()[rid].tolist() == _reference_tokens(model, 24)


# ---------------------------------------------------------------------------
# The host's arithmetic against the device's
# ---------------------------------------------------------------------------

def test_device_lengths_follow_the_host_mirror(model):
    eng = _engine(model, num_slots=3, max_seq_len=96)
    mp = eng.max_pages
    rng = np.random.default_rng(1)
    # slot 2 stays empty for the first stretch, then requests of
    # different lengths fill and empty all three
    lengths = [(11, 30), (5, 30)] + [
        (int(rng.integers(3, 20)), int(rng.integers(2, 9)))
        for _ in range(8)]
    pending = [(rng.integers(0, 1024, n).astype(np.int32), k)
               for n, k in lengths]
    for p, k in pending[:2]:
        eng.submit(p, k)
    pending = pending[2:]
    compared = empty_seen = 0
    for step in range(6 * PAGE):
        if step >= 3 * PAGE // 2 and pending and step % 3 == 0:
            eng.submit(*pending.pop())
        eng.step()
        if step % 2:
            continue  # leave the step in flight: the next runs ahead
        # the device's copy is that of the NEWEST launch: it agrees with
        # the mirrors once every launched step is settled
        eng._settle_inflight()
        if eng._resident is None:
            continue
        dev = np.asarray(eng._resident)
        assert dev.shape == eng._packed.shape
        np.testing.assert_array_equal(dev[:, mp], eng._lens)
        np.testing.assert_array_equal(dev[:, :mp], eng._table)
        busy = np.array([r is not None for r in eng._slots])
        np.testing.assert_array_equal(dev[busy, mp + 1], eng._cur[busy])
        assert (dev[~busy, mp] == 0).all()
        compared += 1
        empty_seen += int((~busy).any())
    assert compared >= PAGE and empty_seen >= PAGE // 2
    assert not pending
    assert eng.decode_steps_ahead >= PAGE
    eng.run()


def test_flight_summary_totals_add_up(model):
    eng = _engine(model)
    _serve(eng)
    card = eng.flight_summary()
    h2d = _h2d(eng)
    assert card["decode_steps_uploaded"] == sum(h2d) > 0
    assert card["decode_steps_resident"] == h2d.count(0) > 0
    assert len(h2d) == eng.programs_launched["decode"]
    assert all(("decode_h2d" in e) == ("decode" in e["programs"])
               for e in eng.timeline)


# ---------------------------------------------------------------------------
# One decode step in flight ahead of the host (ISSUE 29)
# ---------------------------------------------------------------------------

def _ahead(eng):
    """``decode_ahead`` of the records that carry it, oldest first."""
    return [e["decode_ahead"] for e in eng.timeline if "decode_ahead" in e]


def _serve_streams(eng, never_ahead=False):
    """``_serve``'s results and every ``on_token`` call in order."""
    calls = []
    return _serve(eng, never_ahead=never_ahead, calls=calls), calls


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_tokens_identical_to_an_engine_that_never_runs_ahead(build, variant):
    ahead = build(variant)
    got, got_calls = _serve_streams(ahead)
    sync = build(variant)
    want, want_calls = _serve_streams(sync, never_ahead=True)
    assert got == want
    # a request's stream is the same calls in the same order; only the
    # point at which a queued request gets its freed slot may move
    for rid in {r for r, _, _ in want_calls}:
        assert [c for c in got_calls if c[0] == rid] == \
            [c for c in want_calls if c[0] == rid]
    assert sync.decode_steps_ahead == 0 and set(_ahead(sync)) <= {0}
    if variant in SINGLE_STEP:
        assert ahead.decode_steps_ahead > 0
        assert ahead._decode_jit._cache_size() == 1
        # nothing is left in flight once the last slot has emptied
        assert ahead._inflight is None
    else:
        assert ahead.decode_steps_ahead == 0 and ahead._inflight is None
    ahead.close()
    sync.close()


def _eos_case(model):
    """A token the reference stream of prompt 0 holds for the first
    time at its 5th to 12th place, so that an ``eos_token`` finish
    lands mid-stream, with a step in flight over it."""
    ref = _reference_tokens(model, 24)[len(_prompts()[0]):]
    for k in range(4, 12):
        if ref[k] not in ref[:k]:
            return ref[k], ref[:k + 1]
    raise AssertionError(f"no usable eos in {ref}")


@pytest.mark.parametrize("queued", [False, True],
                         ids=["slot_stays_free", "slot_is_refilled"])
def test_eos_finish_under_a_step_in_flight(model, queued):
    eos, want = _eos_case(model)
    eng = _engine(model)
    calls = []
    first = eng.submit(_prompts()[0], 24, eos_token=eos,
                       on_token=lambda r, t, d: calls.append((t, d)))
    other = eng.submit(_prompts()[1], 30)
    late = eng.submit(_prompts()[3], 6) if queued else None
    req = eng._queue[0]
    while not req.done:
        eng.step()
    # the finish was not known when the step after it was launched
    assert eng._inflight is not None and eng._resident is None
    assert eng.decode_rows_dropped == 0
    eng.step()  # settles it (the one row is dropped), then admits
    assert eng.decode_rows_dropped == 1
    assert (eng._slots[0] is not None) == queued
    out = eng.run()
    assert out[first].tolist()[len(_prompts()[0]):] == want
    # the row computed past the end reached nobody
    assert calls == [(t, False) for t in want[:-1]] + [(eos, True)]
    assert req.stats.tokens_out == len(want)
    assert out[other].tolist() == _reference_tokens(model, 30, prompt=1)
    if queued:
        assert out[late].tolist() == _reference_tokens(model, 6, prompt=3)
    eng.allocator.check_no_leak()
    eng.close()


def _stall_eviction(eng):
    """The watchdog's typed eviction (what a cancel would be: the
    engine has no such call)."""
    victim = eng._slots[1]
    victim.last_emit_t = victim.stats.admit_t = time.monotonic() - 60.0
    eng.stall_timeout_s = 30.0
    assert eng.evict_stalled() == [victim] and victim.state == "stalled"
    eng.stall_timeout_s = None


def _dump(eng):
    before = [len(r.generated) for r in eng._slots]
    snap = eng.dump_inflight()
    # the step in flight was folded into the snapshot
    assert eng._inflight is None
    assert [len(r.generated) for r in snap] == [n + 1 for n in before]
    return "settled"


def _swap(eng):
    state = eng.model.state_dict(include_non_persistable_buffer=True)
    with pytest.raises(cb.SwapFailed, match="engine busy"):
        eng.swap_weights(state)
    assert eng._inflight is None  # settled before the slots were counted
    return "settled"


IN_FLIGHT = dict(EVENTS, stall_eviction=(_stall_eviction, True),
                 dump_inflight=(_dump, True), swap_weights=(_swap, True))


@pytest.mark.parametrize("variant", ["plain", "chunked"])
@pytest.mark.parametrize("event", sorted(IN_FLIGHT))
def test_event_arrives_with_a_step_in_flight(build, model, event, variant):
    happen, second_slot = IN_FLIGHT[event]
    eng = build(variant)
    calls = []
    rid = eng.submit(_prompts()[0], 24,
                     on_token=lambda r, t, d: calls.append(t))
    if second_slot:
        eng.submit(_prompts()[1], 12)
    _decode_until_clean(eng)
    eng.step()
    assert eng._inflight is not None and _ahead(eng)[-1] == 1
    when = happen(eng)
    if event == "finish":
        # found out at its settle, under the step launched over it
        assert eng._inflight is not None and eng._resident is None
    elif event in ("engine_step_fault", "launch_fault"):
        # the step in flight went with the failure, never handed out
        assert eng._inflight is None and eng._resident is None
    elif when == "settled" or when is None:
        assert eng._inflight is None
    want = _reference_tokens(model, 24)
    n = len(_prompts()[0])
    # what was handed out is a prefix of the stream, each token once
    assert calls == want[n:n + len(calls)]
    assert eng.run()[rid].tolist() == want
    assert calls == want[n:]
    eng.allocator.check_no_leak()


def test_close_with_a_step_in_flight_hands_its_tokens_out_first(model):
    done = []
    eng = _engine(model, on_complete=done.append)
    calls = []
    eng.submit(_prompts()[0], 24, on_token=lambda r, t, d: calls.append(t))
    _decode_until_clean(eng)
    assert eng._inflight is not None
    (req,) = [r for r in eng._slots if r is not None]
    handed = len(calls)
    eng.close()  # asserts that nothing leaked
    assert eng._inflight is None
    assert len(calls) == handed + 1 == len(req.generated)
    assert done == [req] and req.state == "evicted"
    n = len(_prompts()[0])
    assert calls == _reference_tokens(model, 24)[n:n + len(calls)]


def test_a_step_ahead_is_launched_before_the_fetch_and_uploads_nothing(
        model, monkeypatch):
    eng = _engine(model)
    eng.submit(_prompts()[0], 12)
    eng.submit(_prompts()[1], 12)
    eng.step()  # admits both; its decode step stays in flight
    assert (_h2d(eng), _ahead(eng)) == ([1], [0])
    assert eng._inflight is not None
    order = []
    counting = _CountingNumpy()
    real_asarray = counting.asarray

    def fetch(x, *a, **k):
        if isinstance(x, jax.Array):
            order.append("fetch")
        return real_asarray(x, *a, **k)

    monkeypatch.setattr(counting, "asarray", fetch)
    monkeypatch.setattr(cb, "np", counting)
    real_jit = eng._decode_jit

    def launch(*a):
        order.append("launch")
        return real_jit(*a)

    eng._decode_jit = launch
    for _ in range(3):
        with jax.transfer_guard_host_to_device("disallow_explicit"):
            eng.step()
    eng._decode_jit = real_jit
    assert order == ["launch", "fetch"] * 3
    assert (_h2d(eng), _ahead(eng)) == ([1, 0, 0, 0], [0, 1, 1, 1])
    # one fetch a call: the tokens of the step before, and nothing else
    assert counting.fetched == [(eng.num_slots,)] * 3
    eng.run()


def test_ahead_totals_add_up(model):
    eng = _engine(model)
    results, calls = _serve_streams(eng)
    card = eng.flight_summary()
    h2d, ahead = _h2d(eng), _ahead(eng)
    assert len(h2d) == len(ahead) == eng.programs_launched["decode"]
    assert all(("decode_ahead" in e) == ("decode_h2d" in e)
               for e in eng.timeline)
    # a step ahead is fed the outputs of the step it is launched over
    assert all(not (a and h) for a, h in zip(ahead, h2d))
    assert 0 < card["decode_steps_ahead"] == sum(ahead) \
        <= card["decode_steps_resident"] == h2d.count(0)
    assert card["decode_steps_uploaded"] == sum(h2d)
    # every token after a request's first came out of a decode step,
    # and every row of a launched step was handed out or dropped
    decoded = sum(len(r) for r in results) \
        - sum(len(p) for p in _prompts()) - len(results)
    assert decoded == len(calls) - len(results)
    assert card["decode_rows_dropped"] > 0  # finishes rode the look-ahead
