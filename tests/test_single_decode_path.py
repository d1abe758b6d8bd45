"""One way to keep the device fed (ISSUE 30): the macro-step decode path
(``multi_step``, ``inprogram``) is gone, and the look-ahead of depth one
is what every engine runs. Pinned here:

- each removed option is refused at each surface, not ignored: the
  engine's keywords, the server's and the supervisor's flag, and a
  resurrection recipe that still carries the keyword, at construction;
- what the two deleted CPU benches counted, as exact numbers: the kinds
  in ``programs_launched`` per engine variant, decode launches against
  decode steps, prefill launches against admitted requests;
- engine behaviour that only the macro tests pinned, for the engine that
  remains: a ``max_new_tokens=1`` request ends at its prefill; streamed
  tokens precede ``on_complete`` with a step in flight; the deadline
  gate charges ``decode_ema_s`` a launch; only a speculative engine
  reserves growth pages, and returns them; the supervisor hands each
  engine flag to its replicas as it was given.
"""

import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.inference import SpeculativeConfig, create_decode_engine
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

PAGE = 8
ENGINE_KW = dict(num_slots=2, page_size=PAGE, max_seq_len=64,
                 timeline_steps=4096)
VARIANTS = {
    "plain": lambda: {},
    "chunked": lambda: {"prefill_chunk_tokens": PAGE},
    "speculative": lambda: {
        "speculative": SpeculativeConfig(k=2, draft="ngram")},
}


@pytest.fixture(scope="module", autouse=True)
def _compile_cache(module_compile_cache):
    yield


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


def _engine(m, **kw):
    return create_decode_engine(m, **{**ENGINE_KW, **kw})


def _prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 1024, n).astype(np.int32)
            for n in (17, 9, 13, 20)]


# ---------------------------------------------------------------------------
# A removed option is refused, not ignored
# ---------------------------------------------------------------------------

class _Reached(RuntimeError):
    """The supervisor's `main` got past its argument parser."""


def _refuse_engine(model, keyword):
    with pytest.raises(TypeError, match=keyword):
        _engine(model, **{keyword: 4})


def _refuse_flag(model, module):
    import importlib
    mod = importlib.import_module(f"paddle_tpu.serving.{module}")
    with pytest.raises(SystemExit) as e:
        mod.main(["--model", "gpt_tiny", "--multi-step", "4"])
    assert e.value.code == 2  # argparse: unrecognized arguments


def _refuse_recipe(model, _):
    """A recipe a deployment kept from before: refused where the server
    is built, not at the first step or the first resurrection."""
    from paddle_tpu.serving.server import ServingServer
    with pytest.raises(TypeError, match="multi_step"):
        ServingServer(model, port=0, multi_step=4, **ENGINE_KW)


REMOVED = {
    "engine_multi_step": (_refuse_engine, "multi_step"),
    "engine_inprogram": (_refuse_engine, "inprogram"),
    "server_flag": (_refuse_flag, "server"),
    "supervisor_flag": (_refuse_flag, "supervisor"),
    "engine_kwargs_recipe": (_refuse_recipe, None),
}


@pytest.mark.parametrize("surface", sorted(REMOVED))
def test_removed_option_is_refused(model, surface):
    refuse, arg = REMOVED[surface]
    refuse(model, arg)


# ---------------------------------------------------------------------------
# Launches, counted
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_program_kinds_and_launch_counts(model, variant):
    eng = _engine(model, **VARIANTS[variant]())
    new = (9, 5, 12, 7)
    for p, n in zip(_prompts(), new):
        eng.submit(p, n)
    eng.run()
    got = dict(eng.programs_launched)
    tl = eng.step_timeline()
    decoded = sum(1 for e in tl if "decode" in e["programs"])
    decode_tokens = sum(n - 1 for n in new)
    if variant == "plain":
        # a whole-prompt prefill a request; a decode program a call that
        # had a slot decoding; a settled step a launch, but for the
        # launches whose every row a finish had emptied (dropped)
        assert set(got) == {"prefill", "decode"}
        assert got["prefill"] == len(new)
        assert got["decode"] == decoded
        assert 0 <= got["decode"] - eng.steps <= len(new)
        assert max(new) - 1 <= eng.steps <= decode_tokens
        assert eng.steps * eng.num_slots >= decode_tokens
    elif variant == "chunked":
        # chunk 1 of a prompt is the dense program, the others chained:
        # 17, 9, 13 and 20 tokens in chunks of 8
        assert set(got) == {"prefill", "prefill_chained", "decode"}
        assert got["prefill"] == len(new)
        assert got["prefill_chained"] == sum(
            -(-len(p) // PAGE) - 1 for p in _prompts())
        assert got["decode"] == decoded >= eng.steps
    else:
        # a verify program a step, never the single-step decode program
        assert set(got) == {"prefill", "verify"}
        assert got["prefill"] == len(new)
        assert got["verify"] == eng.steps == sum(
            1 for e in tl if "verify" in e["programs"])
        assert got["verify"] < decode_tokens
        assert eng._decode_jit is None
    assert sum(sum(e["programs"].values()) for e in tl) == sum(got.values())
    eng.close()


# ---------------------------------------------------------------------------
# What only the macro tests pinned, for the engine that remains
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_single_token_requests_end_at_their_prefill(model, variant):
    eng = _engine(model, **VARIANTS[variant]())
    calls = []
    rids = [eng.submit(p, 1, on_token=lambda r, t, d: calls.append((r, d)))
            for p in _prompts()]
    out = eng.run()
    assert [len(out[r]) - len(p) for r, p in zip(rids, _prompts())] \
        == [1] * 4
    assert calls == [(r, True) for r in rids]
    # no slot ever decoded: no decode or verify program, none in flight
    assert not {"decode", "verify"} & set(eng.programs_launched)
    assert eng._inflight is None and eng.decode_steps_ahead == 0
    eng.close()


def test_streamed_tokens_precede_completion_with_a_step_in_flight(model):
    events = []
    # `_tl_decode`: (decode_h2d, decode_ahead) of the step this call
    # launched, before it settled the one whose tokens it hands out
    eng = _engine(model, on_complete=lambda r: events.append(
        ("done", r.req_id, eng._tl_decode == (0, 1))))
    rids = [eng.submit(p, 8, on_token=lambda r, t, d: events.append(
        ("tok", r, d))) for p in _prompts()[:2]]
    eng.run()
    for rid in rids:
        toks = [i for i, e in enumerate(events) if e[:2] == ("tok", rid)]
        (done,) = [i for i, e in enumerate(events)
                   if e[:2] == ("done", rid)]
        assert len(toks) == 8 and toks[-1] < done
        assert events[toks[-1]] == ("tok", rid, True)
    # a count-known finish rides the look-ahead: at least one of the two
    # completions was handed out under a launched step
    assert any(e[2] for e in events if e[0] == "done")
    eng.close()


@pytest.mark.parametrize("variant,tokens,hopeless", [
    ("plain", 8, False), ("plain", 16, True),
    ("speculative", 30, False), ("speculative", 31, True)])
def test_deadline_gate_charges_the_ema_a_launch(model, variant, tokens,
                                                hopeless):
    """``decode_ema_s`` is the cadence of one launch, a token a slot on
    the plain engine and at best k + 1 = 3 on the speculative one: at
    0.25 s, 8 tokens (30) fit 2.6 s and 16 (31: an eleventh launch) do
    not. Nothing divides it by anything else."""
    eng = _engine(model, **VARIANTS[variant]())
    eng.decode_ema_s = 0.25
    now = time.monotonic()
    eng.submit(_prompts()[0], tokens, deadline_t=now + 2.6)
    assert eng._deadline_hopeless(eng._queue[-1], now) is hopeless
    eng.close()


@pytest.mark.parametrize("variant", ["plain", "speculative"])
def test_only_a_speculative_engine_reserves_growth_pages(model, variant):
    eng = _engine(model, **VARIANTS[variant]())
    for p in _prompts():
        eng.submit(p, 12)
    reserved = []
    while eng.num_queued or eng.num_active:
        eng.step()
        reserved.append(eng.allocator.reserved_total)
        assert eng.timeline[-1]["reserved_pages"] == reserved[-1]
    if variant == "plain":
        # every page is bound at admission
        assert set(reserved) == {0}
        assert eng._reserve_growth is False
    else:
        # admission binds the prompt's pages and reserves the rest;
        # every finish gives the remainder back
        assert max(reserved) > 0 and reserved[-1] == 0
    assert eng.allocator.reserved_total == 0
    eng.close()  # asserts that nothing leaked


# the supervisor's flags that reach a replica's engine, and the server's
# argv each becomes
FORWARDED = {
    "prefill_chunk": (["--prefill-chunk", "16"], ["--prefill-chunk", "16"]),
    "no_fused_step": (["--no-fused-step"], ["--no-fused-step"]),
    "spill_mb": (["--spill-mb", "64"], ["--spill-mb", "64"]),
    "trace_sample": (["--trace-sample", "0.25"], ["--trace-sample", "0.25"]),
    "slo_ttft_ms": (["--slo-ttft-ms", "250.0"], ["--slo-ttft-ms", "250.0"]),
    "slo_tpot_ms": (["--slo-tpot-ms", "40.0"], ["--slo-tpot-ms", "40.0"]),
}


@pytest.mark.parametrize("flag", sorted(FORWARDED))
def test_supervisor_forwards_an_engine_flag_verbatim(monkeypatch, flag):
    import signal

    from paddle_tpu.serving import supervisor as sup_mod
    given, want = FORWARDED[flag]
    seen = {}

    class Captured:
        def __init__(self, **kw):
            seen.update(kw)
            raise _Reached  # before anything is spawned

    monkeypatch.setattr(sup_mod, "Supervisor", Captured)
    monkeypatch.setattr(signal, "signal", lambda *a: None)
    with pytest.raises(_Reached):
        sup_mod.main(["--replicas", "1", "--model", "gpt_tiny"] + given)
    args = seen["server_args"]
    at = args.index(want[0])
    assert args[at:at + len(want)] == want
    # and nothing it was not given
    assert [a for a in args if a.startswith("--")] == [want[0]]
