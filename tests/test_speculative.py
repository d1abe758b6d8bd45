"""Speculative decoding over the paged engine (ISSUE r8 acceptance):

- greedy draft-and-verify output is BIT-IDENTICAL to the vanilla
  engine for every draft source (n-gram, draft model, adversarial
  always-wrong), across kv_cache paged and paged_int8, prefix cache
  on and off;
- rejection storms roll back cleanly: seq_lens rewound, wholly-unused
  pages returned to the allocator mid-flight, ``check_no_leak`` green
  on every path, shared prefix pages never touched;
- the ``serving.verify`` fault site retries transients invisibly
  (same pattern as ``serving.prefill``) and fails loudly when
  persistent;
- acceptance-rate / tokens-per-step telemetry flows through
  RequestStats into ServingMetrics and the Prometheus export.
"""

import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.distributed import fault_inject as fi
from paddle_tpu.inference import (CallableDraft, ModelDraft, NGramDraft,
                                  PageAllocator, SpeculativeConfig,
                                  create_decode_engine)
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import ServingMetrics

VOCAB = 1024


@pytest.fixture(autouse=True)
def _clean_injector():
    fi.reset()
    yield
    fi.reset()


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


def _engine(m, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("num_pages", 12)
    return create_decode_engine(m, **kw)


def _prompts():
    shared = (np.arange(19, dtype=np.int32) * 5) % 100
    return [np.concatenate([shared,
                            (np.arange(t, dtype=np.int32) + 3 * t) % 100])
            for t in (3, 5, 7, 9)]


def _run(m, new_tokens=12, **kw):
    done = []
    eng = _engine(m, on_complete=done.append, **kw)
    rids = [eng.submit(p, max_new_tokens=new_tokens) for p in _prompts()]
    out = eng.run()
    eng.close()
    eng.allocator.check_no_leak()
    return [out[r] for r in rids], done


@pytest.fixture(scope="module")
def vanilla(model):
    out, _ = _run(model)
    return out


def _wrong_draft():
    """Adversarial draft: always proposes a token != the target's
    greedy choice cannot be guaranteed, but (last + 7) mod vocab is
    wrong in practice for a random-weight model — the rejection-storm
    generator the rollback tests lean on."""
    return CallableDraft(lambda h, k: [(int(h[-1]) + 7) % VOCAB] * k)


# ---------------------------------------------------------------------------
# Shared sampler + verify math (nn/decode.py)
# ---------------------------------------------------------------------------

class TestSharedSampler:
    def test_sample_token_greedy_is_argmax(self):
        import jax.numpy as jnp
        from paddle_tpu.nn.decode import sample_token
        rng = np.random.default_rng(0)
        last = jnp.asarray(rng.standard_normal((4, 16)).astype(
            np.float32))
        tok, key = sample_token(last, 0.0)
        assert key is None
        np.testing.assert_array_equal(np.asarray(tok),
                                      np.argmax(np.asarray(last), -1))
        assert np.asarray(tok).dtype == np.int32

    def test_sample_token_temperature_topk_in_range(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.nn.decode import sample_token
        rng = np.random.default_rng(0)
        last = jnp.asarray(rng.standard_normal((8, 32)).astype(
            np.float32))
        tok, key = sample_token(last, 0.7, 4, jax.random.PRNGKey(0))
        # every sample must come from the top-4 of its row
        top4 = np.argsort(np.asarray(last), -1)[:, -4:]
        for i, t in enumerate(np.asarray(tok)):
            assert t in top4[i]
        # key advanced (deterministic resume point)
        assert not np.array_equal(np.asarray(key),
                                  np.asarray(jax.random.PRNGKey(0)))

    def test_verify_tokens_greedy_semantics(self):
        import jax.numpy as jnp
        from paddle_tpu.nn.decode import speculative_verify_tokens
        # [1, 3, 4] logits with known argmaxes 2, 0, 3
        lg = np.full((1, 3, 4), -5.0, np.float32)
        lg[0, 0, 2] = lg[0, 1, 0] = lg[0, 2, 3] = 5.0
        drafts = np.asarray([[2, 1]], np.int32)  # first right, 2nd wrong
        accept, resid, full, _ = speculative_verify_tokens(
            jnp.asarray(lg), jnp.asarray(drafts), 0.0)
        np.testing.assert_array_equal(np.asarray(full), [[2, 0, 3]])
        np.testing.assert_array_equal(np.asarray(accept),
                                      [[True, False]])
        np.testing.assert_array_equal(np.asarray(resid), [[2, 0]])

    def test_verify_tokens_residual_excludes_draft(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.nn.decode import speculative_verify_tokens
        rng = np.random.default_rng(0)
        lg = jnp.asarray(rng.standard_normal((3, 4, 8)).astype(
            np.float32))
        drafts = jnp.asarray(rng.integers(0, 8, (3, 3)).astype(
            np.int32))
        for seed in range(5):
            _, resid, full, _ = speculative_verify_tokens(
                lg, drafts, 0.9, None, jax.random.PRNGKey(seed))
            # a residual resample NEVER returns the rejected draft
            assert not np.any(np.asarray(resid) == np.asarray(drafts))
            assert np.asarray(full).shape == (3, 4)


class TestNGramDraft:
    def test_repeated_pattern_proposes_continuation(self):
        d = NGramDraft(max_ngram=3)
        h = np.asarray([7, 8, 9, 1, 2, 3, 4, 5, 1, 2, 3], np.int32)
        out = d.propose([h], 4)
        # suffix (1, 2, 3) matched at h[3:6] -> proposes what followed
        # there: 4, 5, 1, 2
        np.testing.assert_array_equal(out[0], [4, 5, 1, 2])
        # a continuation shorter than k pads with its last token
        out2 = d.propose([np.asarray([1, 2, 1, 2], np.int32)], 4)
        np.testing.assert_array_equal(out2[0], [1, 2, 2, 2])

    def test_no_match_and_empty_history(self):
        d = NGramDraft()
        out = d.propose([None, np.asarray([3, 1, 4], np.int32)], 3)
        np.testing.assert_array_equal(out[0], [0, 0, 0])
        np.testing.assert_array_equal(out[1], [4, 4, 4])  # repeat-last
        assert out.dtype == np.int32 and out.shape == (2, 3)


# ---------------------------------------------------------------------------
# PageAllocator reservations (the rollback discipline)
# ---------------------------------------------------------------------------

class TestAllocatorReservations:
    def test_reserve_alloc_release_cycle(self):
        a = PageAllocator(8)
        assert a.reserve("r", 5)
        assert a.free_count == 3 and a.reserved("r") == 5
        # reserved capacity is invisible to plain alloc
        assert a.alloc("other", 4) is None
        pages = a.alloc_reserved("r", 2)
        assert len(pages) == 2 and a.reserved("r") == 3
        # rollback: pages go back, capacity returns to the reservation
        a.release_pages("r", pages, rereserve=True)
        assert a.reserved("r") == 5 and a.free_count == 3
        with pytest.raises(RuntimeError, match="reserved"):
            a.alloc_reserved("r", 6)
        a.free("r")  # drops pages AND reservation
        a.check_no_leak()

    def test_check_no_leak_flags_dangling_reservation(self):
        a = PageAllocator(4)
        a.reserve("r", 2)
        with pytest.raises(RuntimeError, match="reserved"):
            a.check_no_leak()
        a.free("r")
        a.check_no_leak()

    def test_release_unowned_page_rejected(self):
        a = PageAllocator(4)
        pages = a.alloc("r", 2)
        with pytest.raises(RuntimeError, match="not owned"):
            a.release_pages("r", [p for p in range(4)
                                  if p not in pages][:1])
        a.free("r")
        a.check_no_leak()


# ---------------------------------------------------------------------------
# Bit-identity pins (the acceptance contract)
# ---------------------------------------------------------------------------

class TestSpecBitIdentical:
    def test_ngram_draft(self, model, vanilla):
        out, _ = _run(model, speculative=SpeculativeConfig(k=4))
        for a, b in zip(vanilla, out):
            np.testing.assert_array_equal(a, b)

    def test_adversarial_draft_rejection_storm(self, model, vanilla):
        out, done = _run(model, speculative=SpeculativeConfig(
            k=8, draft=_wrong_draft()))
        for a, b in zip(vanilla, out):
            np.testing.assert_array_equal(a, b)
        # the storm really happened: every draft rejected
        assert sum(r.stats.spec_accepted for r in done) == 0
        assert sum(r.stats.spec_drafted for r in done) > 0

    def test_model_draft_accepts_and_matches(self, model, vanilla):
        out, done = _run(model, speculative=SpeculativeConfig(
            k=4, draft=ModelDraft(model, window=64)))
        for a, b in zip(vanilla, out):
            np.testing.assert_array_equal(a, b)
        # self-draft within the context window is exact -> tokens/step
        # must beat 1 (the whole point of the verify amortization)
        steps = sum(r.stats.spec_steps for r in done)
        toks = sum(r.stats.tokens_out - 1 for r in done)
        assert steps and toks / steps > 1.5

    def test_int8_kv_pages(self, model):
        ref, _ = _run(model, kv_int8=True)
        out, _ = _run(model, kv_int8=True,
                      speculative=SpeculativeConfig(k=4))
        adv, _ = _run(model, kv_int8=True,
                      speculative=SpeculativeConfig(
                          k=8, draft=_wrong_draft()))
        for a, b, c in zip(ref, out, adv):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)

    def test_prefix_cache_on(self, model, vanilla):
        from paddle_tpu.serving import PrefixCache
        pc = PrefixCache(8)
        out, _ = _run(model, prefix_cache=pc,
                      speculative=SpeculativeConfig(
                          k=4, draft=ModelDraft(model, window=64)))
        for a, b in zip(vanilla, out):
            np.testing.assert_array_equal(a, b)
        assert pc.hit_pages > 0  # the shared prefix was actually reused

    def test_eos_inside_accepted_drafts(self, model, vanilla):
        prompt = _prompts()[0]
        # pick the 5th greedy token as EOS: with k=4 drafting it lands
        # INSIDE an accepted run, exercising the truncation path
        eos = int(vanilla[0][len(prompt) + 4])
        e0 = _engine(model)
        ra = e0.submit(prompt, max_new_tokens=12, eos_token=eos)
        ref = e0.run()[ra]
        e0.close()
        e1 = _engine(model, speculative=SpeculativeConfig(
            k=4, draft=ModelDraft(model, window=64)))
        rb = e1.submit(prompt, max_new_tokens=12, eos_token=eos)
        out = e1.run()[rb]
        e1.close()
        e1.allocator.check_no_leak()
        np.testing.assert_array_equal(ref, out)
        assert len(ref) < len(prompt) + 12  # EOS actually truncated


# ---------------------------------------------------------------------------
# Rollback mechanics
# ---------------------------------------------------------------------------

class TestRollback:
    def test_rejection_rollback_returns_pages_mid_flight(self, model):
        """k=8 over page_size=8: every verify window crosses a page
        boundary, so a rejection storm allocates speculation pages and
        must RETURN them each step (not just at request teardown)."""
        eng = _engine(model, num_slots=1, num_pages=12,
                      speculative=SpeculativeConfig(
                          k=8, draft=_wrong_draft()))
        released = []
        orig = eng.allocator.release_pages

        def spy(owner, pages, rereserve=False):
            released.append((owner, tuple(pages), rereserve))
            return orig(owner, pages, rereserve=rereserve)

        eng.allocator.release_pages = spy
        rid = eng.submit(_prompts()[0], max_new_tokens=16)
        eng.run()
        assert released, "rollback never returned a page"
        assert all(r[2] for r in released), "rollback must re-reserve"
        assert any(r[0] == rid for r in released)
        eng.close()
        eng.allocator.check_no_leak()

    def test_shared_prefix_pages_never_rolled_back(self, model):
        """With the prefix cache holding the shared pages, a rejection
        storm's rollback touches only the request's PRIVATE pages —
        the cache's books stay balanced (check_consistent audits every
        page against the allocator)."""
        from paddle_tpu.serving import PrefixCache
        pc = PrefixCache(8)
        eng = _engine(model, prefix_cache=pc, num_pages=16,
                      speculative=SpeculativeConfig(
                          k=8, draft=_wrong_draft()))
        for p in _prompts():
            eng.submit(p, max_new_tokens=12)
        eng.run()
        assert pc.total_pages() > 0
        pc.check_consistent(eng.allocator)
        eng.close()
        eng.allocator.check_no_leak()

    def test_oversubscribed_pool_recycles_under_speculation(self, model):
        """More concurrent requests than the pool can hold at once:
        admission blocks on the free list, finished requests' pages
        recycle, and speculation's reservations never deadlock it."""
        eng = _engine(model, num_slots=2, num_pages=8,
                      speculative=SpeculativeConfig(k=4))
        ref = _engine(model, num_slots=2, num_pages=8)
        rids = [eng.submit(p, max_new_tokens=10) for p in _prompts()]
        rref = [ref.submit(p, max_new_tokens=10) for p in _prompts()]
        out, expect = eng.run(), ref.run()
        for a, b in zip(rids, rref):
            np.testing.assert_array_equal(out[a], expect[b])
        eng.close()
        ref.close()
        eng.allocator.check_no_leak()


# ---------------------------------------------------------------------------
# serving.verify fault site (same pattern as serving.prefill)
# ---------------------------------------------------------------------------

class TestServingVerifyFault:
    def test_transient_verify_fault_retried_bit_identical(self, model,
                                                          vanilla):
        fi.get_injector().arm("serving.verify", at_calls=[1])
        out, _ = _run(model, speculative=SpeculativeConfig(k=4))
        assert fi.get_injector().counts("serving.verify")["fired"] == 1
        # the builtin serving.verify policy retried it invisibly
        for a, b in zip(vanilla, out):
            np.testing.assert_array_equal(a, b)

    def test_persistent_verify_fault_raises_and_cleans_up(self, model):
        fi.get_injector().arm("serving.verify", probability=1.0)
        eng = _engine(model, speculative=SpeculativeConfig(k=4))
        eng.submit(_prompts()[0], max_new_tokens=8)
        with pytest.raises(Exception):
            eng.run()
        eng.close()  # hard stop still returns every page
        eng.allocator.check_no_leak()


# ---------------------------------------------------------------------------
# Telemetry: RequestStats -> ServingMetrics -> Prometheus
# ---------------------------------------------------------------------------

class TestSpecTelemetry:
    def test_stats_and_histograms(self, model):
        metrics = ServingMetrics(registry=StatRegistry())
        _, done = _run(model, speculative=SpeculativeConfig(
            k=4, draft=ModelDraft(model, window=64)))
        for r in done:
            st = r.stats
            assert st.spec_steps > 0
            assert 0.0 <= st.acceptance_rate <= 1.0
            assert st.tokens_per_step >= 1.0
            d = st.to_dict()
            assert d["acceptance_rate"] == st.acceptance_rate
            assert d["tokens_per_step"] == st.tokens_per_step
            metrics.observe_request(r)
        snap = metrics.snapshot()
        assert snap["spec_accept_rate"]["count"] == len(done)
        assert snap["spec_tokens_per_step"]["p50"] >= 1.0
        assert snap["counters"]["spec_drafted_total"] > 0
        text = metrics.prometheus_text()
        assert "serving_spec_accept_rate_bucket" in text
        assert "serving_spec_tokens_per_step_bucket" in text

    def test_vanilla_requests_skip_spec_histograms(self, model):
        metrics = ServingMetrics(registry=StatRegistry())
        _, done = _run(model)
        for r in done:
            metrics.observe_request(r)
        assert metrics.spec_accept_rate.total == 0


# ---------------------------------------------------------------------------
# Server front-end passthrough
# ---------------------------------------------------------------------------

class TestServerSpeculative:
    def test_server_end_to_end_with_speculation(self, model):
        from paddle_tpu.serving import ServingServer, client_request
        srv = ServingServer(
            model, num_slots=2, page_size=8, max_seq_len=96,
            num_pages=12,
            metrics=ServingMetrics(registry=StatRegistry()),
            speculative=SpeculativeConfig(
                k=4, draft=ModelDraft(model, window=64)))
        port = srv.start()
        toks = []
        rep = client_request("127.0.0.1", port, {
            "op": "generate", "prompt": list(range(1, 9)),
            "max_new_tokens": 8, "stream": True}, on_token=toks.append)
        assert "error" not in rep, rep
        assert rep["generated"] == toks and len(toks) == 8
        assert rep["stats"]["tokens_per_step"] >= 1.0
        assert "acceptance_rate" in rep["stats"]
        srv.stop()
        srv.engine.allocator.check_no_leak()


# ---------------------------------------------------------------------------
# Persistent compile cache (placed from outside)
# ---------------------------------------------------------------------------

class TestCompileCache:
    @pytest.fixture
    def dir_updates(self, monkeypatch):
        """Every ``jax_compilation_cache_dir`` update made in code,
        recorded instead of applied."""
        import jax
        from paddle_tpu.core import compile_cache as cc
        calls = []
        real = jax.config.update

        def update(name, val):
            if name == "jax_compilation_cache_dir":
                calls.append(val)
            else:
                real(name, val)
        monkeypatch.setattr(jax.config, "update", update)
        monkeypatch.setattr(cc, "_reset_jax_cache", lambda: None)
        return calls

    def test_variable_set_means_no_directory_set_in_code(
            self, monkeypatch, tmp_path, dir_updates):
        from paddle_tpu.core import compile_cache as cc
        monkeypatch.setenv(cc.ENV_VAR, str(tmp_path / "outside"))
        assert cc.enable_compile_cache() == str(tmp_path / "outside")
        assert dir_updates == []

    def test_variable_unset_means_the_fixed_checkout_directory(
            self, monkeypatch, dir_updates):
        from paddle_tpu.core import compile_cache as cc
        monkeypatch.delenv(cc.ENV_VAR, raising=False)
        monkeypatch.setattr(cc, "compile_cache_dir", lambda: None)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert cc.enable_compile_cache() == os.path.join(repo,
                                                         ".jax_cache")
        # one fixed path: no pid, time or temporary name in it
        assert dir_updates == [os.path.join(repo, ".jax_cache")]

    def test_enable_writes_cache_files(self, tmp_path):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.core import compile_cache as cc
        d = str(tmp_path / "cc")
        before = cc.compile_cache_dir()
        try:
            assert cc.enable_compile_cache(d) == os.path.abspath(d)
            # idempotent
            assert cc.enable_compile_cache(d) == os.path.abspath(d)
            jax.jit(lambda x: (x * 3 + 1).sum())(
                jnp.ones((64, 64))).block_until_ready()
        finally:
            cc.disable_compile_cache()
            if before:
                cc.enable_compile_cache(before)
        files = [f for _, _, fs in os.walk(d) for f in fs]
        assert files, "no executable persisted to the cache dir"
