"""Fused decode hot path (r13): one-program engine step with fused
dequant–attention–sampling kernels.

The contracts this suite pins (ISSUE r13 acceptance):

- the FUSED engine's greedy output is BIT-IDENTICAL to the unfused
  (``fused_step=False``) engine across int8/fp KV pages, speculative
  on/off, chunked prefill on/off, and a 2-way serving mesh;
- the new fused kernels (`paged_attention_fused` epilogue,
  `fused_sample` streaming argmax) match their pure-JAX references in
  interpret mode, and the streaming sampler matches ``jnp.argmax``
  bit-for-bit including ties;
- decode-step traced-program op counts (the launch counter) are
  STRICTLY reduced under fusion;
- every fused exit path returns its pages (zero-leak audits);
- the conftest stray-serving guard detects but does NOT kill outside
  CI (the PR 7 tier-1 hazard's fix is detection-only by default).
"""

import functools
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.inference import SpeculativeConfig, create_decode_engine
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import fused_sample as fs
from paddle_tpu.ops.pallas import paged_attention as pa

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module", autouse=True)
def _compile_cache(module_compile_cache):
    """Engine-heavy file: reuse XLA compiles across tests (see
    conftest.module_compile_cache)."""
    yield


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


def _engine(m, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq_len", 64)
    return create_decode_engine(m, **kw)


_PROMPTS = [(5,), (9,), (13,), (7,)]


def _prompts(vocab=1024):
    rng = np.random.default_rng(0)
    return [rng.integers(0, vocab, n).astype(np.int32)
            for (n,) in _PROMPTS]


def _run_stream(m, **kw):
    eng = _engine(m, **kw)
    rids = [eng.submit(p, max_new_tokens=8) for p in _prompts()]
    res = eng.run()
    eng.close()
    return [res[r].tolist() for r in rids], dict(eng.step_programs)


# ---------------------------------------------------------------------------
# Streaming sampler semantics (pure paths)
# ---------------------------------------------------------------------------

class TestFusedSampleSemantics:
    def test_streaming_argmax_bit_identical_odd_vocab(self, rng):
        for b, d, v, tile in [(4, 32, 1000, 256), (2, 16, 97, 32),
                              (3, 8, 5, 2048)]:
            hidden = jnp.asarray(rng.standard_normal((b, d)), jnp.float32)
            w = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
            got = fs.fused_sample(hidden, w, transpose_y=True, tile=tile)
            ref = jnp.argmax(hidden @ w.T, -1)
            assert (np.asarray(got) == np.asarray(ref)).all(), (b, d, v)

    def test_tie_breaks_to_first_index_like_argmax(self):
        # duplicate rows STRADDLING a tile boundary force exact ties
        hidden = jnp.ones((2, 4), jnp.float32)
        row = jnp.asarray([[1., 2., 3., 4.]], jnp.float32)
        w = jnp.concatenate([row * 0.5, row, row * 0.25, row, row],
                            axis=0)  # max tied at rows 1, 3, 4
        for tile in (2, 3, 5):
            got = fs.fused_sample(hidden, w, transpose_y=True, tile=tile)
            ref = jnp.argmax(hidden @ w.T, -1)
            assert (np.asarray(got) == np.asarray(ref)).all()
            assert (np.asarray(got) == 1).all()

    def test_feature_major_layout_and_bias(self, rng):
        hidden = jnp.asarray(rng.standard_normal((3, 16)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((16, 100)), jnp.float32)
        bias = jnp.asarray(rng.standard_normal((100,)), jnp.float32)
        got = fs.fused_sample(hidden, w, bias=bias, tile=32)
        ref = jnp.argmax(hidden @ w + bias, -1)
        assert (np.asarray(got) == np.asarray(ref)).all()

    def test_nan_logits_match_argmax_first_nan(self, rng):
        # a numerically-blown checkpoint must produce the SAME tokens
        # fused or unfused, or --no-fused-step bisection misattributes
        # the divergence to fusion: jnp.argmax returns the FIRST NaN
        # index, and the streaming carry must contaminate identically
        hidden = jnp.ones((2, 16), jnp.float32)
        w = jnp.asarray(rng.standard_normal((90, 16)), jnp.float32)
        for nan_rows in ((50,), (20, 70), (0,)):
            wn = w
            for r in nan_rows:
                wn = wn.at[r].set(jnp.nan)
            ref = jnp.argmax(hidden @ wn.T, -1)
            got = fs.fused_sample(hidden, wn, transpose_y=True, tile=32)
            assert (np.asarray(got) == np.asarray(ref)).all(), nan_rows
            assert (np.asarray(got) == min(nan_rows)).all()

    def test_topk_reservoir_matches_lax_topk(self, rng):
        hidden = jnp.asarray(rng.standard_normal((4, 32)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((500, 32)), jnp.float32)
        vals, idxs = fs.fused_sample(hidden, w, transpose_y=True,
                                     top_k=7, tile=64)
        fv, fi_ = jax.lax.top_k(hidden @ w.T, 7)
        np.testing.assert_allclose(np.asarray(vals), np.asarray(fv),
                                   rtol=1e-6)
        assert (np.asarray(idxs) == np.asarray(fi_)).all()

    def test_fused_sample_token_topk_draws_inside_topk(self, rng):
        from paddle_tpu.nn.decode import fused_sample_token
        hidden = jnp.asarray(rng.standard_normal((4, 32)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((300, 32)), jnp.float32)
        _, top_idx = jax.lax.top_k(hidden @ w.T, 5)
        key = jax.random.PRNGKey(0)
        for _ in range(5):
            tok, key = fused_sample_token(hidden, w, 0.8, 5, key,
                                          transpose_y=True, tile=64)
            for b in range(4):
                assert int(tok[b]) in set(np.asarray(top_idx[b]).tolist())

    def test_fused_verify_tokens_greedy_matches_unfused(self, rng):
        from paddle_tpu.nn.decode import (fused_verify_tokens,
                                          speculative_verify_tokens)
        b, s, d, v = 2, 4, 16, 200
        hidden = jnp.asarray(rng.standard_normal((b, s, d)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
        logits = hidden @ w.T
        drafts = jnp.asarray(rng.integers(0, v, (b, s - 1)), jnp.int32)
        a1, r1, f1, _ = fused_verify_tokens(hidden, drafts, w,
                                            transpose_y=True, tile=64)
        a2, r2, f2, _ = speculative_verify_tokens(logits, drafts)
        for x, y in ((a1, a2), (r1, r2), (f1, f2)):
            assert (np.asarray(x) == np.asarray(y)).all()


# ---------------------------------------------------------------------------
# Mosaic kernels vs references, interpret mode
# ---------------------------------------------------------------------------

class TestFusedKernelsInterpret:
    """The same harness TestPallasKernel uses on the CPU lane."""

    @pytest.fixture(autouse=True)
    def _interpret_mode(self, monkeypatch):
        for mod in (pa, fs):
            orig = mod.pl.pallas_call
            monkeypatch.setattr(mod.pl, "pallas_call",
                                functools.partial(orig, interpret=True))
        yield

    def test_fused_epilogue_matches_reference(self, rng):
        n_pages, page, h, d = 6, 8, 2, 64
        e = h * d
        kp = jnp.asarray(rng.standard_normal((n_pages, page, h, d)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((n_pages, page, h, d)),
                         jnp.float32)
        table = jnp.asarray([[0, 2, 4], [5, 3, 1]], jnp.int32)
        lens = jnp.asarray([20, 7], jnp.int32)
        q = jnp.asarray(rng.standard_normal((2, 1, h, d)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((e, e)), jnp.float32)
        bias = jnp.asarray(rng.standard_normal((e,)), jnp.float32)
        with fa.force_flash_for_aot():
            assert pa.fused_epilogue_supported(q.shape, kp.shape,
                                               w.shape)
            out = pa.paged_attention_fused(q, kp, vp, table, lens, w,
                                           bias)
        ref = pa.paged_attention_fused_reference(q, kp, vp, table, lens,
                                                 w, bias)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

    def test_fused_epilogue_int8_pages(self, rng):
        from paddle_tpu.quantization.quant import quantize_kv
        n_pages, page, h, d = 5, 8, 2, 64
        e = h * d
        kq, ks = quantize_kv(jnp.asarray(
            rng.standard_normal((n_pages, page, h, d)), jnp.float32))
        vq, vs = quantize_kv(jnp.asarray(
            rng.standard_normal((n_pages, page, h, d)), jnp.float32))
        table = jnp.asarray([[1, 2, 3]], jnp.int32)
        lens = jnp.asarray([19], jnp.int32)
        q = jnp.asarray(rng.standard_normal((1, 1, h, d)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((e, e)), jnp.float32)
        with fa.force_flash_for_aot():
            out = pa.paged_attention_fused(q, kq, vq, table, lens, w,
                                           k_scale=ks, v_scale=vs)
        ref = pa.paged_attention_fused_reference(q, kq, vq, table, lens,
                                                 w, k_scale=ks,
                                                 v_scale=vs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

    @staticmethod
    def _ragged_batch(rng, b, lens, dtype=jnp.float32):
        """``b`` slots over pages of 8, two heads of 64: every slot owns
        three distinct pages whatever its length (a parked slot's table
        row is as stale as the engine leaves it)."""
        page, h, d, mp = 8, 2, 64, 3
        e = h * d
        n_pages = b * mp + 1

        def arr(*shape):
            return jnp.asarray(rng.standard_normal(shape), dtype)

        table = jnp.asarray(
            rng.permutation(n_pages - 1)[:b * mp].reshape(b, mp), jnp.int32)
        return (arr(b, 1, h, d), arr(n_pages, page, h, d),
                arr(n_pages, page, h, d), table,
                jnp.asarray(lens, jnp.int32), arr(e, e), arr(e))

    @pytest.mark.parametrize("with_bias", [True, False],
                             ids=["bias", "no_bias"])
    def test_fused_epilogue_live_parked_partial_interleaved(self, rng,
                                                            with_bias):
        """One projection a call over all 16 slots' rows: live slots
        (whole pages), parked slots (length 0) and slots whose last page
        is partial, interleaved, each match the reference ROW BY ROW; a
        parked slot's row is the bias alone (zeros without one)."""
        lens = [16, 0, 7, 24, 0, 0, 1, 8, 19, 0, 24, 3, 0, 16, 23, 0]
        q, kp, vp, table, lens, w, bias = self._ragged_batch(rng, 16, lens)
        bias = bias if with_bias else None
        with fa.force_flash_for_aot():
            assert pa.fused_epilogue_supported(q.shape, kp.shape, w.shape)
            out = pa.paged_attention_fused(q, kp, vp, table, lens, w, bias)
        ref = pa.paged_attention_fused_reference(q, kp, vp, table, lens,
                                                 w, bias)
        assert out.shape == ref.shape == (16, 1, w.shape[1])
        out, ref = np.asarray(out), np.asarray(ref)
        for row in range(16):
            np.testing.assert_allclose(out[row], ref[row], rtol=2e-3,
                                       atol=2e-3, err_msg=f"slot {row}")
        parked = np.asarray(lens) == 0
        want = np.asarray(bias) if with_bias else np.zeros(w.shape[1])
        assert (out[parked, 0] == want).all()

    @pytest.mark.parametrize("b,dtype", [(5, jnp.float32),
                                         (12, jnp.bfloat16)],
                             ids=["5_f32", "12_bf16"])
    def test_fused_epilogue_batch_not_a_sublane_tile(self, rng, b, dtype):
        """The gate admits any batch: the contexts' scratch is padded to
        the output dtype's sublane tile (8 rows of f32, 16 of bf16) and
        the rows past the batch reach no output row."""
        lens = ([9, 0, 24, 17, 1] * 3)[:b]
        q, kp, vp, table, lens, w, bias = self._ragged_batch(
            rng, b, lens, dtype)
        with fa.force_flash_for_aot():
            assert pa.fused_epilogue_supported(
                q.shape, kp.shape, w.shape, w_itemsize=w.dtype.itemsize)
            out = pa.paged_attention_fused(q, kp, vp, table, lens, w, bias)
        ref = pa.paged_attention_fused_reference(q, kp, vp, table, lens,
                                                 w, bias)
        assert out.shape == (b, 1, w.shape[1]) and out.dtype == dtype
        tol = 2e-3 if dtype == jnp.float32 else 0.15
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)

    def test_fused_argmax_kernel_matches_reference(self, rng):
        hidden = jnp.asarray(rng.standard_normal((4, 128)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((1000, 128)), jnp.float32)
        ref = jnp.argmax(hidden @ w.T, -1)
        with fa.force_flash_for_aot():
            assert fs.fused_sample_supported(hidden.shape, w.shape)
            got = fs._fused_argmax_pallas(hidden, w, 0, None, 256)
            # feature-major layout streams natively (no transpose)
            got_fm = fs._fused_argmax_pallas(
                hidden, jnp.asarray(w.T), 1, None, 256)
        assert (np.asarray(got) == np.asarray(ref)).all()
        assert (np.asarray(got_fm) == np.asarray(ref)).all()

    def test_fused_argmax_kernel_nan_matches_argmax(self, rng):
        hidden = jnp.ones((2, 128), jnp.float32)
        w = jnp.asarray(rng.standard_normal((600, 128)), jnp.float32)
        w = w.at[300].set(jnp.nan)  # NaN row in the second tile
        ref = jnp.argmax(hidden @ w.T, -1)
        with fa.force_flash_for_aot():
            got = fs._fused_argmax_pallas(hidden, w, 0, None, 256)
        assert (np.asarray(got) == np.asarray(ref)).all()
        assert (np.asarray(got) == 300).all()

    def test_supported_gates(self):
        with fa.force_flash_for_aot():
            ok = pa.fused_epilogue_supported
            assert ok((4, 1, 2, 64), (10, 8, 2, 64), (128, 128))
            # projection rows must equal H*D
            assert not ok((4, 1, 2, 64), (10, 8, 2, 64), (256, 128))
            # E_out must lane-tile
            assert not ok((4, 1, 2, 64), (10, 8, 2, 64), (128, 100))
            # weight over the VMEM budget falls back (fp32)...
            assert not ok((4, 1, 16, 128), (10, 64, 16, 128),
                          (2048, 2048))
            # ...but the same head in bf16 storage fits the budget
            assert ok((4, 1, 16, 128), (10, 64, 16, 128),
                      (2048, 2048), w_itemsize=2)
        assert not pa.fused_epilogue_supported(
            (4, 1, 2, 64), (10, 8, 2, 64), (128, 128), backend="cpu")
        assert not fs.fused_sample_supported((4, 128), (100, 128),
                                             backend="cpu")


# ---------------------------------------------------------------------------
# Engine A/B: fused vs unfused bit-identity, program counts, leak audits
# ---------------------------------------------------------------------------

class TestFusedEngineParity:
    @pytest.mark.parametrize("kw", [
        {},
        {"kv_int8": True},
        {"speculative": "spec"},
        {"prefill_chunk_tokens": 8},
        {"speculative": "spec", "prefill_chunk_tokens": 8,
         "kv_int8": True},
    ], ids=["fp", "int8", "spec", "chunked", "spec_chunked_int8"])
    def test_fused_greedy_bit_identical(self, model, kw):
        kw = dict(kw)
        if kw.get("speculative") == "spec":
            kw["speculative"] = SpeculativeConfig(k=3)
        fused, _ = _run_stream(model, fused_step=True, **kw)
        if "speculative" in kw:
            kw["speculative"] = SpeculativeConfig(k=3)
        unfused, _ = _run_stream(model, fused_step=False, **kw)
        assert fused == unfused

    def test_mesh_two_way_bit_identical(self, model):
        from paddle_tpu.distributed.topology import make_serving_mesh
        mesh = make_serving_mesh(2)
        fused, _ = _run_stream(model, fused_step=True, mesh=mesh)
        unfused, _ = _run_stream(model, fused_step=False, mesh=mesh)
        single, _ = _run_stream(model, fused_step=True)
        assert fused == unfused == single

    def test_decode_programs_strictly_reduced(self, model):
        _, fused = _run_stream(model, fused_step=True)
        _, unfused = _run_stream(model, fused_step=False)
        assert fused["decode"] < unfused["decode"], (fused, unfused)
        assert fused["prefill"] < unfused["prefill"]

    def test_verify_programs_strictly_reduced(self, model):
        _, fused = _run_stream(model, fused_step=True,
                               speculative=SpeculativeConfig(k=3))
        _, unfused = _run_stream(model, fused_step=False,
                                 speculative=SpeculativeConfig(k=3))
        assert fused["verify"] < unfused["verify"], (fused, unfused)

    def test_generate_jit_paged_fused_matches_eager(self, model):
        # the jitted generate now samples through the streaming lm_head
        # and (paged) the fused attention epilogue; greedy tokens must
        # still match the eager debuggable reference exactly
        ids = np.asarray([[3, 1, 4, 1, 5]], np.int32)
        eager = model.generate(pt.Tensor(ids), max_new_tokens=6,
                               temperature=0.0)
        for kv in ("static", "paged", "paged_int8"):
            jitted = model.generate(pt.Tensor(ids), max_new_tokens=6,
                                    temperature=0.0, use_jit=True,
                                    kv_cache=kv, page_size=8)
            assert np.asarray(jitted.value).tolist() == \
                np.asarray(eager.value).tolist(), kv


class TestFusedLeakAudit:
    def test_close_midflight_returns_pages(self, model):
        for kw in ({}, {"speculative": SpeculativeConfig(k=3)},
                   {"prefill_chunk_tokens": 8}):
            eng = _engine(model, fused_step=True, **kw)
            for p in _prompts():
                eng.submit(p, max_new_tokens=8)
            for _ in range(3):
                eng.step()
            eng.close()  # asserts check_no_leak internally

    def test_deadline_eviction_returns_pages(self, model):
        eng = _engine(model, fused_step=True)
        eng.submit(_prompts()[0], max_new_tokens=8,
                   deadline_t=time.monotonic() + 0.2)
        deadline = time.monotonic() + 5
        while (eng.num_active or eng.num_queued) and \
                time.monotonic() < deadline:
            eng.step()
        eng.allocator.check_no_leak()
        eng.close()

    def test_drain_then_close_no_leak(self, model):
        eng = _engine(model, fused_step=True, kv_int8=True)
        for p in _prompts():
            eng.submit(p, max_new_tokens=4)
        eng.run()
        eng.allocator.check_no_leak()
        eng.close()


# ---------------------------------------------------------------------------
# Serving surface: recipe/escape hatch, health + gauge
# ---------------------------------------------------------------------------

class TestServingSurface:
    def test_server_health_reports_fused_and_programs(self, model):
        from paddle_tpu.serving import ServingServer, client_request
        srv = ServingServer(model, num_slots=2, page_size=8,
                            max_seq_len=64, prefix_cache=False)
        port = srv.start()
        try:
            rep = client_request("127.0.0.1", port, {
                "op": "generate", "prompt": [3, 1, 4, 1],
                "max_new_tokens": 4})
            assert "error" not in rep, rep
            h = client_request("127.0.0.1", port, {"op": "health"})
            assert h["fused_step"] is True
            assert h["step_programs"].get("decode", 0) > 0
            mx = client_request("127.0.0.1", port, {"op": "metrics"})
            assert "serving_step_programs" in mx["text"]
        finally:
            srv.stop()

    def test_engine_kwarg_escape_hatch_threads_through_recipe(self,
                                                              model):
        from paddle_tpu.serving import ServingServer
        srv = ServingServer(model, num_slots=2, page_size=8,
                            max_seq_len=64, prefix_cache=False,
                            fused_step=False)
        try:
            assert srv.engine.fused_step is False
            # the resurrection recipe rebuilds from the same kwargs
            assert srv._engine_kwargs.get("fused_step") is False
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Stray serving-process guard (the PR 7 tier-1 hazard's fix)
# ---------------------------------------------------------------------------

class TestServingGuard:
    def _spawn_marker(self):
        # argv carries the serving marker without running a server;
        # the child has THIS process as parent (ppid != 1), i.e. it
        # models a CONCURRENT run's live server
        return subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(60)",
             "paddle_tpu.serving.server"],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

    def _spawn_orphan_marker(self):
        # double-fork: the intermediate exits immediately, so the
        # marker grandchild reparents to init (ppid 1) — the leaked-
        # from-a-dead-run shape the CI kill targets
        out = subprocess.run(
            [sys.executable, "-c",
             "import subprocess, sys\n"
             "p = subprocess.Popen([sys.executable, '-c',"
             " 'import time; time.sleep(60)',"
             " 'paddle_tpu.serving.server'],"
             " stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)\n"
             "print(p.pid)"],
            capture_output=True, text=True, timeout=30)
        return int(out.stdout.strip())

    @staticmethod
    def _alive(pid):
        try:
            os.kill(pid, 0)
            return True
        except OSError:
            return False

    def test_guard_is_detection_only_outside_ci(self):
        import conftest
        proc = self._spawn_marker()
        try:
            time.sleep(0.2)
            found = conftest._handle_stray_serving(kill=False)
            assert proc.pid in [pid for pid, _, _, _ in found]
            assert proc.poll() is None, \
                "detection-only guard killed the process"
        finally:
            proc.kill()
            proc.wait()

    def test_guard_kills_only_orphans_in_ci_mode(self):
        import conftest
        live = self._spawn_marker()          # live parent: spared
        orphan = self._spawn_orphan_marker()  # ppid 1: reaped
        try:
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:  # wait for reparenting
                strays = {p: pp for p, pp, _ in
                          conftest._stray_serving_procs()}
                if strays.get(orphan) == 1:
                    break
                time.sleep(0.05)
            found = conftest._handle_stray_serving(kill=True)
            by_pid = {p: killed for p, _, _, killed in found}
            assert by_pid.get(orphan) is True, found
            assert by_pid.get(live.pid) is False, found
            assert live.poll() is None, \
                "CI guard killed a concurrent run's live server"
            deadline = time.monotonic() + 5
            while self._alive(orphan) and time.monotonic() < deadline:
                time.sleep(0.05)
            assert not self._alive(orphan)
        finally:
            live.kill()
            live.wait()
            if self._alive(orphan):
                os.kill(orphan, signal.SIGKILL)

    def test_guard_excludes_own_process_tree(self):
        import conftest
        own = conftest._proc_ancestors()
        assert os.getpid() in own
        assert os.getppid() in own
        assert os.getpid() not in [
            pid for pid, _, _ in conftest._stray_serving_procs()]
