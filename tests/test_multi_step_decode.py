"""Device-resident multi-step decode (r19, ROADMAP item 2).

The contracts this suite pins (ISSUE 14 acceptance):

- greedy outputs are BIT-IDENTICAL ``multi_step=N`` vs ``multi_step=1``
  across fp/int8 KV pages, prefix cache on/off, chunked prefill, a
  2-way serving mesh, and EOS landing mid-macro at every offset
  0..N−1;
- host program launches per emitted token are STRICTLY reduced (one
  ``decode_multi`` launch per N tokens vs one ``decode`` launch per
  token — asserted via ``programs_launched``/``step_programs``);
- the streamed ``on_token`` order is identical to ``multi_step=1``
  (the ring drains in exact (step, slot) order and boundary-time
  prefill emissions queue behind it);
- every mid-flight exit at the macro boundary is leak-free — deadline
  expiry, stall eviction, close(), and resurrection
  ``dump_inflight``/replay, which is bit-identical onto a rebuilt
  ``multi_step=N`` engine — and the pre-bound growth reservations
  return with the pages;
- ``decode_ema_s`` is per MACRO LAUNCH with per-token deadline
  estimates derived as ema/N (``_deadline_hopeless`` charges
  ceil(need/N) launches), and the stall watchdog treats engine-wide
  drain progress as liveness for decoding slots between boundaries;
- the recipe threads through the server (``multi_step=`` engine
  kwarg → resurrection recipe) and the supervisor
  (``--multi-step`` → every replica) end to end.
"""

import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.inference import SpeculativeConfig, create_decode_engine
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import (ServingMetrics, ServingServer,
                                client_request)
from paddle_tpu.serving.prefix_cache import PrefixCache


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


def _prompts(vocab=1024):
    rng = np.random.default_rng(0)
    return [rng.integers(0, vocab, n).astype(np.int32)
            for n in (5, 9, 13, 7)]


def _run_stream(m, mnt=8, eos=None, **kw):
    eng = _engine(m, **kw)
    rids = [eng.submit(p, max_new_tokens=mnt, eos_token=eos)
            for p in _prompts()]
    res = eng.run()
    launches = dict(eng.programs_launched)
    eng.close()
    return [res[r].tolist() for r in rids], launches


# ---------------------------------------------------------------------------
# Bit-identity pins (the tentpole contract)
# ---------------------------------------------------------------------------

class TestBitIdentity:
    def test_fp_pages(self, model):
        base, _ = _run_stream(model, multi_step=1)
        for n in (2, 4, 7):
            got, _ = _run_stream(model, multi_step=n)
            assert got == base, f"multi_step={n} diverged"

    def test_eos_mid_macro_every_offset(self, model):
        """EOS landing at every in-macro offset 0..N−1: the masked
        carry stops that slot's emission exactly where the per-token
        host loop would."""
        n = 4
        base, _ = _run_stream(model, multi_step=1)
        plen = len(_prompts()[0])
        for off in range(n):
            # the token req0 emits at generated position 1 + off: with
            # it as EOS the stream ends inside the macro at offset off
            eos = base[0][plen + 1 + off]
            a, _ = _run_stream(model, multi_step=1, eos=eos)
            b, _ = _run_stream(model, multi_step=n, eos=eos)
            assert a == b, f"EOS at macro offset {off} diverged"
            assert len(a[0]) < plen + 8  # the EOS actually fired early

    def test_int8_pages(self, model):
        a, _ = _run_stream(model, multi_step=1, kv_int8=True)
        b, _ = _run_stream(model, multi_step=4, kv_int8=True)
        assert a == b

    def test_prefix_cache_on(self, model):
        a, _ = _run_stream(model, multi_step=1,
                           prefix_cache=PrefixCache(8))
        b, _ = _run_stream(model, multi_step=4,
                           prefix_cache=PrefixCache(8))
        assert a == b

    def test_chunked_prefill(self, model):
        a, _ = _run_stream(model, multi_step=1, prefill_chunk_tokens=8)
        b, _ = _run_stream(model, multi_step=4, prefill_chunk_tokens=8)
        assert a == b

    def test_mesh_two_way(self, model):
        from paddle_tpu.distributed.topology import make_serving_mesh
        a, _ = _run_stream(model, multi_step=1)
        b, _ = _run_stream(model, multi_step=4,
                           mesh=make_serving_mesh(2))
        assert a == b

    def test_speculative_composes_at_boundary(self, model):
        """Spec + multi_step never changes outputs. Since r22 this
        config runs the verify INSIDE the macro program (the ngram
        draft has a device twin, so ``_spec_inprogram`` engages by
        default); the boundary-interleaved cadence this test was born
        pinning is now the ``inprogram=False`` escape hatch — both
        lanes are pinned bit-identical in
        test_inprogram_inner_loop.py."""
        a, _ = _run_stream(model, multi_step=1)
        b, _ = _run_stream(model, multi_step=4,
                           speculative=SpeculativeConfig(k=2,
                                                         draft="ngram"))
        assert a == b

    def test_multi_step_validation(self, model):
        with pytest.raises(ValueError, match="multi_step"):
            _engine(model, multi_step=0)


# ---------------------------------------------------------------------------
# Launch counts: strictly fewer host launches per emitted token
# ---------------------------------------------------------------------------

class TestLaunchCounts:
    def test_decode_launches_strictly_reduced(self, model):
        base, l1 = _run_stream(model, multi_step=1)
        multi, l4 = _run_stream(model, multi_step=4)
        assert multi == base
        tokens = sum(len(s) for s in base) - sum(
            len(p) for p in _prompts())
        # per-token engine: one decode launch per decode step
        assert l1["decode"] > l4.get("decode", 0) + l4["decode_multi"]
        # macro engine: ~tokens/N launches (prefill emits the first
        # token of each request outside any macro)
        assert l4["decode_multi"] <= -(-tokens // 4) + 1
        assert "decode" not in l4  # the per-token jit never ran

    def test_step_programs_records_macro_kind(self, model):
        eng = _engine(model, multi_step=4)
        for p in _prompts()[:2]:
            eng.submit(p, max_new_tokens=6)
        eng.run()
        assert eng.step_programs.get("decode_multi", 0) > 0
        assert eng.macro_launches > 0
        eng.close()


# ---------------------------------------------------------------------------
# Streaming order
# ---------------------------------------------------------------------------

class TestStreaming:
    def _stream(self, model, n, mnt=8):
        toks = []
        eng = _engine(model, multi_step=n)
        for p in _prompts():
            eng.submit(p, max_new_tokens=mnt,
                       on_token=lambda rid, t, d: toks.append(
                           (rid, t, d)))
        eng.run()
        eng.close()
        return toks

    def test_on_token_order_identical(self, model):
        """Global (step, slot) interleave — done flags included —
        matches the per-token engine on this queued-admission stream
        (admissions land at the same relative points in both modes;
        what N coarsens is only WHEN a mid-run arrival can enter)."""
        assert self._stream(model, 1) == self._stream(model, 4)

    def test_single_token_requests(self, model):
        assert self._stream(model, 1, mnt=1) == \
            self._stream(model, 4, mnt=1)


# ---------------------------------------------------------------------------
# Macro-aware EMA + deadline gate + stall watchdog (satellite 1)
# ---------------------------------------------------------------------------

class TestMacroEma:
    def test_ema_tracked_per_macro_launch(self, model):
        eng = _engine(model, multi_step=4)
        for p in _prompts()[:2]:
            eng.submit(p, max_new_tokens=8)
        eng.run()
        # at least two launches ran, so the warmed EMA is set and the
        # per-token derivation is ema / multi_step
        assert eng.macro_launches >= 2
        assert eng.decode_ema_s is not None
        eng.close()

    def test_deadline_gate_charges_launches_not_tokens(self, model):
        """decode_ema_s is per macro launch: a request needing 8
        tokens at N=4 costs 2 launches. Charging the launch EMA per
        TOKEN (the poisoned-estimate bug this pins against) would
        estimate 8x and shed feasible work."""
        eng = _engine(model, multi_step=4)
        eng.decode_ema_s = 1.0  # seconds per LAUNCH
        req_ok = type("R", (), {})()
        now = time.monotonic()
        req = eng._queue  # unused; build a real request via submit
        rid = eng.submit(_prompts()[0], max_new_tokens=8,
                         deadline_t=now + 2.5)
        queued = eng._queue[-1]
        # 8 tokens / 4 per launch = 2 launches * 1.0s = 2.0s < 2.5s
        assert not eng._deadline_hopeless(queued, now)
        # 16 tokens = 4 launches = 4.0s > 2.5s: provably hopeless
        queued.max_new_tokens = 16
        assert eng._deadline_hopeless(queued, now)
        eng.close()

    def test_stall_watchdog_multi_step_aware(self, model):
        """A decoding slot's tokens arrive once per boundary; the
        engine-wide last-drain timestamp is its liveness signal — a
        healthy drain cadence never false-stalls it, a stale one
        still stalls typed."""
        eng = _engine(model, multi_step=4, stall_timeout_s=0.05)
        eng.submit(_prompts()[0], max_new_tokens=32)
        eng.step()  # admit + prefill + dispatch first macro
        eng.step()  # drain + redispatch (sets _last_macro_t)
        req = next(r for r in eng._slots if r is not None)
        stale = time.monotonic() - 10.0
        req.last_emit_t = stale
        req.stats.admit_t = stale
        eng._last_macro_t = time.monotonic()
        assert eng.evict_stalled() == []  # drains are fresh: alive
        assert req.state == "decoding"
        # both signals stale -> genuine stall, typed + leak-free.
        # evict_stalled() flushes the in-flight macro first (a drain
        # refreshes liveness), so exhaust the request's launches
        # before backdating.
        eng.run()
        eng.submit(_prompts()[1], max_new_tokens=8)
        eng.step()
        eng._flush_macro()
        req2 = next(r for r in eng._slots if r is not None)
        req2.last_emit_t = stale
        req2.stats.admit_t = stale
        eng._last_macro_t = stale
        out = eng.evict_stalled()
        assert [r.state for r in out] == ["stalled"]
        assert eng.allocator.reserved_total == 0
        eng.close()


# ---------------------------------------------------------------------------
# Leak-free macro-boundary exits (satellite 2)
# ---------------------------------------------------------------------------

class TestLeakAudits:
    def test_growth_reservation_lifecycle(self, model):
        """Multi-step admission reserves growth capacity (the spec
        discipline); macro dispatch converts it to pages; every exit
        returns both."""
        eng = _engine(model, multi_step=4)
        eng.submit(_prompts()[0], max_new_tokens=32)
        eng.step()  # admit (reserve) + prefill + dispatch
        assert eng.allocator.reserved_total > 0
        eng.run()
        eng.close()
        eng.allocator.check_no_leak()

    def test_mid_flight_close(self, model):
        eng = _engine(model, multi_step=4)
        for p in _prompts():
            eng.submit(p, max_new_tokens=16)
        eng.step()
        eng.step()  # a macro is in flight now
        eng.close()  # flush + evict everything
        eng.allocator.check_no_leak()

    def test_deadline_eviction_mid_macro(self, model):
        states = []
        eng = _engine(model, multi_step=4,
                      on_complete=lambda r: states.append(r.state))
        eng.submit(_prompts()[0], max_new_tokens=32,
                   deadline_t=time.monotonic() + 0.01)
        eng.step()
        time.sleep(0.02)
        eng.step()  # boundary sweep evicts typed
        assert "deadline" in states
        assert eng.num_active == 0
        eng.close()
        eng.allocator.check_no_leak()

    def test_streamed_tokens_precede_completion(self, model):
        events = []
        eng = _engine(model, multi_step=4,
                      on_complete=lambda r: events.append(
                          ("done", r.req_id)))
        for p in _prompts()[:2]:
            eng.submit(p, max_new_tokens=8,
                       on_token=lambda rid, t, d: events.append(
                           ("tok", rid)))
        eng.run()
        eng.close()
        for rid in (0, 1):
            toks = [i for i, e in enumerate(events)
                    if e == ("tok", rid)]
            done = events.index(("done", rid))
            assert all(i < done for i in toks)
            assert len(toks) == 8

    def test_dump_inflight_replays_bit_identical(self, model):
        """Engine-level resurrection contract: mid-flight state dumped
        at a boundary replays bit-identically onto a REBUILT
        multi_step=N engine (prompt + emitted tokens as one chained
        prefill)."""
        base, _ = _run_stream(model, mnt=12, multi_step=1)
        eng = _engine(model, multi_step=4)
        rids = [eng.submit(p, max_new_tokens=12) for p in _prompts()]
        for _ in range(2):
            eng.step()
        snap = eng.dump_inflight()  # flushes the in-flight macro
        # the snapshot must hold mid-decode AND still-queued work
        states = {r.req_id: r.state for r in snap}
        assert "decoding" in states.values()
        assert "queued" in states.values()
        pre = {r.req_id: ([int(t) for t in r.prompt],
                          [int(t) for t in r.generated],
                          r.max_new_tokens) for r in snap}
        eng.close()
        eng.allocator.check_no_leak()
        eng2 = _engine(model, multi_step=4)
        new_rids = {}
        for old_rid, (prompt, gen, mnt) in sorted(pre.items()):
            new_rids[old_rid] = eng2.submit(
                np.asarray(prompt + gen, np.int32),
                max_new_tokens=mnt - len(gen))
        res = eng2.run()
        eng2.close()
        for old_rid in sorted(pre):
            prompt, gen, _mnt = pre[old_rid]
            full = prompt + gen + [
                int(t) for t in
                res[new_rids[old_rid]][len(prompt) + len(gen):]]
            # req_ids are submit-ordered, so base[old_rid] is the
            # uninterrupted run of the same prompt
            assert full == base[old_rid], \
                f"replay diverged for req {old_rid}"


# ---------------------------------------------------------------------------
# Observability: timeline macro records, per-token reconstruction
# ---------------------------------------------------------------------------

class TestObservability:
    def test_timeline_marks_macro_launches(self, model):
        eng = _engine(model, multi_step=4)
        for p in _prompts()[:2]:
            eng.submit(p, max_new_tokens=8)
        eng.run()
        macros = [e["macro"] for e in eng.step_timeline()
                  if "macro" in e]
        assert macros, "no macro records on the timeline"
        for m in macros:
            assert 1 <= m["steps"] <= 4
            assert m["tokens"] == sum(m["per_step_tokens"])
            assert m["overlap_idle_ms"] >= 0.0
        # per-token reconstruction: one row per in-macro step, token
        # counts preserved
        rows = [r for r in eng.per_token_timeline()
                if "macro_launch" in r]
        assert sum(r["tokens"] for r in rows) == \
            sum(m["tokens"] for m in macros)
        assert len(rows) == sum(m["steps"] for m in macros)
        eng.close()

    def test_flight_summary_reports_multi_step(self, model):
        eng = _engine(model, multi_step=4)
        fs = eng.flight_summary()
        assert fs["multi_step"] == 4
        assert fs["macro_launches"] == 0
        eng.close()


# ---------------------------------------------------------------------------
# Serving surface: recipe threading, health/metrics, resurrection E2E
# ---------------------------------------------------------------------------

class TestServingSurface:
    def test_server_health_metrics_and_stream(self, model):
        met = ServingMetrics(registry=StatRegistry())
        srv = ServingServer(model, num_slots=2, page_size=8,
                            max_seq_len=64, prefix_cache=False,
                            metrics=met, multi_step=4)
        port = srv.start()
        try:
            toks = []
            rep = client_request("127.0.0.1", port, {
                "op": "generate", "prompt": [3, 1, 4, 1, 5],
                "max_new_tokens": 8, "stream": True},
                on_token=toks.append)
            assert "error" not in rep, rep
            assert toks == rep["generated"]
            h = client_request("127.0.0.1", port, {"op": "health"})
            assert h["multi_step"] == 4
            assert h["macro_launches"] >= 2
            s = client_request("127.0.0.1", port, {"op": "stats"})
            assert s["multi_step"] == 4
            t = client_request("127.0.0.1", port, {"op": "trace"})
            assert t["multi_step"] == 4
            assert any("macro" in e for e in t["step_timeline"])
            assert t["per_token_timeline"]
            mx = client_request("127.0.0.1", port,
                                {"op": "metrics"})["text"]
            assert "serving_macro_steps_total" in mx
            assert "serving_steps_per_launch" in mx
            assert "serving_host_overlap_idle_ms" in mx
            # the counter carries the engine's launches
            line = [ln for ln in mx.splitlines()
                    if ln.startswith("serving_macro_steps_total")]
            assert line and int(line[0].split()[-1]) >= 2
            chk = client_request("127.0.0.1", port,
                                 {"op": "leak_check"})
            assert chk["ok"], chk
        finally:
            srv.stop()

    def test_recipe_threads_through_rebuild(self, model):
        srv = ServingServer(model, num_slots=2, page_size=8,
                            max_seq_len=64, prefix_cache=False,
                            multi_step=4)
        try:
            assert srv.engine.multi_step == 4
            assert srv._engine_kwargs.get("multi_step") == 4
            # the resurrection path rebuilds from the same kwargs
            rebuilt = srv._build_engine()
            assert rebuilt.multi_step == 4
            rebuilt.close()
        finally:
            srv.stop()

    def test_resurrection_replays_onto_multi_step_engine(self, model):
        """Server resurrection E2E on a multi_step=4 engine: streams
        gapless/dupeless, finals bit-identical to the fault-free
        multi-step run, zero leaks."""
        from paddle_tpu.distributed import fault_inject as fi
        fi.reset()
        prompts = [list(range(1, 7)), list(range(3, 12))]
        ref = _engine(model, multi_step=4)
        rids = [ref.submit(np.asarray(p, np.int32), 8)
                for p in prompts]
        results = ref.run()
        ref.close()
        expected = [[int(t) for t in results[r][len(p):]]
                    for r, p in zip(rids, prompts)]
        # calls 2 and 3, not 3 and 4: 8 tokens at multi_step 4 need a
        # second step whatever the arrival order, but two clients that
        # arrive together finish in two (observed under load: no fault
        # fired, 0 restarts)
        fi.get_injector().arm("engine.step", at_calls=[2, 3])
        try:
            met = ServingMetrics(registry=StatRegistry())
            srv = ServingServer(model, num_slots=2, page_size=8,
                                max_seq_len=64, prefix_cache=False,
                                metrics=met, max_engine_errors=2,
                                multi_step=4)
            port = srv.start()
            outs = [None, None]
            toks = [[], []]

            def client(i):
                outs[i] = client_request(
                    "127.0.0.1", port,
                    {"op": "generate", "prompt": prompts[i],
                     "max_new_tokens": 8, "stream": True},
                    timeout_s=180.0, on_token=toks[i].append)

            ts = [threading.Thread(target=client, args=(i,))
                  for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=180)
            for i in range(2):
                assert outs[i] is not None, "client hung"
                assert "error" not in outs[i], outs[i]
                assert outs[i]["generated"] == expected[i]
                assert toks[i] == expected[i]  # no dup, no gap
            assert srv.engine.multi_step == 4  # rebuilt multi-step
            counters = met.snapshot()["counters"]
            assert counters["engine_restarts_total"] == 1
            chk = client_request("127.0.0.1", port,
                                 {"op": "leak_check"})
            assert chk["ok"], chk
            srv.stop()
            srv.engine.allocator.check_no_leak()
        finally:
            fi.reset()

    def test_supervisor_forwards_multi_step(self):
        """CLI plumbing: --multi-step lands in every replica's server
        args (arg-assembly level — the spawn E2E below proves the
        full path)."""
        from paddle_tpu.serving import supervisor as sup_mod
        import unittest.mock as mock
        captured = {}

        class _Stop(RuntimeError):
            pass

        class FakeSup:
            def __init__(self, **kw):
                captured.update(kw)
                raise _Stop  # unwind main() before anything spawns

        with mock.patch.object(sup_mod, "Supervisor", FakeSup):
            with pytest.raises(_Stop):
                sup_mod.main(["--replicas", "1", "--multi-step", "8"])
        assert "--multi-step" in captured.get("server_args", [])
        idx = captured["server_args"].index("--multi-step")
        assert captured["server_args"][idx + 1] == "8"

    @pytest.mark.slow
    def test_supervisor_spawn_e2e(self, tmp_path):
        """One spawned replica with --multi-step 4: health reports it
        and a routed generate matches the in-process per-token
        reference."""
        from paddle_tpu.serving.supervisor import (FailoverRouter,
                                                   Supervisor)
        env = {"JAX_PLATFORMS": "cpu", "TPU_SKIP_MDS_QUERY": "true",
               "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")}
        sup = Supervisor(
            model="gpt_tiny", replicas=1,
            server_args=["--page-size", "8", "--max-seq-len", "96",
                         "--num-slots", "2", "--multi-step", "4"],
            replica_env=env, probe_interval_s=0.2,
            backoff_base_s=3600)
        try:
            sup.start(wait_ready=True)
            router = FailoverRouter(sup)
            port = router.start()
            try:
                rep = client_request(
                    "127.0.0.1", port,
                    {"op": "generate", "prompt": [1, 2, 3, 4, 5],
                     "max_new_tokens": 6}, timeout_s=120.0)
                assert rep.get("done"), rep
                h = client_request(
                    "127.0.0.1", sup.replicas[0].port,
                    {"op": "health"})
                assert h["multi_step"] == 4
                assert h["macro_launches"] >= 1
                pt.seed(0)
                m = GPTForCausalLM(gpt_tiny())
                m.eval()
                eng = create_decode_engine(m, num_slots=2, page_size=8,
                                           max_seq_len=96)
                rid = eng.submit(np.asarray([1, 2, 3, 4, 5], np.int32),
                                 max_new_tokens=6)
                ref = eng.run()[rid].tolist()
                eng.close()
                assert rep["tokens"] == ref
            finally:
                router.stop()
        finally:
            sup.stop()
