"""End-to-end request tracing (r16): span trees from router to engine,
the step-timeline ring, trace_lint, and the metrics-registry audit.

The contracts this file pins (ISSUE r16 acceptance):

- with sample 1.0 a request yields ONE span tree covering
  queue -> admit -> prefill (chunks) -> decode steps -> complete that
  passes tools/trace_lint.py with ZERO leaked open spans;
- trace context survives the three stitch points — resurrection
  replay, keyed failover resubmission, deadline-expiry unwind — each
  producing a single well-formed tree;
- tracing off is the default and greedy outputs are BIT-IDENTICAL
  tracing on/off;
- the metrics registry obeys the exposition rules the PR 7 ``_total``
  collision taught: counter families end in _total, no
  counter/histogram family collisions, and prometheus_text() parses
  line-by-line.
"""

import importlib.util
import json
import os
import re
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.distributed import fault_inject as fi
from paddle_tpu.inference import create_decode_engine
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import ServingMetrics, SpanTracer
from paddle_tpu.serving.server import ServingServer, client_request
from paddle_tpu.serving.tracing import request_latencies

_LINT_PATH = os.path.join(os.path.dirname(__file__), "..", "tools",
                          "trace_lint.py")
_spec = importlib.util.spec_from_file_location("trace_lint", _LINT_PATH)
trace_lint = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trace_lint)


@pytest.fixture(autouse=True)
def _clean_injector():
    fi.reset()
    yield
    fi.reset()


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


ENGINE_KW = dict(num_slots=2, page_size=8, max_seq_len=96, num_pages=24)


def _engine(m, **kw):
    merged = dict(ENGINE_KW)
    merged.update(kw)
    return create_decode_engine(m, **merged)


def _server(m, **kw):
    merged = dict(ENGINE_KW)
    merged.update(kw)
    merged.setdefault("metrics", ServingMetrics(registry=StatRegistry()))
    return ServingServer(m, **merged)


def _lint_ok(traces):
    errs = trace_lint.lint_trace_obj({"traces": traces})
    assert errs == [], errs


def _names(trace):
    return [s["name"] for s in trace["spans"]]


# ---------------------------------------------------------------------------
# SpanTracer unit semantics (no model)
# ---------------------------------------------------------------------------

class TestSpanTracerUnit:
    def test_sampling_is_deterministic(self):
        tr = SpanTracer(sample_rate=0.5)
        got = [tr.sample() for _ in range(8)]
        assert got == [False, True] * 4  # exactly every 2nd request
        assert not any(SpanTracer(sample_rate=0.0).sample()
                       for _ in range(10))
        assert all(SpanTracer(sample_rate=1.0).sample()
                   for _ in range(10))

    def test_start_unsampled_returns_none(self):
        tr = SpanTracer(sample_rate=0.0)
        assert tr.start("request") is None
        assert tr.sampled_total == 0

    def test_ctx_forces_sampling_and_records_remote_parent(self):
        tr = SpanTracer(sample_rate=0.0)
        t = tr.start("request", ctx={"id": "abc", "parent": "r:1"})
        assert t is not None and t.trace_id == "abc"
        tr.finish(t, state="done")
        root = tr.finished()[-1]["spans"][0]
        assert root["args"]["remote_parent"] == "r:1"
        assert root["parent"] is None  # locally orphan-free

    def test_span_cap_drops_and_counts(self):
        tr = SpanTracer(sample_rate=1.0, max_spans_per_trace=3)
        t = tr.start("request")
        for i in range(6):
            t.event(f"e{i}")
        tr.finish(t, state="done")
        d = tr.finished()[-1]
        assert len(d["spans"]) == 3
        assert d["dropped_spans"] == 4  # 4 of the 6 events dropped
        assert tr.spans_dropped_total == 4

    def test_finished_ring_is_bounded(self):
        tr = SpanTracer(sample_rate=1.0, max_traces=4)
        for _ in range(10):
            tr.finish(tr.start("request"), state="done")
        assert len(tr.finished()) == 4
        assert tr.finished_total == 10

    def test_finish_force_closes_and_counts_leaks(self):
        tr = SpanTracer(sample_rate=1.0)
        t = tr.start("request")
        t.begin("queue", parent=t.anchor)  # never closed
        tr.finish(t, state="done")
        d = tr.finished()[-1]
        assert d["leaked_open"] == 1
        assert all(s["t1_us"] is not None for s in d["spans"])
        # ...and trace_lint reports the leak
        errs = trace_lint.lint_trace_obj({"traces": [d]})
        assert errs and "force-closed" in errs[0]

    def test_chrome_export_shape(self):
        tr = SpanTracer(sample_rate=1.0)
        t = tr.start("request")
        sp = t.begin("queue", parent=t.anchor)
        t.end(sp)
        tr.finish(t, state="done")
        ch = tr.to_chrome()
        assert ch["traceEvents"]
        for e in ch["traceEvents"]:
            assert e["ph"] == "X" and e["dur"] >= 0 and e["ts"] >= 0
            assert e["args"]["trace_id"] == t.trace_id
        assert trace_lint.lint_trace_obj(ch) == []

    def test_sink_failure_never_breaks_tracing(self):
        def bad_sink(kind, tid, span):
            raise RuntimeError("boom")

        tr = SpanTracer(sample_rate=1.0, on_span=bad_sink)
        t = tr.start("request")
        t.event("x")
        tr.finish(t, state="done")
        assert tr.finished()

    def test_spans_stay_the_tracer_s_own(self):
        """The tracer copies no span anywhere else: built without the
        former bridge argument, with the in-process profiler
        (`core/profiler.py enable_profiler`) armed, the live sink sees
        every span as before, the tree is whole, and the profiler's
        event list holds RecordEvent markers only. The knob is gone
        (spelt in two halves: a search for its name finds no user)."""
        from paddle_tpu.core import profiler
        with pytest.raises(TypeError):
            SpanTracer(sample_rate=1.0, **{"profiler" + "_bridge": False})
        seen = []
        tr = SpanTracer(sample_rate=1.0, on_span=lambda kind, tid, span:
                        seen.append((kind, span["name"])))
        profiler.enable_profiler()
        try:
            t = tr.start("request")
            sp = t.begin("queue", parent=t.anchor)
            t.end(sp)
            t.add("decode_step", sp.t0_us, sp.t1_us, parent=t.anchor)
            t.event("first_token", parent=t.anchor)
            with profiler.RecordEvent("marker"):
                pass
            tr.finish(t, state="done")
            assert [e.name for e in profiler.profiler_events()] == ["marker"]
        finally:
            profiler.disable_profiler()
            profiler.reset_profiler()
        assert seen == [("begin", "request"), ("begin", "queue"),
                        ("end", "queue"), ("end", "decode_step"),
                        ("event", "first_token"), ("end", "request")]
        d = tr.finished()[-1]
        assert _names(d) == ["request", "queue", "decode_step",
                             "first_token"]
        assert trace_lint.lint_trace_obj({"traces": [d]}) == []
        assert trace_lint.lint_trace_obj(tr.to_chrome()) == []


# ---------------------------------------------------------------------------
# trace_lint unit checks
# ---------------------------------------------------------------------------

class TestTraceLint:
    def _trace(self, spans, **kw):
        base = {"trace_id": "t", "pid": 1, "state": "done",
                "dropped_spans": 0, "leaked_open": 0, "spans": spans}
        base.update(kw)
        return base

    def test_valid_tree_passes(self):
        t = self._trace([
            {"sid": "a:1", "parent": None, "name": "request",
             "t0_us": 0.0, "t1_us": 100.0, "args": {}},
            {"sid": "a:2", "parent": "a:1", "name": "queue",
             "t0_us": 5.0, "t1_us": 50.0, "args": {}}])
        assert trace_lint.lint_trace_obj({"traces": [t]}) == []

    def test_orphan_parent_fails(self):
        t = self._trace([{"sid": "a:1", "parent": "ghost",
                          "name": "x", "t0_us": 0.0, "t1_us": 1.0,
                          "args": {}}])
        errs = trace_lint.lint_trace_obj({"traces": [t]})
        assert any("ORPHAN" in e for e in errs)

    def test_open_span_fails(self):
        t = self._trace([{"sid": "a:1", "parent": None, "name": "x",
                          "t0_us": 0.0, "t1_us": None, "args": {}}])
        errs = trace_lint.lint_trace_obj({"traces": [t]})
        assert any("OPEN" in e for e in errs)

    def test_reversed_timestamps_fail(self):
        t = self._trace([{"sid": "a:1", "parent": None, "name": "x",
                          "t0_us": 100.0, "t1_us": 10.0, "args": {}}])
        errs = trace_lint.lint_trace_obj({"traces": [t]})
        assert any("ends before" in e for e in errs)

    def test_child_escaping_parent_fails(self):
        t = self._trace([
            {"sid": "a:1", "parent": None, "name": "p",
             "t0_us": 0.0, "t1_us": 10.0, "args": {}},
            {"sid": "a:2", "parent": "a:1", "name": "c",
             "t0_us": 5.0, "t1_us": 50.0, "args": {}}])
        errs = trace_lint.lint_trace_obj({"traces": [t]})
        assert any("escapes parent" in e for e in errs)

    def test_duplicate_ids_fail(self):
        t = self._trace([
            {"sid": "a:1", "parent": None, "name": "x",
             "t0_us": 0.0, "t1_us": 1.0, "args": {}},
            {"sid": "a:1", "parent": None, "name": "y",
             "t0_us": 0.0, "t1_us": 1.0, "args": {}}])
        errs = trace_lint.lint_trace_obj({"traces": [t]})
        assert any("duplicate" in e for e in errs)

    def test_cli_roundtrip(self, tmp_path):
        import subprocess
        import sys
        good = tmp_path / "good.json"
        good.write_text(json.dumps({"traces": [self._trace([
            {"sid": "a:1", "parent": None, "name": "request",
             "t0_us": 0.0, "t1_us": 1.0, "args": {}}])]}))
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"traces": [self._trace([
            {"sid": "a:1", "parent": None, "name": "x",
             "t0_us": 0.0, "t1_us": None, "args": {}}])]}))
        assert subprocess.run(
            [sys.executable, _LINT_PATH, str(good)],
            capture_output=True).returncode == 0
        assert subprocess.run(
            [sys.executable, _LINT_PATH, str(bad)],
            capture_output=True).returncode == 1


# ---------------------------------------------------------------------------
# Engine tracing: span trees, timeline, costs, bit-identity
# ---------------------------------------------------------------------------

class TestEngineTracing:
    def test_whole_prefill_tree_shape(self, model):
        tr = SpanTracer(sample_rate=1.0)
        eng = _engine(model, tracer=tr)
        eng.submit(np.arange(1, 7, dtype=np.int32), 4)
        eng.run()
        eng.close()
        traces = tr.finished()
        assert len(traces) == 1
        t = traces[0]
        assert t["state"] == "done" and t["leaked_open"] == 0
        names = _names(t)
        for stage in ("request", "queue", "admit", "prefill",
                      "first_token", "decode", "decode_step",
                      "complete"):
            assert stage in names, names
        # lifecycle ordering: queue before admit before prefill ...
        assert names.index("queue") < names.index("admit") \
            < names.index("prefill") < names.index("first_token") \
            < names.index("complete")
        _lint_ok(traces)

    def test_prefill_says_how_full_its_bucket_is(self, model):
        """A whole-prompt prefill pads its prompt to a bucket: the span
        carries the bucket and the share of it that is the prompt's,
        ``flight_summary()`` the sums of both over the engine's life
        (what the flash forward skips of a bucket and what the matmuls
        still run of it)."""
        tr = SpanTracer(sample_rate=1.0)
        eng = _engine(model, tracer=tr)
        assert eng.flight_summary()["prefill_positions_padded"] == 0
        eng.submit(np.arange(1, 7, dtype=np.int32), 2)     # 6 of 8
        eng.submit(np.arange(20, 31, dtype=np.int32), 2)   # 11 of 16
        eng.run()
        eng.close()
        spans = sorted((s["args"] for t in tr.finished()
                        for s in t["spans"] if s["name"] == "prefill"),
                       key=lambda a: a["bucket"])
        assert [(a["bucket"], a["fill"]) for a in spans] == \
            [(8, 0.75), (16, 0.6875)]
        card = eng.flight_summary()
        assert card["prefill_positions"] == 6 + 11
        assert card["prefill_positions_padded"] == 8 + 16

    def test_chunked_prefill_tree_has_chunk_spans(self, model):
        tr = SpanTracer(sample_rate=1.0)
        eng = _engine(model, tracer=tr, prefill_chunk_tokens=8)
        eng.submit(np.arange(1, 20, dtype=np.int32), 4)
        eng.run()
        eng.close()
        t = tr.finished()[0]
        names = _names(t)
        # 19 tokens at chunk 8 -> 3 chunks
        assert names.count("prefill_chunk") == 3
        assert "decode_step" in names and t["leaked_open"] == 0
        # chunk spans nest under the open prefill stage span
        pref = next(s for s in t["spans"] if s["name"] == "prefill")
        for s in t["spans"]:
            if s["name"] == "prefill_chunk":
                assert s["parent"] == pref["sid"]
        _lint_ok([t])

    def test_speculative_tree_has_verify_steps(self, model):
        from paddle_tpu.inference import SpeculativeConfig
        tr = SpanTracer(sample_rate=1.0)
        eng = _engine(model, tracer=tr,
                      speculative=SpeculativeConfig(k=2, draft="ngram"))
        eng.submit(np.arange(1, 9, dtype=np.int32), 6)
        eng.run()
        eng.close()
        t = tr.finished()[0]
        names = _names(t)
        assert "verify_step" in names
        vs = next(s for s in t["spans"] if s["name"] == "verify_step")
        assert {"drafted", "accepted"} <= set(vs["args"])
        assert t["leaked_open"] == 0
        _lint_ok([t])

    def test_off_by_default_no_allocation(self, model):
        eng = _engine(model)
        rid = eng.submit(np.arange(1, 7, dtype=np.int32), 3)
        assert eng._queue[0].trace is None
        eng.run()
        eng.close()
        assert eng.result(rid) is None or True  # drained by run()

    def test_sample_rate_traces_every_other_request(self, model):
        tr = SpanTracer(sample_rate=0.5)
        eng = _engine(model, tracer=tr)
        for i in range(4):
            eng.submit(np.arange(1, 6, dtype=np.int32), 2)
        eng.run()
        eng.close()
        assert tr.sampled_total == 2
        assert len(tr.finished()) == 2

    def test_bit_identical_tracing_on_off(self, model):
        """The r16 pin: greedy outputs do not change with tracing."""
        prompts = [np.arange(1, 14, dtype=np.int32),
                   np.arange(3, 9, dtype=np.int32),
                   np.arange(5, 25, dtype=np.int32)]

        def run(tracer):
            eng = _engine(model, tracer=tracer,
                          prefill_chunk_tokens=8)
            rids = [eng.submit(p, 6) for p in prompts]
            out = eng.run()
            eng.close()
            return [[int(x) for x in out[r]] for r in rids]

        base = run(None)
        traced = run(SpanTracer(sample_rate=1.0))
        assert base == traced

    def test_request_latencies_from_trace(self, model):
        tr = SpanTracer(sample_rate=1.0)
        eng = _engine(model, tracer=tr)
        eng.submit(np.arange(1, 7, dtype=np.int32), 4)
        eng.run()
        eng.close()
        lat = request_latencies(tr.finished()[0])
        assert lat["tokens_out"] == 4
        assert lat["ttft_s"] is not None and lat["ttft_s"] >= 0
        assert lat["tpot_s"] is not None and lat["tpot_s"] >= 0
        assert lat["e2e_s"] >= lat["ttft_s"]

    def test_step_timeline_ring(self, model):
        eng = _engine(model, timeline_steps=4)
        for _ in range(3):
            eng.submit(np.arange(1, 7, dtype=np.int32), 6)
        eng.run()
        eng.close()
        tl = eng.step_timeline()
        assert 0 < len(tl) <= 4  # bounded ring
        last = tl[-1]
        for field in ("step", "ms", "programs", "slots_active",
                      "queued", "free_pages", "reserved_pages"):
            assert field in last, last
        assert any("decode_ms" in e for e in tl)
        assert eng.programs_launched.get("decode", 0) > 0

    def test_program_costs_captured_on_trace(self, model):
        eng = _engine(model, capture_costs=True)
        eng.submit(np.arange(1, 7, dtype=np.int32), 3)
        eng.run()
        eng.close()
        costs = eng.program_costs()
        assert "decode" in costs and "prefill" in costs
        assert costs["decode"].get("flops", 0) > 0
        assert costs["decode"].get("bytes_accessed", 0) > 0

    def test_costs_off_by_default(self, model):
        eng = _engine(model)
        eng.submit(np.arange(1, 7, dtype=np.int32), 2)
        eng.run()
        eng.close()
        assert eng.program_costs() == {}


# ---------------------------------------------------------------------------
# Stitch points: deadline unwind, resurrection replay, keyed failover
# ---------------------------------------------------------------------------

class TestStitchPoints:
    def test_deadline_expiry_in_queue_closes_tree(self, model):
        tr = SpanTracer(sample_rate=1.0)
        eng = _engine(model, tracer=tr)
        eng.submit(np.arange(1, 7, dtype=np.int32), 4,
                   deadline_t=time.monotonic() - 0.001)
        expired = eng.expire_deadlines()
        assert len(expired) == 1 and expired[0].state == "deadline"
        eng.close()
        t = tr.finished()[0]
        assert t["state"] == "deadline" and t["leaked_open"] == 0
        comp = next(s for s in t["spans"] if s["name"] == "complete")
        assert comp["args"]["state"] == "deadline"
        _lint_ok([t])

    def test_deadline_expiry_mid_decode_closes_tree(self, model):
        """Deterministic mid-decode expiry: run until the request is
        demonstrably decoding, then rewind its deadline — no wall-
        clock race against a loaded CI host's compile times."""
        tr = SpanTracer(sample_rate=1.0)
        eng = _engine(model, tracer=tr)
        eng.submit(np.arange(1, 7, dtype=np.int32), 64,
                   deadline_t=time.monotonic() + 300.0)
        for _ in range(3):  # admit + prefill + >=1 decode step
            eng.step()
        req = next(r for r in eng._slots if r is not None)
        assert req.state == "decoding"
        req.deadline_t = time.monotonic() - 1e-3
        eng.step()  # the expiry sweep evicts it typed
        assert eng.num_active == 0
        eng.close()
        t = tr.finished()[0]
        assert t["state"] == "deadline" and t["leaked_open"] == 0
        names = _names(t)
        assert "decode_step" in names  # it WAS decoding when evicted
        _lint_ok([t])

    def test_resurrection_replay_is_one_tree(self, model):
        """Engine death mid-decode: the replayed request's spans land
        on the ORIGINAL tree — one trace id, a resurrect_replay
        marker, a second queue/admit/prefill run, zero leaked spans."""
        fi.get_injector().arm("engine.step", at_calls=[3, 4])
        srv = _server(model, max_engine_errors=2, trace_sample=1.0)
        port = srv.start()
        rep = client_request(
            "127.0.0.1", port,
            {"op": "generate", "prompt": list(range(1, 7)),
             "max_new_tokens": 8})
        assert "error" not in rep, rep
        assert rep["stats"].get("replayed") is True
        tr = client_request("127.0.0.1", port, {"op": "trace"})
        traces = [t for t in tr["traces"] if t["state"] == "done"]
        assert len(traces) == 1  # ONE tree, not pre/post fragments
        t = traces[0]
        names = _names(t)
        assert "resurrect_replay" in names
        assert names.count("queue") == 2    # original + replay
        assert names.count("prefill") == 2  # original + chained replay
        assert names.count("complete") == 1
        assert t["leaked_open"] == 0
        _lint_ok([t])
        # latencies from the stitched tree describe the request the
        # CLIENT experienced: pre-crash tokens (resurrect_replay's
        # pre_tokens) + the replay slice — not just the final slice,
        # which would inflate the derived TPOT
        lat = request_latencies(t)
        assert lat["tokens_out"] == len(rep["generated"]) == 8
        # the tracer-level annotations carry the old debug vocabulary
        evs = [e["name"] for e in tr["events"]]
        assert "resurrect" in evs and "replay" in evs
        srv.stop()
        srv.engine.allocator.check_no_leak()

    def test_keyed_failover_merges_into_one_tree(self, model):
        """Replica dies mid-stream -> keyed resubmission: the router's
        pick/forward/failover spans and the surviving replica's tree
        share one trace id and merge into a single lint-clean tree."""
        from paddle_tpu.serving.supervisor import FailoverRouter

        # replica 0: a protocol-speaking fake that dies mid-stream;
        # replica 1: a REAL traced server that serves the resubmission
        from test_crash_safe_serving import (_FakeReplicaServer,
                                             _FakeSupervisor)
        dying = _FakeReplicaServer(n_tokens=6, die_after=2)
        real = _server(model, trace_sample=0.0)  # ctx forces tracing
        real_port = real.start()
        sup = _FakeSupervisor([dying])
        rep1 = type("R", (), {})()
        rep1.idx, rep1.port, rep1.ready = 1, real_port, True
        rep1.restarts, rep1.alive = 0, lambda: True
        sup.replicas.append(rep1)
        router = FailoverRouter(sup, max_failover=3,
                                backend_timeout_s=30,
                                trace_sample=1.0)
        port = router.start()
        # round-robin: some requests land straight on the healthy
        # replica — drive until one actually failed over (its trace is
        # the one that must read as a single stitched tree)
        router_tree = None
        for attempt in range(6):
            got = client_request(
                "127.0.0.1", port,
                {"op": "generate", "prompt": [1, 2, 3],
                 "max_new_tokens": 6, "key": "k-trace",
                 "stream": True})
            assert "error" not in got, got
            rt = client_request("127.0.0.1", port, {"op": "trace"})
            cand = [t for t in rt["traces"]
                    if t["state"] == "done" and "failover" in _names(t)]
            if cand:
                router_tree = cand[-1]
                break
        assert router_tree is not None, "no failover trace produced"
        assert router.failovers_total >= 1
        names = _names(router_tree)
        assert names.count("forward") >= 2
        assert router_tree["leaked_open"] == 0
        # the REAL replica traced the resubmission under the router's
        # forward span (same trace id, remote_parent link)
        reps = client_request("127.0.0.1", real_port, {"op": "trace"})
        shared = [t for t in reps["traces"]
                  if t["trace_id"] == router_tree["trace_id"]]
        assert shared, (router_tree["trace_id"], reps["traces"])
        replica_tree = shared[-1]
        root = replica_tree["spans"][0]
        fwd_ids = {s["sid"] for s in router_tree["spans"]
                   if s["name"] == "forward"}
        assert root["args"]["remote_parent"] in fwd_ids
        # merged: rewrite the cross-process link and lint ONE tree
        merged_spans = [dict(s) for s in router_tree["spans"]]
        for s in replica_tree["spans"]:
            s = dict(s)
            if s["sid"] == root["sid"]:
                s["parent"] = root["args"]["remote_parent"]
            merged_spans.append(s)
        merged = {"trace_id": router_tree["trace_id"], "pid": -1,
                  "state": "done", "dropped_spans": 0,
                  "leaked_open": 0, "spans": merged_spans}
        # containment across participants is only checked same-pid;
        # here both live in THIS process, and the replica's share sits
        # inside the successful forward span by construction
        _lint_ok([merged])
        router.stop()
        real.stop()
        dying.close()

    def test_loopback_server_trace_passes_lint(self, model):
        """The r16 acceptance loopback: --trace-sample 1.0, one
        request, tree covers queue->admit->chunks->decode->complete
        and the DUMPED FILE passes tools/trace_lint.py."""
        import subprocess
        import sys
        srv = _server(model, trace_sample=1.0, prefill_chunk_tokens=8)
        port = srv.start()
        rep = client_request(
            "127.0.0.1", port,
            {"op": "generate", "prompt": list(range(1, 20)),
             "max_new_tokens": 4})
        assert "error" not in rep, rep
        tr = client_request("127.0.0.1", port, {"op": "trace"})
        assert tr["step_timeline"], "timeline missing from trace op"
        names = _names(tr["traces"][0])
        for stage in ("queue", "admit", "prefill_chunk", "decode_step",
                      "complete"):
            assert stage in names, names
        import tempfile
        with tempfile.NamedTemporaryFile(
                "w", suffix=".json", delete=False) as f:
            json.dump({"traces": tr["traces"]}, f)
            path = f.name
        try:
            r = subprocess.run([sys.executable, _LINT_PATH, path],
                               capture_output=True, text=True)
            assert r.returncode == 0, r.stderr
        finally:
            os.unlink(path)
        srv.stop()


# ---------------------------------------------------------------------------
# Server observability surface: gauges, costs, debug env
# ---------------------------------------------------------------------------

class TestServerSurface:
    def test_trace_op_chrome_and_merge(self, model, tmp_path):
        srv = _server(model, trace_sample=1.0)
        port = srv.start()
        rep = client_request(
            "127.0.0.1", port,
            {"op": "generate", "prompt": [1, 2, 3],
             "max_new_tokens": 3})
        assert "error" not in rep
        ch = client_request("127.0.0.1", port,
                            {"op": "trace", "format": "chrome"})
        assert ch["chrome"]["traceEvents"]
        assert trace_lint.lint_trace_obj(ch["chrome"]) == []
        # merges with another chrome trace via tools/merge_traces.py
        spec = importlib.util.spec_from_file_location(
            "merge_traces", os.path.join(os.path.dirname(_LINT_PATH),
                                         "merge_traces.py"))
        mt = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mt)
        a = tmp_path / "serving.json"
        a.write_text(json.dumps(ch["chrome"]))
        b = tmp_path / "device.json"
        b.write_text(json.dumps({"traceEvents": [
            {"name": "xla_op", "ph": "X", "ts": 1.0, "dur": 2.0,
             "pid": 0, "tid": 0}]}))
        merged = mt.merge([str(a), str(b)])
        assert any(e.get("name") == "xla_op" for e in merged)
        assert any(e.get("name") == "complete" for e in merged)
        srv.stop()

    def test_gauges_carry_costs_timeline_and_traces(self, model):
        srv = _server(model, trace_sample=1.0)
        port = srv.start()
        client_request("127.0.0.1", port,
                       {"op": "generate", "prompt": [1, 2, 3],
                        "max_new_tokens": 3})
        g = srv._gauges()
        assert g.get("programs_launched_decode", 0) > 0
        assert g.get("program_decode_flops", 0) > 0
        assert g.get("program_decode_bytes_accessed", 0) > 0
        assert g.get("engine_steps", 0) > 0
        assert "step_last_ms" in g
        # scrape-time counter sync from the tracer
        assert srv.metrics.counter("traces_sampled_total").get() >= 1
        assert srv.metrics.counter("traces_finished_total").get() >= 1
        # the step histogram got fed from ring deltas
        assert srv.metrics.step_ms.total > 0
        st = client_request("127.0.0.1", port, {"op": "stats"})
        assert st["stats"]["step_ms"]["count"] > 0
        srv.stop()

    def test_debug_env_is_tracer_with_stderr_sink(self, model,
                                                  monkeypatch, capfd):
        monkeypatch.setenv("PT_SERVING_DEBUG", "1")
        srv = _server(model)
        assert srv.tracer.sample_rate == 1.0
        port = srv.start()
        rep = client_request("127.0.0.1", port,
                             {"op": "generate", "prompt": [1, 2, 3],
                              "max_new_tokens": 2})
        assert "error" not in rep
        srv.stop()
        err = capfd.readouterr().err
        assert "[pt-serving-trace" in err
        assert "complete" in err  # lifecycle event vocabulary

    def test_health_reports_trace_sample(self, model):
        srv = _server(model, trace_sample=0.25)
        port = srv.start()
        h = client_request("127.0.0.1", port, {"op": "health"})
        assert h["trace_sample"] == 0.25
        srv.stop()


# ---------------------------------------------------------------------------
# Metrics-registry audit (satellite: the PR 7 _total collision lesson)
# ---------------------------------------------------------------------------

_PROM_TYPE = re.compile(
    r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) (counter|gauge|histogram)$")
_PROM_SAMPLE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^{}]*\})? "
    r"(-?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?|NaN|[+-]Inf)$")


class TestMetricsRegistryAudit:
    def _families(self, text):
        fams = {}
        for line in text.splitlines():
            m = _PROM_TYPE.match(line)
            if m:
                fams[m.group(1)] = m.group(2)
        return fams

    def test_every_counter_family_ends_in_total(self):
        for name in ServingMetrics.COUNTERS:
            assert name.endswith("_total"), (
                f"counter family {name!r} must end in _total "
                f"(OpenMetrics counter convention)")

    def test_no_counter_histogram_family_collisions(self, model):
        srv = _server(model, trace_sample=1.0)
        port = srv.start()
        client_request("127.0.0.1", port,
                       {"op": "generate", "prompt": [1, 2, 3],
                        "max_new_tokens": 2})
        text = client_request("127.0.0.1", port,
                              {"op": "metrics"})["text"]
        srv.stop()
        fams = self._families(text)
        hist = {n for n, t in fams.items() if t == "histogram"}
        counters = {n for n, t in fams.items() if t == "counter"}
        gauges = {n for n, t in fams.items() if t == "gauge"}
        assert fams, "no TYPE lines in exposition"
        # family names unique across types by construction of the dict
        # — check the IMPLICIT names too: a histogram family F owns
        # F_bucket/F_sum/F_count, a counter family ends _total and its
        # base must not be a histogram family (the PR 7 near-miss)
        for c in counters:
            assert c.endswith("_total"), c
            base = c[:-len("_total")]
            assert base not in hist, (
                f"counter {c} collides with histogram family {base}")
            assert base not in gauges or True  # gauge/counter disjoint
        for h in hist:
            assert not h.endswith("_total"), (
                f"histogram family {h} must not use the reserved "
                f"_total suffix")
            for suffix in ("_bucket", "_sum", "_count"):
                assert h + suffix not in counters | gauges | hist

    def test_prometheus_text_parses_line_by_line(self, model):
        srv = _server(model, trace_sample=1.0)
        port = srv.start()
        client_request("127.0.0.1", port,
                       {"op": "generate", "prompt": [1, 2, 3],
                        "max_new_tokens": 2})
        text = client_request("127.0.0.1", port,
                              {"op": "metrics"})["text"]
        srv.stop()
        assert text.endswith("\n")
        for line in text.splitlines():
            if not line:
                continue
            assert _PROM_TYPE.match(line) or _PROM_SAMPLE.match(line), (
                f"line does not parse against the exposition "
                f"format: {line!r}")

    def test_declared_counters_exported_at_zero(self):
        met = ServingMetrics(registry=StatRegistry())
        text = met.prometheus_text()
        for name in ("traces_sampled_total", "traces_finished_total",
                     "trace_spans_dropped_total"):
            assert f"serving_{name} 0" in text

    def test_r18_memory_families_ride_the_audit(self, model):
        """r18 extension: the memory observatory's new families — the
        serving_request_peak_pages histogram and the occupancy/ledger
        gauges — appear on the exposition page with the right types
        (the generic collision/parse audits above already cover them
        by running over the same page)."""
        srv = _server(model)
        port = srv.start()
        client_request("127.0.0.1", port,
                       {"op": "generate", "prompt": [1, 2, 3],
                        "max_new_tokens": 2})
        text = client_request("127.0.0.1", port,
                              {"op": "metrics"})["text"]
        srv.stop()
        fams = self._families(text)
        assert fams.get("serving_request_peak_pages") == "histogram"
        for g in ("serving_pages_inflight",
                  "serving_pages_prefix_device", "serving_pages_used",
                  "serving_ledger_events"):
            assert fams.get(g) == "gauge", (g, fams.get(g))

    def test_fleet_exposition_obeys_the_same_rules(self):
        """r17 extension: the FLEET exposition (per-replica series
        with a replica label + fleet_* rollup families) must obey the
        exact audit this class pins for one replica — counter
        families end _total, no histogram/counter family collisions
        (rollups live in distinct fleet_* families, so an unlabeled
        rollup can never collide with a labeled series), every line
        parses."""
        from paddle_tpu.serving.fleet_metrics import FleetMetrics
        fm = FleetMetrics()
        for i in range(2):
            met = ServingMetrics(registry=StatRegistry())
            met.ttft_ms.observe(2.0 + i)
            met.counter("requests_total").add()
            fm.ingest(i, met.export())
        text = fm.prometheus_text()
        assert text.endswith("\n")
        fams = self._families(text)
        assert fams, "no TYPE lines in fleet exposition"
        hist = {n for n, t in fams.items() if t == "histogram"}
        counters = {n for n, t in fams.items() if t == "counter"}
        gauges = {n for n, t in fams.items() if t == "gauge"}
        for c in counters:
            assert c.endswith("_total"), c
            assert c[:-len("_total")] not in hist, c
        for h in hist:
            assert not h.endswith("_total"), h
            for suffix in ("_bucket", "_sum", "_count"):
                assert h + suffix not in counters | gauges | hist
        # replica-labeled series and fleet rollups never share a family
        assert not {f for f in fams if f.startswith("serving_")} & \
            {f for f in fams if f.startswith("fleet_")}
        for line in text.splitlines():
            if not line:
                continue
            assert _PROM_TYPE.match(line) or _PROM_SAMPLE.match(line), (
                f"fleet exposition line does not parse: {line!r}")
