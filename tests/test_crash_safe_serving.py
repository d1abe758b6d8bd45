"""Crash-safe serving (r9): engine resurrection with in-flight replay,
deadline propagation, stall watchdog, replica supervision, and the
seeded chaos harness (tools/chaos_serving.py).

The contracts pinned here (ISSUE r9 acceptance):

- a persistent engine-step failure is survived by RESURRECTION —
  teardown (pages audited), rebuild, and replay of every in-flight
  request, with greedy outputs BIT-IDENTICAL to the uninterrupted run;
- ``deadline_ms`` produces a typed DeadlineExceeded (never a hang, no
  leaked pages) at EVERY lifecycle stage: queued, mid-prefill,
  mid-decode, and mid-speculative-run;
- the chaos harness invariants hold with engine.step + alloc.page +
  net.recv armed and one replica SIGKILLed: 100% typed termination,
  clean per-replica leak audits after drain, bit-identical replayed
  outputs.
"""

import importlib.util
import os
import pathlib
import re
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.distributed import fault_inject as fi
from paddle_tpu.distributed.resilience import (_BUILTIN_SITE_POLICIES,
                                               NO_RETRY_SITES)
from paddle_tpu.inference import SpeculativeConfig, create_decode_engine
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import (ServingMetrics, ServingServer,
                                client_request)

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _clean_injector():
    fi.reset()
    yield
    fi.reset()


@pytest.fixture(scope="module", autouse=True)
def _compile_cache(module_compile_cache):
    """Engine-heavy file: reuse XLA compiles across tests (see
    conftest.module_compile_cache) — most of this file's tier-1 wall
    cost is repeated compiles of the same gpt_tiny shapes."""
    yield


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


ENGINE_KW = dict(num_slots=2, page_size=8, max_seq_len=96, num_pages=12)


def _engine(m, **kw):
    merged = dict(ENGINE_KW)
    merged.update(kw)
    return create_decode_engine(m, **merged)


def _server(m, **kw):
    merged = dict(ENGINE_KW)
    merged.update(kw)
    merged.setdefault("metrics", ServingMetrics(registry=StatRegistry()))
    return ServingServer(m, **merged)


def _gen(port, payload, timeout_s=180.0, on_token=None):
    return client_request("127.0.0.1", port, payload,
                          timeout_s=timeout_s, on_token=on_token)


# ---------------------------------------------------------------------------
# Engine resurrection: bit-identical replay (tentpole pin)
# ---------------------------------------------------------------------------

class TestResurrection:
    def _expected(self, model, prompts, mnt):
        eng = _engine(model)
        rids = [eng.submit(np.asarray(p, np.int32), mnt)
                for p in prompts]
        results = eng.run()
        eng.close()
        return [[int(t) for t in results[r][len(p):]]
                for r, p in zip(rids, prompts)]

    def test_replay_bit_identical_streams_and_finals(self, model):
        """Two in-flight requests survive an engine death mid-decode:
        the rebuilt engine replays prompt + emitted tokens as one
        chained prefill, the clients' STREAMS carry no duplicates and
        no gaps, and the final sequences equal the fault-free run."""
        prompts = [list(range(1, 7)), list(range(3, 12))]
        expected = self._expected(model, prompts, 8)
        # two consecutive step faults at calls 3,4 breach
        # max_engine_errors=2 while both requests are mid-decode
        fi.get_injector().arm("engine.step", at_calls=[3, 4])
        met = ServingMetrics(registry=StatRegistry())
        srv = _server(model, metrics=met, max_engine_errors=2)
        port = srv.start()
        results = [None, None]
        toks = [[], []]

        def client(i):
            results[i] = _gen(port, {
                "op": "generate", "prompt": prompts[i],
                "max_new_tokens": 8, "stream": True},
                on_token=toks[i].append)

        ts = [threading.Thread(target=client, args=(i,))
              for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=180)
        for i in range(2):
            assert results[i] is not None, "client hung"
            assert "error" not in results[i], results[i]
            assert results[i]["generated"] == expected[i]
            assert toks[i] == expected[i]  # pause, no dup, no gap
            assert results[i]["stats"].get("replayed") is True
            assert results[i]["tokens"] == \
                prompts[i] + expected[i]
        counters = met.snapshot()["counters"]
        assert counters["engine_restarts_total"] == 1
        assert counters["replayed_requests_total"] == 2
        # telemetry is stitched too: every token a client received is
        # counted exactly once, pre-crash tokens included — not just
        # the post-resurrection slice
        assert counters["tokens_generated_total"] == \
            sum(len(e) for e in expected)
        # the server still serves new work after resurrection
        rep = _gen(port, {"op": "generate", "prompt": [5, 6, 7],
                          "max_new_tokens": 3})
        assert "error" not in rep and len(rep["generated"]) == 3
        chk = _gen(port, {"op": "leak_check"})
        assert chk["ok"], chk
        srv.stop()
        srv.engine.allocator.check_no_leak()

    def test_replay_survives_short_prompt_bucket_ladder(self, model):
        """A custom prompt_buckets ladder that stops short of
        max_seq_len must not turn a transparent replay into
        ReplayFailed: replay submits prompt + emitted tokens as ONE
        chained prefill, so the server extends the ladder to
        max_seq_len (prefill jits retrace per shape lazily — the extra
        bucket is free until used)."""
        prompts = [list(range(1, 16))]  # 15 tokens: fits bucket 16,
        expected = self._expected(model, prompts, 8)  # replay won't
        fi.get_injector().arm("engine.step", at_calls=[3, 4])
        srv = _server(model, max_engine_errors=2, prompt_buckets=(16,))
        assert srv.engine.prompt_buckets[-1] == ENGINE_KW["max_seq_len"]
        port = srv.start()
        rep = _gen(port, {"op": "generate", "prompt": prompts[0],
                          "max_new_tokens": 8})
        assert "error" not in rep, rep
        assert rep["generated"] == expected[0]
        assert rep["stats"].get("replayed") is True
        srv.stop()
        srv.engine.allocator.check_no_leak()

    def test_restart_budget_exhausted_escalates_typed(self, model):
        """engine.step failing FOREVER: resurrection is tried
        max_engine_restarts times, then the server fails everything
        typed and stops admitting — never an untyped wedge."""
        fi.get_injector().arm("engine.step", probability=1.0)
        srv = _server(model, max_engine_errors=2,
                      max_engine_restarts=1)
        port = srv.start()
        rep = _gen(port, {"op": "generate", "prompt": [1, 2, 3],
                          "max_new_tokens": 4}, timeout_s=90)
        assert rep.get("error") in ("EngineFailed", "ServerEvicted"), rep
        h = _gen(port, {"op": "health"})
        assert h["status"] == "draining"
        assert h["engine_restarts"] == 1
        srv.stop()
        srv.engine.allocator.check_no_leak()

    def test_queued_requests_replay_too(self, model):
        """Requests still QUEUED at engine death (never prefilled) ride
        the same replay path with an empty pre-crash history."""
        prompts = [list(range(1, 20)), list(range(2, 21)),
                   list(range(3, 22))]  # 3 requests, 2 slots: one queues
        expected = self._expected(model, prompts, 6)
        fi.get_injector().arm("engine.step", at_calls=[3, 4])
        srv = _server(model, max_engine_errors=2)
        port = srv.start()
        results = [None] * 3

        def client(i):
            results[i] = _gen(port, {
                "op": "generate", "prompt": prompts[i],
                "max_new_tokens": 6})

        ts = [threading.Thread(target=client, args=(i,))
              for i in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=180)
        for i in range(3):
            assert results[i] is not None and \
                "error" not in results[i], results[i]
            assert results[i]["generated"] == expected[i]
        srv.stop()
        srv.engine.allocator.check_no_leak()


# ---------------------------------------------------------------------------
# Deadline propagation: typed expiry at every lifecycle stage
# ---------------------------------------------------------------------------

class TestDeadlineLifecycle:
    def test_expired_in_queue_shed_before_prefill(self, model):
        done = []
        eng = _engine(model, on_complete=done.append)
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), 4,
                         deadline_t=time.monotonic() - 0.01)
        eng.step()
        (req,) = [r for r in done if r.req_id == rid]
        assert req.state == "deadline"
        assert req.stats.prefill_attempts == 0  # shed BEFORE prefill
        assert req.stats.tokens_out == 0
        eng.allocator.check_no_leak()

    def test_expired_mid_prefill_unwinds_typed(self, model):
        done = []
        eng = _engine(model, on_complete=done.append)
        orig_get = eng._get_prefill

        def slow_get(chained):
            jit = orig_get(chained)

            def wrapped(*a, **kw):
                time.sleep(0.15)
                return jit(*a, **kw)
            return wrapped

        eng._get_prefill = slow_get
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), 4,
                         deadline_t=time.monotonic() + 0.05)
        eng.step()  # admission prefill outlives the deadline
        (req,) = [r for r in done if r.req_id == rid]
        assert req.state == "deadline"
        assert req.stats.prefill_attempts == 1  # prefill DID run
        assert req.stats.tokens_out == 0        # but nothing delivered
        assert eng.num_active == 0
        eng.allocator.check_no_leak()

    def test_expired_mid_decode_evicts_and_returns_pages(self, model):
        done = []
        eng = _engine(model, on_complete=done.append)
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), 12,
                         deadline_t=time.monotonic() + 3600)
        eng.step()
        eng.step()
        (req,) = [r for r in eng._slots if r is not None]
        req.deadline_t = time.monotonic() - 0.01  # force expiry
        eng.step()
        (req,) = [r for r in done if r.req_id == rid]
        assert req.state == "deadline"
        assert 1 <= req.stats.tokens_out < 12  # partial, then evicted
        assert eng.num_active == 0
        eng.allocator.check_no_leak()

    def test_expired_mid_speculative_run_frees_reservation(self, model):
        done = []
        eng = _engine(model, on_complete=done.append,
                      speculative=SpeculativeConfig(k=2, draft="ngram"))
        rid = eng.submit(np.arange(1, 10, dtype=np.int32), 24,
                         deadline_t=time.monotonic() + 3600)
        eng.step()
        eng.step()
        assert eng.allocator.reserved_total > 0  # spec admission held
        (req,) = [r for r in eng._slots if r is not None]
        req.deadline_t = time.monotonic() - 0.01
        eng.step()
        (req,) = [r for r in done if r.req_id == rid]
        assert req.state == "deadline"
        assert eng.allocator.reserved_total == 0  # reservation returned
        eng.allocator.check_no_leak()

    def test_hopeless_deadline_never_admitted(self, model):
        """The admission gate: with a step-time estimate available, a
        request whose token budget cannot fit its deadline is expired
        typed instead of wasting a prefill."""
        done = []
        eng = _engine(model, on_complete=done.append)
        eng.submit(np.arange(1, 4, dtype=np.int32), 4)
        while eng.num_active or eng.num_queued:
            eng.step()  # warm: establishes step_ema_s
        assert eng.step_ema_s is not None
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), 64,
                         deadline_t=time.monotonic()
                         + eng.step_ema_s)  # 64 tokens in ~1 step: no
        eng.step()
        (req,) = [r for r in done if r.req_id == rid]
        assert req.state == "deadline"
        assert req.stats.prefill_attempts == 0
        eng.allocator.check_no_leak()

    def test_hopeless_gate_is_best_case_not_expected(self, model):
        """The gate must use a provable LOWER bound on remaining work:
        an eos_token can end the generation after one token and a
        speculative step emits up to k+1 tokens, so neither request
        below is provably hopeless even though max_new_tokens * ema
        overshoots the budget."""
        eng = _engine(model)
        eng.step_ema_s = 0.01
        now = time.monotonic()
        # 64-token CAP but eos could finish it in one step: feasible
        eng.submit(np.arange(1, 6, dtype=np.int32), 64, eos_token=2,
                   deadline_t=now + 5 * eng.step_ema_s)
        assert not eng._deadline_hopeless(eng._queue[-1], now)
        # same budget without eos: provably needs 64 steps — hopeless
        eng.submit(np.arange(1, 6, dtype=np.int32), 64,
                   deadline_t=now + 5 * eng.step_ema_s)
        assert eng._deadline_hopeless(eng._queue[-1], now)
        # speculative k=3: 64 tokens can land in 16 verify steps
        spec = _engine(model, num_pages=24,
                       speculative=SpeculativeConfig(k=3, draft="ngram"))
        spec.step_ema_s = 0.01
        spec.submit(np.arange(1, 6, dtype=np.int32), 64,
                    deadline_t=now + 20 * spec.step_ema_s)
        assert not spec._deadline_hopeless(spec._queue[-1], now)

    def test_mid_prefill_expiry_charges_no_fairness(self, model):
        """A mid-prefill deadline unwind is NOT a committed admission:
        it must not reach scheduler.note_admitted (phantom bypass
        charges from deadline-tight traffic could starve the queue)."""
        class _SpyScheduler:
            def __init__(self):
                self.noted = []

            def select(self, queue, fits, now):
                for i, r in enumerate(queue):
                    if fits(r):
                        return i
                return None

            def shed(self, queue, now):
                return []

            def note_admitted(self, req, queue, now):
                self.noted.append(req.req_id)

        spy = _SpyScheduler()
        done = []
        eng = _engine(model, on_complete=done.append, scheduler=spy)
        orig_get = eng._get_prefill

        def slow_get(chained):
            jit = orig_get(chained)

            def wrapped(*a, **kw):
                time.sleep(0.15)
                return jit(*a, **kw)
            return wrapped

        eng._get_prefill = slow_get
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), 4,
                         deadline_t=time.monotonic() + 0.05)
        eng.step()  # admission prefill outlives the deadline
        (req,) = [r for r in done if r.req_id == rid]
        assert req.state == "deadline"
        assert spy.noted == []  # unwound admission: no fairness charge
        eng._get_prefill = orig_get
        rid2 = eng.submit(np.arange(1, 6, dtype=np.int32), 2)
        while not any(r.req_id == rid2 for r in done):
            eng.step()
        assert spy.noted == [rid2]  # committed admission IS charged
        eng.allocator.check_no_leak()

    def test_server_deadline_protocol(self, model):
        srv = _server(model)
        port = srv.start()
        # generous budget: completes normally
        rep = _gen(port, {"op": "generate", "prompt": [1, 2, 3],
                          "max_new_tokens": 4, "deadline_ms": 120000})
        assert "error" not in rep and len(rep["generated"]) == 4
        # doomed budget: typed DeadlineExceeded, never a hang
        rep = _gen(port, {"op": "generate", "prompt": [1, 2, 3],
                          "max_new_tokens": 4, "deadline_ms": 1})
        assert rep.get("error") == "DeadlineExceeded", rep
        # malformed budgets are BadRequest
        for bad in (-5, 0, "soon"):
            rep = _gen(port, {"op": "generate", "prompt": [1],
                              "max_new_tokens": 2, "deadline_ms": bad})
            assert rep.get("error") == "BadRequest", (bad, rep)
        st = _gen(port, {"op": "stats"})
        assert st["stats"]["counters"]["deadline_exceeded_total"] == 1
        srv.stop()
        srv.engine.allocator.check_no_leak()


# ---------------------------------------------------------------------------
# Stall watchdog (satellite)
# ---------------------------------------------------------------------------

class TestStallWatchdog:
    def test_stalled_slot_evicted_typed(self, model):
        done = []
        eng = _engine(model, stall_timeout_s=0.05,
                      on_complete=done.append)
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), 12)
        eng.step()  # admit + first tokens
        time.sleep(0.1)  # no step() => no emission: a stall
        out = eng.evict_stalled()
        assert [r.req_id for r in out] == [rid]
        (req,) = [r for r in done if r.req_id == rid]
        assert req.state == "stalled"
        assert eng.num_active == 0
        eng.allocator.check_no_leak()

    def test_server_stalled_decoding_slot_typed(self, model):
        """A slot that was admitted and then starves (step faults
        forever after) gets RequestStalled with its pages back — via
        the sweep the serving loop runs when step() itself keeps
        raising."""
        met = ServingMetrics(registry=StatRegistry())
        srv = _server(model, stall_timeout_s=0.3, metrics=met,
                      max_engine_errors=10**6, max_engine_restarts=0)
        port = srv.start()
        got = {}
        first_tok = threading.Event()

        def client():
            got["rep"] = _gen(port, {"op": "generate",
                                     "prompt": [1, 2, 3],
                                     "max_new_tokens": 64,
                                     "stream": True},
                              timeout_s=120,
                              on_token=lambda t: first_tok.set())

        t = threading.Thread(target=client)
        t.start()
        # arm only once the request is ADMITTED and decoding (first
        # streamed token observed) — from then on every step fails and
        # the slot starves
        assert first_tok.wait(timeout=60), "request never started"
        fi.get_injector().arm("engine.step", probability=1.0)
        t.join(timeout=120)
        fi.reset()
        assert got.get("rep") is not None, "client hung"
        assert got["rep"].get("error") == "RequestStalled", got["rep"]
        assert met.snapshot()["counters"]["stalled_total"] == 1
        chk = _gen(port, {"op": "leak_check"})
        assert chk["ok"], chk
        srv.stop()


# ---------------------------------------------------------------------------
# Speculative drain/close leak audit (satellite)
# ---------------------------------------------------------------------------

class TestSpecDrainClose:
    def test_close_mid_spec_returns_reservations(self, model):
        eng = _engine(model,
                      speculative=SpeculativeConfig(k=2, draft="ngram"))
        eng.submit(np.arange(1, 10, dtype=np.int32), 24)
        eng.submit(np.arange(2, 8, dtype=np.int32), 24)
        eng.step()
        assert eng.allocator.reserved_total > 0
        eng.close()  # reserved-but-unallocated capacity must die here
        eng.allocator.check_no_leak()
        assert eng.allocator.free_count == eng.num_pages

    def test_server_stop_mid_spec_no_leak(self, model):
        srv = _server(model,
                      speculative=SpeculativeConfig(k=2, draft="ngram"))
        port = srv.start()
        got = {}

        def client():
            got["rep"] = _gen(port, {"op": "generate",
                                     "prompt": list(range(1, 10)),
                                     "max_new_tokens": 24})

        t = threading.Thread(target=client)
        t.start()
        time.sleep(0.2)  # mid-flight, reservations live
        srv.stop()
        t.join(timeout=60)
        assert got.get("rep") is not None, "client hung through stop()"
        srv.engine.allocator.check_no_leak()
        assert srv.engine.allocator.reserved_total == 0

    def test_check_no_leak_counts_dangling_reservation(self):
        from paddle_tpu.inference import PageAllocator
        alloc = PageAllocator(4)
        assert alloc.reserve("r", 2)
        with pytest.raises(RuntimeError, match="reserved"):
            alloc.check_no_leak()
        alloc.free("r")
        alloc.check_no_leak()


# ---------------------------------------------------------------------------
# Fault-site registry audit (satellite)
# ---------------------------------------------------------------------------

class TestFaultSiteAudit:
    def _source_files(self):
        # the PRODUCTION tree: tests may arm ad-hoc sites for unit
        # coverage of the injector itself
        roots = [REPO / "paddle_tpu", REPO / "tools"]
        for root in roots:
            yield from root.rglob("*.py")
        yield REPO / "bench_all.py"

    def test_every_used_site_is_registered_with_disposition(self):
        """Every site string passed to fault_point() anywhere in the
        tree must (a) be declared in fault_inject.FAULT_SITES with a
        docstring and (b) carry a retry disposition — a
        get_retry_policy entry or an explicit NO_RETRY_SITES marker."""
        pat = re.compile(r"fault_point\(\s*[\"']([a-z_.]+)[\"']")
        used = set()
        for f in self._source_files():
            used |= set(pat.findall(f.read_text(encoding="utf-8")))
        assert used, "audit regex found no fault_point call sites"
        unregistered = used - set(fi.FAULT_SITES)
        assert not unregistered, \
            f"fault sites used but not in FAULT_SITES: {unregistered}"
        for site, doc in fi.FAULT_SITES.items():
            assert isinstance(doc, str) and doc.strip(), \
                f"site {site!r} has no docstring"
        undisposed = (set(fi.FAULT_SITES)
                      - set(_BUILTIN_SITE_POLICIES)
                      - set(NO_RETRY_SITES))
        assert not undisposed, \
            f"sites with neither a retry policy nor an explicit " \
            f"no-retry marker: {undisposed}"
        ambiguous = set(_BUILTIN_SITE_POLICIES) & set(NO_RETRY_SITES)
        assert not ambiguous, \
            f"sites claiming BOTH retry and no-retry: {ambiguous}"

    def test_no_dead_registry_entries(self):
        """Every registered site appears as a string literal somewhere
        in the tree (catches registry entries outliving their call
        sites — including dynamic ones like ps.push/ps.pull/ps.call,
        which reach fault_point(site) through a variable)."""
        blob = "\n".join(f.read_text(encoding="utf-8")
                         for f in self._source_files())
        for site in fi.FAULT_SITES:
            assert f'"{site}"' in blob or f"'{site}'" in blob, \
                f"registered site {site!r} never appears in the tree"

    def test_no_retry_markers_have_reasons(self):
        for site, reason in NO_RETRY_SITES.items():
            assert isinstance(reason, str) and len(reason) > 10, \
                f"no-retry marker for {site!r} must explain who owns " \
                f"recovery"

    def test_injector_log_never_retains_tracebacks(self):
        """The injector's fired-fault log must hold traceback-FREE
        records: logging the raised exception itself pins every frame
        on the faulting stack (and whatever those frames reference —
        in the r9 chaos run, the torn connection's socket fd, turning
        a clean net.recv teardown into a 60s client hang because the
        FIN never left the process)."""
        fi.get_injector().arm("audit.retention", probability=1.0)
        sock_alive = {}

        def faulting_frame():
            # a frame-local standing in for the leaked socket: if the
            # raised exception's traceback is retained, this frame —
            # and the local — survive the except block
            import weakref

            class Resource:
                pass

            res = Resource()
            sock_alive["ref"] = weakref.ref(res)
            fi.fault_point("audit.retention")

        with pytest.raises(fi.InjectedFault):
            faulting_frame()
        log = fi.get_injector().log
        assert log, "fault fired but nothing logged"
        assert log[-1].__traceback__ is None, \
            "injector.log retained a RAISED exception (traceback pins " \
            "the faulting frames)"
        import gc
        gc.collect()
        assert sock_alive["ref"]() is None, \
            "faulting frame's locals survived the handled fault"


# ---------------------------------------------------------------------------
# Occupancy gauges + resurrection counters (satellite)
# ---------------------------------------------------------------------------

class TestMetricsGauges:
    def test_gauges_ride_snapshot_and_prometheus(self, model):
        srv = _server(model)
        port = srv.start()
        # a FRESH server must already export the declared counters at
        # 0 (absent-until-first-event counters break scrape-side
        # rate()/alerting) — probe before any request or stats call
        fresh = _gen(port, {"op": "metrics"})["text"]
        assert "serving_engine_restarts_total 0" in fresh
        assert "serving_replayed_requests_total 0" in fresh
        rep = _gen(port, {"op": "generate", "prompt": [1, 2, 3],
                          "max_new_tokens": 3})
        assert "error" not in rep
        st = _gen(port, {"op": "stats"})
        g = st["stats"]["gauges"]
        for key in ("inflight_slots", "queued_requests", "free_pages",
                    "reserved_pages", "prefix_cache_pages",
                    "num_pages"):
            assert key in g, (key, g)
        assert g["num_pages"] == 12
        assert g["free_pages"] + g["prefix_cache_pages"] == 12
        mx = _gen(port, {"op": "metrics"})["text"]
        assert "# TYPE serving_inflight_slots gauge" in mx
        assert "# TYPE serving_free_pages gauge" in mx
        assert "serving_engine_restarts_total 0" in mx
        assert "serving_replayed_requests_total 0" in mx
        srv.stop()

    def test_gauge_source_failure_never_kills_scrape(self):
        met = ServingMetrics(registry=StatRegistry())
        met.set_gauge_fn(lambda: (_ for _ in ()).throw(RuntimeError()))
        assert met.gauges() == {}
        assert "serving_ttft_ms" in met.prometheus_text()

    def test_health_reports_occupancy(self, model):
        srv = _server(model)
        port = srv.start()
        h = _gen(port, {"op": "health"})
        for key in ("reserved_pages", "cached_pages",
                    "engine_restarts", "step_ema_ms"):
            assert key in h, (key, h)
        srv.stop()


# ---------------------------------------------------------------------------
# Failover router over fake replicas (unit: no subprocesses)
# ---------------------------------------------------------------------------

class _FakeReplicaServer:
    """Protocol-speaking stand-in for a ServingServer process: streams
    ``n_tokens`` deterministic tokens then a final reply; optionally
    dies (closes the connection) after ``die_after`` token messages."""

    def __init__(self, n_tokens=6, die_after=None):
        import json as _json
        import socket as _socket
        self.n_tokens = n_tokens
        self.die_after = die_after
        self._json = _json
        self._sock = _socket.socket(_socket.AF_INET,
                                    _socket.SOCK_STREAM)
        self._sock.setsockopt(_socket.SOL_SOCKET,
                              _socket.SO_REUSEADDR, 1)
        self._sock.bind(("127.0.0.1", 0))
        self._sock.listen(8)
        self.port = self._sock.getsockname()[1]
        self.served = 0
        self.msgs = []
        self._stop = False
        self._t = threading.Thread(target=self._loop, daemon=True)
        self._t.start()

    def _loop(self):
        while not self._stop:
            try:
                self._sock.settimeout(0.2)
                conn, _ = self._sock.accept()
            except OSError:
                continue
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn):
        f = conn.makefile("rw", encoding="utf-8")
        try:
            line = f.readline()
            msg = self._json.loads(line)
            self.served += 1
            self.msgs.append(msg)
            for j in range(self.n_tokens):
                if self.die_after is not None and j >= self.die_after:
                    conn.close()  # died mid-stream
                    return
                f.write(self._json.dumps(
                    {"rid": 0, "token": 100 + j,
                     "done": j == self.n_tokens - 1}) + "\n")
                f.flush()
            f.write(self._json.dumps(
                {"rid": 0, "done": True,
                 "tokens": list(msg["prompt"])
                 + [100 + j for j in range(self.n_tokens)],
                 "generated": [100 + j for j in range(self.n_tokens)],
                 "stats": {}}) + "\n")
            f.flush()
        except (OSError, ValueError):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def close(self):
        self._stop = True
        try:
            self._sock.close()
        except OSError:
            pass


class _FakeSupervisor:
    def __init__(self, servers):
        self.host = "127.0.0.1"
        self.replicas = []
        for i, s in enumerate(servers):
            rep = type("R", (), {})()
            rep.idx, rep.port, rep.ready = i, s.port, True
            rep.restarts = 0
            rep.alive = lambda: True
            self.replicas.append(rep)

    def live(self):
        return [r for r in self.replicas if r.ready]


class TestFailoverRouter:
    def test_keyed_request_fails_over_with_stream_dedupe(self):
        from paddle_tpu.serving.supervisor import FailoverRouter
        dying = _FakeReplicaServer(n_tokens=6, die_after=3)
        healthy = _FakeReplicaServer(n_tokens=6)
        sup = _FakeSupervisor([dying, healthy])
        router = FailoverRouter(sup, max_failover=3,
                                backend_timeout_s=10)
        port = router.start()
        toks = []
        # drive requests until one lands on the dying replica first
        for _ in range(4):
            toks.clear()
            rep = _gen(port, {"op": "generate", "prompt": [1, 2],
                              "max_new_tokens": 6, "stream": True,
                              "key": "k1"}, timeout_s=30,
                       on_token=toks.append)
            assert "error" not in rep, rep
            # dedupe contract: exactly one copy of each token, even
            # when the first 3 were relayed by the replica that died
            assert toks == [100 + j for j in range(6)]
            assert rep["generated"] == toks
            if dying.served and router.failovers_total:
                break
        assert router.failovers_total >= 1
        router.stop()
        dying.close()
        healthy.close()

    def test_unkeyed_request_gets_typed_replica_failed(self):
        from paddle_tpu.serving.supervisor import FailoverRouter
        dying = _FakeReplicaServer(n_tokens=6, die_after=2)
        sup = _FakeSupervisor([dying])
        router = FailoverRouter(sup, max_failover=3,
                                backend_timeout_s=10)
        port = router.start()
        rep = _gen(port, {"op": "generate", "prompt": [1],
                          "max_new_tokens": 6, "stream": True},
                   timeout_s=30)
        assert rep.get("error") == "ReplicaFailed", rep
        assert rep.get("retryable") is True
        router.stop()
        dying.close()

    def test_failover_carries_remaining_deadline_budget(self):
        """deadline_ms is a budget from ARRIVAL covering the whole
        request: every forward — the failover resubmission especially —
        must carry only the remaining budget, or each replica would
        restart the clock and the client could wait up to
        max_failover * deadline_ms."""
        from paddle_tpu.serving.supervisor import FailoverRouter
        dying = _FakeReplicaServer(n_tokens=6, die_after=3)
        healthy = _FakeReplicaServer(n_tokens=6)
        sup = _FakeSupervisor([dying, healthy])
        router = FailoverRouter(sup, max_failover=3,
                                backend_timeout_s=10)
        port = router.start()
        for _ in range(4):
            rep = _gen(port, {"op": "generate", "prompt": [1, 2],
                              "max_new_tokens": 6, "stream": True,
                              "key": "kb", "deadline_ms": 60_000},
                       timeout_s=30)
            assert "error" not in rep, rep
            if router.failovers_total:
                break
        assert router.failovers_total >= 1
        budgets = [m.get("deadline_ms") for s in (dying, healthy)
                   for m in s.msgs]
        assert budgets and all(
            isinstance(b, (int, float)) and 0 < b < 60_000
            for b in budgets), budgets
        router.stop()
        dying.close()
        healthy.close()

    def test_dead_client_is_not_a_dead_replica(self):
        """A send() failure toward the ROUTER'S client must abort the
        request quietly — not mark the healthy replica lost, not fail
        over (burning other replicas generating into a dead socket),
        and not corrupt the failover/replica-failure metrics."""
        from paddle_tpu.serving.supervisor import FailoverRouter
        healthy = _FakeReplicaServer(n_tokens=4)
        sup = _FakeSupervisor([healthy])
        router = FailoverRouter(sup, max_failover=3,
                                backend_timeout_s=10)
        sent = []

        def dying_send(obj):
            sent.append(obj)
            if len(sent) >= 2:  # client vanishes after the 1st token
                raise BrokenPipeError("client hung up")

        router._route_generate({"op": "generate", "prompt": [1, 2],
                                "max_new_tokens": 4, "stream": True,
                                "key": "k3"}, dying_send)
        assert router.failovers_total == 0
        assert router.replica_failures_total == 0
        assert healthy.served == 1  # no pointless resubmission
        router.stop()
        healthy.close()

    def test_router_net_recv_fault_triggers_failover(self):
        from paddle_tpu.serving.supervisor import FailoverRouter
        a = _FakeReplicaServer(n_tokens=4)
        b = _FakeReplicaServer(n_tokens=4)
        sup = _FakeSupervisor([a, b])
        router = FailoverRouter(sup, max_failover=3,
                                backend_timeout_s=10)
        port = router.start()
        fi.get_injector().arm("net.recv", at_calls=[2])
        rep = _gen(port, {"op": "generate", "prompt": [7],
                          "max_new_tokens": 4, "key": "k2",
                          "stream": True}, timeout_s=30)
        assert "error" not in rep, rep
        assert rep["generated"] == [100, 101, 102, 103]
        assert router.failovers_total >= 1
        router.stop()
        a.close()
        b.close()


class TestSupervisor:
    def test_never_ready_replica_is_reclaimed(self):
        """A replica process that stays alive but never answers a
        health probe (e.g. a hung compile during startup) must be
        killed and queued for respawn after ready_timeout_s — not run
        as permanent capacity loss."""
        import subprocess
        import sys
        from paddle_tpu.serving.supervisor import Supervisor
        sup = Supervisor(model="gpt_tiny", replicas=1,
                         probe_interval_s=0.05, probe_timeout_s=0.2,
                         ready_timeout_s=0.3, backoff_base_s=3600)
        rep = sup.replicas[0]
        rep.port = 1  # nothing listens: every probe fails
        rep.proc = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(600)"])
        rep.spawn_t = time.monotonic() - 10.0  # warmup long expired
        t = threading.Thread(target=sup._monitor_loop, daemon=True)
        t.start()
        try:
            for _ in range(100):
                if rep.next_spawn_t is not None:
                    break
                time.sleep(0.05)
            assert rep.next_spawn_t is not None, \
                "never-ready replica was not reclaimed"
            rep.proc.wait(timeout=5)  # killed, not leaked
        finally:
            sup._stop.set()
            t.join(timeout=2.0)
            if rep.proc.poll() is None:
                rep.proc.kill()


# ---------------------------------------------------------------------------
# Chaos harness (acceptance): seeded faults + SIGKILL, three invariants
# ---------------------------------------------------------------------------

def _load_chaos():
    import sys
    spec = importlib.util.spec_from_file_location(
        "chaos_serving", REPO / "tools" / "chaos_serving.py")
    mod = importlib.util.module_from_spec(spec)
    # sys.modules registration is REQUIRED: the module's dataclasses
    # resolve their (future-import) string annotations through
    # sys.modules[cls.__module__]
    sys.modules["chaos_serving"] = mod
    spec.loader.exec_module(mod)
    return mod


def _chaos_env_ok():
    # the harness spawns real server subprocesses; skip only where
    # subprocesses are impossible
    return os.access(REPO, os.R_OK)


class TestChaosHarness:
    def test_chaos_fast_lane_all_invariants(self):
        """Acceptance pin: engine.step + alloc.page + net.recv armed,
        one replica SIGKILLed — 100% typed termination, clean
        leak_check on every replica after drain, bit-identical greedy
        outputs on every success (replayed ones included)."""
        chaos = _load_chaos()
        report = chaos.run_chaos(replicas=2, requests=10, seed=0,
                                 kill_replica=True)
        assert report.ok, report.to_dict()
        assert report.hangs == 0
        assert report.mismatches == 0
        assert report.leak_failures == 0
        assert report.completed + report.typed_errors == 10
        # the SIGKILLed replica was resurrected by the supervisor
        assert report.supervisor_restarts >= 1, report.to_dict()
        # the engine.step burst forced at least one engine
        # resurrection on a surviving replica
        assert report.engine_restarts >= 1, report.to_dict()
        assert report.replicas_checked == 2

    @pytest.mark.slow
    def test_chaos_soak(self):
        """Soak variant: more requests, hotter fault schedule, a second
        seed — the invariants must hold wherever the schedule lands."""
        chaos = _load_chaos()
        report = chaos.run_chaos(
            replicas=2, requests=24, seed=7,
            replica_faults=("engine.step:at=4|5|6,p=0.01,max=9;"
                            "alloc.page:p=0.08,max=6;"
                            "net.recv:p=0.04,max=4"),
            router_fault_p=0.1, router_fault_max=5,
            kill_replica=True)
        assert report.ok, report.to_dict()
        assert report.engine_restarts >= 1
        assert report.supervisor_restarts >= 1
        assert report.replicas_checked == 2
