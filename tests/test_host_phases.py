"""Host phases (core/profiler.py HostPhases / host_phase): the engine
thread's time in the step timeline, and the same phases on the
profiler's clock.

The partition contract: the phases of a step do not overlap, what they
leave of ``ms`` is ``other``, the names are the documented set, and on
the path the benchmark's cells run (whole-prompt prefill, single-step
decode) ``other`` is small. With ``jax.profiler`` running, every phase
is a ``pt.host.*`` event on the host plane: every program launch lies
inside a ``pt.host.launch``, every blocking read inside a
``pt.host.wait``.

The same phases as stretches with a place on the clock (``segs`` of the
accumulator, ``phases`` of a record): ordered, disjoint, inside the
step, summed by name equal to ``host_us``; every ``launch`` names the
kind of program it dispatched and every ``wait`` the kind it fetched,
decode steps settled in the order they were launched; and under a
profiler session one offset lays every ``pt.host.<name>`` event inside
the segment of that name.
"""

import collections
import glob
import json
import os
import statistics
import time

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.core.profiler import HostPhases, RecordEvent, host_phase
from paddle_tpu.distributed import fault_inject as fi
from paddle_tpu.inference import SpeculativeConfig, create_decode_engine
from paddle_tpu.inference.continuous_batching import HOST_PHASES
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import ServingMetrics, SpanTracer
from paddle_tpu.serving import tracing as span_tracing
from paddle_tpu.serving.server import ServingServer, client_request

ENGINE_KW = dict(num_slots=2, page_size=8, max_seq_len=96, num_pages=24,
                 timeline_steps=4096)
NAMES = set(HOST_PHASES) | {"other"}

# engine variants: the default path the cells run, and the two paths
# no cell runs (every jit call inside a launch, every blocking read
# inside a wait; what else they do may be `other`)
PATHS = {
    "default": {},
    "chunked": {"prefill_chunk_tokens": 8},
    "speculative": {"speculative": SpeculativeConfig(k=2, draft="ngram")},
}


# a step-timeline record: the keys every record carries, then by path
# (what every record of that path carries besides, what only some do)
RECORD_ALWAYS = {
    "step", "t_us", "ms", "host_us", "phases", "commit_us", "gap_us",
    "programs",
    "slots_active", "slots_decoding", "queued", "free_pages",
    "reserved_pages", "occupancy"}
_DECODE = {"decode_ms", "decode_h2d", "decode_ahead"}
RECORD_KEYS = {
    "default": (set(), {"cpu_us", "prefill_ms"} | _DECODE),
    "chunked": (set(), {"cpu_us", "chunk_ms"} | _DECODE),
    "speculative": ({"verify_ms"}, {"cpu_us", "prefill_ms"}),
    "rings": ({"kv_pages"}, {"cpu_us", "prefill_ms", "moe"} | _DECODE),
}
FLIGHT_KEYS = {
    "steps", "num_slots", "num_active", "num_queued", "num_pages",
    "free_pages", "reserved_pages", "page_size", "max_seq_len",
    "decode_ema_ms", "prefill_chunk_ema_ms", "prefill_debt_tokens",
    "prefill_chunk_tokens", "fused_step", "decode_steps_resident",
    "decode_steps_uploaded", "decode_steps_ahead", "decode_rows_dropped",
    "state_pool_bytes", "state_rows_overwritten", "latent_pool_bytes",
    "prefill_positions", "prefill_positions_padded",
    "model_counters", "window_ring_pages", "speculative", "mesh",
    "programs_launched", "step_programs", "ledger_events"}
HEALTH_KEYS = {
    "status", "pid", "active", "queued", "role", "page_size",
    "weight_generation", "weight_swaps", "prefix_keys",
    "prefix_keys_truncated", "free_pages", "reserved_pages",
    "cached_pages", "num_pages", "steps", "mesh", "engine_restarts",
    "step_ema_ms", "prefill_chunk_ema_ms", "prefill_debt_tokens",
    "prefill_chunk_tokens", "fused_step", "step_programs",
    "trace_sample", "traces_finished", "uptime_s"}


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


def _drive(eng, rounds=2, new_tokens=10):
    """Admits, prefills, decodes and evicts: more requests than slots,
    so finished slots are refilled mid-flight."""
    for _ in range(rounds):
        for i in range(4):
            eng.submit(np.arange(1, 8 + 3 * i, dtype=np.int32),
                       new_tokens + i)
        eng.run()


# ---------------------------------------------------------------------------
# The mechanism
# ---------------------------------------------------------------------------

class TestHostPhases:
    def test_nested_phase_pauses_the_outer(self):
        acc = HostPhases()
        with acc.phase("admit") as outer:
            time.sleep(0.002)
            with acc.phase("launch") as inner:
                time.sleep(0.004)
            time.sleep(0.002)
        us, _ = acc.take()
        assert set(us) == {"admit", "launch"}
        # each second counted once: the sum is the outer's wall time
        assert us["admit"] + us["launch"] == pytest.approx(
            outer.t1 - outer.t0, abs=1e-9)
        assert us["launch"] == pytest.approx(inner.t1 - inner.t0, abs=1e-9)
        assert 0.004 <= us["launch"] < us["admit"] + us["launch"]
        assert acc.t == outer.t1 and acc.take() == ({}, [])

    def test_paused_outer_phase_leaves_two_segments(self):
        acc = HostPhases()
        with acc.phase("admit") as outer:
            with acc.phase("launch", "decode") as inner:
                pass
            with acc.phase("wait", "decode") as read:
                pass
        assert acc.segs == [
            ("admit", outer.t0, inner.t0, None),
            ("launch", inner.t0, inner.t1, "decode"),
            ("admit", inner.t1, read.t0, None),
            ("wait", read.t0, read.t1, "decode"),
            ("admit", read.t1, outer.t1, None)]
        us, segs = acc.take()
        # the stretches are the sums, placed: no other clock was read
        for name in us:
            assert us[name] == pytest.approx(
                sum(b - a for n, a, b, _ in segs if n == name), abs=1e-12)
        assert acc.segs == [] and acc.take() == ({}, [])

    def test_exception_closes_the_phase(self):
        acc = HostPhases()
        with pytest.raises(ValueError):
            with acc.phase("admit"):
                with acc.phase("wait"):
                    raise ValueError("boom")
        assert acc._open is None and set(acc.us) == {"admit", "wait"}

    def test_cost_without_a_session_is_microseconds(self):
        acc = HostPhases()
        n = 2000
        t0 = time.perf_counter()
        for _ in range(n):
            with acc.phase("x"):
                pass
        per = (time.perf_counter() - t0) / n
        assert per < 50e-6, per  # measured 1.4 us; the bound is slack


# ---------------------------------------------------------------------------
# The step timeline's records
# ---------------------------------------------------------------------------

class TestTimelineRecords:
    @pytest.mark.parametrize("path", sorted(PATHS))
    def test_partition_contract(self, model, path):
        eng = _engine(model, **PATHS[path])
        _drive(eng)
        eng.close()
        tl = eng.step_timeline()
        assert len(tl) > 10
        seen = set()
        for e in tl:
            host = e["host_us"]
            assert set(host) <= NAMES and "other" in host, host
            seen |= set(host)
            # the phases and `other` add up to the step, and no phase
            # ran twice over the same time: nothing is negative
            assert sum(host.values()) == pytest.approx(e["ms"] * 1e3,
                                                       abs=1.0)
            assert all(v >= 0 for k, v in host.items() if k != "other")
            assert host["other"] >= -1.0, host
            assert e["commit_us"] > 0 and e["gap_us"] >= 0
        assert all("cpu_us" in e for e in tl[1:])
        assert all(0 <= e["cpu_us"] <= e["gap_us"] + e["ms"] * 1e3
                   + e["commit_us"] + 1e3 for e in tl[1:])
        assert seen == NAMES, seen

    @pytest.mark.parametrize("path", sorted(PATHS))
    def test_phases_are_the_sums_with_their_places(self, model, path):
        """`phases` (read by `benchmarks/host_clock.py`): the step's
        stretches in order, disjoint, inside `[0, ms]`, summed by name
        equal to `host_us`; one `launch` for every program `programs`
        counts, under its kind, and every `wait` with the kind it
        fetched."""
        eng = _engine(model, **PATHS[path])
        _drive(eng)
        eng.close()
        tl = eng.step_timeline()
        kinds = collections.Counter()
        for e in tl:
            end, sums, launched = 0.0, collections.Counter(), \
                collections.Counter()
            for seg in e["phases"]:
                name, start, us = seg[:3]
                assert name in HOST_PHASES, seg
                assert start >= end - 1e-6 and us >= 0, (seg, end)
                end = start + us
                sums[name] += us
                # a launch and a wait say what of, nothing else does
                assert len(seg) == (4 if name in ("launch", "wait")
                                    else 3), seg
                if name == "launch":
                    launched[seg[3]] += 1
                kinds[name, seg[-1]] += len(seg) == 4
            assert end <= e["ms"] * 1e3 + 0.1, (end, e["ms"])
            host = dict(e["host_us"])
            host.pop("other")
            assert set(sums) == set(host)
            for name, us in host.items():  # a tenth a stretch: rounding
                assert sums[name] == pytest.approx(
                    us, abs=0.1 * len(e["phases"])), (name, e)
            assert launched == e["programs"], (launched, e["programs"])
        want = {"default": {"prefill", "decode"},
                "chunked": {"prefill", "prefill_chained", "decode"},
                "speculative": {"prefill", "verify"}}[path]
        assert {k for (n, k), c in kinds.items()
                if n == "launch" and c} == want
        assert {k for (n, k), c in kinds.items()
                if n == "wait" and c} == want

    @pytest.mark.parametrize("path", sorted(RECORD_KEYS))
    def test_record_keys_are_exactly_these(self, model, path):
        """What `benchmarks/host_phases.py` and the per-layer readers
        are handed (tier-1 does not run `benchmarks/tests`): `ms`,
        `t_us`, `host_us`, `commit_us`, `gap_us`, `cpu_us`, `programs`,
        `slots_decoding`, `decode_h2d`, `decode_ahead`, and a routed
        model's `kv_pages` and `moe`. A key that goes, or a new one,
        shows here before a reader meets it."""
        if path == "rings":
            from paddle_tpu.models import (SmallThinkerForCausalLM,
                                           smallthinker_tiny)
            eng = _engine(SmallThinkerForCausalLM(smallthinker_tiny(),
                                                  seed=3))
        else:
            eng = _engine(model, **PATHS[path])
        _drive(eng, rounds=1)
        eng.close()
        tl = eng.step_timeline()
        every, some = RECORD_ALWAYS | RECORD_KEYS[path][0], \
            RECORD_KEYS[path][1]
        for e in tl:
            assert every <= set(e) <= every | some, sorted(e)
            assert set(e["host_us"]) <= NAMES
            assert ("decode_h2d" in e) == ("decode_ahead" in e) \
                == ("decode" in e["programs"])
        assert set().union(*map(set, tl)) == every | some
        assert all("cpu_us" in e for e in tl[1:])

    def test_flight_summary_keys_are_exactly_these(self, model):
        eng = _engine(model)
        _drive(eng, rounds=1)
        card = eng.flight_summary()
        eng.close()
        assert set(card) == FLIGHT_KEYS
        json.dumps(card)  # the flight recorder writes it as it is

    def test_health_keys_are_exactly_these(self, model):
        srv = ServingServer(model, port=0, metrics=ServingMetrics(
            registry=StatRegistry()), **ENGINE_KW)
        try:
            assert set(srv._health()) == HEALTH_KEYS
        finally:
            srv.engine.close()

    def test_other_is_small_on_the_default_path(self, model):
        eng = _engine(model)
        _drive(eng, rounds=3)
        eng.close()
        tl = [e for e in eng.step_timeline()[2:] if e["programs"]]
        # 2 % of the step or 50 us: gpt_tiny's step on the CPU is about
        # a millisecond, of which the Python between two phases is
        # some 20 us (a device step is 10 ms, and reads 0.3 % there)
        over = [e["host_us"]["other"] - 0.02 * e["ms"] * 1e3 for e in tl]
        assert statistics.median(over) <= 50.0, sorted(over)[-5:]

    def test_older_keys_come_from_the_same_stamps(self, model):
        eng = _engine(model)
        _drive(eng, rounds=1)
        eng.close()
        for e in eng.step_timeline():
            host = e["host_us"]
            if e["programs"] == {"decode": 1}:
                # decode_ms is the dispatch of the decode program: the
                # launch phase, to the rounding of the two
                assert e["decode_ms"] * 1e3 == pytest.approx(
                    host["launch"], abs=0.2)
            if "prefill_ms" in e:
                assert e["prefill_ms"] * 1e3 <= (
                    host["upload"] + host["launch"] + host["wait"] + 0.5)

    @pytest.mark.parametrize("path", ["default", "chunked"])
    def test_decode_waits_settle_launches_in_order(self, model, path):
        """A `wait` of kind `decode` for every decode step settled
        inside a call, in launch order: steps launched ahead (settled
        by the next call), masked steps (a half-prefilled slot:
        settled where they are launched), a step dropped in flight by
        a failed step and one whose launch raised. The rule a reader
        pairs by (`benchmarks/host_clock.py launches`): waits settle
        launches first in, first out, and a record's `decode_ahead`
        says how many launches were still unfetched when its own was
        made, which forgets a step that never was fetched."""
        eng = _engine(model, **PATHS[path])
        ran = []  # ("launch" | "settle", the step), as the engine ran
        launch, settle = eng._launch_decode, eng._settle_decode

        def launched(ahead):
            new = launch(ahead=ahead)
            new["nth"] = sum(1 for what, _ in ran if what == "launch")
            ran.append(("launch", new["nth"]))
            return new

        def settled(pend):
            ran.append(("settle", pend["nth"]))
            return settle(pend)

        eng._launch_decode, eng._settle_decode = launched, settled
        for i in range(4):
            eng.submit(np.arange(1, 20 + 9 * i, dtype=np.int32), 8 + i)
        for _ in range(40):  # until a step is ahead of the host
            eng.step()
            if eng._inflight is not None:
                break
        assert eng._inflight is not None
        fi.get_injector().arm("engine.step", at_calls=[1])
        with pytest.raises(fi.InjectedFault):
            eng.step()
        fi.reset()
        for _ in range(3):
            eng.step()
        real = eng._decode_jit

        def broken(*a):
            raise RuntimeError("launch failed")

        eng._decode_jit = broken
        with pytest.raises(RuntimeError, match="launch failed"):
            eng.step()
        eng._decode_jit = real
        eng.run()
        in_calls = sum(1 for what, _ in ran if what == "settle")
        eng.close()  # settles what is in flight outside any call

        order = [nth for what, nth in ran if what == "launch"]
        truth = [nth for what, nth in ran if what == "settle"][:in_calls]
        flying, read, n = [], [], 0
        for e in eng.step_timeline():
            for seg in e["phases"]:
                if seg[-1] != "decode":
                    continue
                if seg[0] == "launch" and "decode" in e["programs"]:
                    ahead = e["decode_ahead"]
                    flying = flying[len(flying) - ahead:] if ahead else []
                    flying.append(n)
                    n += 1
                elif seg[0] == "wait":
                    assert flying, e  # nothing fetched that was not sent
                    read.append(flying.pop(0))
        assert n == len(order) and read == truth
        # ahead, masked (chunked only) and dropped steps all occurred
        assert any(a != b + 1 for a, b in zip(truth[1:], truth))
        assert len(set(order)) - len(set(truth)) >= 1
        if path == "chunked":
            assert any(
                [s[0] for s in e["phases"] if s[-1] == "decode"]
                in (["launch", "wait"], ["launch", "wait", "wait"])
                for e in eng.step_timeline())

    def test_record_size_does_not_depend_on_tokens(self, model):
        def keys(new_tokens):
            eng = _engine(model)
            _drive(eng, rounds=1, new_tokens=new_tokens)
            eng.close()
            tl = eng.step_timeline()
            # a call that launches a decode step alone: admit, upload,
            # launch and one settle (wait, emit), with `admit` cut in
            # three where it is the admission that settles; a prefill
            # adds its upload, launch, wait and emit and cuts `admit`
            # four times more. No stretch a token.
            for e in tl:
                prefills = sum(n for k, n in e["programs"].items()
                               if k != "decode")
                assert len(e["phases"]) <= 7 + 8 * prefills, e
                if not prefills:  # 629 bytes as JSON read here, 480 before
                    assert len(json.dumps(e)) <= 900, e
            return {(k, len(v) if isinstance(v, dict) else 1)
                    for e in tl if e["programs"] == {"decode": 1}
                    for k, v in e.items() if k != "occupancy"}
        assert keys(4) == keys(40)

    def test_phases_outside_a_step_belong_to_no_record(self, model):
        eng = _engine(model)
        eng.submit(np.arange(1, 7, dtype=np.int32), 3)
        with eng._phase("emit"):
            time.sleep(0.01)
        eng.step()
        assert eng.step_timeline()[-1]["host_us"].get("emit", 0) < 5e3
        eng.run()
        eng.close()

    def test_no_span_object_without_sampling(self, model, monkeypatch):
        """The existing contract, kept: with no profiler session and
        `trace_sample` 0 a step creates no span object."""
        made = []
        real = span_tracing.Span.__init__

        def counting(self, *a, **kw):
            made.append(1)
            real(self, *a, **kw)

        monkeypatch.setattr(span_tracing.Span, "__init__", counting)
        eng = _engine(model, tracer=SpanTracer(sample_rate=0.0))
        _drive(eng, rounds=1)
        eng.close()
        assert made == [] and len(eng.step_timeline()) > 5


# ---------------------------------------------------------------------------
# The same phases on the profiler's clock
# ---------------------------------------------------------------------------

def _host_events(trace_dir):
    """One list of ``(name, start_ns, end_ns)`` for every line (a
    thread) of the host planes that holds a `pt.host.*` or RecordEvent
    event. Lines are kept apart: every Python thread's line is called
    "python", and a worker that ran other files first may still have
    an idle server's loop on a line of its own."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            if any(n.startswith("pt.") or n == "my_region"
                   for n, _, _ in evs):
                out.append(evs)
    return out


def _stepping_line(lines):
    """The line of the thread that stepped an engine."""
    mine = [evs for evs in lines
            if any(e[0] == "pt.host.launch" for e in evs)]
    assert len(mine) == 1, len(mine)  # one thread stepped
    return mine[0]


def _trace(work, tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the annotations, not every call
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    return _host_events(str(tmp_path))


def _idle_after(eng, n0, timeout_s=60.0):
    """Wait for the engine to commit a record past the first ``n0`` that
    leaves it with nothing active and nothing queued. A reply reaches
    its client from inside the step that finished the request, before
    that step's record is committed."""
    deadline = time.monotonic() + timeout_s
    while True:
        tl = eng.step_timeline()
        if len(tl) > n0 and tl[-1]["slots_active"] == 0 \
                and tl[-1]["queued"] == 0:
            return len(tl)
        assert time.monotonic() < deadline, "the engine never went idle"
        time.sleep(0.005)


def _inside(ev, spans):
    return any(a <= ev[1] and ev[2] <= b for _, a, b in spans)


def _check_line(evs, launches):
    """One thread's line: phases flat, one launch per program, every
    jit call inside a launch and every blocking read inside a wait."""
    phases = sorted((e for e in evs if e[0].startswith("pt.host.")
                     and e[0] != "pt.host.inbox"),
                    key=lambda e: e[1])
    assert {n[len("pt.host."):] for n, _, _ in phases} <= (
        set(HOST_PHASES) | {"commit", "loop"})
    for a, b in zip(phases, phases[1:]):
        assert a[2] <= b[1], (a, b)  # none overlaps another
    launch = [e for e in phases if e[0] == "pt.host.launch"]
    wait = [e for e in phases if e[0] == "pt.host.wait"]
    assert len(launch) == launches
    # JAX's own events on the same line: a jitted call, a host read.
    # Building a device argument may run a small program of JAX's own
    # (convert_element_type for a list, a PRNG key): inside `upload`.
    upload = [e for e in phases if e[0] == "pt.host.upload"]
    programs = [e for e in evs if e[0].startswith("PjitFunction(")]
    reads = [e for e in evs if e[0] == "np.asarray(jax.Array)"
             or e[0].endswith("Buffer::Await")]
    assert programs and reads
    for e in programs:
        assert _inside(e, launch) or _inside(e, upload), e
    for span in launch:  # and no launch phase without its program
        assert any(_inside(e, [span]) for e in programs), span
    for e in reads:
        assert _inside(e, wait), e


def _check_segments(evs, records):
    """Each `pt.host.<name>` event of the stepping thread lies inside
    the record's segment of that name: the events and the segments
    (with each record's `commit` behind its step) are the same names
    in the same order, and ONE offset between the profiler's clock and
    `time.monotonic` puts every event inside its segment (an event
    opens after its segment's first stamp and closes before its
    last). Returns the offsets that do, in microseconds."""
    events = sorted((e for e in evs if e[0].startswith("pt.host.")
                     and e[0] not in ("pt.host.loop", "pt.host.inbox")),
                    key=lambda e: e[1])
    segs = []
    for e in records:
        segs += [(s[0], e["t_us"] + s[1], e["t_us"] + s[1] + s[2])
                 for s in e["phases"]]
        end = e["t_us"] + e["ms"] * 1e3
        segs.append(("commit", end, end + e["commit_us"]))
    assert [e[0] for e in events] == ["pt.host." + s[0] for s in segs]
    lo = max(e[2] * 1e-3 - s[2] for e, s in zip(events, segs))
    hi = min(e[1] * 1e-3 - s[1] for e, s in zip(events, segs))
    assert lo <= hi + 0.2, (lo, hi)  # the record rounds to 0.1 us
    return lo, hi


class TestProfilerPlane:
    @pytest.mark.parametrize("path", sorted(PATHS))
    def test_engine_phases_on_the_host_plane(self, model, tmp_path, path):
        eng = _engine(model, **PATHS[path])
        _drive(eng, rounds=1, new_tokens=4)  # compiled before the trace
        n0 = len(eng.step_timeline())
        lines = _trace(lambda: _drive(eng, rounds=1, new_tokens=6),
                       tmp_path)
        eng.close()
        tl = eng.step_timeline()[n0:]
        launched = sum(sum(e["programs"].values()) for e in tl)
        line = _stepping_line(lines)
        _check_line(line, launched)
        _check_segments(line, tl)

    def test_server_loop_and_inbox(self, model, tmp_path):
        srv = ServingServer(model, port=0, metrics=ServingMetrics(
            registry=StatRegistry()), **ENGINE_KW)
        port = srv.start()
        try:
            client_request("127.0.0.1", port, {
                "op": "generate", "prompt": [1, 2, 3, 4, 5],
                "max_new_tokens": 3})  # compiled before the trace
            n0 = _idle_after(srv.engine, 0)

            def work():
                client_request("127.0.0.1", port, {
                    "op": "generate", "prompt": [1, 2, 3, 4, 5, 6],
                    "max_new_tokens": 5})
                _idle_after(srv.engine, n0)
            lines = _trace(work, tmp_path)
            tl = srv.engine.step_timeline()[n0:]
        finally:
            srv.stop()
        # the engine thread's line holds the loop AND the step's phases
        line = _stepping_line(lines)
        loops = [e for e in line if e[0] == "pt.host.loop"]
        inbox = [e for e in line if e[0] == "pt.host.inbox"]
        assert loops and len(inbox) == len(loops)
        assert all(_inside(e, loops) for e in inbox)
        _check_line(line, sum(sum(e["programs"].values()) for e in tl))
        _check_segments(line, tl)
        # gap_us is that loop: a working gap is about one loop event
        assert all(e["gap_us"] >= 0 for e in tl)

    def test_record_event_and_trainer_launch(self, tmp_path):
        def work():
            with RecordEvent("my_region"):
                with host_phase("train_launch"):
                    jax.block_until_ready(jax.numpy.ones((8,)) + 1)
        names = {e[0] for v in _trace(work, tmp_path) for e in v}
        assert {"my_region", "pt.host.train_launch"} <= names

    def test_train_step_launch_is_annotated(self, tmp_path):
        from paddle_tpu import nn, optimizer
        from paddle_tpu.jit import TrainStep
        pt.seed(0)
        net = nn.Linear(4, 2)
        opt = optimizer.SGD(learning_rate=0.1,
                            parameters=net.parameters())
        step = TrainStep(net, opt, lambda m, b: (m(b) ** 2).mean())
        x = pt.to_tensor(np.ones((3, 4), np.float32))
        step(x)
        lines = _trace(lambda: jax.block_until_ready(step(x)), tmp_path)
        evs = [e for v in lines for e in v]
        launch = [e for e in evs if e[0] == "pt.host.train_launch"]
        assert len(launch) == 1
        assert any(e[0].startswith("PjitFunction(") and _inside(e, launch)
                   for e in evs)
