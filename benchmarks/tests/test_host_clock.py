"""`host_clock.py` on a small made table: six calls of an engine thread
as the step timeline records them, and the device plane of a capture
that began after the first call's program and ended before the last
one's. Every number below is worked out by hand from the table.

Times are microseconds on the host's clock; the device's events are
written at `host + OFFSET`. The dispatch latencies (program start less
launch start) are 100 at the least and the fetch latencies (wait end
less program end) 100 at the least, so the bracket is `OFFSET +- 100 us`
and its middle the made offset itself.
"""

import copy

import pytest

from benchmarks import host_clock, spec, xplane

BASE = 5_000_000_000.0  # the host's clock at the table's zero, us
OFFSET = -4999.0        # trace's clock less the host's, seconds
PLANE = "/device:TPU:0"


def _call(t, gap, ms, phases, programs, ahead=None, commit=100.0):
    e = {"t_us": BASE + t, "gap_us": gap, "ms": ms, "phases": phases,
         "programs": programs, "commit_us": commit}
    if ahead is not None:
        e["decode_ahead"] = ahead
    return e


# call 0 launches a step before the capture; call 2 admits (the
# admission settles the step in flight, then prefills); call 4 finds a
# slot written, settles first and launches with nothing in flight,
# after 50 us in no phase; call 5's step ends after the capture
TIMELINE = [
    _call(1000, 200, 0.6, [
        ["admit", 0, 100], ["upload", 100, 100],
        ["launch", 200, 300, "decode"]], {"decode": 1}, 0),
    _call(1800, 100, 1.6, [
        ["admit", 0, 100], ["upload", 100, 50],
        ["launch", 150, 250, "decode"], ["wait", 400, 1000, "decode"],
        ["emit", 1400, 200]], {"decode": 1}, 1),
    _call(3600, 100, 4.0, [
        ["admit", 0, 100], ["wait", 100, 800, "decode"],
        ["emit", 900, 100], ["admit", 1000, 100], ["upload", 1100, 200],
        ["admit", 1300, 50], ["launch", 1350, 250, "prefill"],
        ["wait", 1600, 2000, "prefill"], ["emit", 3600, 100],
        ["upload", 3700, 100], ["launch", 3800, 200, "decode"]],
        {"prefill": 1, "decode": 1}, 0),
    _call(7800, 100, 1.2, [
        ["admit", 0, 100], ["upload", 100, 50],
        ["launch", 150, 250, "decode"], ["wait", 400, 600, "decode"],
        ["emit", 1000, 200]], {"decode": 1}, 1),
    _call(9200, 100, 1.4, [
        ["wait", 0, 700, "decode"], ["emit", 700, 100],
        ["admit", 850, 100], ["upload", 950, 150],
        ["launch", 1100, 300, "decode"]], {"decode": 1}, 0),
    _call(10800, 100, 1.4, [
        ["admit", 0, 100], ["upload", 100, 50],
        ["launch", 150, 250, "decode"], ["wait", 400, 900, "decode"],
        ["emit", 1300, 100]], {"decode": 1}, 1),
]


def _ev(line, name, start, end, offset=OFFSET):
    return {"plane": PLANE, "line": line, "name": name, "stats": {},
            "start": (BASE + start) * 1e-6 + offset,
            "dur": (end - start) * 1e-6}


def _op(start, end, offset=OFFSET):
    return _ev(xplane.OPS_LINE, "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} "
               "%p.1), kind=kLoop, calls=%fused_computation", start, end,
               offset)


def _events(offset=OFFSET):
    mod = xplane.MODULES_LINE
    return [
        # step 1 (call 1's launch at 1950): its two ops leave 100 us
        # idle inside the program, while the host is in call 1's wait
        _ev(mod, "jit_step(456)", 2050, 4400, offset),
        _op(2050, 3000, offset), _op(3100, 4400, offset),
        # an argument of the prefill, built inside call 2's upload
        _ev(mod, "jit_convert_element_type(9)", 4750, 4760, offset),
        _op(4750, 4760, offset),
        _ev(mod, "jit_prefill(123)", 5100, 7000, offset),
        _op(5100, 7000, offset),
        _ev(mod, "jit_step(456)", 7520, 8600, offset),
        _op(7520, 8600, offset),
        # launched ahead: it starts where the step before it ends
        _ev(mod, "jit_step(456)", 8600, 9050, offset),
        _op(8600, 9050, offset),
        _ev(mod, "jit_step(456)", 10450, 11500, offset),
        _op(10450, 11500, offset),
    ]


# the device's idle stretches and what covers each, by hand (us):
#  [3000, 3100]   inside step 1's program                in_program 100
#  [4400, 4750]   call 2: wait 100, emit 100, admit 100, upload 50
#  [4760, 5100]   call 2: upload 140, admit 50, launch 150
#  [7000, 7520]   call 2: wait 200, emit 100, upload 100, launch 120
#  [9050, 10450]  call 3: commit 50; loop 100; call 4: wait 700,
#                 emit 100, no phase 50, admit 100, upload 150, launch 150
WINDOW = 11500.0 - 2050.0
BY_HAND = {"wait": 1000.0, "emit": 300.0, "admit": 250.0, "upload": 440.0,
           "launch": 420.0, "commit": 50.0, "loop": 100.0,
           "in_program": 100.0, "unplaced": 50.0,
           # the second cut: call 2 is [3500, 7700], call 4 [9100, 10700]
           "prefill_calls": 350.0 + 340.0 + 520.0, "settled_calls": 1350.0}


def _art(timeline=TIMELINE, events=None):
    return {"timeline": copy.deepcopy(timeline),
            "events": _events() if events is None else events}


def test_the_made_offset_is_inside_the_bracket():
    est = host_clock.estimate(TIMELINE, _events())
    # the first call's program was launched before the capture: the
    # traced programs are launches 1..5
    assert est["shift"] == 1 and est["programs"] == 5
    assert est["fetched"] == 5
    assert est["lo"] == pytest.approx(OFFSET - 100e-6, abs=1e-9)
    assert est["hi"] == pytest.approx(OFFSET + 100e-6, abs=1e-9)
    assert est["delta"] == pytest.approx(OFFSET, abs=1e-9)


def test_the_attribution_at_the_middle_gives_the_made_shares():
    got = host_clock.shares(_art())
    for name, us in BY_HAND.items():
        assert got[name] == pytest.approx(100.0 * us / WINDOW, abs=1e-4), name
    assert got["bracket_us"] == pytest.approx(200.0, abs=1e-3)


def test_the_nine_names_sum_to_the_idle_share():
    art = _art()
    got = host_clock.shares(art)
    assert sum(got[n] for n in host_clock.NAMES) == pytest.approx(
        xplane.idle_pct(art["events"]), abs=0.05)
    assert sum(BY_HAND[n] for n in host_clock.NAMES) == 2710.0


def test_idle_inside_a_program_is_never_the_wait_s():
    """The 100 us between step 1's two ops pass under call 1's `wait`:
    they are the device's own. With them given to `wait` it would read
    1,100 us."""
    got = host_clock.attribute(TIMELINE, _events(), OFFSET)
    assert got["wait"] == pytest.approx(1000e-6, abs=1e-9)
    assert got["in_program"] == pytest.approx(100e-6, abs=1e-9)


def test_the_edges_of_the_bracket_move_what_lies_at_a_boundary():
    """100 us later the host's stretches cover each idle stretch 100 us
    later: the wait's tail grows at the launch's cost; nothing is lost."""
    mid = host_clock.attribute(TIMELINE, _events(), OFFSET)
    late = host_clock.attribute(TIMELINE, _events(), OFFSET + 100e-6)
    assert late["wait"] > mid["wait"] and late["launch"] < mid["launch"]
    assert sum(late[n] for n in host_clock.NAMES) == pytest.approx(
        sum(mid[n] for n in host_clock.NAMES), abs=1e-9)


def test_an_interleaving_that_fits_two_shifts_gives_nothing():
    """Ten even steps, the same latencies each, three of them traced:
    every shift's bracket holds an offset."""
    timeline = [_call(1000.0 * i, 100, 0.8, [
        ["admit", 0, 100], ["upload", 100, 100],
        ["launch", 200, 200, "decode"], ["wait", 400, 300, "decode"],
        ["emit", 700, 100]], {"decode": 1}, 1) for i in range(10)]
    events = []
    for i in (4, 5, 6):
        events += [_ev(xplane.MODULES_LINE, "jit_step(456)",
                       1000.0 * i + 300, 1000.0 * i + 1200),
                   _op(1000.0 * i + 300, 1000.0 * i + 1200)]
    assert len(host_clock.agreeing_shifts(
        host_clock.programs(events), host_clock.launches(timeline))) == 8
    assert host_clock.estimate(timeline, events) is None
    assert host_clock.shares(_art(timeline, events)) is None


def test_an_empty_bracket_gives_nothing():
    """The last step on the device 500 us early: it would have started
    before its launch, or the first ended after its fetch."""
    events = _events()
    for e in events[-2:]:
        e["start"] -= 500e-6
    assert len(host_clock.agreeing_shifts(
        host_clock.programs(events), host_clock.launches(TIMELINE))) == 1
    assert host_clock.estimate(TIMELINE, events) is None


def test_an_older_program_s_records_give_nothing():
    old = [{k: v for k, v in e.items() if k != "phases"} for e in TIMELINE]
    assert host_clock.launches(old) is None
    assert host_clock.shares(_art(old)) is None
    assert host_clock.shares({"timeline": TIMELINE, "events": None}) is None
    assert host_clock.shares({"timeline": [], "events": _events()}) is None


def test_a_step_dropped_in_flight_is_fetched_by_no_wait():
    """`decode_ahead` 0 at a launch says nothing was in flight: a step
    launched before it and never fetched is forgotten, and the next
    wait is the newer step's."""
    timeline = copy.deepcopy(TIMELINE)
    timeline[1]["phases"] = timeline[1]["phases"][:3]  # no settle
    timeline[2]["phases"] = [s for s in timeline[2]["phases"]
                             if s[:2] != ["wait", 100]]
    calls = host_clock.launches(timeline)
    assert [c[3] is None for c in calls] == [
        True, True, False, False, False, False, True]
    assert calls[3][3] == pytest.approx((BASE + 8800.0) * 1e-6)


def test_the_shifted_spans_are_what_the_breakdown_takes():
    """What is left for a `benchmark` PR: `run.py` hands these spans to
    `xplane.breakdown`, and `idle_gaps` stops saying `engine host`."""
    spans = host_clock.host_spans(TIMELINE, OFFSET)
    assert all(a <= b for _, a, b in spans)
    assert all(x[2] <= y[1] + 1e-12 for x, y in zip(spans, spans[1:]))
    gaps = dict(xplane.breakdown(_events(), spans)["idle_gaps"])
    assert "engine host" not in gaps and "wait" in gaps


NEW = ["device_idle_pct.serve." + n
       for n in host_clock.NAMES + host_clock.CALLS] \
    + ["host_clock_bracket_us"]
SERVE = {"serve-1p3b-chat-r80", "serve-1p3b-chat-sat", "serve-1p3b-long-r80",
         "serve-1p3b-chat-burst-r80", "serve-smallthinker-mixed-sat",
         "serve-solar2-longctx-sat", "serve-glm47flash-longctx-sat"}


def _entries():
    return {m["name"]: m for m in spec.load_benchmark()["per_layer"]}


@pytest.mark.parametrize("metric", NEW)
def test_every_reader_has_its_entry_and_reads_the_table(metric):
    """A reader left out by the 0.3-point rule has neither file nor
    entry (PERF.md section 3 names it); one that is there has both,
    lists at least the seven serve cells and reads the made share."""
    cell = spec.Cell("serve-1p3b-chat-sat")
    entry = _entries().get(metric)
    if entry is None:
        with pytest.raises(FileNotFoundError):
            cell.load_module("layer_metrics", metric)
        return
    assert SERVE <= set(entry["workloads"])
    assert entry["source"] == "device_trace"
    assert entry["moves"] == "serve_tokens_per_s"
    assert entry["better"] == "lower"
    assert entry in cell.per_layer
    reader = cell.load_module("layer_metrics", metric)
    name = metric.rsplit(".", 1)[-1]
    want = 200.0 if metric == "host_clock_bracket_us" \
        else 100.0 * BY_HAND[name] / WINDOW
    assert reader.read(_art()) == pytest.approx(want, abs=1e-3)
    assert reader.read(_art([{k: v for k, v in e.items() if k != "phases"}
                             for e in TIMELINE])) is None


def test_the_train_cell_gets_none_of_them():
    names = {m["name"] for m in spec.Cell("train-1p3b-s2048").per_layer}
    assert not names & set(NEW)
